"""From a profiler trace to numbers: the one reduction every PR is read by.

Two stages, so that the arithmetic can be checked without a chip:

1. ``load(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
   and keeps plain tuples: for every device plane (``/device:TPU:n``) the
   events of its op line (``XLA Ops``) and of its module line (``XLA
   Modules``), and from the host planes the benchmark's own marks
   (``TraceAnnotation``s named ``bench/<kind>/<epoch>``, written by the
   window clock at every bus event, so host phases sit on the trace's clock).
2. Pure functions over that structure (``Trace``): busy/idle by interval
   union, executions of the train program, epoch boundaries, idle inside an
   epoch, collectives and how much of them is exposed, the share of a kind
   of op, the longest idle gaps by host phase.  ``tests/test_trace.py``
   checks them on ``tests/data/small_trace.json``.

All times are nanoseconds on the trace's clock until a function says
seconds.  A trace with no device plane (a CPU rehearsal) loads to a
``Trace`` without devices, and every device reduction returns ``None``.
"""

from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
MARK_PREFIX = "bench/"
# An op's name here is ``<opcode>:<instruction name>``, cut by ``short_op``
# from the HLO text the TPU trace gives ("%fusion.9 = f32[8]{0} fusion(...),
# kind=kLoop, ..."); a name that is not HLO text is kept as it is, and HLO
# names begin with their opcode, so the patterns anchor at the start.
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
# ops that only contain other ops: their time is their children's
CONTROL = re.compile(r"^(while|conditional|call)([.\-_:]|$)")
HLO_TEXT = re.compile(r"^%?(?P<name>[\w.\-]+) = (?P<rest>.*)$", re.DOTALL)
OPCODE = re.compile(r"(?:^|[\s)}\]])(?P<op>[a-z][a-z0-9\-]*)\(")


def is_pallas(name: str) -> bool:
    """A Pallas kernel is a custom call to Mosaic (``tpu_custom_call``);
    other custom calls (``AllocateBuffer``, ...) are the compiler's own."""
    parts = name.split(":")
    return parts[0].startswith("custom-call") and (
        len(parts) < 3 or parts[2] == "tpu_custom_call"
    )


def short_op(text: str) -> tuple[str, str]:
    """``(name, note)`` of one op event: ``<opcode>:<instruction name>``,
    and for the breakdown the output shape (layouts dropped) with the
    fusion kind or custom-call target."""
    m = HLO_TEXT.match(text)
    if not m:
        return text, ""
    rest = m.group("rest")
    op = OPCODE.search(rest)
    if not op:
        return m.group("name"), ""
    shape = re.sub(r"\{[^{}]*\}", "", rest[: op.start() + 1]).strip()
    extra = re.search(r"kind=(\w+)|custom_call_target=\"([^\"]+)\"", rest)
    note = shape[:70] + (f" {extra.group(1) or extra.group(2)}" if extra else "")
    name = f"{op.group('op')}:{m.group('name')}"
    if extra and extra.group(2):
        name += f":{extra.group(2)}"
    return name, note


@dataclasses.dataclass
class Device:
    name: str
    ops: list  # (name, start_ns, duration_ns), sorted by start
    modules: list  # (name, start_ns, duration_ns), sorted by start


@dataclasses.dataclass
class Trace:
    devices: list  # of Device, in plane order
    marks: list  # (kind, epoch, start_ns), sorted by start
    notes: dict = dataclasses.field(default_factory=dict)  # op name -> shape

    def mark(self, kind: str, epoch: int):
        for k, e, t in self.marks:
            if k == kind and e == epoch:
                return t
        return None


# ------------------------------------------------------------------ loading


def load(path: str | Path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, marks, notes, cut = [], [], {}, {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OP_LINE:
                    for e in line.events:
                        if e.name not in cut:
                            cut[e.name] = short_op(e.name)
                            notes.setdefault(*cut[e.name])
                        ops.append((cut[e.name][0], e.start_ns, e.duration_ns))
                elif line.name == MODULE_LINE:
                    modules = [
                        (e.name, e.start_ns, e.duration_ns) for e in line.events
                    ]
            if ops or modules:
                devices.append(Device(plane.name, sorted(ops, key=_start),
                                      sorted(modules, key=_start)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(MARK_PREFIX):
                        parts = e.name.split("/")
                        if len(parts) == 3 and parts[2].lstrip("-").isdigit():
                            marks.append((parts[1], int(parts[2]), e.start_ns))
    return Trace(devices, sorted(marks, key=lambda m: m[2]), notes)


def from_json(path: str | Path) -> Trace:
    """A recorded trace kept as plain JSON (``to_json``'s output)."""
    return from_json_text(Path(path).read_text())


def from_json_text(text: str) -> Trace:
    raw = json.loads(text)
    return Trace(
        [
            Device(d["name"], [tuple(e) for e in d["ops"]],
                   [tuple(e) for e in d["modules"]])
            for d in raw["devices"]
        ],
        [tuple(m) for m in raw["marks"]],
        raw.get("notes", {}),
    )


def to_json(trace: Trace) -> str:
    return json.dumps({
        "devices": [dataclasses.asdict(d) for d in trace.devices],
        "marks": trace.marks,
        "notes": trace.notes,
    })


def _start(event):
    return event[1]


# --------------------------------------------------------------- intervals


def union(intervals, lo=None, hi=None) -> list:
    """Merged, sorted ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted(intervals):
        if lo is not None:
            s, e = max(s, lo), max(e, lo)
        if hi is not None:
            s, e = min(s, hi), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo, hi) -> list:
    """The idle ``(start, end)`` intervals of ``[lo, hi]`` given merged busy."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def op_intervals(device: Device) -> list:
    return [(s, s + d) for _, s, d in device.ops]


def self_times(ops) -> list:
    """``(name, start, self_ns)`` per op: its duration less that of the ops
    nested inside it on the same line (a ``while`` holds its body's ops)."""
    out, stack = [], []  # stack of [name, start, end, child_ns]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, start, end, child = stack.pop()
            out.append((name, start, max(0.0, (end - start) - child)))
            if stack:
                stack[-1][3] += end - start

    for name, start, dur in ops:
        close(start)
        stack.append([name, start, start + dur, 0.0])
    close(float("inf"))
    return sorted(out, key=_start)


# ------------------------------------------------------------ the window


def span(trace: Trace, first_epoch: int, epochs: int):
    """The traced span: from the mark at ``epoch_start`` of ``first_epoch``
    to the one ``epochs`` epoch starts later — whole epochs with their
    boundaries."""
    lo = trace.mark("epoch_start", first_epoch)
    hi = trace.mark("epoch_start", first_epoch + epochs)
    if lo is None or hi is None or hi <= lo:
        return None
    return lo, hi


def busy_seconds(trace: Trace, lo, hi):
    """Seconds in which an op ran, averaged over the devices."""
    if not trace.devices:
        return None
    return sum(
        total(union(op_intervals(d), lo, hi)) for d in trace.devices
    ) / len(trace.devices) / 1e9


def train_modules(device: Device, lo, hi) -> set:
    """The train program's modules: the one whose executions take most
    device time in the span (an epoch, or a chunk of it, per execution),
    and any other program of the same function (``jit_f(<id>)``: a
    remainder chunk has another id) whose executions last at least a
    twentieth as long — which leaves out a namesake such as the state
    snapshot, another ``<lambda>`` of a fraction of a millisecond."""
    spent: dict = {}
    for name, s, d in device.modules:
        if s >= lo and s + d <= hi:
            total_ns, n = spent.get(name, (0.0, 0))
            spent[name] = (total_ns + d, n + 1)
    if not spent:
        return set()
    top = max(spent, key=lambda k: spent[k][0])
    floor = spent[top][0] / spent[top][1] / 20.0
    return {
        name for name, (total_ns, n) in spent.items()
        if _module_name(name) == _module_name(top) and total_ns / n >= floor
    }


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)  # "jit_run(123)" -> "jit_run"


def train_executions(device: Device, lo, hi) -> list:
    names = train_modules(device, lo, hi)
    return [
        (s, s + d) for n, s, d in device.modules
        if n in names and s >= lo and s + d <= hi
    ]


def train_seconds(trace: Trace, lo, hi):
    """Device seconds inside executions of the train program, averaged
    over the devices."""
    if not trace.devices:
        return None
    return sum(
        total(train_executions(d, lo, hi)) for d in trace.devices
    ) / len(trace.devices) / 1e9


def epochs_of(trace: Trace, device: Device, first_epoch: int, epochs: int):
    """Per traced epoch: ``(train executions, end)`` where ``end`` is the
    next epoch's ``epoch_start`` mark."""
    out = []
    for k in range(first_epoch, first_epoch + epochs):
        lo, hi = trace.mark("epoch_start", k), trace.mark("epoch_start", k + 1)
        if lo is None or hi is None:
            return None
        out.append((train_executions(device, lo, hi), hi))
    return out


def boundary_seconds(trace: Trace, first_epoch: int, epochs: int):
    """Mean seconds from the end of an epoch's last train execution to the
    start of the next epoch's first (for the last traced epoch: to the end
    of the span), on the first device."""
    if not trace.devices:
        return None
    per_epoch = epochs_of(trace, trace.devices[0], first_epoch, epochs)
    if not per_epoch or not all(execs for execs, _ in per_epoch):
        return None
    out = []
    for i, (execs, end) in enumerate(per_epoch):
        nxt = per_epoch[i + 1][0][0][0] if i + 1 < len(per_epoch) else end
        out.append(nxt - execs[-1][1])
    return sum(out) / len(out) / 1e9


def idle_inside_epochs(trace: Trace, first_epoch: int, epochs: int):
    """``(idle_s, span_s)`` between the first and last train execution of
    each traced epoch, on the first device: the device waiting for input."""
    if not trace.devices:
        return None
    dev = trace.devices[0]
    per_epoch = epochs_of(trace, dev, first_epoch, epochs)
    if not per_epoch or not all(execs for execs, _ in per_epoch):
        return None
    idle = length = 0.0
    for execs, _ in per_epoch:
        lo, hi = execs[0][0], execs[-1][1]
        idle += (hi - lo) - total(union(op_intervals(dev), lo, hi))
        length += hi - lo
    return idle / 1e9, length / 1e9


def collectives(trace: Trace, lo, hi):
    """``(total_s, exposed_s)`` of the collective ops on the first device.

    A synchronous collective blocks the core for its whole duration.  An
    asynchronous one is a ``-start`` and a ``-done`` op: it lasts from the
    start's beginning to the done's end, and only the two ops themselves
    block the core; other ops run in between."""
    if not trace.devices:
        return None
    pending, total_ns, exposed_ns = {}, 0.0, 0.0
    for name, s, d in trace.devices[0].ops:
        if s < lo or s + d > hi or not COLLECTIVE.match(name):
            continue
        exposed_ns += d
        if "-start" in name:
            pending[name.replace("-start", "")] = (s, d)
        elif "-done" in name and name.replace("-done", "") in pending:
            began, _ = pending.pop(name.replace("-done", ""))
            total_ns += (s + d) - began
        else:
            total_ns += d
    # a start whose done fell outside the span counts for itself
    total_ns += sum(d for _, d in pending.values())
    return total_ns / 1e9, exposed_ns / 1e9


def share_of_busy(trace: Trace, lo, hi, match=is_pallas):
    """Share (0..1) of the first device's op self-time in the ops that
    ``match(name)`` picks."""
    if not trace.devices:
        return None
    hit = every = 0.0
    for name, s, self_ns in self_times(trace.devices[0].ops):
        if s < lo or s >= hi:
            continue
        every += self_ns
        if match(name):
            hit += self_ns
    return hit / every if every else None


# ------------------------------------------------------------- breakdown


def top_ops(trace: Trace, lo, hi, n=10) -> list:
    """``[[what, seconds], ...]``: op self-time on the first device,
    control-flow containers left out.  Ops are summed by opcode, output
    shape and fusion kind where the trace gives them (the twelve unrolled
    layers of a trunk are one row, ``x12``), else by name."""
    if not trace.devices:
        return []
    spent: dict = {}
    for name, s, self_ns in self_times(trace.devices[0].ops):
        if lo <= s < hi and not CONTROL.match(name):
            note = trace.notes.get(name)
            what = f"{name.split(':')[0]} {note}" if note else name
            total_ns, names = spent.get(what, (0.0, set()))
            names.add(name)
            spent[what] = (total_ns + self_ns, names)
    top = sorted(spent.items(), key=lambda kv: -kv[1][0])[:n]
    return [
        [(what if len(names) == 1 else f"{what} x{len(names)}")[:120], ns / 1e9]
        for what, (ns, names) in top
    ]


def idle_by_host_phase(trace: Trace, lo, hi, n=10) -> list:
    """``[[phase, seconds], ...]``: the first device's idle time in the
    span, cut at the benchmark's marks and summed by the pair of bus events
    that brackets each piece (``epoch_end->writer``: validation is over,
    the snapshot is being taken)."""
    if not trace.devices:
        return []
    idle = gaps(union(op_intervals(trace.devices[0]), lo, hi), lo, hi)
    marks = [m for m in trace.marks if lo <= m[2] <= hi]
    by_phase: dict = {}
    for s, e in idle:
        cuts = [s] + [t for _, _, t in marks if s < t < e] + [e]
        for a, b in zip(cuts, cuts[1:]):
            before = [k for k, _, t in marks if t <= a]
            after = [k for k, _, t in marks if t >= b]
            phase = f"{before[-1] if before else 'span_start'}->" \
                    f"{after[0] if after else 'span_end'}"
            by_phase[phase] = by_phase.get(phase, 0.0) + (b - a)
    top = sorted(by_phase.items(), key=lambda kv: -kv[1])[:n]
    return [[phase, ns / 1e9] for phase, ns in top]


def describe(trace: Trace) -> dict:
    """What a trace holds, for the first look at one by hand."""
    by_name: dict = {}
    for name, _, d in (trace.devices[0].ops if trace.devices else ()):
        if COLLECTIVE.match(name):
            n, ns = by_name.get(name, (0, 0.0))
            by_name[name] = (n + 1, ns + d)
    return {
        "devices": [
            {"name": d.name, "ops": len(d.ops), "modules": len(d.modules),
             "module_names": sorted({_module_name(m[0]) for m in d.modules})[:20]}
            for d in trace.devices
        ],
        "marks": len(trace.marks),
        "mark_kinds": sorted({m[0] for m in trace.marks}),
        "collective_ops": [
            [name, n, ns / 1e9] for name, (n, ns) in
            sorted(by_name.items(), key=lambda kv: -kv[1][1])[:12]
        ],
    }
