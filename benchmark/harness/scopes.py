"""The names a device trace already carries: each op's ``op_name`` and each
program's name, and what they split a step into.

Every instruction XLA compiles keeps the JAX name stack it was traced
under as ``metadata={op_name="..."}``: flax names each module call, JAX
wraps what it differentiates in ``jvp(...)`` and ``transpose(jvp(...))``,
and the program adds the scopes no module gives (``augment``, ``loss``,
``guards``, ``optimizer``, ``attn``, ``mlp``, ``attention``; PERF.md §3).
``harness/trace.py`` keeps an op event's opcode and shape; this file keeps
its ``op_name`` too, and reads:

- the **phase** of an op: ``backward`` if its ``op_name`` holds
  ``transpose(``; ``forward`` if it holds ``jvp(`` and no ``transpose(``,
  or sits under ``augment`` or ``loss``; ``update`` under ``guards`` or
  ``optimizer``; ``other`` otherwise (the permutation, the slices and the
  ``scan`` plumbing of the runner, and ops with no ``op_name`` at all);
- the **scope** of an op: its path components after JAX's transform
  wrappers are peeled off (``transpose(jvp(ResNet))`` is ``ResNet``);
- the **program** of a module event: ``jit_device_chunk_runner(123)`` is
  ``jit_device_chunk_runner``.

Times are **self-times** (``trace.self_times``): a ``while`` holds its
body's ops and is never booked, and only ops inside executions of the train
program (``jit_<the mix's train_program>``) count.  A fusion has one
``op_name``, its root's, so a fusion that spans two scopes is booked to one.

A program without named programs (PR 22's, whose train program is a
``jit__lambda`` among others) gives no train execution, and every reader
built on this returns ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import re
from pathlib import Path

from harness.trace import (
    CONTROL, MODULE_LINE, OP_LINE, _module_name, self_times, short_op,
)

PHASES = ("forward", "backward", "update", "other")
EVAL_PROGRAM = "jit_eval_runner"  # validation: train/step.py make_eval_runner
# the copy a save starts from (train/trainer.py): saves throttle on the wall
# clock, so an epoch may hold one or none, and a count an epoch leaves it out
SNAPSHOT_PROGRAM = "jit_state_snapshot"
# JAX wraps the first scope entered inside a transform: jvp(ResNet),
# transpose(jvp(ResNet)), checkpoint(ViTBlock).  These are peeled off a
# path component before it is compared with a scope's name.
WRAPPERS = (
    "jvp", "transpose", "vmap", "checkpoint", "remat", "custom_jvp",
    "custom_vjp",
)
_WRAPPED = re.compile(rf"^(?:{'|'.join(WRAPPERS)})\((.*)\)$")
# Where a TPU trace carries the HLO metadata's op_name: the stat ``tf_op``
# (``<op_name>:<op type>``, the type empty for JAX) of the op event's
# *metadata*, the record all events of one instruction share.
# ``jax.profiler.ProfileData`` gives an event's own stats (offset and
# duration) and not its metadata's, so that one table is read from the
# protobuf's wire format (PERF.md §6, PR 24, shows three events).
OP_NAME_STAT = "tf_op"


@dataclasses.dataclass
class Scoped:
    """The first device of a trace: ``ops`` are ``(instruction, start_ns,
    duration_ns, op_name)`` sorted by start, ``modules`` are ``(program,
    start_ns, duration_ns)`` sorted by start."""

    device: str
    ops: list
    modules: list


# ------------------------------------------------------------------ names


@functools.lru_cache(maxsize=None)  # a trace repeats each name every step
def components(op_name: str) -> tuple:
    """The path components of an ``op_name`` with JAX's transform wrappers
    peeled off: ``jit(f)/transpose(jvp(ResNet))/stage1_block0/mul`` gives
    ``("jit(f)", "ResNet", "stage1_block0", "mul")``."""
    out = []
    for part in (op_name or "").split("/"):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        out.append(part)
    return tuple(out)


def under(op_name: str, scope: str) -> bool:
    """Whether ``scope`` is one of the op's path components, compared
    whole; a ``scope`` that ends in ``*`` is compared as a prefix
    (``stage1_*`` holds ``stage1_block0`` and ``stage1_block1``)."""
    if scope.endswith("*"):
        return any(c.startswith(scope[:-1]) for c in components(op_name))
    return scope in components(op_name)


def phase_of(op_name: str) -> str:
    name = op_name or ""
    if "transpose(" in name:
        return "backward"
    if "jvp(" in name:
        return "forward"
    parts = components(name)
    if "augment" in parts or "loss" in parts:
        return "forward"
    if "guards" in parts or "optimizer" in parts:
        return "update"
    return "other"


def program_of(module: str) -> str:
    """``jit_eval_runner(9132)`` -> ``jit_eval_runner``."""
    return _module_name(module)


# ---------------------------------------------------------------- loading


def load_xplane(path: str | Path) -> Scoped | None:
    """The first device plane of an ``.xplane.pb`` that has ops or
    modules; ``None`` where there is none (a CPU rehearsal)."""
    from jax.profiler import ProfileData

    path = Path(path)
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/device:"):
            continue
        ops, modules, cut = [], [], {}
        for line in plane.lines:
            if line.name == OP_LINE:
                for e in line.events:
                    if e.name not in cut:
                        cut[e.name] = short_op(e.name)[0]
                    ops.append((cut[e.name], e.start_ns, e.duration_ns, e.name))
            elif line.name == MODULE_LINE:
                modules = [(e.name, e.start_ns, e.duration_ns)
                           for e in line.events]
        if ops or modules:
            named = op_names(path.read_bytes(), plane.name)
            ops = [(n, s, d, named.get(text, "")) for n, s, d, text in ops]
            return Scoped(plane.name, sorted(ops, key=_start),
                          sorted(modules, key=_start))
    return None


# The XSpace wire schema (tsl/profiler/protobuf/xplane.proto), field numbers
# only: XSpace planes=1; XPlane name=2 event_metadata=4 stat_metadata=5 (both
# maps: key=1 value=2); XEventMetadata name=2 stats=5; XStatMetadata name=2;
# XStat metadata_id=1 str_value=5 ref_value=7 (a string kept once, as the
# name of a stat metadata).


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    ``memoryview`` for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"xplane: wire type {wire}")
        yield tag >> 3, value


def _first(buf, number, default=None):
    return next((v for f, v in _fields(buf) if f == number), default)


def op_names(xspace: bytes, plane_name: str) -> dict:
    """``{event name: op_name}`` for the instructions of one plane, from
    the ``tf_op`` stat of their event metadata."""
    for f, plane in _fields(memoryview(xspace)):
        if f != 1 or bytes(_first(plane, 2, b"")).decode() != plane_name:
            continue
        stat_names, events = {}, []
        for f, entry in _fields(plane):
            if f == 5:
                stat_names[_first(entry, 1, 0)] = bytes(
                    _first(_first(entry, 2, b""), 2, b"")
                ).decode()
            elif f == 4:
                events.append(_first(entry, 2, b""))
        out = {}
        for event in events:
            for f, stat in _fields(event):
                if f != 5 or stat_names.get(_first(stat, 1)) != OP_NAME_STAT:
                    continue
                text = _first(stat, 5)
                value = (stat_names.get(_first(stat, 7), "") if text is None
                         else bytes(text).decode())
                name = bytes(_first(event, 2, b"")).decode()
                out[name] = value.rpartition(":")[0] if ":" in value else value
        return out
    return {}


def load(run) -> Scoped | None:
    """The scoped trace of a benchmark run (``None`` for an untraced one),
    read once and kept on ``run``."""
    if not hasattr(run, "scoped"):
        trace_dir = getattr(run.clock, "trace_dir", None)
        found = sorted(Path(trace_dir).rglob("*.xplane.pb")) if trace_dir else []
        run.scoped = load_xplane(found[-1]) if found else None
    return run.scoped


def from_json(path: str | Path) -> Scoped:
    """A recorded cut kept as plain JSON (``to_json``'s output)."""
    return from_json_text(Path(path).read_text())


def from_json_text(text: str) -> Scoped:
    raw = json.loads(text)
    return Scoped(raw["device"], [tuple(e) for e in raw["ops"]],
                  [tuple(e) for e in raw["modules"]])


def to_json(scoped: Scoped) -> str:
    return json.dumps(dataclasses.asdict(scoped))


def _start(event):
    return event[1]


# -------------------------------------------------------------- reductions


def executions(scoped: Scoped, program: str, lo, hi) -> list:
    """``(start, end)`` of the executions of ``program`` that lie wholly in
    the span, as ``trace.train_executions`` cuts them."""
    return [
        (s, s + d) for name, s, d in scoped.modules
        if program_of(name) == program and s >= lo and s + d <= hi
    ]


def programs_per_epoch(scoped: Scoped, lo, hi, epochs: int, train: str,
                       skip: tuple = ()):
    """Executions an epoch of every program but ``train`` and those in
    ``skip``, counted in the device's own order: from the start of the
    first traced epoch's first execution of ``train`` to the start of the
    last traced epoch's — whole periods of what the device runs, which
    neither the clock of the host's marks nor the profiler's first
    millisecond can cut.  ``None`` with fewer than two traced epochs, or
    where the span's train executions do not divide among them."""
    runs = executions(scoped, train, lo, hi)
    each, rest = divmod(len(runs), epochs)
    if epochs < 2 or not each or rest:
        return None
    first, last = runs[0][0], runs[(epochs - 1) * each][0]
    return sum(
        first <= s < last and program_of(name) not in (train, *skip)
        for name, s, _ in scoped.modules
    ) / (epochs - 1)


def _train_ops(scoped: Scoped, lo, hi, program: str) -> list:
    """``(op_name, self_ns)`` of every op that starts inside one of the
    span's executions of ``program``, the ops that only hold others
    (``while``) left out.  Kept on the trace: every reader of a run asks
    for the same list."""
    kept = scoped.__dict__.setdefault("booked", {})
    if (lo, hi, program) not in kept:
        kept[lo, hi, program] = _book(scoped, lo, hi, program)
    return kept[lo, hi, program]


def _book(scoped: Scoped, lo, hi, program: str) -> list:
    runs, at, out = executions(scoped, program, lo, hi), 0, []
    timed = self_times([((n, op_name), s, d) for n, s, d, op_name in scoped.ops])
    for (name, op_name), start, self_ns in timed:
        while at < len(runs) and runs[at][1] <= start:
            at += 1
        if at == len(runs):
            break
        if runs[at][0] <= start and not CONTROL.match(name):
            out.append((op_name, self_ns))
    return out


def seconds(scoped: Scoped, lo, hi, pick, program: str):
    """Seconds of op **self-time** inside the span's executions of
    ``program`` in the ops whose ``op_name`` ``pick`` accepts; a
    ``while`` (or any op that only holds others) is never booked.  ``None``
    where the span holds no execution of ``program``."""
    if not executions(scoped, program, lo, hi):
        return None
    return sum(
        self_ns for op_name, self_ns in _train_ops(scoped, lo, hi, program)
        if pick(op_name)
    ) / 1e9


def by_phase(scoped: Scoped, lo, hi, program: str):
    """``{phase: nanoseconds}`` over the four phases, which partition the
    self-time of the train program's ops."""
    if not executions(scoped, program, lo, hi):
        return None
    out = dict.fromkeys(PHASES, 0.0)
    for op_name, self_ns in _train_ops(scoped, lo, hi, program):
        out[phase_of(op_name)] += self_ns
    return out


def table(scoped: Scoped, lo, hi, program: str, depth: int = 1) -> list:
    """``[(scope path, forward_s, backward_s, rest_s)]`` sorted by time:
    each op of the train program booked to its ``scope_path`` at
    ``depth``."""
    rows: dict = {}
    for op_name, self_ns in _train_ops(scoped, lo, hi, program):
        row = rows.setdefault(scope_path(op_name, depth), [0.0, 0.0, 0.0])
        column = {"forward": 0, "backward": 1}.get(phase_of(op_name), 2)
        row[column] += self_ns / 1e9
    return sorted(
        ((path, *row) for path, row in rows.items()),
        key=lambda r: -sum(r[1:]),
    )


# components that are the compiler's plumbing, not a scope anybody named
PLUMBING = ("while", "body", "cond", "closed_call")


def scope_path(op_name: str, depth: int) -> str:
    """The scope an op is booked to: its path from the model down —
    from the first component JAX wrapped (``jvp(ResNet)``, ``jvp(loss)``)
    or, for an op outside the differentiated function, from the first
    below the program (``augment``, ``optimizer``) — with ``depth``
    components below that head, the plumbing of ``scan`` left out and the
    op itself (the last component: ``mul``, ``reduce_sum``) too."""
    if not op_name:
        return "(no op_name)"
    raw, parts = op_name.split("/"), components(op_name)
    head = next((i for i, (r, p) in enumerate(zip(raw, parts)) if r != p),
                1 if parts[0].startswith("jit(") else 0)
    path = [p for p in parts[head:-1] if p not in PLUMBING]
    return "/".join(path[:1 + depth]) or "(program)"


# ------------------------------------------------- what the readers share


def train_program(run) -> str:
    return "jit_" + run.mix["train_program"]


def _span_of(run):
    """``(scoped, lo, hi)`` of a traced run whose trace names the train
    program; ``None`` for an untraced run and for a program whose jitted
    functions have no names of their own (PR 22's)."""
    if run.trace_span is None or load(run) is None:
        return None
    if not executions(run.scoped, train_program(run), *run.trace_span):
        return None
    return (run.scoped, *run.trace_span)


def train_ms_per_step(run, pick):
    """Milliseconds a step of the traced span spends in the train
    program's ops that ``pick(op_name)`` accepts."""
    found = _span_of(run)
    if found is None:
        return None
    return 1e3 * seconds(*found, pick, train_program(run)) / run.traced_steps


def program_runs(run, program: str):
    """The traced span's executions of ``program``."""
    found = _span_of(run)
    if found is None:
        return None
    scoped, lo, hi = found
    return executions(scoped, program, lo, hi)


def small_programs_per_epoch(run):
    found = _span_of(run)
    if found is None:
        return None
    return programs_per_epoch(
        *found, run.clock.trace_epochs, train_program(run),
        skip=(EVAL_PROGRAM, SNAPSHOT_PROGRAM),
    )


def train_share_pct(run, phase: str):
    """The share (%) of the train program's op self-time in ``phase``."""
    found = _span_of(run)
    if found is None:
        return None
    spent = by_phase(*found, train_program(run))
    return 100.0 * spent[phase] / sum(spent.values()) if any(spent.values()) else None
