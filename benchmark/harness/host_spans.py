"""The program's own spans on the trace's clock, and the device's idle time
booked to the span the host was in.

Every ``obs/spans.py`` span is a ``jax.profiler.TraceAnnotation``, so the
``.xplane.pb`` a traced run writes holds, on the host line of the trainer's
thread, the span tree ``Trainer.fit()`` draws: ``epoch`` (with ``dispatch``
and ``compute`` inside) and its sibling ``boundary``, whose children are
what the host does between an epoch's train program and the next
``epoch_start`` (``train/trainer.py _boundary``).  Two stages, as
``harness/trace.py``:

1. ``load_xplane(path)`` keeps the events of that one line as plain
   ``(name, start_ns, end_ns)`` tuples (``HostSpans``).  The line is the one
   that holds the benchmark's ``bench/epoch_start/*`` marks; a capture
   without marks (an operator's ``--profile-dir``) gives the line that holds
   the ``boundary`` spans.
2. Pure functions over it and ``trace.Trace``: the program's spans among the
   line's events, the boundaries of a span of time, the first device's idle
   intervals cut at span edges and booked to the innermost program span
   open at that time, and their sums by ``GROUPS``.
   ``tests/test_host_spans.py`` checks them on a hand-made recorded trace.

**Which events are the program's.**  The line also carries JAX's own
``TraceMe``s (``PjitFunction(floor)``, ``PjRtCpuExecutable::Execute``, and
beneath them, on a CPU, the ops themselves: ``copy``).  A program span is an
event named like an identifier that lies beneath no event that is not one:
the program cannot open a span inside JAX's call.  Python frames (``$file:n
f``, there when the profiler's Python tracer is on) are looked through.

A program that draws no ``boundary`` span (one from before ISSUE 38) gives
every reader built on this ``None``.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import re
from pathlib import Path

from harness import trace as trace_mod

BOUNDARY = "boundary"
UNATTRIBUTED = "unattributed"
NO_SPAN = "(no span)"
# Data, not code: the group each span's idle time is summed into.  A name
# this table lacks, ``boundary``'s own time (the loop's glue between its
# children) and time under no span at all are *unattributed*: above a tenth
# of the idle time, a span is missing from the program or a row from here.
GROUPS = {
    # the train program's launch and the wait for its results
    "epoch": "launch", "dispatch": "launch", "compute": "launch",
    # validation: the call, then the blocking fetch of its totals
    "eval": "eval", "eval_dispatch": "eval", "eval_fetch": "eval",
    # the save: decision, device-side copy or collective fetch, hand-over
    "ckpt_decide": "ckpt", "ckpt_snapshot": "ckpt", "ckpt_fetch": "ckpt",
    "ckpt_submit": "ckpt", "ckpt_drain": "ckpt", "writer_stats": "ckpt",
    # what the host does for its own records
    "health": "bookkeeping", "policy": "bookkeeping",
    "step_log": "bookkeeping", "epoch_log": "bookkeeping",
    "epoch_end_emit": "bookkeeping", "metrics_flush": "bookkeeping",
    "heartbeat": "bookkeeping", "moe_log": "bookkeeping",
    "resilience": "bookkeeping", "rollback": "bookkeeping",
}
GROUP_NAMES = tuple(dict.fromkeys(GROUPS.values()))  # in the table's order
_IDENTIFIER = re.compile(r"^[a-z][a-z0-9_]*$")
_EPOCH_START = trace_mod.MARK_PREFIX + "epoch_start/"


@dataclasses.dataclass
class HostSpans:
    """One host line: ``spans`` are ``(name, start_ns, end_ns)``, sorted by
    start and, among equal starts, the longer first."""

    thread: str
    spans: list


# ---------------------------------------------------------------- loading


def load_xplane(path: str | Path) -> HostSpans | None:
    from jax.profiler import ProfileData

    marked = drawn = None
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            if any(n.startswith(_EPOCH_START) for n, _, _ in events):
                marked = (line.name, events)
            elif drawn is None and any(n == BOUNDARY for n, _, _ in events):
                drawn = (line.name, events)
    found = marked or drawn
    if found is None:
        return None
    thread, events = found
    return HostSpans(thread, _ordered(
        e for e in events
        if not e[0].startswith((trace_mod.MARK_PREFIX, "$"))
    ))


def load(run) -> HostSpans | None:
    """The trainer's line of a benchmark run's trace (``None`` for an
    untraced run), found as ``scopes.load`` finds the file, read once and
    kept on ``run``."""
    if not hasattr(run, "host_spans"):
        trace_dir = getattr(run.clock, "trace_dir", None)
        found = sorted(Path(trace_dir).rglob("*.xplane.pb")) if trace_dir else []
        run.host_spans = load_xplane(found[-1]) if found else None
    return run.host_spans


def from_json(path: str | Path) -> HostSpans:
    """A recorded line kept as plain JSON (``to_json``'s output)."""
    return from_json_text(Path(path).read_text())


def from_json_text(text: str) -> HostSpans:
    raw = json.loads(text)
    return HostSpans(raw["thread"], _ordered(tuple(e) for e in raw["spans"]))


def to_json(host: HostSpans) -> str:
    return json.dumps(dataclasses.asdict(host))


def _ordered(events) -> list:
    return sorted(events, key=lambda e: (e[1], -e[2]))


# ------------------------------------------------------------- the tree


def program_spans(host: HostSpans) -> list:
    """``(name, start_ns, end_ns, depth)`` of the program's spans on the
    line, nested by containment: the events named like an identifier that
    lie beneath no other kind of event (the module's docstring says why)."""
    out, stack = [], []  # stack of (end_ns, is the program's)
    for name, start, end in host.spans:
        while stack and stack[-1][0] <= start:
            stack.pop()
        ours = bool(_IDENTIFIER.match(name)) and all(p for _, p in stack)
        if ours:
            if stack:  # a child never outlasts its parent
                end = min(end, stack[-1][0])
            out.append((name, start, end, len(stack)))
        stack.append((end, ours))
    return out


def boundaries(host: HostSpans, lo, hi) -> list:
    """``(start, end)`` of the ``boundary`` spans that lie wholly in
    ``[lo, hi]``."""
    return [
        (s, e) for name, s, e, _ in program_spans(host)
        if name == BOUNDARY and s >= lo and e <= hi
    ]


def innermost(spans: list) -> list:
    """Disjoint ``(start, end, name)`` pieces in time order: over each, the
    innermost of ``spans`` (``program_spans``' output) open at that time."""
    out, stack, at = [], [], None  # stack of (name, end)

    def emit(upto):
        nonlocal at
        if upto > at:
            out.append((at, upto, stack[-1][0]))
        at = max(at, upto)

    for name, start, end, _ in spans:
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(start)
        at = start
        stack.append((name, end))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def idle_by_span(trace, host: HostSpans, lo, hi) -> dict:
    """``{span name: idle ns}``: the first device's idle intervals in
    ``[lo, hi]`` cut at span edges, each piece booked to the innermost
    program span open at that time (``NO_SPAN`` where none is).  The values
    add up to the device's idle time in the span."""
    if not trace.devices:
        return {}
    idle = trace_mod.gaps(
        trace_mod.union(trace_mod.op_intervals(trace.devices[0]), lo, hi), lo, hi
    )
    pieces = innermost(program_spans(host))
    starts = [p[0] for p in pieces]
    out: dict = {}
    for a, b in idle:
        covered = 0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(pieces) and pieces[i][0] < b:
            s, e, name = pieces[i]
            both = min(b, e) - max(a, s)
            if both > 0:
                out[name] = out.get(name, 0) + both
                covered += both
            i += 1
        if (b - a) - covered > 0:
            out[NO_SPAN] = out.get(NO_SPAN, 0) + (b - a) - covered
    return out


def by_group(idle: dict) -> dict:
    """``idle_by_span``'s output summed by ``GROUPS``; what the table does
    not name, ``boundary``'s own time and ``NO_SPAN`` are
    ``UNATTRIBUTED``."""
    out = dict.fromkeys((*GROUP_NAMES, UNATTRIBUTED), 0)
    for name, ns in idle.items():
        out[GROUPS.get(name, UNATTRIBUTED)] += ns
    return out


def clock_lead_ns(trace, host: HostSpans, lo, hi):
    """How far the device's timeline runs ahead of the host's, at its worst
    in ``[lo, hi]``: the most by which a train execution starts, on the
    device's line, before the host's ``dispatch`` span that launched it
    opens (0 where every execution follows its dispatch).  The two lines are
    one trace but two clocks brought together by the profiler; where they
    disagree, neighbouring spans trade that much idle time and a train
    execution may fall outside its epoch's marks (PERF.md §7).  ``None``
    where executions and dispatches cannot be paired."""
    if not trace.devices:
        return None
    runs = trace_mod.train_executions(trace.devices[0], lo, hi)
    calls = [s for name, s, e, _ in program_spans(host)
             if name == "dispatch" and s >= lo and e <= hi]
    if not runs or len(runs) != len(calls):
        return None
    return max(0, max(call - start for (start, _), call in zip(runs, calls)))


# ------------------------------------------------- what the readers share


def _found(run):
    """``(trace, host, lo, hi)`` of a traced run whose program draws the
    boundary's span tree; ``None`` for an untraced run and for a program
    from before it."""
    if run.trace_span is None or load(run) is None:
        return None
    lo, hi = run.trace_span
    if not boundaries(run.host_spans, lo, hi):
        return None
    return run.trace, run.host_spans, lo, hi


def boundary_host_ms(run):
    """Mean milliseconds of the ``boundary`` spans in the traced span."""
    found = _found(run)
    if found is None:
        return None
    _, host, lo, hi = found
    spans = boundaries(host, lo, hi)
    return sum(e - s for s, e in spans) / len(spans) / 1e6


def idle_groups(run):
    """``by_group`` of the traced span, in nanoseconds, kept on ``run``:
    five readers ask for it."""
    found = _found(run)
    if found is None or not found[0].devices:  # no device plane: a rehearsal
        return None
    if not hasattr(run, "idle_by_group"):
        run.idle_by_group = by_group(idle_by_span(*found))
    return run.idle_by_group


def idle_ms_per_epoch(run, group: str):
    """Milliseconds a traced epoch the first device sat idle while the host
    was in a span of ``group``."""
    groups = idle_groups(run)
    if groups is None:
        return None
    return groups[group] / 1e6 / run.clock.trace_epochs


def idle_unattributed_pct(run):
    groups = idle_groups(run)
    if groups is None or not sum(groups.values()):
        return None
    return 100.0 * groups[UNATTRIBUTED] / sum(groups.values())
