"""``harness/host_spans.py`` on a hand-made recorded trace.

``data/boundary_trace.json`` is two epochs of 100 ms each on one device (a
``trace.Trace`` as ``small_trace.json``, with the trainer's host line beside
it under ``host``).  An epoch, in ms from its ``epoch_start`` mark:

    device busy   [6, 58] train   [62, 62.5] the schedule   [69, 84] eval
                  [86, 86.2] the schedule again   [93.5, 96] the snapshot
    host          epoch [1, 60]: dispatch [2, 4], compute [5, 60]
                  boundary [60, 98]: health [60.5, 64], policy, step_log,
                  eval [65, 85] = eval_dispatch [65, 67] + eval_fetch,
                  epoch_log [85, 88], epoch_end_emit, metrics_flush,
                  heartbeat, new_thing [90.5, 92] (a span GROUPS lacks),
                  ckpt_decide, ckpt_snapshot [92.5, 94], ckpt_submit,
                  writer_stats, resilience [96, 97]

with JAX's own ``TraceMe``s on the same line (``PjitFunction(...)``,
``np.asarray(jax.Array)`` and, beneath one, an identifier-like ``copy``).
The idle gap [62.5, 69] straddles five spans, [0, 1] and [98, 100] lie under
no span, and the second epoch's train execution is missing from the module
line — the case in which ``trace.boundary_seconds`` takes validation for
the train program.
"""

import json
import types
from pathlib import Path

import pytest

from harness import host_spans as H, load_module, trace as T

DATA = Path(__file__).parent / "data"
METRICS = Path(__file__).resolve().parents[1] / "layer_metrics"
MS = 1_000_000
LO, HI = 0, 200 * MS
# one epoch's idle milliseconds by the innermost span open, worked by hand
# from the docstring's intervals; the two epochs are alike
IDLE_MS = {
    H.NO_SPAN: 3.0, "epoch": 2.0, "dispatch": 2.0, "compute": 3.0,
    "boundary": 1.5, "health": 3.0, "policy": 0.5, "step_log": 0.5,
    "eval_dispatch": 2.0, "eval_fetch": 3.0, "epoch_log": 2.8,
    "epoch_end_emit": 1.0, "metrics_flush": 1.0, "heartbeat": 0.5,
    "new_thing": 1.5, "ckpt_decide": 0.5, "ckpt_snapshot": 1.0,
    "resilience": 1.0,
}
GROUP_MS = {"launch": 7.0, "eval": 5.0, "bookkeeping": 10.3, "ckpt": 1.5,
            H.UNATTRIBUTED: 6.0}


def recorded():
    text = (DATA / "boundary_trace.json").read_text()
    host = H.from_json_text(json.dumps(json.loads(text)["host"]))
    return T.from_json_text(text), host


def run_of(trace, host, span=(LO, HI)):
    """What a reader sees of ``benchmark/run.py``'s run."""
    return types.SimpleNamespace(
        trace=trace, trace_span=span, host_spans=host, trace_mod=T,
        clock=types.SimpleNamespace(
            trace_epochs=2, trace_dir=None, first_epoch=1
        ),
    )


def read(name, run):
    return load_module(METRICS / f"{name}.py").read(run)


def test_program_spans_leave_jaxs_own_names_out_and_nest_by_containment():
    _, host = recorded()
    spans = H.program_spans(host)
    names = {name for name, _, _, _ in spans}
    assert "copy" not in names  # identifier-like, but beneath JAX's call
    assert not any("(" in n or "." in n for n in names)
    assert "new_thing" in names  # unknown to GROUPS, still the program's
    depth = {name: d for name, _, _, d in spans}
    assert (depth["epoch"], depth["boundary"]) == (0, 0)
    assert (depth["dispatch"], depth["eval"], depth["eval_fetch"]) == (1, 1, 2)
    first = [name for name, s, _, _ in spans if s < 100 * MS]
    assert first == [
        "epoch", "dispatch", "compute", "boundary", "health", "policy",
        "step_log", "eval", "eval_dispatch", "eval_fetch", "epoch_log",
        "epoch_end_emit", "metrics_flush", "heartbeat", "new_thing",
        "ckpt_decide", "ckpt_snapshot", "ckpt_submit", "writer_stats",
        "resilience",
    ]


def test_json_round_trip():
    _, host = recorded()
    again = H.from_json_text(H.to_json(host))
    assert (again.thread, again.spans) == (host.thread, host.spans)


def test_boundaries_are_the_spans_wholly_inside():
    _, host = recorded()
    assert H.boundaries(host, LO, HI) == [
        (60 * MS, 98 * MS), (160 * MS, 198 * MS),
    ]
    assert H.boundaries(host, 61 * MS, HI) == [(160 * MS, 198 * MS)]
    assert H.boundaries(host, LO, 50 * MS) == []


def test_innermost_pieces_are_disjoint_and_cover_each_span_once():
    _, host = recorded()
    spans = H.program_spans(host)
    pieces = H.innermost(spans)
    assert all(a[1] <= b[0] for a, b in zip(pieces, pieces[1:]))
    top = sum(e - s for _, s, e, d in spans if d == 0)
    assert sum(e - s for s, e, _ in pieces) == top
    at_66 = [name for s, e, name in pieces if s <= 66 * MS < e]
    assert at_66 == ["eval_dispatch"]  # not eval, not boundary


def test_idle_by_span_cuts_gaps_at_span_edges():
    trace, host = recorded()
    idle = H.idle_by_span(trace, host, LO, HI)
    assert {k: v / MS for k, v in idle.items()} == pytest.approx(
        {k: 2 * v for k, v in IDLE_MS.items()}
    )
    # the rows add up to the device's idle time: 1 - busy / span
    busy = T.busy_seconds(trace, LO, HI)
    assert sum(idle.values()) / 1e9 == pytest.approx((HI - LO) / 1e9 - busy)
    # one epoch alone, cut mid-gap on both sides
    one = H.idle_by_span(trace, host, 63 * MS, 68 * MS)
    assert {k: v / MS for k, v in one.items()} == pytest.approx({
        "health": 1.0, "policy": 0.5, "step_log": 0.5, "eval_dispatch": 2.0,
        "eval_fetch": 1.0,
    })


def test_groups_and_the_unattributed_part_add_up_to_the_idle_time():
    trace, host = recorded()
    groups = H.by_group(H.idle_by_span(trace, host, LO, HI))
    assert {k: v / MS / 2 for k, v in groups.items()} == pytest.approx(GROUP_MS)
    assert H.GROUP_NAMES == ("launch", "eval", "ckpt", "bookkeeping")
    # boundary's own time, no span and the unknown name, and nothing else
    assert GROUP_MS[H.UNATTRIBUTED] == (
        IDLE_MS[H.NO_SPAN] + IDLE_MS["boundary"] + IDLE_MS["new_thing"]
    )


def test_the_six_readers_on_the_recorded_trace():
    run = run_of(*recorded())
    assert read("boundary_host_ms", run) == pytest.approx(38.0)
    for group in H.GROUP_NAMES:
        assert read(f"idle_{group}_ms_per_epoch", run) == pytest.approx(
            GROUP_MS[group]
        )
    assert read("idle_unattributed_pct", run) == pytest.approx(100 * 6.0 / 29.8)
    # by construction: the five parts are device_idle_pct x span / epochs
    idle_pct = read("device_idle_pct", run)
    parts = sum(GROUP_MS.values())
    assert parts == pytest.approx(idle_pct / 100 * 200.0 / 2)


def test_a_missing_train_execution_moves_the_device_reader_not_the_span():
    """The second epoch's train program is not on the module line.
    ``epoch_boundary_ms`` then takes the epoch's longest program,
    validation, for the train program and reads 63.5 ms; the program's own
    span still reads the boundary, and nothing raises."""
    run = run_of(*recorded())
    assert read("epoch_boundary_ms", run) == pytest.approx(63.5)
    assert read("boundary_host_ms", run) == pytest.approx(38.0)


def test_clock_lead_is_how_far_an_execution_precedes_its_dispatch():
    trace, host = recorded()
    # the second epoch's train execution is not on the line: no pairing
    assert H.clock_lead_ns(trace, host, LO, HI) is None
    # first epoch alone: dispatch opens at 2 ms, the execution starts at 6
    assert H.clock_lead_ns(trace, host, LO, 100 * MS) == 0
    # the device's line 10 ms early: the execution "starts" at -4 ms... cut
    # to the span, so move it 5: it starts at 1 ms, 1 ms before its dispatch
    dev = trace.devices[0]
    early = T.Trace(
        [T.Device(dev.name, dev.ops,
                  [(n, s - 5 * MS, d) for n, s, d in dev.modules])],
        trace.marks,
    )
    assert H.clock_lead_ns(early, host, LO, 100 * MS) == 1 * MS


NEW = ["boundary_host_ms", "idle_launch_ms_per_epoch", "idle_eval_ms_per_epoch",
       "idle_bookkeeping_ms_per_epoch", "idle_ckpt_ms_per_epoch",
       "idle_unattributed_pct"]


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_where_there_is_nothing_to_read(name):
    trace, host = recorded()
    # an untraced run
    assert read(name, run_of(trace, host, span=None)) is None
    # a trace directory with nothing in it: as good as none
    empty = run_of(trace, host)
    del empty.host_spans
    empty.clock.trace_dir = "/nonexistent"
    assert read(name, empty) is None
    # the parent's program: epoch, dispatch, compute, eval and ckpt_snapshot
    # are spans there, boundary and its other children are not
    before = H.HostSpans(host.thread, [
        s for s in host.spans
        if s[0] not in ("boundary", "new_thing")
        and (s[0] in ("eval", "ckpt_snapshot") or H.GROUPS.get(s[0]) in (None, "launch"))
    ])
    assert "eval" in {n for n, _, _, _ in H.program_spans(before)}
    assert read(name, run_of(trace, before)) is None
    # no device plane (a CPU rehearsal): spans, and nothing to book
    rehearsal = run_of(T.Trace([], trace.marks), host)
    value = read(name, rehearsal)
    assert value == (pytest.approx(38.0) if name == "boundary_host_ms" else None)
