"""The trace reduction on ``data/small_trace.json``: a trace written by hand
to the layout of a TPU trace (see the comments where each number is
checked), two devices, two traced epochs (1 and 2) with their boundaries.

Device 0, times in ms: epoch 1 = mark 0, one train execution 2..22 (two
steps of 10: fusion 4, all-reduce-start 1, fusion 2, all-reduce-done 1,
custom-call 2, all inside a ``while``), validation 30..33, next mark at 40.
Epoch 2 = mark 40, two train executions 42..62 and 66..86 (a 4 ms wait for
input between them), validation 90..93, next mark at 100.
Device 1 is busy 0..50 in one execution.
"""

from pathlib import Path

import pytest

from harness import trace as T

MS = 1e6


@pytest.fixture(scope="module")
def tr():
    return T.from_json(Path(__file__).parent / "data" / "small_trace.json")


def test_union_and_gaps():
    busy = T.union([(0, 5), (3, 8), (10, 12), (12, 13), (20, 30)], lo=1, hi=25)
    assert busy == [(1, 8), (10, 13), (20, 25)]
    assert T.total(busy) == 15
    assert T.gaps(busy, 1, 25) == [(8, 10), (13, 20)]


def test_span_from_marks(tr):
    assert T.span(tr, 1, 2) == (0, 100 * MS)
    assert T.span(tr, 1, 3) is None  # no mark for epoch 4


def test_busy_is_a_union_averaged_over_devices(tr):
    lo, hi = T.span(tr, 1, 2)
    # device 0: 20 + 3 + 20 + 20 + 3 = 66 ms (the while and its body count
    # once); device 1: 50 ms
    assert T.busy_seconds(tr, lo, hi) == pytest.approx((0.066 + 0.050) / 2)


def test_train_program_is_the_module_with_most_time(tr):
    lo, hi = T.span(tr, 1, 2)
    dev = tr.devices[0]
    # the snapshot at the boundary, jit__lambda_(99), is another <lambda>
    # of 0.3 ms: same function name, not the train program
    assert T.train_modules(dev, lo, hi) == {"jit__lambda_(11)"}
    assert T.train_executions(dev, lo, hi) == [
        (2 * MS, 22 * MS), (42 * MS, 62 * MS), (66 * MS, 86 * MS)
    ]
    assert T.train_seconds(tr, lo, hi) == pytest.approx((0.060 + 0.050) / 2)


def test_boundaries(tr):
    # epoch 1: 22 -> 42 (the next epoch's first execution) = 20 ms;
    # epoch 2: 86 -> 100 (the end of the span) = 14 ms
    assert T.boundary_seconds(tr, 1, 2) == pytest.approx(0.017)


def test_idle_inside_epochs_is_the_input_wait(tr):
    idle, length = T.idle_inside_epochs(tr, 1, 2)
    assert idle == pytest.approx(0.004)  # 62 -> 66 in epoch 2
    assert length == pytest.approx(0.020 + 0.044)


def test_collectives_total_and_exposed(tr):
    lo, hi = T.span(tr, 1, 2)
    total, exposed = T.collectives(tr, lo, hi)
    # six steps; each all-reduce lasts 4 ms from start to done, of which
    # the start and done ops block the core for 1 + 1
    assert total == pytest.approx(6 * 0.004)
    assert exposed == pytest.approx(6 * 0.002)


def test_self_time_and_custom_call_share(tr):
    lo, hi = T.span(tr, 1, 2)
    # the while's self time is 0; custom calls are 2 of every 10 ms of a
    # step, and validation adds 2 x 3 ms of fusions: 12 / 66
    assert T.share_of_busy(tr, lo, hi) == pytest.approx(12 / 66)
    top = dict(T.top_ops(tr, lo, hi))
    assert "while.3" not in top
    assert top["fusion.1"] == pytest.approx(6 * 0.004)
    assert top["custom-call.7"] == pytest.approx(6 * 0.002)


def test_idle_gaps_are_named_by_the_bus_events_around_them(tr):
    lo, hi = T.span(tr, 1, 2)
    phases = dict(T.idle_by_host_phase(tr, lo, hi))
    # between an epoch_start and its epoch_end the device idles at 0..2
    # (dispatch), 22..30, 33..34 (validation over, event not yet out),
    # 40..42, 62..66, 86..90 and 93..94
    assert phases["epoch_start->epoch_end"] == pytest.approx(
        0.002 + 0.008 + 0.001 + 0.002 + 0.004 + 0.004 + 0.001
    )
    assert phases["epoch_end->writer"] == pytest.approx(0.004)  # 34..38
    assert phases["writer->epoch_start"] == pytest.approx(0.002)  # 38..40
    assert phases["epoch_end->epoch_start"] == pytest.approx(0.006)  # 94..100
    assert sum(phases.values()) == pytest.approx(0.100 - 0.066)


def test_recorded_chip_trace():
    """``data/recorded_resnet18_job.json``: the traced span of
    ``resnet18_job`` as recorded on a TPU v5 lite (my chip run, PR 22),
    cut to the top-level ops (each ``while`` without its body) so that it
    stays small; modules and marks are whole.  Busy time, executions and
    boundaries are those of the full trace."""
    rec = T.from_json(Path(__file__).parent / "data" / "recorded_resnet18_job.json")
    lo, hi = T.span(rec, 1, 2)
    assert (hi - lo) / 1e9 == pytest.approx(2.876109182)
    assert T.busy_seconds(rec, lo, hi) == pytest.approx(2.821364418)
    # one program id is the epoch; the state snapshot is a namesake
    names = {T._module_name(m[0]) for m in rec.devices[0].modules}
    assert "jit__lambda" in names and "jit_run" in names
    assert len({m[0] for m in rec.devices[0].modules
                if T._module_name(m[0]) == "jit__lambda"}) == 2
    assert len(T.train_modules(rec.devices[0], lo, hi)) == 1
    assert len(T.train_executions(rec.devices[0], lo, hi)) == 2
    assert T.train_seconds(rec, lo, hi) == pytest.approx(2.687711994)
    assert T.boundary_seconds(rec, 1, 2) == pytest.approx(0.093370132)
    idle, length = T.idle_inside_epochs(rec, 1, 2)
    assert idle / length < 1e-4  # one execution an epoch: nothing to wait for
    assert T.collectives(rec, lo, hi) == (0.0, 0.0)
    assert T.share_of_busy(rec, lo, hi) == 0.0  # AllocateBuffer is not Pallas
    phases = dict(T.idle_by_host_phase(rec, lo, hi))
    assert sum(phases.values()) == pytest.approx(2.876109182 - 2.821364418)
    assert phases["metrics->writer"] == pytest.approx(0.016426933)


HLO = (
    '%fusion.976 = (f32[64]{0:T(128)}, f32[4096,32,32,64]{0,3,2,1:T(8,128)}) '
    'fusion(f32[64]{0:T(128)S(1)} %copy-done.124, bf16[4096,32,32,64]'
    '{0,3,2,1:T(8,128)(2,1)} %get-tuple-element.5876), kind=kOutput, '
    'calls=%fused_computation.1471.clone.clone'
)


def test_op_names_are_cut_from_hlo_text():
    assert T.short_op(HLO) == (
        "fusion:fusion.976", "(f32[64], f32[4096,32,32,64]) kOutput"
    )
    name, _ = T.short_op(
        '%custom-call.11 = s32[10]{0:T(128)} custom-call(), '
        'custom_call_target="AllocateBuffer"'
    )
    assert name == "custom-call:custom-call.11:AllocateBuffer"
    assert not T.is_pallas(name)
    assert T.is_pallas("custom-call:custom-call.3:tpu_custom_call")
    assert T.is_pallas("custom-call.7") and not T.is_pallas("fusion.7")
    name, _ = T.short_op(
        "%while.6 = (s32[]{:T(128)}, f32[100]{0:T(128)}) while((s32[]{:T(128)}, "
        "f32[100]{0:T(128)}) %tuple.1), condition=%cond, body=%body"
    )
    assert name == "while:while.6" and T.CONTROL.match(name)
    name, _ = T.short_op(
        "%all-reduce-start.2 = f32[1024]{0} all-reduce-start(f32[1024]{0} %p), "
        "replica_groups={{0,1,2,3}}, to_apply=%add"
    )
    assert name == "all-reduce-start:all-reduce-start.2"
    assert T.COLLECTIVE.match(name)
    assert T.short_op("fusion.1") == ("fusion.1", "")  # not HLO text: kept


def test_load_reads_planes_lines_and_marks(monkeypatch):
    """``load`` on a stand-in for ``ProfileData`` laid out as the chip's
    trace is: a device plane with op and module lines (op names are HLO
    text), a host plane with the benchmark's marks among other events."""
    import types

    import jax.profiler

    def event(name, start, dur):
        return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)

    def line(name, events):
        return types.SimpleNamespace(name=name, events=events)

    planes = [
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            line("XLA Modules", [event("jit__lambda(7)", 10, 100)]),
            line("XLA Ops", [event(HLO, 20, 50), event(HLO, 80, 20)]),
            line("Steps", [event("1", 0, 200)]),
        ]),
        types.SimpleNamespace(name="/host:CPU", lines=[
            line("python", [event("bench/epoch_start/1", 5, 1),
                            event("PjitFunction(<lambda>)", 6, 3),
                            event("bench/epoch_start/3", 150, 1)]),
        ]),
        types.SimpleNamespace(name="Task Environment", lines=[]),
    ]
    monkeypatch.setattr(
        jax.profiler.ProfileData, "from_file",
        staticmethod(lambda path: types.SimpleNamespace(planes=planes)),
    )
    got = T.load("anything.xplane.pb")
    assert [d.name for d in got.devices] == ["/device:TPU:0"]
    assert got.devices[0].ops == [
        ("fusion:fusion.976", 20, 50), ("fusion:fusion.976", 80, 20)
    ]
    assert got.devices[0].modules == [("jit__lambda(7)", 10, 100)]
    assert got.marks == [("epoch_start", 1, 5), ("epoch_start", 3, 150)]
    assert got.notes == {"fusion:fusion.976": "(f32[64], f32[4096,32,32,64]) kOutput"}
    lo, hi = T.span(got, 1, 2)
    assert T.busy_seconds(got, lo, hi) == pytest.approx(70e-9)
    assert T.top_ops(got, lo, hi) == [
        ["fusion (f32[64], f32[4096,32,32,64]) kOutput", pytest.approx(70e-9)]
    ]
    assert T.from_json_text(T.to_json(got)) == got


def test_no_device_plane_reduces_to_nothing():
    empty = T.Trace([], [("epoch_start", 1, 0), ("epoch_start", 3, 10)])
    assert T.busy_seconds(empty, 0, 10) is None
    assert T.train_seconds(empty, 0, 10) is None
    assert T.boundary_seconds(empty, 1, 2) is None
    assert T.collectives(empty, 0, 10) is None
    assert T.top_ops(empty, 0, 10) == []
