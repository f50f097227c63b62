"""The scope and program names of a device trace (``harness/scopes.py``):
the phase and scope of an ``op_name``, self-time by phase, the readers
built on them, and the ``tf_op`` table read from the protobuf's wire format.

``data/scoped_trace.json`` is a recorded cut of one chip run of PR 24's
program in each cell (my chip run, PR 24; TPU v5 lite, ``--trace 1``): all
module events of the trace and its traced span, and of the first train
execution the ops around its ``while``, the ``while`` at its recorded length
and the body's ops of the first step only — so the ``while`` keeps the
other steps' time as its own, booked nowhere.  Times are as recorded; an
op's ``op_name`` is an index into the cell's ``op_names``.
"""

import json
import types
from pathlib import Path

import pytest

from harness import load_module, scopes as S, trace as T

DATA = Path(__file__).parent / "data" / "scoped_trace.json"
METRICS = Path(__file__).resolve().parents[1] / "layer_metrics"
NEW = (
    "fwd_ms_per_step", "bwd_ms_per_step", "update_ms_per_step",
    "unscoped_step_pct", "stage1_ms_per_step", "attention_ms_per_step",
    "eval_device_ms", "small_programs_per_epoch",
)
TRAIN = "jit_device_chunk_runner"


@pytest.fixture(scope="module")
def cells():
    raw = json.loads(DATA.read_text())["cells"]
    return {
        name: types.SimpleNamespace(
            scoped=S.Scoped(
                c["device"],
                [(n, s, d, c["op_names"][i]) for n, s, d, i in c["ops"]],
                [tuple(e) for e in c["modules"]],
            ),
            span=tuple(c["span"]), steps=c["steps"], epochs=c["epochs"],
        )
        for name, c in raw.items()
    }


def run_of(cell):
    """What a reader sees of ``benchmark/run.py``'s run."""
    return types.SimpleNamespace(
        trace_span=cell.span, scoped=cell.scoped, traced_steps=cell.steps,
        mix={"train_program": "device_chunk_runner"},
        clock=types.SimpleNamespace(trace_epochs=cell.epochs, trace_dir=None),
    )


def read(name, run):
    return load_module(METRICS / f"{name}.py").read(run)


# ------------------------------------------------------------------ names

# the shapes of op_name ISSUE 24 quotes, and what the program's own scopes
# add to them
PHASE_CASES = [
    ("jit(step)/jvp(ResNet)/stage1_block0/BatchNorm_0/mul", "forward"),
    ("jit(step)/transpose(jvp(ResNet))/stage1_block0/BatchNorm_0/reduce_sum",
     "backward"),
    ("jit(step)/transpose(jvp(ViT))/ViT.trunk/while/body/closed_call/blocks/"
     "bqhd,bkhd->bqhk/transpose", "backward"),
    ("jit(step)/mul", "other"),
    ("jit(step)/sub", "other"),
    ("jit(f)/while/body/closed_call/jvp(loss)/reduce_sum", "forward"),
    ("jit(f)/while/body/closed_call/transpose(jvp(loss))/mul", "backward"),
    ("blocks/bqhd,bkhd->bqhk/dot_general", "other"),
    ("jit(f)/while/body/closed_call/augment/jit(random_crop_flip)/"
     "biwc,bwj->bijc/dot_general", "forward"),
    ("jit(f)/while/body/closed_call/loss/top_k", "forward"),
    ("jit(f)/while/body/closed_call/guards/jit(_where)/select_n", "update"),
    ("jit(f)/while/body/closed_call/optimizer/jit(_where)/select_n", "update"),
    ("jit(f)/jit(_shuffle)/jit(_threefry_split)/epoch_permutation/while", "other"),
    ("", "other"),
    (None, "other"),
]


@pytest.mark.parametrize("op_name,phase", PHASE_CASES)
def test_phase_of(op_name, phase):
    assert S.phase_of(op_name) == phase


def test_scopes_are_whole_components_with_wrappers_peeled():
    name = ("jit(device_chunk_runner)/while/body/closed_call/"
            "transpose(jvp(ViT))/ViT.trunk/closed_call/blocks/attn/attention/"
            "bqhk,bkhd->bqhd/dot_general")
    assert S.components(name)[4:7] == ("ViT", "ViT.trunk", "closed_call")
    assert S.under(name, "attention") and S.under(name, "attn")
    assert S.under(name, "ViT") and not S.under(name, "mlp")
    assert not S.under(name, "att") and S.under(name, "att*")
    # the wrappers the two cells show, and the ones a remat or a custom
    # gradient would add
    for wrapped in ("jvp(loss)", "transpose(jvp(loss))", "checkpoint(loss)",
                    "transpose(jvp(checkpoint(loss)))", "vmap(loss)",
                    "custom_vjp(loss)", "custom_jvp(loss)", "remat(loss)"):
        assert S.under(f"jit(f)/{wrapped}/mul", "loss"), wrapped
    assert not S.under("jit(f)/cross_entropy_loss/mul", "loss")
    assert not S.under("jit(loss)/mul", "loss")  # a program is no scope
    assert S.under("jit(f)/jvp(ResNet)/stage1_block1/Conv_0/mul", "stage1_*")
    assert not S.under("jit(f)/jvp(ResNet)/stage2_block0/Conv_0/mul", "stage1_*")
    assert S.program_of("jit_eval_runner(9132)") == "jit_eval_runner"


def test_scope_path():
    bwd = ("jit(r)/while/body/closed_call/transpose(jvp(ResNet))/stage1_block0/"
           "BatchNorm_0/reduce_sum")
    assert S.scope_path(bwd, 0) == "ResNet"
    assert S.scope_path(bwd, 1) == "ResNet/stage1_block0"
    assert S.scope_path(bwd, 2) == S.scope_path(bwd, 9) == (
        "ResNet/stage1_block0/BatchNorm_0")
    vit = ("jit(r)/while/body/closed_call/jvp(ViT)/ViT.trunk/closed_call/blocks/"
           "attn/attention/bqhd,bkhd->bqhk/dot_general")
    assert S.scope_path(vit, 4) == "ViT/ViT.trunk/blocks/attn/attention"
    assert S.scope_path("jit(r)/while/body/closed_call/optimizer/mul", 1) == (
        "optimizer")
    assert S.scope_path("jit(r)/while/body/closed_call/jvp(loss)/mul", 1) == "loss"
    assert S.scope_path("jit(r)/while", 1) == "(program)"
    assert S.scope_path("", 1) == "(no op_name)"


# ---------------------------------------------------- self-time by phase

MS = 1e6


def hand_made():
    """One train execution 0..100 ms: a ``while`` 10..90 holding a forward
    fusion 10..30, a backward 30..70, an optimizer op 70..80 and an unnamed
    copy 80..85 (5 ms of the ``while`` are its own); a permutation op
    2..4 before it; validation 110..120 with a forward-looking op in it."""
    w = "jit(device_chunk_runner)/while"
    ops = [
        ("fusion:fusion.1", 2 * MS, 2 * MS, "jit(device_chunk_runner)/jit(_shuffle)/sort"),
        ("while:while.3", 10 * MS, 80 * MS, w),
        ("fusion:fusion.2", 10 * MS, 20 * MS, w + "/body/closed_call/jvp(ResNet)/stage1_block0/Conv_0/conv"),
        ("fusion:fusion.3", 30 * MS, 40 * MS, w + "/body/closed_call/transpose(jvp(ResNet))/stage2_block0/Conv_0/conv"),
        ("fusion:fusion.4", 70 * MS, 10 * MS, w + "/body/closed_call/optimizer/mul"),
        ("copy:copy.5", 80 * MS, 5 * MS, ""),
        ("fusion:fusion.9", 110 * MS, 10 * MS, "jit(eval_runner)/while/body/closed_call/ResNet/stem_conv/conv"),
    ]
    modules = [
        ("jit_device_chunk_runner(7)", 0.0, 100 * MS),
        ("jit__where(5)", 101 * MS, 1000.0),
        ("jit_eval_runner(8)", 110 * MS, 10 * MS),
        ("jit_state_snapshot(9)", 121 * MS, 1 * MS),
    ]
    return S.Scoped("/device:TPU:0", ops, modules)


def test_self_time_never_books_a_while():
    sc = hand_made()
    spent = S.by_phase(sc, 0, 200 * MS, TRAIN)
    assert spent == {"forward": 20 * MS, "backward": 40 * MS,
                     "update": 10 * MS, "other": 2 * MS + 5 * MS}
    # the while's own 5 ms (80 less 75 of children) are in no phase, and a
    # pick that accepts everything still leaves the while out
    assert S.seconds(sc, 0, 200 * MS, lambda n: True, TRAIN) == pytest.approx(0.077)
    assert S.seconds(sc, 0, 200 * MS, lambda n: S.under(n, "stage1_*"), TRAIN) == (
        pytest.approx(0.020))
    # only ops inside executions of the train program count: validation's
    # op would read as *other*, and is not read at all
    assert S.seconds(sc, 0, 200 * MS, lambda n: "eval_runner" in n, TRAIN) == 0.0
    # an execution cut by the span's edge is no execution
    assert S.seconds(sc, 1 * MS, 200 * MS, lambda n: True, TRAIN) is None
    assert S.by_phase(sc, 0, 200 * MS, "jit_no_such_program") is None
    rows = dict((r[0], r[1:]) for r in S.table(sc, 0, 200 * MS, TRAIN, 1))
    assert rows["ResNet/stage1_block0"] == (0.020, 0.0, 0.0)
    assert rows["ResNet/stage2_block0"] == (0.0, 0.040, 0.0)
    assert rows["optimizer"] == (0.0, 0.0, 0.010)
    assert rows["(no op_name)"] == (0.0, 0.0, 0.005)


@pytest.mark.parametrize("cell", ("resnet18_job", "vit_small_p2_job"))
def test_phases_partition_the_train_program(cells, cell):
    """forward + backward + update + other = the self-time of the train
    program's ops, to the nanosecond, against ``trace.self_times`` summed
    without this file's help."""
    c = cells[cell]
    spent = S.by_phase(c.scoped, *c.span, TRAIN)
    runs = S.executions(c.scoped, TRAIN, *c.span)
    assert runs and set(spent) == set(S.PHASES)
    whole = sum(
        self_ns
        for name, start, self_ns in T.self_times(
            [(n, s, d) for n, s, d, _ in c.scoped.ops])
        if not T.CONTROL.match(name)
        and any(lo <= start < hi for lo, hi in runs)
    )
    assert sum(spent.values()) == whole and whole > 0
    assert all(v > 0 for v in spent.values())
    # and a while is among the ops, holding most of the time, booked nowhere
    whiles = [o for o in c.scoped.ops if T.CONTROL.match(o[0])]
    assert whiles and max(d for _, _, d, _ in whiles) > 0.9 * whole / len(runs)


# ---------------------------------------------------------------- readers


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_without_a_trace(name):
    untraced = types.SimpleNamespace(
        trace_span=None, mix={"train_program": "device_chunk_runner"},
        clock=types.SimpleNamespace(trace_epochs=2, trace_dir=None),
    )
    assert read(name, untraced) is None
    # a trace directory with nothing in it: as good as none
    traced_nothing = types.SimpleNamespace(
        trace_span=(0, 1), mix={"train_program": "device_chunk_runner"},
        clock=types.SimpleNamespace(trace_epochs=2, trace_dir="/nonexistent"),
    )
    assert read(name, traced_nothing) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_for_unnamed_programs(name):
    """PR 22's program: the train program is a ``jit__lambda`` and
    validation a ``jit_run``.  Nothing is read, nothing raises."""
    sc = hand_made()
    sc.modules = [
        (n.replace("device_chunk_runner", "_lambda").replace("eval_runner", "run"),
         s, d) for n, s, d in sc.modules
    ]
    run = types.SimpleNamespace(
        trace_span=(0, 200 * MS), scoped=sc, traced_steps=2,
        mix={"train_program": "device_chunk_runner"},
        clock=types.SimpleNamespace(trace_epochs=1, trace_dir=None),
    )
    assert read(name, run) is None


def test_readers_on_the_hand_made_trace():
    run = types.SimpleNamespace(
        trace_span=(0, 200 * MS), scoped=hand_made(), traced_steps=2,
        mix={"train_program": "device_chunk_runner"},
        clock=types.SimpleNamespace(trace_epochs=1, trace_dir=None),
    )
    assert read("fwd_ms_per_step", run) == pytest.approx(10.0)
    assert read("bwd_ms_per_step", run) == pytest.approx(20.0)
    assert read("update_ms_per_step", run) == pytest.approx(5.0)
    assert read("unscoped_step_pct", run) == pytest.approx(100 * 7 / 77)
    assert read("stage1_ms_per_step", run) == pytest.approx(10.0)
    assert read("attention_ms_per_step", run) == 0.0
    assert read("eval_device_ms", run) == pytest.approx(10.0)
    assert read("small_programs_per_epoch", run) is None  # one epoch: no period


def two_epochs(shift=0.0):
    """The module line of two epochs as the chip gives them (100 ms each):
    two scalars, the train program, four schedule programs, a fingerprint,
    validation, four schedule programs; a snapshot in the second epoch
    only.  ``shift`` moves the device's clock against the host's marks."""
    small = ("jit_convert_element_type(1)", "jit_convert_element_type(1)")
    sched = ("jit_floor(2)", "jit__power(3)", "jit_multiply(4)", "jit__where(5)")
    modules = []
    for epoch in (0, 1):
        at = 100 * MS * epoch + shift
        modules += [(n, at + (1 + i) * MS / 10, 1000.0) for i, n in enumerate(small)]
        modules.append(("jit_device_chunk_runner(7)", at + 1 * MS, 70 * MS))
        after = sched + ("jit_param_fingerprint(6)", "jit_eval_runner(8)") + sched
        modules += [(n, at + (72 + i) * MS, 1000.0) for i, n in enumerate(after)]
    modules.append(("jit_state_snapshot(9)", 190 * MS + shift, 1 * MS))
    return S.Scoped("/device:TPU:0", [], sorted(modules, key=lambda m: m[1]))


@pytest.mark.parametrize("shift", (0.0, 0.5 * MS, -0.5 * MS, 5 * MS))
def test_small_programs_repeat_exactly(shift):
    """Eleven an epoch whatever the marks cut: a device clock half a
    millisecond off the host's puts a scalar program outside the span, a
    snapshot lands in one epoch and not the other; a count between the
    marks would read 10.5, 11.5 or 12."""
    run = types.SimpleNamespace(
        trace_span=(0.0, 200 * MS), scoped=two_epochs(shift), traced_steps=2,
        mix={"train_program": "device_chunk_runner"},
        clock=types.SimpleNamespace(trace_epochs=2, trace_dir=None),
    )
    between_marks = sum(
        0 <= s and s + d <= 200 * MS
        and S.program_of(n) not in (TRAIN, S.EVAL_PROGRAM)
        for n, s, d in run.scoped.modules
    ) / 2
    assert between_marks != 11.0
    assert read("small_programs_per_epoch", run) == 11.0
    # the second epoch's train execution lost: no whole period, no count
    run.scoped.modules = [
        m for m in run.scoped.modules if m[0] != "jit_device_chunk_runner(7)"
    ] + [("jit_device_chunk_runner(7)", 1 * MS + shift, 70 * MS)]
    assert read("small_programs_per_epoch", run) is None


# ------------------------------------------------------- the tf_op table


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _ld(number, payload):  # a length-delimited field
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _vi(number, value):
    return _varint(number << 3) + _varint(value)


def _entry(number, key, value):  # one entry of a map<int64, message>
    return _ld(number, _vi(1, key) + _ld(2, value))


HLO_1 = "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop, calls=%fc"
HLO_2 = "%copy.2 = f32[8]{0} copy(f32[8]{0} %q)"


def xspace():
    """An XSpace laid out as the chip's is: a host plane first, then the
    device plane with its lines (skipped whole), stat names and event
    metadata — ``tf_op`` once as a string, once as a reference to a string
    kept among the stat names, once absent."""
    stat = lambda id_, name: _entry(5, id_, _vi(1, id_) + _ld(2, name.encode()))  # noqa: E731
    event = lambda id_, name, stats=b"": _entry(  # noqa: E731
        4, id_, _vi(1, id_) + _ld(2, name.encode()) + stats)
    device = (
        _vi(1, 7) + _ld(2, b"/device:TPU:0")
        + _ld(3, _vi(1, 1) + _ld(2, b"XLA Ops") + b"\x19" + b"\0" * 8)
        + stat(1, "flops") + stat(2, "tf_op")
        + stat(3, "jit(f)/transpose(jvp(M))/blocks/attn/attention/mul:")
        + event(10, HLO_1, _ld(5, _vi(1, 1) + _vi(3, 99))
                + _ld(5, _vi(1, 2) + _ld(5, b"jit(f)/jvp(M)/stage1_block0/add:")))
        + event(11, HLO_2, _ld(5, _vi(1, 2) + _vi(7, 3)))
        + event(12, "%while.3 = () while()")
    )
    return _ld(1, _vi(1, 1) + _ld(2, b"/host:CPU")) + _ld(1, device)


def test_op_names_are_read_from_the_event_metadata():
    assert S.op_names(xspace(), "/device:TPU:0") == {
        HLO_1: "jit(f)/jvp(M)/stage1_block0/add",
        HLO_2: "jit(f)/transpose(jvp(M))/blocks/attn/attention/mul",
    }
    assert S.op_names(xspace(), "/device:TPU:1") == {}


def test_load_xplane_joins_events_and_names(monkeypatch, tmp_path):
    """Events by ``ProfileData`` (as ``trace.load`` reads them), names from
    the wire: an op is ``(opcode:instruction, start, duration, op_name)``."""
    import jax.profiler

    def event(name, start, dur):
        return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)

    def line(name, events):
        return types.SimpleNamespace(name=name, events=events)

    planes = [
        types.SimpleNamespace(name="/host:CPU", lines=[]),
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            line("XLA Modules", [event("jit_device_chunk_runner(7)", 10, 100)]),
            line("XLA Ops", [event(HLO_2, 80, 20), event(HLO_1, 20, 50),
                             event("%while.3 = () while()", 15, 90)]),
        ]),
    ]
    monkeypatch.setattr(
        jax.profiler.ProfileData, "from_file",
        staticmethod(lambda path: types.SimpleNamespace(planes=planes)),
    )
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace())
    got = S.load_xplane(path)
    assert got.device == "/device:TPU:0"
    assert got.modules == [("jit_device_chunk_runner(7)", 10, 100)]
    assert got.ops == [
        ("while:while.3", 15, 90, ""),
        ("fusion:fusion.1", 20, 50, "jit(f)/jvp(M)/stage1_block0/add"),
        ("copy:copy.2", 80, 20,
         "jit(f)/transpose(jvp(M))/blocks/attn/attention/mul"),
    ]
    assert S.from_json_text(S.to_json(got)) == got
    planes[1].lines = []
    assert S.load_xplane(path) is None  # no device ops: a CPU rehearsal


def test_readers_on_the_recorded_chip_traces(cells):
    """What the chip run's own last line gave for the whole trace (my chip
    run, PR 24): programs are whole in the cut, so the Trainer's numbers
    are the run's; the step's are those of the first step alone, within a
    percent of the run's mean over 20 and 72 steps."""
    resnet, vit = run_of(cells["resnet18_job"]), run_of(cells["vit_small_p2_job"])
    assert read("eval_device_ms", resnet) == pytest.approx(66.4533685)
    assert read("eval_device_ms", vit) == pytest.approx(150.972977)
    # between the marks the resnet trace holds 23 (one snapshot): 11.5
    assert read("small_programs_per_epoch", resnet) == 11.0
    assert read("small_programs_per_epoch", vit) == 11.0
    # the run: 37.03, 96.34, 0.192, 50.74, 0.59 %
    assert read("fwd_ms_per_step", resnet) == pytest.approx(37.03, rel=0.01)
    assert read("bwd_ms_per_step", resnet) == pytest.approx(96.34, rel=0.01)
    assert read("update_ms_per_step", resnet) == pytest.approx(0.192, rel=0.05)
    assert read("stage1_ms_per_step", resnet) == pytest.approx(50.74, rel=0.01)
    assert read("attention_ms_per_step", resnet) == 0.0
    # the run: 39.46, 87.46, 0.378, 58.86; the cut holds the program's
    # prologue (permutation, key tables) against one step, not 36
    assert read("fwd_ms_per_step", vit) == pytest.approx(39.46, rel=0.01)
    assert read("bwd_ms_per_step", vit) == pytest.approx(87.46, rel=0.01)
    assert read("update_ms_per_step", vit) == pytest.approx(0.378, rel=0.05)
    assert read("attention_ms_per_step", vit) == pytest.approx(58.86, rel=0.01)
    assert read("stage1_ms_per_step", vit) == 0.0
    assert 0 < read("unscoped_step_pct", resnet) < 5
    assert 0 < read("unscoped_step_pct", vit) < 5
    # none of the old names is left on the module line
    for cell in cells.values():
        programs = {S.program_of(m[0]) for m in cell.scoped.modules}
        assert not programs & {"jit__lambda", "jit_run"}
        assert {TRAIN, S.EVAL_PROGRAM, "jit_state_snapshot",
                "jit_param_fingerprint"} <= programs


def test_scope_table_on_the_recorded_traces(cells):
    c = cells["resnet18_job"]
    rows = {r[0]: r[1:] for r in S.table(c.scoped, *c.span, TRAIN, 1)}
    assert {"ResNet/stage1_block0", "ResNet/stage4_block1", "guards",
            "augment/jit(random_crop_flip)", "loss"} <= set(rows)
    fwd, bwd, rest = rows["ResNet/stage1_block0"]
    assert bwd > 3 * fwd > 0 and rest == 0.0
    # the optimizer has no row: XLA fuses its elementwise update into the
    # select that guards it, and a fusion carries its root's name
    assert "optimizer" not in rows
    assert rows["guards/jit(_where)"][:2] == (0.0, 0.0)
    assert rows["guards/jit(_where)"][2] == pytest.approx(0.000186, rel=0.05)
    c = cells["vit_small_p2_job"]
    rows = {r[0]: r[1:] for r in S.table(c.scoped, *c.span, TRAIN, 4)}
    attention = rows["ViT/ViT.trunk/blocks/attn/attention"]
    assert sum(attention) == pytest.approx(0.05886, rel=0.01)
    assert "ViT/ViT.trunk/blocks/mlp/mlp_down" in rows
