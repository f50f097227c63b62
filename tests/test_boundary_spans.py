"""The epoch boundary as a span tree (ISSUE 38): what ``Trainer.fit()``
leaves in its span recorder between an epoch's train program and the next
``epoch_start``, and the three fields that make the spans a tree.

One two-epoch fit of ``TinyNet`` on the CPU feeds most tests here (a module
fixture); nothing in this file is a measurement — the one timing is a loose
ceiling on a span's cost with no profiler session.
"""

import threading
import time
from pathlib import Path

import pytest

from distributed_training_comparison_tpu import obs
from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.obs.spans import SpanRecorder
from distributed_training_comparison_tpu.resilience.goodput import GoodputMeter
from distributed_training_comparison_tpu.train import Trainer

from test_obs import span_as_it_was
from test_train import TinyNet

ARGS = [
    "--synthetic-data",
    "--limit-examples", "640",   # 576 train examples -> 18 steps/epoch @32
    "--batch-size", "32",
    "--epoch", "2",
    "--save-last-min-secs", "0",  # every epoch saves: snapshot and submits
    "--no-progress",
    "--seed", "7",
    "--eval-step", "1000",
    "--num-devices", "1",
]
# fit()'s order (train/trainer.py _boundary); TinyNet has no expert layer,
# so no ``moe_log``, and one process, so ``ckpt_snapshot`` not ``ckpt_fetch``
CHILDREN = [
    "health", "policy", "step_log", "eval", "epoch_log", "epoch_end_emit",
    "metrics_flush", "heartbeat", "ckpt_decide", "ckpt_snapshot",
    "ckpt_submit", "writer_stats", "resilience",
]


def _trainer(ckpt_path, extra=()):
    obs.reset()
    obs.set_recorder(None)
    hp = load_config("tpu", argv=[*ARGS, "--ckpt-path", str(ckpt_path), *extra])
    return Trainer(hp, model=TinyNet(num_classes=100))


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """``(spans, events)`` of one two-epoch fit: every closed span, and
    every bus event with the monotonic time a subscriber saw it at."""
    trainer = _trainer(tmp_path_factory.mktemp("boundary"))
    events = []
    tap = lambda ev: events.append((time.monotonic(), ev["kind"], ev.get("epoch")))  # noqa: E731
    trainer.bus.subscribe(tap)
    try:
        trainer.fit()
    finally:
        trainer.bus.unsubscribe(tap)
        trainer.close()
        obs.reset()
        obs.set_recorder(None)
    return trainer.tracer.spans(), events


def _children(spans, parent):
    return sorted(
        (s for s in spans if s["parent"] == parent["id"]
         and s["thread_id"] == parent["thread_id"]),
        key=lambda s: s["t0"],
    )


def _boundaries(spans):
    return sorted(
        (s for s in spans if s["name"] == "boundary"), key=lambda s: s["t0"]
    )


def test_each_epoch_has_one_boundary_with_the_listed_children_in_order(run):
    spans, _ = run
    boundaries = _boundaries(spans)
    assert [b["epoch"] for b in boundaries] == [0, 1]
    for b in boundaries:
        kids = _children(spans, b)
        assert [k["name"] for k in kids] == CHILDREN
        for k in kids:
            assert isinstance(k["id"], int) and k["id"] != b["id"]
            assert k["epoch"] == b["epoch"]  # inherited, never passed
            assert k["depth"] == b["depth"] + 1
    # validation's call and its fetch, under ``eval``
    for ev in (s for s in spans if s["name"] == "eval"):
        assert [k["name"] for k in _children(spans, ev)] == [
            "eval_dispatch", "eval_fetch",
        ]
    ids = [s["id"] for s in spans]
    assert len(ids) == len(set(ids))


def test_boundary_children_are_strictly_nested_and_do_not_overlap(run):
    spans, _ = run
    for b in _boundaries(spans):
        at = b["t0"]
        for k in _children(spans, b):
            assert at <= k["t0"] <= k["t1"] <= b["t1"], k["name"]
            at = k["t1"]


def test_children_cover_the_boundary(run):
    """What no child covers is the loop's glue.  A later edit that adds
    work to the boundary outside a span fails here, and the device's idle
    time under it would read as unattributed on the chip."""
    spans, _ = run
    for b in _boundaries(spans):
        covered = sum(k["t1"] - k["t0"] for k in _children(spans, b))
        whole = b["t1"] - b["t0"]
        assert covered >= 0.95 * whole, (covered, whole)


def test_boundary_is_the_epochs_sibling_and_ends_at_the_next_epoch_start(run):
    spans, events = run
    epochs = sorted((s for s in spans if s["name"] == "epoch"),
                    key=lambda s: s["t0"])
    starts = {e: t for t, kind, e in events if kind == "epoch_start"}
    for ep, b in zip(epochs, _boundaries(spans)):
        assert (ep["depth"], ep["parent"], b["depth"], b["parent"]) == (
            0, None, 0, None,
        )
        assert ep["epoch"] == b["epoch"] and ep["t1"] <= b["t0"]
        nxt = starts.get(b["epoch"] + 1)
        if nxt is not None:
            assert b["t1"] <= nxt
    # the last boundary closes before the writer is drained
    (drain,) = [s for s in spans if s["name"] == "ckpt_drain"]
    assert _boundaries(spans)[-1]["t1"] <= drain["t0"]


def test_ckpt_write_names_the_ckpt_submit_that_queued_it(run):
    spans, _ = run
    submits = {s["id"]: s for s in spans if s["name"] == "ckpt_submit"}
    writes = [s for s in spans if s["name"] == "ckpt_write"]
    assert writes and submits
    for w in writes:
        cause = submits[w["parent"]]  # KeyError: no ckpt_submit is its parent
        assert w["thread_id"] != cause["thread_id"]  # the one cross-thread edge
        assert w["epoch"] == cause["epoch"]
        assert w["t0"] >= cause["t0"]


def test_writer_starts_no_job_inside_a_boundary(run):
    """``fit()`` holds the writer over the boundary: a save queued in epoch
    k's boundary starts after epoch k + 1's train dispatch returned (the
    last epoch's at the drain), so its fetch of the state never runs beside
    the host's part of an epoch."""
    spans, _ = run
    dispatched = {}  # epoch -> when its first train dispatch returned
    for s in sorted(spans, key=lambda s: s["t0"]):
        if s["name"] == "dispatch":
            dispatched.setdefault(s["epoch"], s["t1"])
    (drain,) = [s for s in spans if s["name"] == "ckpt_drain"]
    writes = [s for s in spans if s["name"] == "ckpt_write"]
    assert {w["epoch"] for w in writes} == {0, 1}
    for w in writes:
        assert w["t0"] >= dispatched.get(w["epoch"] + 1, drain["t0"])
        for b in _boundaries(spans):
            assert not b["t0"] <= w["t0"] <= b["t1"]


def test_eval_spans_return_what_validate_returned(tmp_path):
    """Splitting ``_run_eval``'s call from its fetch split the statement,
    not the semantics: the same floats as the one-expression form."""
    trainer = _trainer(tmp_path)
    try:
        images, labels, weights = trainer._val
        totals = {
            k: float(v) for k, v in trainer.eval_runner(
                trainer.state, images, labels, weights
            ).items()
        }
        assert trainer.validate(0) == {
            "val_loss": totals["loss_sum"] / totals["count"],
            "val_acc": 100.0 * totals["top1_count"] / totals["count"],
        }
        names = [s["name"] for s in trainer.tracer.spans()]
        assert names == ["eval_dispatch", "eval_fetch"]
    finally:
        trainer.close()
        obs.reset()
        obs.set_recorder(None)


def test_a_span_is_a_tree_node_on_any_thread():
    rec = SpanRecorder()
    seen = {}

    def writer(cause):
        with rec.span("write", parent=cause, key="last") as w:
            with rec.span("fsync"):
                pass
        seen["write"] = w

    with rec.span("epoch", epoch=3) as ep:
        with rec.span("submit") as sub:
            assert rec.open_span() is sub
            t = threading.Thread(target=writer, args=(rec.open_span(),))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    assert rec.open_span() is None
    by_name = {s["name"]: s for s in rec.spans()}
    assert by_name["epoch"]["parent"] is None and by_name["epoch"]["epoch"] == 3
    assert by_name["submit"]["parent"] == ep.id
    assert by_name["write"]["parent"] == sub.id  # across threads
    assert by_name["fsync"]["parent"] == seen["write"].id
    assert {s["epoch"] for s in rec.spans()} == {3}
    assert by_name["write"]["depth"] == 0  # depth stays the thread's own
    # an epoch given on a span wins over the inherited one
    with rec.span("outer", epoch=1):
        with rec.span("inner", epoch=2) as inner:
            pass
    assert inner.epoch == 2
    events = {e["name"]: e for e in obs.chrome_trace(rec.spans())["traceEvents"]
              if e["ph"] == "X"}
    assert events["write"]["args"] == {
        "key": "last", "id": seen["write"].id, "parent": sub.id, "epoch": 3,
    }
    assert events["epoch"]["args"] == {"epoch": 3, "id": ep.id}


def test_goodput_phase_books_its_total_and_draws_the_span():
    rec = SpanRecorder()
    meter = GoodputMeter(tracer=rec)
    with meter.phase("ckpt", span="ckpt_snapshot"):
        time.sleep(0.002)
    with meter.phase("ckpt"):  # no span asked for: the total alone
        pass
    (s,) = rec.spans()
    assert s["name"] == "ckpt_snapshot"
    assert meter.seconds["ckpt"] >= s["t1"] - s["t0"] >= 0.002
    with GoodputMeter().phase("eval", span="eval"):  # no recorder: inert
        pass


def test_a_span_with_no_session_costs_microseconds():
    """A loose ceiling on a CPU number, not a result: ISSUE 38 counts on a
    span costing 2-3 us while no profiler session runs (about fifteen more
    an epoch), so the id, the parent and the inherited epoch must not
    change that: under 4 us (2.6-2.8 here, alone on the machine).  Under
    the driver's six workers the same span read 4.3, so the ceiling
    stretches with the machine: the span as it was before PR 24 (no
    annotation, no tree; 2.5 us here) is timed in the same rounds, and a
    span may cost a third more than that.  The best of many short rounds: a
    quiet millisecond is what shows a cost."""
    rec, ref = SpanRecorder(), SpanRecorder()
    best = before = float("inf")
    with rec.span("boundary", epoch=1), span_as_it_was(ref, "boundary"):
        for _ in range(40):
            t0 = time.perf_counter()
            for _ in range(1_000):
                with rec.span("health"):
                    pass
            t1 = time.perf_counter()
            for _ in range(1_000):
                with span_as_it_was(ref, "health"):
                    pass
            best = min(best, (t1 - t0) / 1_000)
            before = min(before, (time.perf_counter() - t1) / 1_000)
    said = f"{best * 1e6:.2f} us a span, {before * 1e6:.2f} us as it was"
    assert best < max(4e-6, 1.35 * before), said


def test_profile_dir_capture_holds_the_boundary(tmp_path):
    """An operator's ``--profile-dir`` capture stops where ``boundary``
    closes, so it shows the part of an epoch where the chip waits."""
    from jax.profiler import ProfileData

    trainer = _trainer(
        tmp_path / "ckpt", extra=["--profile-dir", str(tmp_path / "prof")]
    )
    try:
        trainer.fit()
    finally:
        trainer.close()
        obs.reset()
        obs.set_recorder(None)
    (pb,) = Path(tmp_path / "prof").rglob("*.xplane.pb")
    names = {
        e.name
        for plane in ProfileData.from_file(str(pb)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
    }
    assert {"epoch", "boundary", "eval_fetch", "resilience"} <= names
