"""Deep-telemetry tests (ISSUE 6): the typed per-step metric sketches and
their sampling budget, the SIGKILL-surviving mmap flight ring + cross-host
black box, the clock-skew estimator, ``run_report`` --follow, the
serve-metrics reservoir bound, and the watchdog's per-LR-phase baselines.

The load-bearing properties pinned here:

- histogram-sketch merge is ASSOCIATIVE and order-independent — the
  contract that lets per-flush deltas recombine exactly across flushes,
  hosts, and attempts;
- a torn mmap ring page decodes to the surviving slots (CRC-dropped, never
  raised on) — the contract that makes the ring readable after any death;
- the skew estimator degrades to a no-op on one-host runs and runs with no
  shared anchors — it can tighten ordering, never break it.
"""

import json
import math
import os
import signal
import struct
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))

import run_report  # noqa: E402

from distributed_training_comparison_tpu import obs
from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.health.watchdog import (
    HealthConfig,
    Watchdog,
)
from distributed_training_comparison_tpu.obs.blackbox import (
    MmapRing,
    _FILE_HEADER,
    _SLOT_HEADER,
    collect_black_box,
    decode_ring,
    ring_filename,
)
from distributed_training_comparison_tpu.obs.bus import EventBus
from distributed_training_comparison_tpu.obs.metrics import (
    Histogram,
    MetricRegistry,
    histogram_quantile,
    histogram_summary,
    merge_histograms,
    merge_metric_events,
)
from distributed_training_comparison_tpu.serve.metrics import (
    ServeMetrics,
    _Reservoir,
)
from distributed_training_comparison_tpu.train import AsyncCheckpointer


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    monkeypatch.delenv(obs.RUN_ID_ENV, raising=False)
    monkeypatch.delenv(obs.ATTEMPT_ENV, raising=False)
    obs.reset()
    yield
    obs.reset()


# ------------------------------------------------------- histogram sketches


def test_histogram_quantiles_track_exact_percentiles():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(0.0, 1.0, 5000)
    h = Histogram("x")
    h.record_many(samples)
    snap = h.snapshot(reset=False)
    for q in (0.5, 0.95, 0.99):
        exact = float(np.quantile(samples, q))
        approx = histogram_quantile(snap, q)
        # bucket midpoints bound the error by the bucket ratio (~±7.5% at
        # 16 buckets/decade); 20% leaves room for rank granularity
        assert abs(approx - exact) / exact < 0.20, (q, approx, exact)
    summ = histogram_summary(snap)
    assert summ["count"] == len(samples)
    assert abs(summ["mean"] - samples.mean()) < 1e-3
    assert summ["max"] == pytest.approx(samples.max())


def test_histogram_merge_is_associative_and_order_independent():
    rng = np.random.default_rng(1)
    samples = rng.lognormal(0.5, 0.8, 3000)
    whole = Histogram("x")
    whole.record_many(samples)
    reference = whole.snapshot()

    parts = []
    for chunk in np.array_split(samples, 7):
        h = Histogram("x")
        h.record_many(chunk)
        parts.append(h.snapshot())

    def fold(snaps):
        out = None
        for s in snaps:
            out = merge_histograms(out, s)
        return out

    left = fold(parts)
    right = fold(list(reversed(parts)))
    # associativity: pairwise tree-merge == linear fold
    mid = merge_histograms(
        merge_histograms(parts[0], parts[1]),
        fold(parts[2:]),
    )
    for merged in (left, right, mid):
        assert merged["count"] == reference["count"]
        assert merged["buckets"] == reference["buckets"]
        assert merged["min"] == reference["min"]
        assert merged["max"] == reference["max"]
        assert abs(merged["sum"] - reference["sum"]) < 1e-3


def test_histogram_side_counts_for_nonfinite_and_nonpositive():
    h = Histogram("x")
    for v in (float("nan"), float("inf"), -1.0, 0.0, 1.0, 10.0):
        h.record(v)
    snap = h.snapshot()
    assert snap["nonfinite"] == 2
    assert snap["zeros"] == 2      # -1.0 and 0.0: no log bucket exists
    assert snap["count"] == 4      # finite samples, zeros included
    assert snap["min"] == -1.0 and snap["max"] == 10.0
    # a low quantile resolves to the sub-bucket region (the exact min)
    assert histogram_quantile(snap, 0.0) == -1.0


def test_record_many_matches_scalar_record():
    rng = np.random.default_rng(2)
    samples = np.concatenate(
        [rng.lognormal(0.0, 1.0, 500), [0.0, -2.0, np.nan, np.inf]]
    )
    a, b = Histogram("a"), Histogram("b")
    a.record_many(samples)
    for v in samples:
        b.record(v)
    sa, sb = a.snapshot(), b.snapshot()
    sa.pop("type"), sb.pop("type")
    assert sa == sb


def test_merge_metric_events_counters_sum_gauges_last_win():
    evs = [
        {"payload": {"metrics": {
            "c": {"type": "counter", "n": 2},
            "g": {"type": "gauge", "value": 1.0},
        }}},
        {"payload": {"metrics": {
            "c": {"type": "counter", "n": 3},
            "g": {"type": "gauge", "value": 7.0},
        }}},
    ]
    out = merge_metric_events(evs)
    assert out["c"] == {"type": "counter", "n": 5}
    assert out["g"]["value"] == 7.0


# ----------------------------------------------------------- flush budget


def test_registry_budget_bounds_bus_traffic():
    bus = EventBus(persist=False)
    reg = MetricRegistry(flush_steps=50)
    reg.histogram("h").record(1.0)
    # under budget: maybe_flush is a no-op however often it is called
    for step in range(49):
        reg.note_steps(1)
        assert reg.maybe_flush(bus, epoch=0, step=step) is None
    reg.note_steps(1)
    ev = reg.maybe_flush(bus, epoch=0, step=50)
    assert ev is not None and ev["kind"] == "metrics"
    assert obs.validate_event(ev) == []
    assert ev["payload"]["steps"] == 50
    assert ev["payload"]["metrics"]["h"]["count"] == 1
    # the flush reset the deltas AND the budget
    assert reg.maybe_flush(bus, epoch=0, step=50) is None
    assert reg.flush(bus) is None  # nothing recorded since


def test_registry_gauges_survive_flush_counters_reset():
    bus = EventBus(persist=False)
    reg = MetricRegistry(flush_steps=1)
    reg.counter("c").inc(4)
    reg.gauge("g").set(3.0)
    ev = reg.flush(bus)
    assert ev["payload"]["metrics"]["c"]["n"] == 4
    assert ev["payload"]["metrics"]["g"]["value"] == 3.0
    reg.gauge("g").set(5.0)
    ev2 = reg.flush(bus)
    # the delta reset; the counter keeps reporting EXPLICIT zero windows
    # once it has ever fired (PR 8: counter alert rules — skipped steps,
    # the recompilation sentinel — resolve on observed clean windows,
    # never on absences)
    assert ev2["payload"]["metrics"]["c"]["n"] == 0
    assert ev2["payload"]["metrics"]["g"]["value"] == 5.0
    reg2 = MetricRegistry(flush_steps=1)
    reg2.counter("never")  # registered but never fired: stays dead weight
    reg2.gauge("g2").set(1.0)
    ev3 = reg2.flush(bus)
    assert "never" not in ev3["payload"]["metrics"]


def test_registry_name_type_conflict_raises():
    reg = MetricRegistry()
    reg.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.histogram("x")


# -------------------------------------------------------------- mmap ring


def test_mmap_ring_roundtrip_and_wraparound(tmp_path):
    ring = MmapRing(tmp_path / "flight.ring", slots=8, slot_size=256)
    for i in range(20):
        ring.append(json.dumps({"kind": "e", "step": i}))
    ring.close()
    events, torn = decode_ring(tmp_path / "flight.ring")
    assert torn == 0
    assert [e["step"] for e in events] == list(range(12, 20))  # last 8, in order


def test_mmap_ring_torn_page_decodes_surviving_prefix(tmp_path):
    path = tmp_path / "flight.ring"
    ring = MmapRing(path, slots=8, slot_size=256)
    for i in range(6):
        ring.append(json.dumps({"kind": "e", "step": i}))
    ring.close()
    # tear slot 3's payload mid-page, as a writer death would
    raw = bytearray(path.read_bytes())
    base = _FILE_HEADER.size + 3 * 256 + _SLOT_HEADER.size
    raw[base : base + 8] = b"\x00" * 8
    path.write_bytes(bytes(raw))
    events, torn = decode_ring(path)
    assert torn == 1
    assert [e["step"] for e in events] == [0, 1, 2, 4, 5]
    # a file truncated mid-slot loses only the tail slots
    path.write_bytes(bytes(raw[: _FILE_HEADER.size + 2 * 256 + 10]))
    events, torn = decode_ring(path)
    assert [e["step"] for e in events] == [0, 1]
    # not a ring at all: empty result, no exception
    path.write_bytes(b"garbage")
    assert decode_ring(path) == ([], 0)


def test_bus_attach_ring_seeds_prebind_events(tmp_path):
    for kind in ("early", "late"):  # ad-hoc test kinds: registered
        obs.register_kind(kind)
    bus = EventBus(run_id="ab" * 8, persist=False)
    bus.emit("early", note=1)
    assert bus.attach_ring(tmp_path / "flight.ring") is not None
    bus.emit("late", note=2)
    bus.close()
    events, torn = decode_ring(tmp_path / "flight.ring")
    assert torn == 0
    assert [e["kind"] for e in events] == ["early", "late"]
    for ev in events:
        assert obs.validate_event(ev) == []


def test_oversized_event_truncates_instead_of_corrupting(tmp_path):
    ring = MmapRing(tmp_path / "flight.ring", slots=4, slot_size=128)
    ring.append("x" * 1000)
    ring.append(json.dumps({"kind": "ok"}))
    ring.close()
    events, torn = decode_ring(tmp_path / "flight.ring")
    # the raw ring blindly truncates: the oversized slot fails JSON decode
    assert torn == 1
    assert [e["kind"] for e in events] == ["ok"]


def test_bus_swaps_oversized_events_for_envelope_stubs(tmp_path):
    """An event bigger than a ring slot must keep its kind/timing in the
    black box — the bus writes an envelope stub instead of letting a
    mid-JSON cut decode as a torn slot."""
    bus = EventBus(run_id="ab" * 8, persist=False)
    bus.attach_ring(tmp_path / "flight.ring", slot_size=256)
    bus.emit("goodput", epoch=2, blob="y" * 4096)
    bus.emit("small", note=1)
    bus.close()
    events, torn = decode_ring(tmp_path / "flight.ring")
    assert torn == 0
    big, small = events
    assert big["kind"] == "goodput" and big["epoch"] == 2
    assert big["payload"]["truncated"] > 4096  # original serialized size
    assert obs.validate_event(big) == []
    assert small["kind"] == "small" and small["payload"] == {"note": 1}


def test_collect_black_box_merges_rings_across_attempts(tmp_path):
    root = tmp_path
    (root / "version-0").mkdir()
    r0 = MmapRing(root / "version-0" / ring_filename(0, 0), slots=4)
    r0.append(json.dumps({"kind": "a0", "t_wall": 1.0}))
    r0.close()
    r1 = MmapRing(root / "version-0" / ring_filename(1, 0), slots=4)
    r1.append(json.dumps({"kind": "a1", "t_wall": 2.0}))
    r1.close()
    box = collect_black_box(root)
    assert box == root / "blackbox.json"
    report = json.loads(box.read_text())
    assert len(report["rings"]) == 2
    assert [e["kind"] for e in report["events"]] == ["a0", "a1"]
    assert ring_filename(1, 2) == "flight-a1-p2.ring"


def test_sigkill_leaves_decodable_ring(tmp_path):
    """The headline contract: a process killed with SIGKILL — no handler,
    no atexit, no flush — still leaves its ring decodable (the mmap'd
    dirty pages belong to the page cache, not the process)."""
    script = textwrap.dedent(
        f"""
        import json, os, signal, sys
        sys.path.insert(0, {str(Path(__file__).parent.parent)!r})
        from distributed_training_comparison_tpu.obs.bus import EventBus
        bus = EventBus(run_id="cd" * 8, persist=False)
        bus.attach_ring({str(tmp_path / "flight.ring")!r})
        for i in range(10):
            bus.emit("work", step=i)
        os.kill(os.getpid(), signal.SIGKILL)
        """
    )
    proc = subprocess.run([sys.executable, "-c", script])
    assert proc.returncode == -signal.SIGKILL
    events, torn = decode_ring(tmp_path / "flight.ring")
    assert torn == 0
    assert [e["step"] for e in events] == list(range(10))
    assert collect_black_box(tmp_path) is not None


# -------------------------------------------------------------- clock skew


def _ev(kind, process_index=0, attempt=0, t_wall=0.0, **payload):
    ev = {
        "v": 1, "run_id": "ab" * 8, "attempt": attempt,
        "process_index": process_index, "t_wall": t_wall,
        "t_mono": t_wall, "kind": kind,
    }
    if payload:
        ev["payload"] = payload
    return ev


def test_skew_one_host_run_is_identity():
    events = [_ev("run_start", t_wall=1.0), _ev("epoch_end", t_wall=2.0)]
    offsets = run_report.estimate_clock_skew(events)
    assert offsets == {0: 0.0}
    assert run_report.apply_clock_skew(events, offsets) == events


def test_skew_recovered_from_run_start_anchors():
    skew = 5.3  # host 1's clock runs 5.3s ahead
    events = []
    for attempt in (0, 1):
        t = 100.0 * (attempt + 1)
        events.append(_ev("run_start", 0, attempt, t))
        events.append(_ev("run_start", 1, attempt, t + skew))
        # host 1's epoch_end stamps land BEFORE host 0's run_start on the
        # raw clocks — the ordering bug the estimator exists to fix
        events.append(_ev("epoch_end", 0, attempt, t + 10.0))
        events.append(_ev("epoch_end", 1, attempt, t + 10.0 + skew))
    offsets = run_report.estimate_clock_skew(events)
    assert offsets[0] == 0.0
    assert offsets[1] == pytest.approx(skew)
    shifted = run_report.apply_clock_skew(events, offsets)
    for ev, orig in zip(shifted, events):
        if orig["process_index"] == 1:
            assert ev["t_wall"] == pytest.approx(orig["t_wall"] - skew)
        else:
            assert ev["t_wall"] == orig["t_wall"]


def test_skew_absent_anchor_pairs_degrade_to_zero():
    # process 1 died before its run_start: no pair exists → offset 0
    events = [
        _ev("run_start", 0, 0, 10.0),
        _ev("epoch_end", 1, 0, 11.0),
    ]
    offsets = run_report.estimate_clock_skew(events)
    assert offsets == {0: 0.0, 1: 0.0}
    # an anchor with no process-0 counterpart is equally unusable
    events.append(_ev("run_start", 1, 1, 12.0))
    assert run_report.estimate_clock_skew(events)[1] == 0.0


# ------------------------------------------------ run_report metrics + follow


def test_run_report_folds_metric_sketches_per_attempt(tmp_path):
    bus = EventBus(run_id="ab" * 8)
    bus.bind_dir(tmp_path)
    reg = MetricRegistry(flush_steps=1)
    rng = np.random.default_rng(3)
    samples = rng.lognormal(0.0, 0.5, 400)
    # two flushes: the summary must reconstruct the WHOLE distribution
    for half in np.array_split(samples, 2):
        reg.histogram("train/grad_norm").record_many(half)
        reg.counter("train/skipped_steps").inc(1)
        reg.flush(bus, epoch=0)
    bus.emit("epoch_end", epoch=0, secs=1.0)
    bus.close()

    events, _ = run_report.load_run(tmp_path)
    summary = run_report.summarize(events)
    a = summary["attempts"][0]
    assert a["metrics_events"] == 2
    merged = a["metrics"]["train/grad_norm"]
    assert merged["count"] == len(samples)
    assert a["metrics"]["train/skipped_steps"]["n"] == 2
    p95 = histogram_quantile(merged, 0.95)
    assert abs(p95 - np.quantile(samples, 0.95)) / p95 < 0.25
    text = run_report.format_summary("run", summary)
    assert "train/grad_norm" in text and "p95=" in text


def test_follow_events_tails_new_lines_and_files(tmp_path):
    f0 = tmp_path / "events.jsonl"
    f0.write_text(json.dumps(_ev("run_start", t_wall=1.0)) + "\n")
    writes = iter([
        # poll 2: a complete line plus a torn tail — the tail must wait
        lambda: f0.open("a").write(
            json.dumps(_ev("epoch_end", t_wall=2.0)) + "\n" + '{"torn'
        ),
        # poll 3: the torn line completes; a NEW attempt's file appears
        lambda: (
            f0.open("a").write('": true}\n'),
            (tmp_path / "version-0").mkdir(),
            (tmp_path / "version-0" / "events.jsonl").write_text(
                json.dumps(_ev("run_start", attempt=1, t_wall=3.0)) + "\n"
            ),
        ),
    ])

    def fake_sleep(_):
        try:
            next(writes)()
        except StopIteration:
            pass

    batches = list(
        run_report.follow_events(tmp_path, max_polls=4, sleep=fake_sleep)
    )
    flat = [e for b in batches for e in b]
    kinds = [e.get("kind") for e in flat]
    assert kinds[0] == "run_start"
    assert "epoch_end" in kinds
    assert any(e.get("attempt") == 1 for e in flat)  # new file picked up
    # the torn line arrived only once, after completion
    assert sum(1 for e in flat if e.get("torn")) == 1


# --------------------------------------------------------- serve reservoir


def test_reservoir_bounds_memory_keeps_exact_moments():
    r = _Reservoir(cap=64, seed=0)
    values = [float(i % 97) / 10 + 0.1 for i in range(10_000)]
    for v in values:
        r.add(v)
    assert len(r.values) == 64            # bounded however many arrive
    assert r.count == len(values)         # exact
    assert r.max == max(values)           # exact
    assert r.mean == pytest.approx(sum(values) / len(values))
    # the sample stays in-range and roughly representative
    assert all(min(values) <= v <= max(values) for v in r.values)


def test_reservoir_last_is_exact_past_the_cap():
    """The periodic serve/queue_depth gauge reads .last — once the
    reservoir caps, values[-1] is an arbitrary historical sample, so the
    exact latest must survive independently."""
    r = _Reservoir(cap=8, seed=0)
    for i in range(1_000):
        r.add(float(i))
    assert r.last == 999.0  # values[-1] would be some random survivor


def test_serve_queue_depth_gauge_tracks_latest_past_cap():
    bus = EventBus(run_id="ab" * 8, persist=False)
    m = ServeMetrics(bus=bus, emit_every_s=0.0)
    m._queue_depths.cap = 4
    for depth in range(100):
        m.record_batch(4, depth)
    m.record_request_done(0.01)  # triggers the periodic emit
    ev = [e for e in bus.ring_events() if e["kind"] == "metrics"][-1]
    assert ev["payload"]["metrics"]["serve/queue_depth"]["value"] == 99


def test_serve_metrics_summary_flags_sampling():
    m = ServeMetrics()
    for i in range(10):
        m.record_request_done(0.01 * (i + 1))
        m.record_batch(4, i)
    s = m.summary()
    assert s["completed"] == 10 and s["latency_sampled"] is False
    assert s["latency_ms"]["max"] == pytest.approx(100.0)
    assert s["mean_batch_size"] == pytest.approx(4.0)
    assert s["max_queue_depth"] == 9


def test_serve_metrics_periodic_bus_emit_validates():
    bus = EventBus(run_id="ab" * 8, persist=False)
    m = ServeMetrics(bus=bus, emit_every_s=0.0)
    m.record_batch(4, 2)
    m.record_request_done(0.05)
    events = [e for e in bus.ring_events() if e["kind"] == "metrics"]
    assert events, "no periodic metrics event emitted"
    ev = events[-1]
    assert obs.validate_event(ev) == []
    metrics = ev["payload"]["metrics"]
    assert metrics["serve/latency_s"]["count"] == 1
    assert metrics["serve/queue_depth"]["value"] == 2
    # the summary event still carries the histogram delta
    final = m.emit_event(bus)
    assert obs.validate_event(final) == []


def test_serve_emit_event_delta_plus_periodic_reconstructs_all():
    bus = EventBus(run_id="ab" * 8, persist=False)
    m = ServeMetrics(bus=bus, emit_every_s=0.0)
    for i in range(5):
        m.record_request_done(0.01 * (i + 1))
    m.emit_event(bus)
    merged = merge_metric_events(
        [e for e in bus.ring_events() if e["kind"] == "metrics"]
        + [
            {"metrics": {"serve/latency_s": e["payload"]["latency_hist"]}}
            for e in bus.ring_events()
            if e["kind"] == "serve" and "latency_hist" in e["payload"]
        ]
    )
    assert merged["serve/latency_s"]["count"] == 5
    # summarize() performs that very fold: the serve event's delta
    # completes the distribution in the attempt table (and IS the whole
    # distribution for sessions shorter than the periodic emit interval)
    summary = run_report.summarize(bus.ring_events())
    assert summary["attempts"][0]["metrics"]["serve/latency_s"]["count"] == 5


def test_summarize_folds_serve_only_session_without_periodic_emits():
    bus = EventBus(run_id="ab" * 8, persist=False)
    m = ServeMetrics(bus=bus)  # default 5s interval: no periodic emit fires
    for i in range(3):
        m.record_request_done(0.02 * (i + 1))
    m.emit_event(bus)
    summary = run_report.summarize(bus.ring_events())
    hist = summary["attempts"][0]["metrics"]["serve/latency_s"]
    assert hist["count"] == 3


# -------------------------------------------------- watchdog phase baselines


def _cfg(**kw):
    base = dict(
        window=8, spike_mads=8.0, bad_steps=3, max_rollbacks=3,
        desync_every=0, min_baseline=4,
    )
    base.update(kw)
    return HealthConfig(**base)


def test_per_phase_baselines_cut_cross_phase_false_negatives():
    """After an LR decay drops the loss to ~1.0, a 3.0 excursion is a real
    spike — but judged against the pre-decay ~10.0 window it looks normal.
    Per-phase baselines catch it; the global window cannot."""
    none = np.zeros(8)
    warmup = np.full(8, 10.0) + np.linspace(0, 0.4, 8)
    decay = np.full(8, 1.0) + np.linspace(0, 0.04, 8)
    spiked = decay.copy()
    spiked[1] = 3.0  # early in the epoch, while the window still straddles

    # window 32 and TWO warmup epochs: right after the decay, the global
    # window's majority is still pre-decay samples (the realistic straddle)
    per_phase = Watchdog(_cfg(window=32, phase_baselines=True))
    per_phase.observe_epoch(0, warmup, none, phase="lr=0.1")
    per_phase.observe_epoch(1, warmup + 0.01, none, phase="lr=0.1")
    per_phase.observe_epoch(2, decay, none, phase="lr=0.01")
    verdict = per_phase.observe_epoch(3, spiked, none, phase="lr=0.01")
    assert verdict.spikes == 1

    global_win = Watchdog(_cfg(window=32, phase_baselines=False))
    global_win.observe_epoch(0, warmup, none, phase="lr=0.1")
    global_win.observe_epoch(1, warmup + 0.01, none, phase="lr=0.1")
    global_win.observe_epoch(2, decay, none, phase="lr=0.01")
    verdict = global_win.observe_epoch(3, spiked, none, phase="lr=0.01")
    assert verdict.spikes == 0  # masked by the stale warmup baseline


def test_phase_spike_event_carries_phase_label():
    wd = Watchdog(_cfg())
    none = np.zeros(8)
    base = np.full(8, 1.0) + np.linspace(0, 0.04, 8)
    wd.observe_epoch(0, base, none, phase="lr=0.01")
    spiked = base.copy()
    spiked[3] = 50.0
    wd.observe_epoch(1, spiked, none, phase="lr=0.01")
    (spike_ev,) = [e for e in wd.events if e["kind"] == "spike"]
    assert spike_ev["phase"] == "lr=0.01"


def test_phase_none_and_disabled_share_the_global_window():
    wd = Watchdog(_cfg(phase_baselines=False))
    assert wd._detector_for("lr=0.1") is wd.detector
    assert wd._detector_for(None) is wd.detector
    wd2 = Watchdog(_cfg(phase_baselines=True))
    assert wd2._detector_for(None) is wd2.detector
    assert wd2._detector_for("a") is wd2._detector_for("a")
    assert wd2._detector_for("a") is not wd2._detector_for("b")


# ------------------------------------------------- checkpoint-writer metrics


def test_async_checkpointer_feeds_metric_registry():
    reg = MetricRegistry()
    w = AsyncCheckpointer(metrics=reg)
    try:
        for _ in range(3):
            w.submit(lambda: time.sleep(0.005), key="last")
        w.wait()
    finally:
        w.close()
    snaps = reg.snapshot(reset=False)
    assert snaps["ckpt/jobs"]["n"] == 3
    assert snaps["ckpt/queue_depth"]["value"] == 0  # drained
    # superseded jobs (same key) may collapse; every EXECUTED job records
    assert 1 <= snaps["ckpt/write_s"]["count"] <= 3


# ------------------------------------------------- trainer e2e (acceptance)


@pytest.mark.obs
def test_e2e_metrics_events_and_flight_ring(tmp_path):
    """ISSUE 6 acceptance (single-attempt leg): a real training run emits
    periodic ``metrics`` events whose merged sketches reconstruct the
    per-step grad-norm/loss/step-phase distributions for the attempt, and
    leaves an mmap flight ring that decodes into the black box."""
    from test_train import TinyNet  # noqa: E402 (shared tiny model)

    from distributed_training_comparison_tpu.train import Trainer

    hp = load_config(
        "tpu",
        argv=[
            "--synthetic-data",
            "--limit-examples", "640",  # 576 train -> 18 steps/epoch @32
            "--batch-size", "32",
            "--epoch", "3",
            "--save-last-min-secs", "0",
            "--no-progress",
            "--seed", "7",
            "--eval-step", "1000",
            "--ckpt-path", str(tmp_path),
            "--metrics-flush-steps", "8",
        ],
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    try:
        trainer.fit()
    finally:
        trainer.close()
    vdir = tmp_path / "version-0"

    events = obs.load_events(vdir / "events.jsonl")
    flushes = [e for e in events if e["kind"] == "metrics"]
    assert len(flushes) >= 3  # at least one per epoch
    for ev in events:
        assert obs.validate_event(ev) == [], ev
    merged = merge_metric_events(flushes)
    trained = 3 * 18
    for name in ("train/grad_norm", "train/loss"):
        summ = histogram_summary(merged[name])
        assert summ is not None and summ["count"] == trained, (name, summ)
        # like with like: the quantiles as computed are ordered and clamped
        # to the recorded max; the table rounds them to six digits and
        # leaves max as recorded, so a rounded p99 may exceed it
        q50, q95, q99 = (
            histogram_quantile(merged[name], q) for q in (0.50, 0.95, 0.99)
        )
        assert q50 <= q95 <= q99 <= merged[name]["max"] == summ["max"]
        assert (summ["p50"], summ["p95"], summ["p99"]) == (
            round(q50, 6), round(q95, 6), round(q99, 6)
        )
    # the step-phase sketches ride the same stream (one sample per chunk).
    # The FIRST dispatch carried the epoch runner's jit compile, so the
    # compile monitor's taint reroutes it to step/dispatch_compile_s —
    # the straggler-scored clean sketch sees only compile-free samples
    # (PR 8: a warm-resumed host must not read as fast)
    clean = merged["step/dispatch_s"]["count"]
    tainted = merged.get("step/dispatch_compile_s", {}).get("count", 0)
    assert clean + tainted >= 3 and tainted >= 1, (clean, tainted)
    assert merged["step/compute_s"]["count"] >= 3
    # the checkpoint writer's gauge flushed at least once
    assert "ckpt/queue_depth" in merged

    # run_report folds the same stream into the attempt summary
    summary = run_report.summarize(run_report.load_run(tmp_path)[0])
    a = summary["attempts"][0]
    assert a["metrics"]["train/grad_norm"]["count"] == trained
    assert "train/grad_norm" in run_report.format_summary("r", summary)

    # the SIGKILL-surviving ring: present, intact, ending with the run's
    # final events; the black-box pull decodes it
    ring_path = vdir / ring_filename(0, 0)
    assert ring_path.exists()
    ring_events, torn = decode_ring(ring_path)
    assert torn == 0 and ring_events
    assert all(obs.validate_event(e) == [] for e in ring_events)
    kinds = [e["kind"] for e in ring_events]
    assert "run_end" in kinds and "metrics" in kinds
    box = collect_black_box(tmp_path)
    report = json.loads(box.read_text())
    assert report["rings"] and report["events"]


@pytest.mark.obs
def test_e2e_no_flight_ring_flag_writes_no_ring(tmp_path):
    from test_train import TinyNet  # noqa: E402

    from distributed_training_comparison_tpu.train import Trainer

    hp = load_config(
        "tpu",
        argv=[
            "--synthetic-data", "--limit-examples", "640",
            "--batch-size", "32", "--epoch", "1",
            "--no-progress", "--eval-step", "1000",
            "--ckpt-path", str(tmp_path), "--no-flight-ring",
        ],
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    try:
        trainer.fit()
    finally:
        trainer.close()
    assert not list((tmp_path / "version-0").glob("flight*.ring"))


# ------------------------------------------------------------ config flags


def test_telemetry_flags_defaults_and_validation():
    hp = load_config("tpu", ["--synthetic-data"])
    assert hp.metrics_flush_steps == 50
    assert hp.flight_ring is True
    assert hp.health_phase_baselines is True
    hp = load_config(
        "tpu",
        ["--synthetic-data", "--no-flight-ring", "--metrics-flush-steps", "5"],
    )
    assert hp.flight_ring is False and hp.metrics_flush_steps == 5
    with pytest.raises(SystemExit):
        load_config("tpu", ["--metrics-flush-steps", "0"])
