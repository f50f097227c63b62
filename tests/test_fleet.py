"""Live fleet operations (ISSUE 7): heartbeats + stall classification,
cross-host straggler attribution, resource telemetry, the OpenMetrics
exporter, the alert engine, and the satellites that ride along (per-
attempt clock-skew refit, report-tool forward compatibility, and the
event-kind registry lint).

The load-bearing properties pinned here:

- a lagging host is classified (slow vs dead) and reported as ONE
  ``stall`` transition per state change — never a flap stream;
- straggler attribution names host + phase from the per-process sketch
  streams alone, and a single-host run can never produce a finding;
- the exporter's exposition is strict OpenMetrics (a from-scratch parser
  validates TYPE lines, cumulative ``le`` series, the ``_total`` counter
  suffix, and the ``# EOF`` terminator) and its histogram buckets
  reconstruct the sketch's quantiles;
- alert rules honor their ``for=N`` hysteresis in BOTH directions and
  every emitted kind in the package is registered and documented.
"""

import json
import re
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))

import goodput_report  # noqa: E402
import health_report  # noqa: E402
import run_report  # noqa: E402

from distributed_training_comparison_tpu import obs
from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.obs.alerts import (
    AlertEngine,
    AlertRule,
    AlertSpecError,
)
from distributed_training_comparison_tpu.obs.bus import EventBus
from distributed_training_comparison_tpu.obs.heartbeat import (
    FleetWatcher,
    HeartbeatEmitter,
    LivenessTracker,
)
from distributed_training_comparison_tpu.obs.metrics import (
    Histogram,
    MetricRegistry,
)
from distributed_training_comparison_tpu.obs.resource import ResourceSampler
from distributed_training_comparison_tpu.obs.straggler import (
    host_phase_table,
    straggler_findings,
)

WORKER = Path(__file__).parent / "fleet_worker.py"


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    monkeypatch.delenv(obs.RUN_ID_ENV, raising=False)
    monkeypatch.delenv(obs.ATTEMPT_ENV, raising=False)
    obs.reset()
    yield
    obs.reset()


# -------------------------------------------------------------- heartbeats


def test_heartbeat_cadence_bounds_emission():
    bus = EventBus(run_id="ab" * 8)
    hb = HeartbeatEmitter(bus, every_s=3600.0)  # no second emit this test
    ev = hb.beat(epoch=0, step=1, flush_seq=0)
    assert ev is not None and ev["kind"] == "heartbeat"
    assert obs.validate_event(ev) == []
    assert ev["payload"]["flush_seq"] == 0 and ev["step"] == 1
    for i in range(50):
        assert hb.beat(epoch=0, step=2 + i) is None  # rate-limited
    assert hb.emitted == 1
    assert hb.beat(force=True) is not None  # epoch edges may force
    # ages() reflects the last CALL, not the last emit
    assert hb.ages()["p0"] < 1.0


def test_heartbeat_disabled_emits_nothing_but_tracks_age():
    bus = EventBus(run_id="ab" * 8)
    hb = HeartbeatEmitter(bus, every_s=0.0)
    assert hb.beat(epoch=0, step=1) is None
    assert hb.emitted == 0
    assert "p0" in hb.ages()


def _hb(process_index, t_wall, step=0, attempt=0):
    return {
        "v": 1, "run_id": "ab" * 8, "attempt": attempt,
        "process_index": process_index, "t_wall": t_wall, "t_mono": t_wall,
        "kind": "heartbeat", "epoch": 0, "step": step,
    }


def test_liveness_tracker_slow_dead_recovered_transitions():
    tr = LivenessTracker(heartbeat_s=1.0)  # slow > 3s, dead > 10s
    tr.observe(_hb(0, 0.0, step=100), now=0.0)
    tr.observe(_hb(1, 0.0, step=60), now=0.0)
    assert tr.check(now=1.0) == []  # everyone fresh
    tr.observe(_hb(0, 4.0, step=140), now=4.0)
    findings = tr.check(now=4.5)  # p1 is 4.5s stale -> slow; p0 fresh
    assert [f["process_index"] for f in findings] == [1]
    assert findings[0]["state"] == "slow"
    assert findings[0]["behind_steps"] == 140 - 60
    assert tr.check(now=5.0) == []  # still slow: no re-emission, no flap
    findings = tr.check(now=11.0)
    assert [(f["process_index"], f["state"]) for f in findings] == [
        (0, "slow"), (1, "dead"),
    ]
    tr.observe(_hb(1, 11.5, step=150), now=11.5)
    findings = tr.check(now=12.0)
    assert [(f["process_index"], f["state"]) for f in findings] == [
        (1, "recovered"),
    ]


def test_liveness_any_kind_refreshes_but_only_heartbeats_carry_position():
    tr = LivenessTracker(heartbeat_s=1.0)
    tr.observe(_hb(0, 0.0, step=10), now=0.0)
    ev = dict(_hb(0, 4.0, step=999), kind="epoch_end")
    tr.observe(ev, now=4.0)  # alive...
    assert tr.check(now=4.5) == []
    assert tr._procs[0]["step"] == 10  # ...but position is heartbeat-owned


def test_liveness_ignores_watcher_side_kinds():
    # the supervisor's own stall/alert/attempt events land in the tailed
    # root file as process-0 events; counting them as liveness would make
    # the tracker revive the very host it just called out (observed as a
    # slow→recovered flap loop on a real supervised run)
    tr = LivenessTracker(heartbeat_s=1.0)
    tr.observe(_hb(0, 0.0), now=0.0)
    for kind in ("stall", "straggler", "alert", "attempt_end", "backoff"):
        tr.observe(dict(_hb(0, 5.0), kind=kind), now=5.0)
    assert [f["state"] for f in tr.check(now=5.0)] == ["slow"]  # age is 5s


def test_liveness_no_dead_call_before_first_heartbeat():
    # run_start → first beat can be minutes of jit compile: silence before
    # a process has EVER beaten caps at "slow", never pages "dead"
    tr = LivenessTracker(heartbeat_s=1.0)
    tr.observe(dict(_hb(0, 0.0), kind="run_start"), now=0.0)
    findings = tr.check(now=100.0)
    assert [f["state"] for f in findings] == ["slow"]
    tr.observe(_hb(0, 101.0), now=101.0)  # first beat arrives
    assert [f["state"] for f in tr.check(now=102.0)] == ["recovered"]
    findings = tr.check(now=300.0)  # full silence AFTER a beat escalates
    assert [f["state"] for f in findings] == ["dead"]


def test_fleet_watcher_emits_stall_events_from_files(tmp_path):
    child = EventBus(run_id="ab" * 8, process_index=1)
    child.bind_dir(tmp_path / "version-0")
    child.emit("heartbeat", epoch=0, step=5)
    sup = EventBus(run_id="ab" * 8)
    sup.bind_dir(tmp_path)
    w = FleetWatcher(
        tmp_path, sup, tracker=LivenessTracker(heartbeat_s=1.0)
    )
    t0 = time.monotonic()
    w.step(now=t0)  # consumes the heartbeat; everyone fresh
    w.step(now=t0 + 11.0)  # p1 went silent past dead_after
    # the supervisor's own emits (the stall) also land in the tailed root,
    # but the tracker state machine emits once per transition only
    w.step(now=t0 + 12.0)
    stalls = [
        e for e in obs.load_events(tmp_path / "events.jsonl")
        if e["kind"] == "stall"
    ]
    # p1 raced straight past "slow" to "dead" between polls; the
    # supervisor's own p0 events keep IT alive
    assert [
        (e["payload"]["process_index"], e["payload"]["state"]) for e in stalls
    ] == [(1, "dead")]
    assert all(obs.validate_event(e) == [] for e in stalls)
    child.close()
    sup.close()


# -------------------------------------------------------------- stragglers


def _metrics_event(process_index, phase_values, attempt=0, step=50):
    reg_metrics = {}
    for phase, values in phase_values.items():
        hist = Histogram(f"step/{phase}_s")
        hist.record_many(values)
        reg_metrics[f"step/{phase}_s"] = hist.snapshot()
    return {
        "v": 1, "run_id": "ab" * 8, "attempt": attempt,
        "process_index": process_index, "t_wall": 1.0, "t_mono": 1.0,
        "kind": "metrics", "epoch": 0, "step": step,
        "payload": {"metrics": reg_metrics, "steps": 50},
    }


def test_straggler_attribution_names_host_and_phase():
    rng = np.random.default_rng(0)
    fast = lambda: rng.normal(0.10, 0.005, 40).clip(1e-4)  # noqa: E731
    events = [
        _metrics_event(0, {"dispatch": fast(), "compute": fast()}),
        _metrics_event(1, {"dispatch": fast() * 5, "compute": fast()}),
        _metrics_event(2, {"dispatch": fast(), "compute": fast()}),
    ]
    findings = straggler_findings(events)
    assert len(findings) == 1
    f = findings[0]
    assert (f["process_index"], f["phase"]) == (1, "dispatch")
    assert f["hosts"] == 3 and f["samples"] == 40
    assert f["p95_s"] > f["fleet_p95_s"]


def test_straggler_two_host_fleet_still_attributes():
    # leave-one-out baseline: with a symmetric median/MAD the pair would
    # score each other at exactly 1 MAD and nothing could ever flag
    rng = np.random.default_rng(1)
    fast = rng.normal(0.05, 0.002, 30).clip(1e-4)
    events = [
        _metrics_event(0, {"dispatch": fast}),
        _metrics_event(1, {"dispatch": fast * 8}),
    ]
    findings = straggler_findings(events)
    assert [(f["process_index"], f["phase"]) for f in findings] == [
        (1, "dispatch")
    ]


def test_straggler_balanced_fleet_and_single_host_produce_nothing():
    rng = np.random.default_rng(2)
    mk = lambda: rng.normal(0.1, 0.01, 30).clip(1e-4)  # noqa: E731
    balanced = [
        _metrics_event(p, {"dispatch": mk(), "h2d_wait": mk()})
        for p in range(4)
    ]
    assert straggler_findings(balanced) == []
    solo = [_metrics_event(0, {"dispatch": mk() * 100})]
    assert straggler_findings(solo) == []


def test_straggler_merges_across_flush_windows_per_host():
    # two flushes per host merge associatively before scoring
    rng = np.random.default_rng(3)
    fast = lambda: rng.normal(0.1, 0.005, 10).clip(1e-4)  # noqa: E731
    events = [
        _metrics_event(0, {"dispatch": fast()}, step=10),
        _metrics_event(0, {"dispatch": fast()}, step=20),
        _metrics_event(1, {"dispatch": fast() * 6}, step=10),
        _metrics_event(1, {"dispatch": fast() * 6}, step=20),
    ]
    table = host_phase_table(events)
    assert table[0][1]["dispatch"]["count"] == 20
    findings = straggler_findings(events)
    assert [(f["process_index"], f["samples"]) for f in findings] == [(1, 20)]


def test_straggler_events_and_report_table(tmp_path):
    rng = np.random.default_rng(4)
    fast = lambda: rng.normal(0.1, 0.005, 30).clip(1e-4)  # noqa: E731
    events = [
        _metrics_event(0, {"dispatch": fast()}),
        _metrics_event(1, {"dispatch": fast() * 7}),
    ]
    bus = EventBus(run_id="ab" * 8)
    bus.bind_dir(tmp_path)
    found = obs.emit_straggler_events(bus, events)
    assert len(found) == 1
    logged = [
        e for e in obs.load_events(tmp_path / "events.jsonl")
        if e["kind"] == "straggler"
    ]
    assert len(logged) == 1
    assert obs.validate_event(logged[0]) == []
    assert logged[0]["payload"]["process_index"] == 1
    # run_report's per-host table flags the same host+phase
    summary = run_report.summarize(events + logged)
    text = run_report.format_summary("r", summary)
    assert "per-host step phases" in text
    assert re.search(r"straggler: attempt 0 process 1 phase dispatch", text)
    bus.close()


# ---------------------------------------------------------------- resources


def test_resource_sampler_records_host_gauges(tmp_path):
    reg = MetricRegistry()
    sampler = ResourceSampler(ckpt_root=tmp_path)
    values = sampler.sample(reg)
    # linux CI: RSS, fds, and disk-free must all be present and sane
    assert values["res/host_rss_bytes"] > 1e6
    assert values["res/open_fds"] >= 3
    assert values["res/disk_free_bytes"] > 0
    snaps = reg.snapshot(reset=False)
    assert snaps["res/open_fds"]["type"] == "gauge"
    # the CPU CI backend reports no HBM stats — the gauge is absent, not 0
    # (on a TPU host the same call yields res/hbm_used_bytes)
    from distributed_training_comparison_tpu._compat import device_memory_stats
    import jax

    if device_memory_stats(jax.local_devices()[0]) is None:
        assert "res/hbm_used_bytes" not in values


def test_resource_sampler_no_ckpt_root_skips_disk():
    values = ResourceSampler().read()
    assert "res/disk_free_bytes" not in values
    assert "res/host_rss_bytes" in values


def test_resource_sampler_rate_limits_but_gauges_persist(tmp_path):
    reg = MetricRegistry()
    sampler = ResourceSampler(ckpt_root=tmp_path, min_interval_s=3600.0)
    assert sampler.sample(reg)  # first call always reads
    assert sampler.sample(reg) == {}  # within the interval: skipped
    assert sampler.samples == 1
    # the registry still carries the last sample on every later flush
    # (gauges are not reset by snapshot)
    assert reg.snapshot(reset=True)["res/open_fds"]["type"] == "gauge"
    assert "res/open_fds" in reg.snapshot(reset=False)


# ------------------------------------------------- OpenMetrics exposition


def parse_openmetrics(text: str) -> dict:
    """Strict-ish OpenMetrics parser: validates the exposition structure
    and returns {family: {"type": t, "samples": {name+labels: value}}}.
    Raises AssertionError on any violation."""
    assert text.endswith("# EOF\n"), "must terminate with # EOF"
    families: dict = {}
    current = None
    sample_re = re.compile(
        r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(-?[0-9.eE+]+|[+-]Inf|NaN)$'
    )
    for line in text.splitlines()[:-1]:  # all but "# EOF"
        assert line.strip() == line and line, f"stray whitespace: {line!r}"
        if line.startswith("# TYPE "):
            _, _, name, mtype = line.split(" ")
            assert mtype in ("counter", "gauge", "histogram"), line
            assert name not in families, f"duplicate family {name}"
            current = name
            families[name] = {"type": mtype, "samples": {}}
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        m = sample_re.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name, labels, value = m.group(1), m.group(2) or "", m.group(3)
        assert current is not None, f"sample before any TYPE: {line!r}"
        mtype = families[current]["type"]
        if mtype == "counter":
            assert name == current + "_total", (
                f"counter sample must be {current}_total, got {name}"
            )
        elif mtype == "gauge":
            assert name == current, line
        else:
            assert name in (
                current + "_bucket", current + "_count", current + "_sum"
            ), f"histogram sample {name} outside family {current}"
            if name == current + "_bucket":
                assert 'le="' in labels, f"bucket without le: {line!r}"
        families[current]["samples"][name + labels] = float(value)
    # histogram invariants: cumulative non-decreasing buckets ending +Inf,
    # with _count equal to the +Inf bucket
    for fam, rec in families.items():
        if rec["type"] != "histogram":
            continue
        buckets = [
            (k, v) for k, v in rec["samples"].items()
            if k.startswith(fam + "_bucket")
        ]
        assert buckets and buckets[-1][0].endswith('le="+Inf"}'), (
            f"{fam}: last bucket must be +Inf"
        )
        counts = [v for _k, v in buckets]
        assert counts == sorted(counts), f"{fam}: buckets must be cumulative"
        assert rec["samples"][fam + "_count"] == counts[-1]
    return families


def test_render_openmetrics_strict_and_quantile_roundtrip():
    reg = MetricRegistry(flush_steps=4)
    rng = np.random.default_rng(0)
    samples = rng.lognormal(0.0, 1.0, 4000)
    reg.histogram("train/loss").record_many(samples)
    reg.counter("train/skipped_steps").inc(3)
    reg.gauge("res/open_fds").set(41)
    bus = EventBus(run_id="ab" * 8)
    reg.note_steps(4)
    reg.flush(bus, epoch=0)
    reg.histogram("train/loss").record_many(samples)  # pending window

    fams = parse_openmetrics(
        obs.render_openmetrics(
            reg.cumulative_snapshot(), {"p0": 0.5}, {"rule:p99>1": False}
        )
    )
    assert fams["dtc_train_skipped_steps"]["samples"][
        "dtc_train_skipped_steps_total"
    ] == 3
    assert fams["dtc_res_open_fds"]["samples"]["dtc_res_open_fds"] == 41
    hist = fams["dtc_train_loss"]["samples"]
    assert hist["dtc_train_loss_count"] == 2 * len(samples)  # cumulative
    assert fams["dtc_heartbeat_age_seconds"]["samples"][
        'dtc_heartbeat_age_seconds{process="0"}'
    ] == 0.5
    assert fams["dtc_alert_firing"]["samples"][
        'dtc_alert_firing{spec="rule:p99>1"}'
    ] == 0
    # p95 reconstructed from the RENDERED buckets matches the exact one
    # within the sketch's bucket-ratio error
    les, counts = [], []
    for key, v in hist.items():
        m = re.search(r'le="([^"]+)"', key)
        if m and m.group(1) != "+Inf":
            les.append(float(m.group(1)))
            counts.append(v)
    order = np.argsort(les)
    les, counts = np.asarray(les)[order], np.asarray(counts)[order]
    rank = 0.95 * hist["dtc_train_loss_count"]
    p95_rendered = les[np.searchsorted(counts, rank)]
    assert abs(p95_rendered - np.quantile(samples, 0.95)) / p95_rendered < 0.2


def test_render_openmetrics_zeros_count_into_every_bucket():
    h = Histogram("x")
    h.record_many([0.0, 0.0, 5.0])
    fams = parse_openmetrics(
        obs.render_openmetrics({"x": h.snapshot()})
    )
    samples = fams["dtc_x"]["samples"]
    first_bucket = min(
        (k for k in samples if "_bucket{" in k and "+Inf" not in k),
        key=lambda k: float(re.search(r'le="([^"]+)"', k).group(1)),
    )
    assert samples[first_bucket] == 3  # the two zeros sit below every le
    assert samples["dtc_x_count"] == 3


def test_exporter_http_scrape_and_404():
    reg = MetricRegistry()
    reg.gauge("res/open_fds").set(7)
    hb = HeartbeatEmitter(EventBus(run_id="ab" * 8), every_s=60)
    hb.beat()
    exp = obs.MetricsExporter(port=0, registry=reg, heartbeats=hb).start()
    try:
        url = f"http://127.0.0.1:{exp.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as r:
            assert r.headers["Content-Type"].startswith(
                "application/openmetrics-text"
            )
            body = r.read().decode()
        fams = parse_openmetrics(body)
        assert fams["dtc_res_open_fds"]["samples"]["dtc_res_open_fds"] == 7
        assert "dtc_heartbeat_age_seconds" in fams
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/nope", timeout=5
            )
        assert exp.scrapes == 1
    finally:
        exp.close()
    # closed: the port no longer accepts
    with pytest.raises(OSError):
        socket.create_connection(("127.0.0.1", exp.port), timeout=0.5)


def test_start_exporter_flag_semantics():
    assert obs.start_exporter(0) is None  # 0 = off
    reg = MetricRegistry()
    exp = obs.start_exporter(_free_port(), process_index=0, registry=reg)
    try:
        assert exp is not None
        # a second process on the same base port gets port+1
        exp2 = obs.start_exporter(exp.port, process_index=1, registry=reg)
        try:
            assert exp2 is not None and exp2.port == exp.port + 1
        finally:
            if exp2 is not None:
                exp2.close()
        # a taken port returns None instead of raising
        assert obs.start_exporter(exp.port, process_index=0) is None
    finally:
        exp.close()


def test_start_exporter_port_overflow_degrades_to_none():
    # a valid base port on a wide host: 65535 + process_index overflows
    # bind()'s range — must degrade like a taken port, not kill training
    assert obs.start_exporter(65535, process_index=7) is None


def test_cumulative_snapshot_is_monotone_across_concurrent_flushes():
    # a scrape racing flush's reset-then-fold must never see a counter dip
    reg = MetricRegistry(flush_steps=1)
    bus = EventBus(run_id="ab" * 8)
    stop = threading.Event()
    dips = []

    def scraper():
        last = 0
        while not stop.is_set():
            snap = reg.cumulative_snapshot().get("c")
            n = (snap or {}).get("n", 0)
            if n < last:
                dips.append((last, n))
            last = n

    t = threading.Thread(target=scraper, daemon=True)
    t.start()
    for i in range(300):
        reg.counter("c").inc(1)
        reg.note_steps(1)
        reg.flush(bus, step=i)
    stop.set()
    t.join(timeout=10)
    assert not dips, dips
    assert reg.cumulative_snapshot()["c"]["n"] == 300


def test_alert_ticker_fires_age_rule_without_manual_ticks():
    bus = EventBus(run_id="ab" * 8)
    hb = HeartbeatEmitter(bus, every_s=60)
    hb.beat()
    eng = AlertEngine(
        [AlertRule.parse("heartbeat:age>0.1:for=1")],
        bus=bus, heartbeats=hb,
    )
    eng.start_ticker(interval_s=0.05)
    try:
        deadline = time.monotonic() + 10.0
        while not eng.firing and time.monotonic() < deadline:
            time.sleep(0.05)  # the monitored thread "hangs" (never ticks)
        assert eng.firing
    finally:
        eng.close()


def test_export_openmetrics_any_firing_source_wins(tmp_path):
    # p0 fired and resolved LAST in the stream; p1 is still firing — the
    # exported state must be firing (per-source OR, not last-writer-wins)
    _write_events(
        tmp_path / "events.jsonl",
        [
            _alert_ev("firing", source="p1", t=1.0),
            _alert_ev("firing", source="p0", t=2.0),
            _alert_ev("resolved", source="p0", t=3.0),
        ],
    )
    fams = parse_openmetrics(run_report.export_openmetrics(tmp_path))
    assert fams["dtc_alert_firing"]["samples"][
        'dtc_alert_firing{spec="x:p99>1:for=1"}'
    ] == 1


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


# -------------------------------------------------------------------- alerts


def test_alert_spec_parse_good_and_bad():
    r = AlertRule.parse("serve/latency_s:p99>0.25:for=3")
    assert (r.metric, r.agg, r.cmp, r.threshold, r.for_windows) == (
        "serve/latency_s", "p99", ">", 0.25, 3
    )
    r2 = AlertRule.parse("res/disk_free_bytes:value<1e9")
    assert r2.for_windows == 1 and r2.cmp == "<" and r2.threshold == 1e9
    assert AlertRule.parse("heartbeat:age>30").on_heartbeat
    for bad in (
        "nonsense", "m:p99", "m:p99>x", "m:bogus>1", "heartbeat:p99>1",
        "train/loss:age>1", "m:p99>1:for=z",
    ):
        with pytest.raises(AlertSpecError):
            AlertRule.parse(bad)
    # and the CLI rejects them before any training starts
    with pytest.raises(SystemExit):
        load_config("tpu", ["--synthetic-data", "--alert", "m:bogus>1"])


def _flush_ev(metric, snap, process_index=0, step=0):
    return {
        "v": 1, "run_id": "ab" * 8, "attempt": 0,
        "process_index": process_index, "t_wall": 1.0, "t_mono": 1.0,
        "kind": "metrics", "step": step,
        "payload": {"metrics": {metric: snap}},
    }


def _gauge(v):
    return {"type": "gauge", "value": v}


def test_alert_engine_for_hysteresis_both_directions(tmp_path):
    bus = EventBus(run_id="ab" * 8)
    bus.bind_dir(tmp_path)
    eng = AlertEngine([AlertRule.parse("res/open_fds:value>100:for=3")], bus=bus)
    for i, v in enumerate((150, 160, 120)):  # 3 consecutive breaches
        eng.observe_event(_flush_ev("res/open_fds", _gauge(v), step=i))
        assert eng.firing == (i == 2)  # fires exactly on the 3rd
    eng.observe_event(_flush_ev("res/open_fds", _gauge(50), step=3))
    assert eng.firing  # one clean window is NOT a resolve yet
    eng.observe_event(_flush_ev("res/open_fds", _gauge(200), step=4))
    eng.observe_event(_flush_ev("res/open_fds", _gauge(40), step=5))
    eng.observe_event(_flush_ev("res/open_fds", _gauge(40), step=6))
    assert eng.firing  # breach reset the clean count
    eng.observe_event(_flush_ev("res/open_fds", _gauge(40), step=7))
    assert not eng.firing  # 3 consecutive clean windows resolve
    events = obs.load_events(tmp_path / "events.jsonl")
    states = [e["payload"]["state"] for e in events if e["kind"] == "alert"]
    assert states == ["firing", "resolved"]
    assert all(
        obs.validate_event(e) == [] for e in events if e["kind"] == "alert"
    )
    bus.close()


def test_alert_engine_histogram_quantile_and_per_process_sources():
    h_fast, h_slow = Histogram("l"), Histogram("l")
    h_fast.record_many(np.full(100, 0.01))
    h_slow.record_many(np.full(100, 0.9))
    eng = AlertEngine([AlertRule.parse("serve/latency_s:p99>0.25:for=1")])
    eng.observe_event(
        _flush_ev("serve/latency_s", h_fast.snapshot(), process_index=0)
    )
    eng.observe_event(
        _flush_ev("serve/latency_s", h_slow.snapshot(), process_index=1)
    )
    assert eng.firing
    # host 1 breached; host 0's clean window did not average it away
    assert [t["source"] for t in eng.transitions] == ["p1"]


def test_alert_engine_serve_record_latency_delta_counts():
    h = Histogram("l")
    h.record_many(np.full(50, 0.5))
    eng = AlertEngine([AlertRule.parse("serve/latency_s:p95>0.25:for=1")])
    eng.observe_event({
        "v": 1, "run_id": "ab" * 8, "attempt": 0, "process_index": 0,
        "t_wall": 1.0, "t_mono": 1.0, "kind": "serve",
        "payload": {"completed": 50, "latency_hist": h.snapshot()},
    })
    assert eng.firing


def test_alert_engine_heartbeat_age_rule_via_tick():
    tr = LivenessTracker(heartbeat_s=1.0)
    tr.observe(_hb(0, 0.0), now=0.0)
    tr.observe(_hb(1, 0.0), now=0.0)
    eng = AlertEngine(
        [AlertRule.parse("heartbeat:age>30:for=1")], heartbeats=tr
    )
    eng.tick(now=10.0)
    assert not eng.firing
    tr.observe(_hb(0, 35.0), now=35.0)  # p0 alive, p1 silent
    eng.tick(now=36.0)
    assert eng.states() == {"heartbeat:age>30:for=1": True}
    assert [t["source"] for t in eng.transitions] == ["p1"]
    tr.observe(_hb(1, 37.0), now=37.0)
    eng.tick(now=38.0)
    assert not eng.firing
    assert [t["state"] for t in eng.transitions] == ["firing", "resolved"]


def test_bus_subscription_feeds_engine_without_recursion(tmp_path):
    bus = EventBus(run_id="ab" * 8)
    bus.bind_dir(tmp_path)
    eng = AlertEngine([AlertRule.parse("res/open_fds:value>10:for=1")], bus=bus)
    bus.subscribe(eng.observe_event)
    reg = MetricRegistry(flush_steps=1)
    reg.gauge("res/open_fds").set(99)
    reg.note_steps(1)
    reg.flush(bus, epoch=0)  # emit -> tap -> engine -> alert emit (no loop)
    kinds = [e["kind"] for e in obs.load_events(tmp_path / "events.jsonl")]
    assert kinds == ["metrics", "alert"]
    bus.close()


# -------------------------------------------- run_report --alerts / export


def _write_events(path, events):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text("".join(json.dumps(e) + "\n" for e in events))


def _alert_ev(state, spec="x:p99>1:for=1", source="p1", t=1.0):
    return {
        "v": 1, "run_id": "ab" * 8, "attempt": 0, "process_index": 0,
        "t_wall": t, "t_mono": t, "kind": "alert",
        "payload": {
            "spec": spec, "metric": spec.split(":")[0], "state": state,
            "value": 2.0, "threshold": 1.0, "source": source,
        },
    }


def test_run_report_alerts_exit_codes(tmp_path, capsys):
    fired = tmp_path / "fired"
    _write_events(
        fired / "events.jsonl",
        [_alert_ev("firing", t=1.0)],
    )
    assert run_report.main([str(fired), "--alerts"]) == 1
    out = capsys.readouterr().out
    assert "FIRING" in out and "x:p99>1" in out

    resolved = tmp_path / "resolved"
    _write_events(
        resolved / "events.jsonl",
        [_alert_ev("firing", t=1.0), _alert_ev("resolved", t=2.0)],
    )
    assert run_report.main([str(resolved), "--alerts"]) == 0

    quiet = tmp_path / "quiet"
    _write_events(quiet / "events.jsonl", [_hb(0, 1.0)])
    assert run_report.main([str(quiet), "--alerts"]) == 0


def test_run_report_export_openmetrics_offline(tmp_path, capsys):
    h = Histogram("train/loss")
    h.record_many([1.0, 2.0, 4.0])
    _write_events(
        tmp_path / "version-0" / "events.jsonl",
        [
            _flush_ev("train/loss", h.snapshot(), step=10),
            _hb(0, t_wall=5.0),
            _alert_ev("firing", t=6.0),
        ],
    )
    out_file = tmp_path / "metrics.om"
    run_report.main(
        [str(tmp_path), "--export-openmetrics", str(out_file)]
    )
    fams = parse_openmetrics(out_file.read_text())
    assert fams["dtc_train_loss"]["samples"]["dtc_train_loss_count"] == 3
    assert "dtc_heartbeat_age_seconds" in fams
    assert fams["dtc_alert_firing"]["samples"][
        'dtc_alert_firing{spec="x:p99>1:for=1"}'
    ] == 1


# ------------------------------------------------ satellites: clock skew


def _anchor(process_index, attempt, t_wall):
    return {
        "v": 1, "run_id": "ab" * 8, "attempt": attempt,
        "process_index": process_index, "t_wall": t_wall, "t_mono": t_wall,
        "kind": "run_start",
    }


def test_skew_refit_per_attempt_tracks_drift():
    # attempt 0: host 1 is +5s; attempt 1 (a day of drift later): +9s —
    # one constant per host would mis-place one attempt by 4s
    events = []
    for attempt, skew in ((0, 5.0), (1, 9.0)):
        t = 100.0 * (attempt + 1)
        events += [
            _anchor(0, attempt, t),
            _anchor(1, attempt, t + skew),
            dict(_hb(0, t + 10.0, attempt=attempt), kind="epoch_end"),
            dict(_hb(1, t + 10.0 + skew, attempt=attempt), kind="epoch_end"),
        ]
    offsets = run_report.estimate_clock_skew_by_attempt(events)
    assert offsets[(1, 0)] == pytest.approx(5.0)
    assert offsets[(1, 1)] == pytest.approx(9.0)
    assert offsets[(1, None)] == pytest.approx(7.0)  # the fallback median
    shifted = run_report.apply_clock_skew(events, offsets)
    for ev in shifted:
        if ev["process_index"] == 1:
            base = 100.0 * (ev["attempt"] + 1)
            expect = base if ev["kind"] == "run_start" else base + 10.0
            assert ev["t_wall"] == pytest.approx(expect)
    # an attempt that died pre-anchor falls back to the across-attempt fit
    orphan = dict(_hb(1, 310.0, attempt=2), kind="epoch_end")
    [shifted_orphan] = run_report.apply_clock_skew([orphan], offsets)
    assert shifted_orphan["t_wall"] == pytest.approx(310.0 - 7.0)
    # the legacy per-process shape still applies (older callers/tests)
    legacy = run_report.estimate_clock_skew(events)
    assert legacy[1] == pytest.approx(7.0)
    assert run_report.apply_clock_skew([orphan], legacy)[0][
        "t_wall"
    ] == pytest.approx(310.0 - 7.0)


# ------------------------- satellites: report-tool forward compatibility


def test_goodput_report_skips_future_kinds(tmp_path):
    events = [
        _hb(0, 1.0),
        _alert_ev("firing"),
        {
            "v": 1, "run_id": "ab" * 8, "attempt": 0, "process_index": 0,
            "t_wall": 2.0, "t_mono": 2.0, "kind": "goodput",
            "payload": {"step_s": 6.0, "wall_s": 10.0},
        },
        dict(_hb(0, 3.0), kind="kind_from_the_future"),
    ]
    path = tmp_path / "events.jsonl"
    _write_events(path, events)
    rep = goodput_report.load_report(path)
    assert rep["attempts"] == 1  # exactly the one goodput record
    assert rep["productive_s"] == pytest.approx(6.0)


def test_health_report_skips_future_kinds(tmp_path, capsys):
    events = [
        dict(_hb(0, 1.0), kind="skip", payload={"count": 2}),
        _hb(0, 2.0),
        _alert_ev("firing"),
        dict(_hb(0, 3.0), kind="kind_from_the_future", payload={"x": 1}),
        dict(_hb(0, 4.0), kind="rollback", payload={"wasted_steps": 9}),
    ]
    path = tmp_path / "health.jsonl"
    _write_events(path, events)
    rep = health_report.load_report(path)
    assert rep["skipped_steps"] == 2 and rep["rollbacks"] == 1
    assert health_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    # unknown kinds are condensed, not echoed and never fatal
    assert "kind_from_the_future×1" in out
    assert "heartbeat×1" in out


def test_run_report_summarize_tolerates_future_kind():
    events = [
        _hb(0, 1.0),
        dict(_hb(0, 2.0), kind="run_start"),
        dict(_hb(0, 3.0), kind="kind_from_the_future", payload={"x": 1}),
    ]
    s = run_report.summarize(events)
    assert s["attempts"][0]["heartbeats"] == 1
    assert "kind_from_the_future" in run_report.format_timeline(events)


# ------------------------------------ satellite: event-kind registry lint


def test_every_emitted_kind_is_registered_and_documented():
    pkg_root = Path(obs.__file__).resolve().parent.parent
    emit_re = re.compile(
        r"""(?:\bemit|\b_events?)\(\s*\n?\s*["']([a-z_]+)["']"""
    )
    const_re = re.compile(r"""^[A-Z_]*KIND\s*=\s*["']([a-z_]+)["']""", re.M)
    emitted: set[str] = set()
    for py in sorted(pkg_root.rglob("*.py")):
        src = py.read_text()
        emitted |= set(emit_re.findall(src))
        emitted |= set(const_re.findall(src))
    # sanity: the scan actually sees the emitters (old, new, and constants)
    for expected in ("run_start", "heartbeat", "stall", "skip", "metrics",
                     "attempt_start", "serve", "alert", "straggler"):
        assert expected in emitted, f"scan lost {expected}"
    unregistered = emitted - obs.KNOWN_KINDS
    assert not unregistered, (
        f"kinds emitted but not in obs.bus.KNOWN_KINDS: {unregistered} — "
        "register them (and document them in the README kind table)"
    )
    readme = (pkg_root.parent / "README.md").read_text()
    kind_row = next(
        line for line in readme.splitlines()
        if line.startswith("| `kind` |")
    )
    undocumented = {
        k for k in obs.KNOWN_KINDS if f"`{k}`" not in kind_row
        # epoch_start/end share one `epoch_start/end` cell, attempt_* too
        and not (
            k in ("epoch_start", "epoch_end") and "`epoch_start/end`" in kind_row
        )
        and not (
            k in ("attempt_start", "attempt_end")
            and "`attempt_start/end`" in kind_row
        )
    }
    assert not undocumented, (
        f"kinds registered but missing from the README kind table: "
        f"{undocumented}"
    )


# ---------------------------------------------------- trainer + e2e legs


def test_trainer_heartbeats_resources_and_exporter(tmp_path):
    """In-process acceptance leg: a real training run emits heartbeats,
    samples the resource gauges into its flushes, and serves OpenMetrics
    on --metrics-port, scraped over HTTP while the trainer is live."""
    from test_train import TinyNet  # noqa: E402

    from distributed_training_comparison_tpu.train import Trainer

    port = _free_port()
    hp = load_config(
        "tpu",
        argv=[
            "--synthetic-data", "--limit-examples", "640",
            "--batch-size", "32", "--epoch", "2",
            "--save-last-min-secs", "0", "--no-progress",
            "--seed", "7", "--eval-step", "1000",
            "--ckpt-path", str(tmp_path),
            "--metrics-flush-steps", "8",
            "--heartbeat-secs", "0.01",
            "--metrics-port", str(port),
            "--device-chunk-steps", "6",
        ],
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    scrape: dict = {}

    def scraper():
        # retry until the exposition carries liveness (the first beat
        # lands only after the first chunk dispatch compiles)
        url = f"http://127.0.0.1:{trainer.exporter.port}/metrics"
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(url, timeout=2) as r:
                    body = r.read().decode()
                if "dtc_heartbeat_age_seconds" in body:
                    scrape["body"] = body
                    return
            except OSError:
                pass
            time.sleep(0.1)

    try:
        assert trainer.exporter is not None and trainer.exporter.port == port
        t = threading.Thread(target=scraper, daemon=True)
        t.start()
        trainer.fit()
        t.join(timeout=60)
        # the live endpoint served a strict exposition during/after fit
        fams = parse_openmetrics(scrape["body"])
        assert "dtc_heartbeat_age_seconds" in fams
        # the post-fit registry view carries everything cumulative
        final = parse_openmetrics(trainer.exporter.render())
        assert final["dtc_train_loss"]["samples"]["dtc_train_loss_count"] == 36
        assert "dtc_res_host_rss_bytes" in final
        assert trainer.heartbeat.emitted >= 2
    finally:
        trainer.close()
    # exporter is down with the trainer
    with pytest.raises(OSError):
        socket.create_connection(("127.0.0.1", port), timeout=0.5)

    events = obs.load_events(tmp_path / "version-0" / "events.jsonl")
    assert all(obs.validate_event(e) == [] for e in events)
    beats = [e for e in events if e["kind"] == "heartbeat"]
    assert beats and all("flush_seq" in e["payload"] for e in beats)
    flushes = [e for e in events if e["kind"] == "metrics"]
    merged = {
        name
        for e in flushes
        for name in (e["payload"].get("metrics") or {})
    }
    assert "res/host_rss_bytes" in merged and "res/open_fds" in merged
    assert "res/disk_free_bytes" in merged


@pytest.mark.obs
def test_e2e_supervised_fleet_with_injected_slow_host(tmp_path):
    """ISSUE 7 acceptance: a supervised run whose attempt carries an
    injected per-host slowdown (fleet_worker emulates host 1 at the
    file level: slowed dispatch sketches, then a dead-then-recovered
    silence) produces straggler attribution naming host 1 + dispatch, a
    stall call for host 1, a firing→resolved heartbeat-age alert pair on
    the merged timeline, a still-firing dispatch alert that makes
    ``run_report --alerts`` exit nonzero, and a timeline that passes
    ``--check``."""
    root = tmp_path / "run"
    cmd = [
        sys.executable, str(WORKER), "--supervise",
        "--synthetic-data", "--limit-examples", "640",
        "--batch-size", "32", "--epoch", "2",
        "--no-progress", "--eval-step", "1000",
        "--save-last-min-secs", "0", "--seed", "7",
        "--ckpt-path", str(root),
        "--metrics-flush-steps", "6",
        "--device-chunk-steps", "3",
        "--heartbeat-secs", "0.2",
        "--goodput-json", str(tmp_path / "GOODPUT.json"),
        "--alert", "step/dispatch_s:p95>0.2:for=1",
        "--alert", "heartbeat:age>2:for=1",
    ]
    proc = subprocess.run(
        cmd, cwd=WORKER.parent.parent, capture_output=True, text=True,
        timeout=420,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "Traceback" not in (proc.stderr or ""), proc.stderr[-3000:]

    events, _files = run_report.load_run(root)
    kinds = {e["kind"] for e in events}
    assert {"heartbeat", "metrics", "stall", "straggler", "alert"} <= kinds

    # stall: the emulated host 1 was called slow and/or dead, then recovered
    stalls = [
        e["payload"] for e in events
        if e["kind"] == "stall" and e["payload"].get("process_index") == 1
    ]
    assert any(s["state"] in ("slow", "dead") for s in stalls), stalls
    assert any(s["state"] == "recovered" for s in stalls), stalls

    # straggler attribution names the right host AND phase
    stragglers = [e["payload"] for e in events if e["kind"] == "straggler"]
    assert [(s["process_index"], s["phase"]) for s in stragglers] == [
        (1, "dispatch")
    ], stragglers

    # the heartbeat-age alert fired during the silence and resolved on the
    # recovery beat — a firing/resolved pair for source p1 on the timeline
    hb_alerts = [
        e["payload"] for e in events
        if e["kind"] == "alert" and e["payload"]["metric"] == "heartbeat"
        and e["payload"].get("source") == "p1"
    ]
    assert [a["state"] for a in hb_alerts] == ["firing", "resolved"], hb_alerts
    # the dispatch-latency alert fired on host 1's slowed sketch and never
    # saw a clean window — still firing, so --alerts gates nonzero
    disp_alerts = [
        e["payload"] for e in events
        if e["kind"] == "alert" and e["payload"]["metric"] == "step/dispatch_s"
    ]
    assert disp_alerts and disp_alerts[-1]["state"] == "firing"
    assert run_report.main([str(root), "--alerts"]) == 1

    # the merged stream stays schema-clean and the summary renders the
    # per-host table with host 1 flagged
    assert run_report.main([str(root), "--check"]) == 0
    text = run_report.format_summary("e2e", run_report.summarize(events))
    assert "straggler: attempt 0 process 1 phase dispatch" in text
    assert "heartbeats:" in text
