"""Multi-process serve fleet tests (ISSUE 17): the process-per-replica
transport and the queueing-aware autoscaler, plus the satellites.

The load-bearing properties pinned here:

- **Wire protocol** (`serve/fleet/transport.py`): a frame round-trips
  header + ndarray body bitwise; torn and oversized frames fail typed
  (``FleetTransportError``), never hang; the per-replica request and
  exporter ports are deterministic and disjoint (the satellite fix for
  N processes colliding on one ``--metrics-port``).
- **Client contract**: a worker-relayed engine error surfaces as a
  typed ``RuntimeError`` (batch fails, replica lives); a vanished peer
  surfaces as ``FleetTransportError`` (batch requeues, supervisor
  relaunches) — the dispatcher's two recovery paths fork on exactly
  this distinction.
- **Autoscaler math** (`serve/fleet/autoscale.py`), in isolation from
  any fleet: the G/G/m fit sizes to the smallest m meeting every p99
  target, degrades explicitly (utilization rule on thin reservoirs,
  hold on no data), and the control loop's hysteresis — immediate up,
  reluctant down, cooldown between applies — is clock-driven and
  deterministic under a fake clock.
- **`scale_serve` autopilot action**: parses, stays dry-run by
  default, spends the policy budget, and is honestly ``unbound``
  without an autoscaler.
- **Requeue-on-death** (`ClassQueue.requeue`): undispatched entries
  return to the FRONT of their lanes (age order preserved), resolved
  futures are skipped, a closed queue fails them typed — a replica
  crash costs latency, not requests.
- **Thread-transport twins**: every fleet-resize behavior
  (``scale_to`` / ``scale_down`` LIFO / ``active_replicas`` / the live
  ticker driving the autoscaler) runs fast in tier-1 against stub
  engines; the REAL process spawn e2e (worker handshake, socket serve,
  kill-mid-stream requeue + supervisor restart) is slow-marked.
"""

import json
import os
import signal
import socket
import struct
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))

import run_report  # noqa: E402

from distributed_training_comparison_tpu import obs
from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.ops import policy as P
from distributed_training_comparison_tpu.serve import (
    ClassQueue,
    ServeMetrics,
    ServeRouter,
    fold_seed,
    request_pool,
)
from distributed_training_comparison_tpu.serve.batcher import BatcherClosed
from distributed_training_comparison_tpu.serve.fleet import (
    Autoscaler,
    FleetTransportError,
    ProcessReplica,
    ReplicaClient,
    decode_array,
    encode_array,
    parse_scale_targets,
    read_handshake,
    recv_msg,
    render_worker_env,
    replica_metrics_port,
    replica_port,
    send_msg,
    size_for_targets,
    worker_hparams_dict,
    wq_ggm,
)
from distributed_training_comparison_tpu.serve.router import READY, STOPPED

from test_policy import FakeBus, _alert
from test_serve_fleet import _StubEngine, _bus, _img, _wait


# ----------------------------------------------------------- the protocol


def test_frame_roundtrip_carries_arrays_bitwise():
    a, b = socket.socketpair()
    try:
        imgs = np.random.default_rng(0).integers(
            0, 256, size=(3, 8, 8, 3), dtype=np.uint8
        )
        meta, body = encode_array(imgs)
        send_msg(a, {"op": "submit", "tag": 7, **meta}, body)
        header, rbody = recv_msg(b)
        assert header["op"] == "submit" and header["tag"] == 7
        out = decode_array(header, rbody)
        assert out.dtype == np.uint8 and np.array_equal(out, imgs)
        # a body-less control frame rides the same framing
        send_msg(b, {"op": "health"})
        header2, rbody2 = recv_msg(a)
        assert header2 == {"op": "health"} and rbody2 == b""
    finally:
        a.close()
        b.close()


def test_torn_and_oversized_frames_fail_typed():
    a, b = socket.socketpair()
    try:
        # oversized: a length prefix past MAX_FRAME is a protocol error,
        # not a big batch the receiver should try to allocate
        a.sendall(struct.pack("!II", 1 << 31, 0))
        with pytest.raises(FleetTransportError):
            recv_msg(b)
    finally:
        a.close()
        b.close()
    a, b = socket.socketpair()
    try:
        # torn: peer vanishes mid-message
        a.sendall(struct.pack("!II", 100, 0) + b'{"op":')
        a.close()
        with pytest.raises(FleetTransportError):
            recv_msg(b)
    finally:
        b.close()
    # a mis-shaped body never silently reshapes
    with pytest.raises(FleetTransportError):
        decode_array({"shape": [2, 4], "dtype": "float32"}, b"\x00" * 12)


def test_replica_ports_are_deterministic_and_disjoint():
    # request ports: base + rid; base 0 = bind-ephemeral (handshake file
    # reports the real port)
    assert [replica_port(9000, r) for r in range(4)] == [
        9000, 9001, 9002, 9003,
    ]
    assert replica_port(0, 5) == 0
    # exporter ports: the router keeps base+0, replica r takes base+1+r —
    # the satellite fix for N processes colliding on one --metrics-port
    ports = {replica_metrics_port(9100, r) for r in range(4)}
    assert ports == {9101, 9102, 9103, 9104}
    assert 9100 not in ports
    assert replica_metrics_port(0, 2) == 0  # exporter off stays off
    # request and exporter ranges for one base pair never overlap
    assert not ports & {replica_port(9000, r) for r in range(4)}


def test_render_worker_env_pins_platform_and_device_slice():
    base = {"PATH": "/bin", "JAX_PLATFORMS": "tpu"}
    env = render_worker_env(base, 1, platform="cpu")
    assert env["JAX_PLATFORMS"] == "cpu" and env["PATH"] == "/bin"
    assert base["JAX_PLATFORMS"] == "tpu"  # caller's env untouched
    tpu = render_worker_env({}, 0, platform="tpu", visible_devices=[2, 3])
    assert tpu["TPU_VISIBLE_CHIPS"] == "2,3"
    gpu = render_worker_env({}, 0, platform="cuda", visible_devices=[1])
    assert gpu["CUDA_VISIBLE_DEVICES"] == "1"


def test_replica_client_forks_engine_errors_from_transport_loss():
    """The dispatcher's two recovery paths hinge on the client's error
    types: an engine error relayed by a LIVE worker is RuntimeError
    (fail the batch, keep the replica); a vanished worker is
    FleetTransportError (requeue the batch, relaunch the worker)."""
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    hits = []

    def worker():
        conn, _ = srv.accept()
        with conn:
            # 1st submit: echo logits; 2nd: relay an engine error;
            # then vanish without a reply
            header, body = recv_msg(conn)
            imgs = decode_array(header, body)
            meta, rbody = encode_array(
                np.ones((imgs.shape[0], 4), np.float32)
            )
            send_msg(conn, {"op": "result", **meta}, rbody)
            recv_msg(conn)
            send_msg(conn, {
                "op": "error", "etype": "ValueError", "error": "boom",
            })
            recv_msg(conn)
            hits.append("gone")

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    try:
        client = ReplicaClient(port, connect_timeout_s=2.0)
        out = client.submit_batch(np.zeros((2, 4, 4, 3), np.uint8))
        assert out.shape == (2, 4)
        with pytest.raises(RuntimeError, match="ValueError: boom"):
            client.submit_batch(np.zeros((1, 4, 4, 3), np.uint8))
        with pytest.raises(FleetTransportError):
            client.submit_batch(np.zeros((1, 4, 4, 3), np.uint8))
        client.close()
    finally:
        srv.close()
        t.join(timeout=5)
    # nobody listening at all is the same typed failure, at connect
    with pytest.raises(FleetTransportError):
        ReplicaClient(port, connect_timeout_s=0.5)


# ------------------------------------------- satellite: seed decorrelation


def test_fold_seed_decorrelates_pools_deterministically():
    assert fold_seed(7, "serve", 0) == fold_seed(7, "serve", 0)
    assert fold_seed(7, "serve", 0) != fold_seed(7, "serve", 1)
    assert fold_seed(7, "serve", 0) != fold_seed(8, "serve", 0)
    base = request_pool(4, image_size=8, seed=5)
    folded = request_pool(4, image_size=8, seed=5, fold=("leg", 1))
    again = request_pool(4, image_size=8, seed=5, fold=("leg", 1))
    assert base.shape == folded.shape
    assert not np.array_equal(base, folded)  # legs stop replaying one stream
    assert np.array_equal(folded, again)  # but each leg is reproducible


# ------------------------------------------------------- autoscaler math


def test_parse_scale_targets_grammar():
    assert parse_scale_targets("p99=250") == {"*": 0.25}
    assert parse_scale_targets("gold:p99=150,p99=400") == {
        "gold": 0.15, "*": 0.4,
    }
    for bad in ("p98=300", "gold:p99=-5", "x", "", "p99="):
        with pytest.raises(ValueError):
            parse_scale_targets(bad)


def test_wq_ggm_sanity_and_saturation():
    assert wq_ggm(0.0, 0.1, 1) == 0.0  # no arrivals, no queue
    assert wq_ggm(20.0, 0.1, 1) == float("inf")  # rho >= 1: saturated
    w1 = wq_ggm(5.0, 0.1, 1)
    assert w1 == pytest.approx(0.05)  # rho=.5: rho^2/(1-rho) * S
    # more servers always shorten the wait; saturation clears at m=2
    assert wq_ggm(5.0, 0.1, 2) < w1
    assert wq_ggm(20.0, 0.1, 3) < float("inf")
    # burstier arrivals (ca2 > 1) lengthen it
    assert wq_ggm(5.0, 0.1, 1, ca2=4.0) > w1


_SVC = {"n": 100, "mean_s": 0.1, "cv2": 0.5, "p99_s": 0.15, "mean_batch": 2.0}


def test_size_for_targets_smallest_m_meeting_every_target():
    m, sized_by, rows = size_for_targets(30.0, _SVC, {"*": 0.4})
    assert (m, sized_by) == (3, "ggm")
    # the returned m meets the bound; m-1 provably violates it
    for row in rows:
        assert row["m"] == 3 and row["predicted_p99_ms"] <= 400.0
    from distributed_training_comparison_tpu.serve.fleet.autoscale import (
        predicted_p99_s,
    )
    assert predicted_p99_s(30.0, _SVC, 2) > 0.4
    # an unmeetable target caps at max_replicas rather than looping
    m_cap, by_cap, _ = size_for_targets(30.0, _SVC, {"*": 0.001},
                                        max_replicas=4)
    assert (m_cap, by_cap) == (4, "ggm")


def test_size_for_targets_degrades_explicitly():
    # a thin reservoir (< MIN_TAIL_SAMPLES) has no tail to fit: the
    # PR-14 utilization rule on the measured mean, honestly labeled
    thin = dict(_SVC, n=10)
    m, sized_by, _ = size_for_targets(30.0, thin, {"*": 0.4})
    assert sized_by == "utilization"
    assert m == 3  # ceil(15 batches/s * 0.1s / 0.7)
    # no data at all: hold at the floor, labeled no-data
    m0, by0, _ = size_for_targets(30.0, dict(_SVC, n=2), {"*": 0.4})
    assert (m0, by0) == (1, "no-data")
    m0, by0, _ = size_for_targets(0.0, _SVC, {"*": 0.4})
    assert by0 == "no-data"


class _ScaleMetrics:
    """Autoscaler-facing metrics stub with twistable load."""

    classes = None

    def __init__(self, lam=30.0, svc=None):
        self.lam = lam
        self.svc = dict(svc or _SVC)

    def arrival_stats(self, window_s=30.0, cls=None):
        return {"n": 100, "lam_rps": self.lam, "ca2": 1.0}

    def service_stats(self):
        return dict(self.svc)


class _ScaleRouter:
    """Router stand-in: just the resize surface the autoscaler drives."""

    def __init__(self, n=1):
        self.n = n
        self.calls = []

    def active_replicas(self):
        return self.n

    def scale_to(self, m):
        self.calls.append(m)
        added = list(range(self.n, m))
        drained = list(range(m, self.n))
        self.n = m
        return {"added": added, "drained": drained}


def test_autoscaler_scale_up_is_immediate_and_emitted():
    fb, clk = FakeBus(), [0.0]
    metrics, router = _ScaleMetrics(lam=30.0), _ScaleRouter(n=1)
    a = Autoscaler(metrics, {"*": 0.4}, bus=fb, clock=lambda: clk[0])
    d = a.step(router)
    assert d["state"] == "applied" and d["proposed"] == 3
    assert router.n == 3 and d["added"] == [1, 2]
    assert fb.states("serve_scale") == ["decision", "applied"]


def test_autoscaler_cooldown_then_scale_down_hysteresis():
    fb, clk = FakeBus(), [0.0]
    metrics, router = _ScaleMetrics(lam=30.0), _ScaleRouter(n=1)
    a = Autoscaler(
        metrics, {"*": 0.4}, bus=fb, clock=lambda: clk[0],
        cooldown_s=15.0, hold=2,
    )
    assert a.step(router)["state"] == "applied"  # up to 3, arms cooldown
    metrics.lam = 0.5  # the flash crowd ends: the math now wants m=1
    d = a.step(router)
    assert d["state"] == "hold" and "cooldown" in d["reason"]
    assert router.n == 3  # nothing moved
    clk[0] = 16.0  # cooldown passed: hysteresis takes over
    d = a.step(router)
    assert d["state"] == "hold" and d["streak"] == 1
    clk[0] = 17.0
    d = a.step(router)  # second consecutive down-vote + headroom clears
    assert d["state"] == "applied" and router.n == 1
    assert d["drained"] == [1, 2]
    # the event trail shows the reluctance: hold, hold, then the apply
    assert fb.states("serve_scale") == [
        "decision", "applied", "hold", "hold", "decision", "applied",
    ]


def test_autoscaler_no_data_holds_silently():
    fb = FakeBus()
    a = Autoscaler(
        _ScaleMetrics(lam=30.0, svc=dict(_SVC, n=0)), {"*": 0.4}, bus=fb,
        clock=lambda: 0.0,
    )
    router = _ScaleRouter(n=2)
    d = a.step(router)
    assert d["state"] == "steady" and d["sized_by"] == "no-data"
    assert d["proposed"] == 2 and router.n == 2
    assert fb.states("serve_scale") == []  # steady ticks don't spam the bus


def test_autoscaler_force_bypasses_hysteresis_not_math():
    fb = FakeBus()
    a = Autoscaler(
        _ScaleMetrics(lam=0.5), {"*": 0.4}, bus=fb, clock=lambda: 0.0,
        cooldown_s=1000.0, hold=5,
    )
    router = _ScaleRouter(n=3)
    d = a.step(router, force=True)  # scale_serve's path
    assert d["state"] == "applied" and d["forced"] and router.n == 1


# ------------------------------------ scale_serve via the policy engine


def test_scale_serve_action_through_the_policy_engine():
    fb = FakeBus()
    metrics, router = _ScaleMetrics(lam=30.0), _ScaleRouter(n=1)
    a = Autoscaler(metrics, {"*": 0.4}, clock=lambda: 0.0)
    eng = P.PolicyEngine(
        P.parse_policy_specs(["m -> scale_serve:cooldown=0"]),
        bus=fb, mode="act", clock=lambda: 1e9,
    )
    eng.bind_actions(P.serve_actions(router, a))
    eng.observe_event(_alert())
    assert fb.states() == ["requested", "completed"]
    done = [e for e in fb.events
            if e["payload"].get("state") == "completed"][0]["payload"]
    # the completed event carries WHAT the forced step decided
    assert done["proposed"] == 3 and done["sized_by"] == "ggm"
    assert done["scale_state"] == "applied"
    assert router.n == 3 and a.applied == 1


def test_scale_serve_dry_run_default_and_budget():
    fb = FakeBus()
    router = _ScaleRouter(n=1)
    a = Autoscaler(_ScaleMetrics(lam=30.0), {"*": 0.4}, clock=lambda: 0.0)
    eng = P.PolicyEngine(
        P.parse_policy_specs(["m -> scale_serve:cooldown=0"]),
        bus=fb, mode="dry-run", clock=lambda: 1e9,
    )
    eng.bind_actions(P.serve_actions(router, a))
    eng.observe_event(_alert())
    assert fb.states() == ["dry_run"]
    assert router.n == 1 and a.applied == 0  # provably took no action
    # act mode: the per-attempt budget bounds an alert storm
    fb2 = FakeBus()
    eng2 = P.PolicyEngine(
        P.parse_policy_specs(
            ["a -> scale_serve:cooldown=0", "b -> scale_serve:cooldown=0"]
        ),
        bus=fb2, mode="act", max_actions=1, clock=lambda: 1e9,
    )
    eng2.bind_actions(P.serve_actions(router, a))
    eng2.observe_event(_alert(metric="a"))
    eng2.observe_event(_alert(metric="b"))
    assert fb2.states() == ["requested", "completed", "budget"]


def test_scale_serve_unbound_without_autoscaler():
    """No --serve-scale-target, no autoscaler: a rule naming
    scale_serve records `unbound` instead of half-acting."""
    fb = FakeBus()
    actions = P.serve_actions(_ScaleRouter(n=1))  # no autoscaler
    assert "scale_serve" not in actions
    eng = P.PolicyEngine(
        P.parse_policy_specs(["m -> scale_serve"]),
        bus=fb, mode="act", clock=lambda: 1e9,
    )
    eng.bind_actions(actions)
    eng.observe_event(_alert())
    assert fb.states() == ["unbound"]


# --------------------------------------------- requeue: crash ≠ loss


def test_requeue_returns_entries_to_lane_front_in_age_order():
    q = ClassQueue(limit=16)
    fa, fb_ = q.submit(_img()), q.submit(_img())
    batch = q.take(2, continuous=True)
    assert [f for _, f in batch] == [fa, fb_]
    fc = q.submit(_img())  # admitted after the doomed dispatch
    assert q.requeue(batch) == 2
    # age preserved: the requeued pair dispatches BEFORE the newcomer
    nxt = q.take(8, continuous=True)
    assert [f for _, f in nxt] == [fa, fb_, fc]
    q.close(drain=False)


def test_requeue_skips_resolved_futures():
    q = ClassQueue(limit=16)
    fa, fb_ = q.submit(_img()), q.submit(_img())
    batch = q.take(2, continuous=True)
    fa.set_result(np.zeros(4, np.float32))  # resolved meanwhile
    assert q.requeue(batch) == 1
    nxt = q.take(8, continuous=True)
    assert [f for _, f in nxt] == [fb_]
    q.close(drain=False)


def test_requeue_on_closed_queue_fails_typed():
    m = ServeMetrics()
    q = ClassQueue(limit=16, metrics=m)
    fut = q.submit(_img())
    batch = q.take(2, continuous=True)
    q.close(drain=False)
    assert q.requeue(batch) == 0
    with pytest.raises(BatcherClosed):
        fut.result(timeout=1)
    assert m.failed == 1  # lands in the SLO denominator


# ------------------------------------------- the sketches the sizer fits


def test_arrival_sketch_counts_admissions_per_class():
    m = ServeMetrics()
    q = ClassQueue(limit=64, metrics=m)
    for _ in range(5):
        q.submit(_img())
    st = m.arrival_stats(window_s=60.0)
    assert st["n"] == 5 and st["lam_rps"] > 0 and st["ca2"] >= 0.0
    # per-class sketches are separate
    assert m.arrival_stats(window_s=60.0, cls="default")["n"] == 5
    assert m.arrival_stats(window_s=60.0, cls="gold")["n"] == 0
    q.close(drain=False)


def test_arrival_sketch_excludes_sheds():
    """Sheds are deliberately not arrivals-for-sizing: sizing to shed
    traffic would chase load the queue already refused."""
    m = ServeMetrics()
    q = ClassQueue(limit=1, metrics=m)
    q.submit(_img())
    from distributed_training_comparison_tpu.serve import QueueOverflow
    with pytest.raises(QueueOverflow):
        q.submit(_img())
    assert m.arrival_stats(window_s=60.0)["n"] == 1  # only the admission
    q.close(drain=False)


def test_service_sketch_welford_mean_cv_and_batch():
    m = ServeMetrics()
    assert m.service_stats() == {
        "n": 0, "mean_s": 0.0, "cv2": 1.0, "p99_s": 0.0, "mean_batch": 1.0,
    }
    for _ in range(10):
        m.record_service(0.1, 2)
    st = m.service_stats()
    assert st["n"] == 10
    assert st["mean_s"] == pytest.approx(0.1)
    assert st["cv2"] == pytest.approx(0.0, abs=1e-9)
    assert st["p99_s"] == pytest.approx(0.1)
    assert st["mean_batch"] == pytest.approx(2.0)
    m.record_service(0.3, 4)  # variance and batch mix move
    st = m.service_stats()
    assert st["cv2"] > 0 and st["mean_batch"] == pytest.approx(24 / 11)


def test_dispatch_feeds_the_service_sketch():
    """The thread path's dispatch_batch times the engine and records
    one service sample per dispatch — the sketch fills itself."""
    m = ServeMetrics()
    q = ClassQueue(limit=16, metrics=m)
    from distributed_training_comparison_tpu.serve.batcher import (
        dispatch_batch,
    )
    eng = _StubEngine(delay_s=0.01)
    futs = [q.submit(_img()) for _ in range(3)]
    done = dispatch_batch(eng, q.take(8, continuous=True), m)
    assert len(done) == 3 and all(f.done() for f in futs)
    st = m.service_stats()
    assert st["n"] == 1 and st["mean_s"] >= 0.01
    assert st["mean_batch"] == pytest.approx(3.0)
    q.close(drain=False)


# ------------------------------------- thread-transport twins (tier-1)


def test_router_scale_to_grows_and_shrinks_lifo(tmp_path):
    stubs = {}

    def factory(rid):
        stubs[rid] = _StubEngine(rid=rid)
        return stubs[rid]

    bus = _bus(tmp_path)
    r = ServeRouter(factory, replicas=1, bus=bus, queue_limit=64,
                    emit_every_s=0.2)
    try:
        r.warmup()
        assert r.active_replicas() == 1
        res = r.scale_to(3)
        assert res == {"added": [1, 2], "drained": []}
        _wait(lambda: r.active_replicas() == 3, what="scale-up to 3")
        _wait(lambda: all(x.state == READY for x in r.replicas),
              what="new replicas ready")
        # shrink retires the NEWEST capacity first (LIFO): the original
        # fleet stays stable
        res = r.scale_to(1)
        assert res == {"added": [], "drained": [2, 1]}
        _wait(lambda: r.active_replicas() == 1, what="scale-down to 1")
        assert r.replicas[0].state == READY
        # the survivor still serves
        assert r.submit(_img()).result(timeout=10).shape == (4,)
        assert r.scale_to(1) == {"added": [], "drained": []}
    finally:
        r.close()
    # both directions left replica lifecycle events behind
    states = {
        (e["payload"]["replica"], e["payload"]["state"])
        for e in obs.load_events(Path(tmp_path) / "events.jsonl")
        if e["kind"] == "replica" and "state" in e.get("payload", {})
    }
    assert (2, "ready") in states and (2, "stopped") in states


def test_router_ticker_drives_the_autoscaler_live(tmp_path):
    """The live loop twin: an attached autoscaler, stepped by the
    router's own ticker, grows the fleet without anyone calling step."""
    bus = _bus(tmp_path)
    r = ServeRouter(
        lambda rid: _StubEngine(rid=rid), replicas=1, bus=bus,
        queue_limit=64, emit_every_s=0.05,
    )
    r._scale_every_s = 0.05
    a = Autoscaler(_ScaleMetrics(lam=30.0), {"*": 0.4}, bus=bus,
                   cooldown_s=0.0, max_replicas=3)
    r.attach_autoscaler(a)
    try:
        r.warmup()
        # wait on the COUNTER, not active_replicas(): the replicas go
        # active inside scale_to, a beat before step() bumps `applied`
        _wait(lambda: a.applied >= 1 and r.active_replicas() == 3,
              what="live scale-up")
    finally:
        r.close()
    evs = obs.load_events(Path(tmp_path) / "events.jsonl")
    applied = [e for e in evs if e["kind"] == "serve_scale"
               and e["payload"]["state"] == "applied"]
    assert applied and applied[0]["payload"]["added"]


def test_thread_replica_stops_with_per_class_latency_payload(tmp_path):
    bus = _bus(tmp_path)
    r = ServeRouter(lambda rid: _StubEngine(rid=rid), replicas=1, bus=bus,
                    queue_limit=16)
    try:
        r.warmup()
        for f in [r.submit(_img()) for _ in range(4)]:
            f.result(timeout=10)
    finally:
        r.close()
    stops = [
        e["payload"] for e in obs.load_events(Path(tmp_path) / "events.jsonl")
        if e["kind"] == "replica" and e["payload"].get("state") == "stopped"
    ]
    assert stops and stops[0]["transport"] == "thread"
    classes = stops[0]["classes"]
    assert classes["default"]["n"] == 4
    assert classes["default"]["p99_ms"] >= 0.0


# --------------------------------------- run_report --serve (satellite)


def test_serve_replica_table_merges_lifecycle(tmp_path):
    bus = _bus(tmp_path)
    bus.emit("replica", replica=0, state="ready", transport="process",
             pid=4242, port=9001)
    bus.emit("replica", replica=0, beat=True, dispatches=6, routed=12,
             transport="process")
    bus.emit("replica", replica=0, state="starting", transport="process",
             restart=1, requeued=4)
    bus.emit("replica", replica=0, state="stopped", transport="process",
             dispatches=9, routed=18,
             classes={"default": {"n": 18, "p99_ms": 12.5}})
    table = run_report.serve_replica_table(
        obs.load_events(Path(tmp_path) / "events.jsonl")
    )
    row = table["0"]
    assert row["transport"] == "process" and row["pid"] == 4242
    assert row["dispatches"] == 9 and row["routed"] == 18  # max, not last
    assert row["restarts"] == 1
    assert row["state"] == "stopped"
    assert row["classes"]["default"]["p99_ms"] == 12.5
    # beats never count as lifecycle transitions
    assert row["drains"] == 0 and row["deaths"] == 0


def test_serve_report_gates_on_scale_fleet_disagreement(tmp_path, capsys):
    """An APPLIED scale decision whose added replica never went ready is
    an autoscaler/fleet disagreement worth an exit 1."""
    ok_dir, bad_dir = tmp_path / "ok", tmp_path / "bad"
    for d, honored in ((ok_dir, True), (bad_dir, False)):
        bus = obs.EventBus(run_id="e" * 16)
        bus.bind_dir(d)
        bus.emit("serve_route", state="routing", classes={
            "default": {"completed": 4, "ok_deadline": 4, "expired": 0,
                        "shed": 0, "priority": 1, "deadline_ms": None,
                        "target": 0.0},
        })
        bus.emit("replica", replica=0, state="ready", transport="process")
        bus.emit("serve_scale", state="applied", current=1, proposed=2,
                 added=[1], drained=[])
        if honored:
            bus.emit("replica", replica=1, state="ready",
                     transport="process")
    assert run_report.serve_scale_mismatches(
        obs.load_events(Path(ok_dir) / "events.jsonl")
    ) == []
    assert run_report.serve_report(ok_dir) == 0
    capsys.readouterr()
    assert run_report.serve_report(bad_dir) == 1
    out = capsys.readouterr().out
    assert "SCALE MISMATCH" in out and "never went ready" in out


# ----------------------------------------------- flags + event registry


def test_fleet_flags_parse_and_validate():
    hp = load_config("tpu", argv=[
        "--serve", "--serve-transport", "process",
        "--serve-scale-target", "gold:p99=150,p99=400",
        "--serve-port-base", "9000", "--serve-max-replicas", "4",
        "--serve-classes", "gold:priority=0:deadline_ms=250",
    ])
    assert hp.serve_transport == "process"
    assert hp.serve_scale_target == "gold:p99=150,p99=400"
    assert hp.serve_port_base == 9000 and hp.serve_max_replicas == 4
    with pytest.raises(SystemExit):  # malformed target dies at the CLI
        load_config("tpu", argv=["--serve-scale-target", "p98=300"])
    with pytest.raises(SystemExit):
        load_config("tpu", argv=["--serve-scale-target", "gold:p99=-5"])
    with pytest.raises(SystemExit):  # port base out of range
        load_config("tpu", argv=["--serve-port-base", "70000"])
    with pytest.raises(SystemExit):
        load_config("tpu", argv=["--serve-max-replicas", "0"])
    with pytest.raises(SystemExit):  # unknown transport
        load_config("tpu", argv=["--serve-transport", "carrier-pigeon"])


def test_serve_scale_kind_is_registered():
    from distributed_training_comparison_tpu.serve.fleet.autoscale import (
        SCALE_KIND,
    )
    assert SCALE_KIND == "serve_scale"
    assert "serve_scale" in obs.KNOWN_KINDS
    assert "replica" in obs.KNOWN_KINDS


def test_serve_replica_kill_scenario_is_registered():
    from distributed_training_comparison_tpu.resilience import (
        CHAOS_SCENARIOS,
        check_chaos_expectations,
    )

    sc = CHAOS_SCENARIOS["serve_replica_kill_flash"]
    assert sc["session"] == "serve"
    assert sc["driver"] == "kill_replica"
    assert "--serve-transport" in sc["extra_args"]
    # the expectation block is satisfiable by a green run...
    observed = {
        "final_rc": 0, "kills": 1, "restarts": 1,
        "failed_requests": 0, "p99_recovered": True,
    }
    assert check_chaos_expectations(sc["expect"], observed) == []
    # ...and actually binds on the zero-loss claim: a single failed
    # request (beyond shed/deadline accounting — there is none here)
    # must flunk the scenario
    assert check_chaos_expectations(
        sc["expect"], dict(observed, failed_requests=1)
    )
    assert check_chaos_expectations(
        sc["expect"], dict(observed, restarts=0)
    )


# ------------------------------------------ the REAL process fleet (slow)


def _process_spec(tmp_path, buckets=(1, 2), image_size=16):
    hp = load_config("single", argv=[
        "--model", "resnet18", "--image-size", str(image_size),
        "--serve-buckets", ",".join(str(b) for b in buckets),
        "--seed", "3", "--ckpt-path", str(tmp_path),
    ])
    return {
        "fleet_dir": str(tmp_path / "serve-fleet"),
        "events_dir": str(tmp_path),
        "hparams": worker_hparams_dict(hp),
        "port_base": 0,
        "metrics_port_base": 0,
        "platform": "cpu",
        "run_id": "f" * 16,
        "attempt": 0,
        "aot_dir": str(tmp_path / "aot"),
    }


@pytest.mark.slow
@pytest.mark.serve_fleet
def test_process_replica_end_to_end_serves_and_drains(tmp_path):
    """One REAL worker process: handshake file, socket serve, engine
    stats over RPC, orderly drain — the transport e2e at test size."""
    bus = _bus(tmp_path)
    spec = _process_spec(tmp_path)
    r = ServeRouter(
        None, replicas=1, transport="process", process_spec=spec,
        bus=bus, queue_limit=64, emit_every_s=0.5,
    )
    try:
        assert r.wait_ready(n=1, timeout=600)
        rep = r.replicas[0]
        assert isinstance(rep, ProcessReplica)
        assert rep.pid and rep.pid != os.getpid()
        hs = read_handshake(spec["fleet_dir"], 0)
        assert hs["state"] == "ready" and hs["pid"] == rep.pid
        # the worker engine compiled for the spec's image size, not the
        # stub fleet's 4px toy — submit at the size the worker serves
        img16 = np.zeros((16, 16, 3), np.uint8)
        futs = [r.submit(img16) for _ in range(8)]
        rows = [f.result(timeout=120) for f in futs]
        assert len(rows) == 8 and rows[0].shape[0] >= 2
    finally:
        r.close()
    _wait(lambda: r.replicas[0].state == STOPPED, timeout=30,
          what="clean drain")
    assert r.replicas[0].restarts == 0
    # the engine's stats crossed the RPC and folded into the router's
    st = r.replicas[0].engine_stats()
    assert st and st["compiles"] >= 1
    # the worker joined the run's event stream as process 1+rid
    worker_events = Path(tmp_path) / "events-p1.jsonl"
    assert worker_events.exists()
    kinds = {e["kind"] for e in obs.load_events(worker_events)}
    assert "replica" in kinds and "compile" in kinds


@pytest.mark.slow
@pytest.mark.serve_fleet
def test_process_replica_kill_requeues_and_supervisor_restarts(tmp_path):
    """SIGKILL the worker mid-stream: in-flight work requeues (zero
    failed requests), the supervisor relaunches inside its budget, and
    the relaunched worker — warm-started from the persisted AOT cache —
    finishes the backlog."""
    bus = _bus(tmp_path)
    spec = _process_spec(tmp_path, buckets=(1, 2), image_size=32)
    r = ServeRouter(
        None, replicas=1, transport="process", process_spec=spec,
        bus=bus, queue_limit=512, emit_every_s=0.5,
    )
    try:
        assert r.wait_ready(n=1, timeout=600)
        rep = r.replicas[0]
        pid = rep.pid
        img32 = np.zeros((32, 32, 3), np.uint8)
        futs = [r.submit(img32) for _ in range(200)]
        _wait(lambda: rep.dispatches >= 2, timeout=120,
              what="dispatches flowing")
        os.kill(pid, signal.SIGKILL)
        # every admitted request still completes: the killed dispatch
        # requeued, the backlog drained by the next incarnation
        rows = [f.result(timeout=600) for f in futs]
        assert len(rows) == 200
        _wait(lambda: rep.pid != pid and rep.state == READY, timeout=120,
              what="relaunched worker ready")
        assert rep.restarts >= 1
        assert r.metrics.failed == 0
    finally:
        r.close()
    evs = obs.load_events(Path(tmp_path) / "events.jsonl")
    lifecycle = [e["payload"] for e in evs if e["kind"] == "replica"]
    assert any(p.get("lifecycle") == "attempt_start" and p.get("attempt")
               for p in lifecycle), "supervisor restart never hit the bus"


def test_process_transport_refuses_on_a_tpu_host(monkeypatch, tmp_path):
    """One process owns a chip: by the time serve_main runs, the router
    process has opened the host's chips, so replica processes cannot (on a
    v5e the worker dies on libtpu's lockfile and the fleet burns its restart
    budget).  Replicas are not placed per chip yet, so the process transport
    must refuse — clearly, before it spawns — wherever the platform is tpu."""
    import subprocess
    import types

    import jax

    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.serve import serve_main

    def no_spawn(*a, **kw):
        raise AssertionError("spawned a replica before refusing")

    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="tpu")]
    )
    hp = load_config(
        "tpu",
        ["--serve", "--serve-transport", "process", "--ckpt-path", str(tmp_path)],
    )
    with pytest.raises(ValueError, match="--serve-transport thread"):
        serve_main(hp)
    assert list(tmp_path.iterdir()) == []
