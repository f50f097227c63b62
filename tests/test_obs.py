"""obs/ subsystem tests: the run-event bus, span tracing, the flight
recorder, and ``tools/run_report.py`` — plus the satellite paths (run_id
stamped into manifests and legacy jsonl records, the checkpoint-writer
queue-depth gauge, supervisor event hooks, ``--check`` validation).

The headline (ISSUE 5 acceptance) is
``test_e2e_faulted_run_events_validate``: the PR 3 nan_grad fault harness
plus an injected preemption, end to end through the real Trainer — every
event kind the run emits parses against the versioned schema, the
Chrome-trace export is valid JSON with strictly nested, monotonically
ordered spans per thread, and the checkpoint manifest / health.jsonl /
goodput.jsonl all carry the run identity the unified timeline joins on.
"""

import json
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))

from distributed_training_comparison_tpu import obs
from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.health import load_health_events
from distributed_training_comparison_tpu.obs.bus import EventBus
from distributed_training_comparison_tpu.obs.spans import SpanRecorder
from distributed_training_comparison_tpu.resilience import (
    EXIT_PREEMPTED,
    Preempted,
    Supervisor,
    load_goodput_records,
    read_manifest,
)
from distributed_training_comparison_tpu.train import AsyncCheckpointer, Trainer

from test_train import TinyNet

BASE_ARGS = [
    "--synthetic-data",
    "--limit-examples", "640",   # 576 train examples -> 18 steps/epoch @32
    "--batch-size", "32",
    "--epoch", "3",
    "--save-last-min-secs", "0",
    "--no-progress",
    "--seed", "7",
    "--eval-step", "1000",
]


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    """Each test gets a pristine process-current bus/recorder and no
    inherited run-id environment (the supervisor seam)."""
    monkeypatch.delenv(obs.RUN_ID_ENV, raising=False)
    monkeypatch.delenv(obs.ATTEMPT_ENV, raising=False)
    obs.reset()
    obs.set_recorder(None)
    yield
    obs.reset()
    obs.set_recorder(None)


# ------------------------------------------------------------------- bus


def test_emit_buffers_before_bind_and_flushes(tmp_path):
    for kind in ("alpha", "beta", "gamma"):  # embedder kinds: registered
        obs.register_kind(kind)
    bus = EventBus(run_id="r" * 16, attempt=2, process_index=0)
    bus.emit("alpha", epoch=0, note="early")
    bus.emit("beta", step=5)
    path = bus.bind_dir(tmp_path)
    bus.emit("gamma")
    bus.close()
    assert path == tmp_path / obs.EVENTS_NAME
    events = obs.load_events(path)
    assert [e["kind"] for e in events] == ["alpha", "beta", "gamma"]
    # construction-time events keep their original (pre-bind) timestamps
    assert events[0]["t_mono"] <= events[1]["t_mono"] <= events[2]["t_mono"]
    for ev in events:
        assert obs.validate_event(ev) == []
        assert ev["run_id"] == "r" * 16 and ev["attempt"] == 2


def test_events_filename_per_process():
    assert obs.events_filename(0) == "events.jsonl"
    assert obs.events_filename(3) == "events-p3.jsonl"
    from distributed_training_comparison_tpu.obs import trace_filename

    assert trace_filename(0, 0) == "trace.json"
    assert trace_filename(2, 0) == "trace-a2.json"
    assert trace_filename(2, 1) == "trace-a2-p1.json"
    assert obs.crash_dump_filename(0, 0) == "crash_dump.json"
    assert obs.crash_dump_filename(1, 0) == "crash_dump-a1.json"
    assert obs.crash_dump_filename(1, 2) == "crash_dump-a1-p2.json"


def test_crash_dump_per_attempt_never_clobbers(tmp_path):
    """A relaunched attempt aborting in the SAME version dir (auto-resume)
    must not overwrite the previous attempt's forensics."""
    first = EventBus(run_id="a" * 16, attempt=0)
    first.emit("tick", step=1)
    first.dump_crash("attempt 0 abort", directory=tmp_path)
    second = EventBus(run_id="a" * 16, attempt=1)
    second.emit("tock", step=2)
    path = second.dump_crash("attempt 1 abort", directory=tmp_path)
    assert path == tmp_path / "crash_dump-a1.json"
    assert json.loads(
        (tmp_path / obs.CRASH_DUMP_NAME).read_text()
    )["reason"] == "attempt 0 abort"
    assert json.loads(path.read_text())["reason"] == "attempt 1 abort"


def test_payload_coercion_numpy_and_paths(tmp_path):
    obs.register_kind("mix")
    bus = EventBus()
    bus.bind_dir(tmp_path)
    bus.emit(
        "mix",
        f32=np.float32(1.5),
        i64=np.int64(7),
        arr=np.arange(3),
        where=tmp_path,
        tags={"a", },
    )
    bus.close()
    (ev,) = obs.load_events(tmp_path / "events.jsonl")
    p = ev["payload"]
    assert p["f32"] == 1.5 and p["i64"] == 7 and p["arr"] == [0, 1, 2]
    assert p["tags"] == ["a"] and str(tmp_path) in p["where"]
    assert obs.validate_event(ev) == []


def test_flight_recorder_ring_bounded_and_first_dump_wins(tmp_path):
    bus = EventBus(ring_size=4)
    for i in range(10):
        bus.emit("tick", step=i)
    ring = bus.ring_events()
    assert len(ring) == 4 and [e["step"] for e in ring] == [6, 7, 8, 9]
    path = bus.dump_crash("specific abort", directory=tmp_path)
    # the generic unhandled-exception net must not overwrite the abort's
    # specific reason
    again = bus.dump_crash("generic re-raise", directory=tmp_path / "other")
    assert again == path
    dump = json.loads((tmp_path / obs.CRASH_DUMP_NAME).read_text())
    assert dump["reason"] == "specific abort"
    assert [e["step"] for e in dump["ring"]] == [6, 7, 8, 9]
    assert not (tmp_path / "other").exists()


def test_dump_crash_carries_exception(tmp_path):
    bus = EventBus()
    try:
        raise ValueError("boom")
    except ValueError as e:
        bus.dump_crash("unhandled", exc=e, directory=tmp_path)
    dump = json.loads((tmp_path / obs.CRASH_DUMP_NAME).read_text())
    assert dump["exception"]["type"] == "ValueError"
    assert "boom" in dump["exception"]["message"]
    assert any("ValueError" in ln for ln in dump["exception"]["traceback"])


def test_unbound_bus_dump_has_nowhere_to_write():
    bus = EventBus()
    bus.emit("tick")
    assert bus.dump_crash("no dir") is None


def test_persist_false_keeps_ring_only(tmp_path):
    """--no-obs buses never buffer pending lines (they will never be
    bound, so a pending list would grow for the whole run) — but the
    flight-recorder ring still records."""
    bus = EventBus(ring_size=4, persist=False)
    for i in range(10):
        bus.emit("tick", step=i)
    assert len(bus.ring_events()) == 4
    assert bus._pending == []
    # a late bind (not the --no-obs path, but legal) starts fresh: only
    # post-bind events land in the file
    bus.bind_dir(tmp_path)
    bus.emit("late")
    bus.close()
    assert [e["kind"] for e in obs.load_events(tmp_path / "events.jsonl")] == [
        "late"
    ]


def test_load_events_tolerates_torn_tail(tmp_path):
    path = tmp_path / "events.jsonl"
    good = json.dumps({"kind": "a"})
    path.write_text(good + "\n" + good + "\n" + '{"kind": "tor')  # torn append
    assert len(obs.load_events(path)) == 2
    assert obs.load_events(tmp_path / "missing.jsonl") == []


def test_reset_guard_spares_a_successor_bus():
    first = obs.configure(run_id="a" * 16)
    second = obs.configure(run_id="b" * 16)
    obs.reset(first)  # stale closer: must NOT tear down the successor
    assert obs.current_bus() is second
    obs.reset(second)
    assert obs.current_bus() is not second  # fresh default after real reset


def test_current_bus_inherits_environment(monkeypatch):
    monkeypatch.setenv(obs.RUN_ID_ENV, "e" * 16)
    monkeypatch.setenv(obs.ATTEMPT_ENV, "5")
    obs.reset()
    bus = obs.current_bus()
    assert bus.run_id == "e" * 16 and bus.attempt == 5


def test_default_bus_is_ring_only():
    """A never-configured bus may never be bound: emits must stay one
    deque append each, never an unbounded pending list (the library-
    embedder contract in obs/__init__.py)."""
    bus = obs.current_bus()
    for i in range(600):
        bus.emit("tick", step=i)
    assert bus._pending == []
    assert len(bus.ring_events()) == obs.bus.RING_SIZE_DEFAULT


# ---------------------------------------------------------------- schema


def test_validate_event_accepts_the_canonical_shape():
    ev = EventBus(run_id="f" * 16).emit("run_start", epoch=1, step=2, x=1)
    assert obs.validate_event(ev) == []
    # embedder kinds are admitted through the registry, not by accident
    ev2 = EventBus(run_id="f" * 16).emit("my_embedder_kind")
    assert obs.validate_event(ev2) != []
    obs.register_kind("my_embedder_kind")
    assert obs.validate_event(ev2) == []


@pytest.mark.parametrize(
    "mutate, expect",
    [
        (lambda e: e.pop("run_id"), "missing required field 'run_id'"),
        (lambda e: e.pop("t_wall"), "missing required field 't_wall'"),
        (lambda e: e.update(v=99), "schema version 99 != 1"),
        (lambda e: e.update(extra=1), "unknown field 'extra'"),
        (lambda e: e.update(kind=7), "field 'kind' has type int"),
        (lambda e: e.update(attempt=True), "field 'attempt' has type bool"),
        (lambda e: e.update(attempt=-1), "field 'attempt' is negative"),
        (lambda e: e.update(run_id=""), "run_id is empty"),
        (lambda e: e.update(payload=[1]), "payload has type list"),
        (
            lambda e: e.update(kind="unregistered_drift"),
            "kind 'unregistered_drift' is not registered "
            "(obs.bus.KNOWN_KINDS / register_kind)",
        ),
    ],
)
def test_validate_event_catches_violations(mutate, expect):
    ev = EventBus(run_id="f" * 16).emit("run_start", epoch=1, x=1)
    mutate(ev)
    assert expect in obs.validate_event(ev)


def test_validate_event_rejects_non_objects():
    assert obs.validate_event([1, 2]) != []
    assert obs.validate_event("nope") != []


# ----------------------------------------------------------------- spans


def _assert_strictly_nested(trace: dict):
    """Per thread: spans are monotonically ordered by begin time and every
    span either contains or is disjoint from every other (no partial
    overlap) — the invariant the per-thread context-manager stacks
    guarantee by construction."""
    lanes: dict = {}
    for ev in trace["traceEvents"]:
        if ev.get("ph") == "X":
            lanes.setdefault(ev["tid"], []).append(ev)
    assert lanes, "trace has no complete events"
    for evs in lanes.values():
        last_ts = -1.0
        stack: list = []
        for ev in evs:
            t0, t1 = ev["ts"], ev["ts"] + ev["dur"]
            assert t0 >= last_ts  # monotonically ordered
            last_ts = t0
            while stack and stack[-1] <= t0:
                stack.pop()
            if stack:
                assert t1 <= stack[-1]  # strictly inside the enclosing span
            stack.append(t1)


def test_span_nesting_across_threads_and_chrome_export(tmp_path):
    rec = SpanRecorder(process_index=0)

    def worker():
        with rec.span("outer_w"):
            with rec.span("inner_w"):
                time.sleep(0.002)

    t = threading.Thread(target=worker, name="lane-b")
    with rec.span("outer", epoch=1):
        t.start()
        with rec.span("inner"):
            time.sleep(0.002)
        with rec.span("inner2"):
            pass
        t.join()
    spans = rec.spans()
    assert {s["name"] for s in spans} == {
        "outer", "inner", "inner2", "outer_w", "inner_w",
    }
    by_name = {s["name"]: s for s in spans}
    assert by_name["inner"]["depth"] == 1 and by_name["outer"]["depth"] == 0
    # the worker's stack is its own: depth restarts at 0 on the new thread
    assert by_name["outer_w"]["depth"] == 0
    assert by_name["outer"]["args"] == {"epoch": 1}

    path = obs.write_chrome_trace(tmp_path / "trace.json", rec, label="t")
    trace = json.loads(path.read_text())  # valid JSON
    _assert_strictly_nested(trace)
    names = {
        e["args"]["name"]
        for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert "lane-b" in names  # lanes carry the thread names


def test_span_recorder_bounded(tmp_path):
    rec = SpanRecorder(max_spans=3)
    for _ in range(5):
        with rec.span("s"):
            pass
    assert len(rec.spans()) == 3 and rec.dropped == 2
    # a capped trace announces its truncation in the process lane name —
    # Perfetto readers must not mistake the cutoff for the run going idle
    trace = json.loads(
        obs.write_chrome_trace(tmp_path / "t.json", rec).read_text()
    )
    (pname,) = [
        e for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "process_name"
    ]
    assert "TRUNCATED: 2 spans dropped" in pname["args"]["name"]


def test_exception_inside_span_still_closes_it():
    rec = SpanRecorder()
    with pytest.raises(ValueError):
        with rec.span("doomed"):
            raise ValueError("x")
    (s,) = rec.spans()
    assert s["name"] == "doomed" and s["t1"] >= s["t0"]


def test_step_time_meter_mirrors_phases_as_spans():
    from distributed_training_comparison_tpu.utils.meters import StepTimeMeter

    rec = SpanRecorder()
    meter = StepTimeMeter(tracer=rec)
    with meter.phase("dispatch"):
        pass
    with meter.phase("compute"):
        pass
    assert [s["name"] for s in rec.spans()] == ["dispatch", "compute"]
    assert meter.seconds["dispatch"] >= 0.0


def test_annotations_are_inert_outside_profiling():
    rec = SpanRecorder()
    # every span enters a TraceAnnotation; no trace session is active
    with rec.span("annotated"):
        pass
    assert rec.spans()[0]["name"] == "annotated"


@contextmanager
def span_as_it_was(rec, name):
    """The span before it entered a ``TraceAnnotation`` (PR 24) and became a
    tree node (PR 38): the reference the cost tests hold a span against,
    timed in the same rounds."""
    stack = rec._stack()
    depth = len(stack)
    stack.append(name)
    thread = threading.current_thread()
    t0 = time.monotonic()
    try:
        with nullcontext():
            yield
    finally:
        t1 = time.monotonic()
        stack.pop()
        rec._append({
            "name": str(name), "t0": t0, "t1": t1,
            "thread_id": thread.ident, "thread_name": thread.name,
            "depth": depth,
        })


def test_span_with_no_profiler_session_is_cheap():
    """A span always enters ``jax.profiler.TraceAnnotation`` (so the host
    lines of any profiler trace carry the spans), which must cost next to
    nothing while no session runs.  Held against the span it replaced,
    which entered a ``nullcontext`` outside a capture, timed here in the
    same rounds — the machine is shared and its speed varies by session,
    so the sharp limit is the ratio (0.8 as measured) and the
    absolute one (ISSUE 24's 5 us, about 2 us as measured) is wide."""

    def mean_seconds(span):
        rec = SpanRecorder()
        t0 = time.perf_counter()
        for _ in range(10_000):
            with span(rec, "dispatch"):
                pass
        mean = (time.perf_counter() - t0) / 10_000
        assert len(rec.spans()) == 10_000
        return mean

    now = before = float("inf")
    for _ in range(5):  # the best of five rounds each, interleaved
        now = min(now, mean_seconds(SpanRecorder.span))
        before = min(before, mean_seconds(span_as_it_was))
    said = f"{now * 1e6:.2f} us a span, {before * 1e6:.2f} us as it was"
    assert now < 1.25 * before, said
    assert now < 20e-6, said


def test_chrome_trace_export_carries_the_span_tree():
    """What a span records and exports: the fields it always had, and
    since ISSUE 38 its ``id``, its ``parent`` and the ``epoch`` it belongs
    to, which ride in the Chrome-trace event's ``args``."""
    rec = SpanRecorder(process_index=3)
    with rec.span("epoch", epoch=2):
        with rec.span("eval"):
            pass
    rec.record("busy", 1.0, 1.5, lane="stage0", stage=0)
    inner, outer, lane = rec.spans()
    assert set(inner) == {"name", "t0", "t1", "thread_id", "thread_name",
                          "depth", "id", "parent", "epoch"}
    assert set(outer) == set(lane) == set(inner) | {"args"}
    assert (inner["name"], inner["depth"]) == ("eval", 1)
    assert (outer["name"], outer["depth"], outer["args"]) == ("epoch", 0, {"epoch": 2})
    assert (inner["parent"], inner["epoch"]) == (outer["id"], 2)
    assert (outer["parent"], lane["parent"], lane["epoch"]) == (None, None, None)
    assert outer["t0"] <= inner["t0"] <= inner["t1"] <= outer["t1"]
    events = [e for e in obs.chrome_trace(rec.spans(), 3)["traceEvents"]
              if e["ph"] == "X"]
    by_name = {e["name"]: e for e in events}
    assert by_name["busy"] == {
        "ph": "X", "name": "busy", "pid": 3, "tid": lane["thread_id"],
        "ts": 1e6, "dur": 5e5, "args": {"stage": 0, "id": lane["id"]},
    }
    assert by_name["eval"] == {
        "ph": "X", "name": "eval", "pid": 3, "tid": inner["thread_id"],
        "ts": round(inner["t0"] * 1e6, 3),
        "dur": round((inner["t1"] - inner["t0"]) * 1e6, 3),
        "args": {"id": inner["id"], "parent": outer["id"], "epoch": 2},
    }
    assert by_name["epoch"]["args"] == {"epoch": 2, "id": outer["id"]}


# --------------------------------------------- checkpoint-writer satellite


def test_async_checkpointer_queue_depth_gauge():
    writer = AsyncCheckpointer()
    release = threading.Event()
    try:
        writer.submit(lambda: release.wait(5), key="a")
        writer.submit(lambda: None, key="b")
        deadline = time.monotonic() + 2
        while writer.stats()["queue_depth"] < 2:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        release.set()
        writer.wait()
        stats = writer.stats()
        assert stats["queue_depth"] == 0
        assert set(stats) == {"busy_s", "alive_s", "busy_frac", "queue_depth"}
    finally:
        release.set()
        writer.close()


def test_async_checkpointer_superseded_jobs_drain_depth():
    writer = AsyncCheckpointer()
    ran = []
    try:
        for i in range(4):  # same key: later submits supersede earlier ones
            writer.submit(lambda i=i: ran.append(i), key="last")
        writer.wait()
        assert writer.stats()["queue_depth"] == 0  # superseded slots drained
        assert ran  # at least the newest job ran
    finally:
        writer.close()


# ----------------------------------------------------- supervisor events


def test_supervisor_emits_attempt_and_backoff_events():
    rcs = iter([EXIT_PREEMPTED, 1, 0])
    seen: list = []
    sup = Supervisor(
        ["true"],
        max_restarts=3,
        backoff_base=0.01,
        runner=lambda cmd, env: next(rcs),
        sleep=lambda s: None,
        log=lambda msg: None,
        events=lambda kind, **p: seen.append((kind, p)),
    )
    sup.run()
    kinds = [k for k, _ in seen]
    assert kinds == [
        "attempt_start", "attempt_end",   # preempted -> immediate relaunch
        "attempt_start", "attempt_end", "backoff",  # crash -> backoff
        "attempt_start", "attempt_end",   # success
    ]
    ends = [p for k, p in seen if k == "attempt_end"]
    assert ends[0]["preempted"] is True and ends[0]["returncode"] == EXIT_PREEMPTED
    assert ends[2]["returncode"] == 0


def test_supervisor_emits_give_up():
    seen: list = []
    sup = Supervisor(
        ["true"],
        max_restarts=0,
        runner=lambda cmd, env: 9,
        sleep=lambda s: None,
        log=lambda msg: None,
        events=lambda kind, **p: seen.append(kind),
    )
    sup.run()
    assert seen == ["attempt_start", "attempt_end", "give_up"]


# ----------------------------------------------------------- config flags


def test_obs_flags_defaults_and_validation():
    hp = load_config("tpu", ["--synthetic-data"])
    assert hp.obs is True and hp.flight_recorder_size == 256
    hp = load_config("tpu", ["--synthetic-data", "--no-obs"])
    assert hp.obs is False
    with pytest.raises(SystemExit):
        load_config("tpu", ["--flight-recorder-size", "0"])


# ------------------------------------------------- trainer e2e (acceptance)


def _fit(tmp_path, extra=()):
    hp = load_config(
        "tpu", argv=BASE_ARGS + ["--ckpt-path", str(tmp_path), *extra]
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    try:
        trainer.fit()
    finally:
        trainer.close()
    return trainer


@pytest.mark.obs
def test_e2e_faulted_run_events_validate(tmp_path):
    """ISSUE 5 acceptance (single-attempt leg): the PR 3 nan_grad harness
    plus an injected preemption → every emitted event kind validates
    against the versioned schema, the run identity is stamped into the
    manifest and the legacy jsonl records, and the Chrome-trace export is
    valid JSON with strictly nested spans per thread."""
    hp = load_config(
        "tpu",
        argv=BASE_ARGS + [
            "--ckpt-path", str(tmp_path),
            "--fault-plan", "nan_grad@epoch=1;preempt@epoch=2",
        ],
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    with pytest.raises(Preempted):
        trainer.fit()
    trainer.close()
    vdir = tmp_path / "version-0"

    events = obs.load_events(vdir / "events.jsonl")
    assert events, "faulted run emitted no events"
    for ev in events:
        assert obs.validate_event(ev) == [], ev
    kinds = {e["kind"] for e in events}
    assert {
        "run_start", "epoch_start", "epoch_end", "skip", "rollback",
        "preempt", "writer", "goodput",
    } <= kinds
    run_id = events[0]["run_id"]
    assert all(e["run_id"] == run_id for e in events)
    # one emitter per subsystem: the rollback cause and the preemption
    # point are attributable straight off the stream
    (rb,) = [e for e in events if e["kind"] == "rollback"]
    assert "bad steps" in rb["payload"]["reason"]
    (pre,) = [e for e in events if e["kind"] == "preempt"]
    assert pre["epoch"] == 2 and pre["payload"]["mid_epoch"] is False

    # satellite: the run identity rides the checkpoint manifest and the
    # legacy per-subsystem jsonl records (old records stay valid: the
    # loaders don't require the stamp)
    manifest = read_manifest(vdir / "last.ckpt")
    assert manifest["run_id"] == run_id and manifest["attempt"] == 0
    health = load_health_events(vdir / "health.jsonl")
    assert health and all(h["run_id"] == run_id for h in health)
    (record,) = load_goodput_records(vdir / "goodput.jsonl")
    assert record["run_id"] == run_id and record["attempt"] == 0
    assert "queue_depth" in record["ckpt_writer"]  # the new writer gauge

    # span timeline: valid JSON, strictly nested, the trainer + writer
    # lanes both present
    trace = json.loads((vdir / "trace.json").read_text())
    _assert_strictly_nested(trace)
    span_names = {
        e["name"] for e in trace["traceEvents"] if e.get("ph") == "X"
    }
    assert {"epoch", "eval", "rollback", "ckpt_write"} <= span_names
    lanes = {
        e["tid"] for e in trace["traceEvents"] if e.get("ph") == "X"
    }
    assert len(lanes) >= 2  # trainer loop + checkpoint writer


@pytest.mark.obs
def test_e2e_abort_leaves_crash_dump(tmp_path):
    """A rollback wanted with the budget already spent aborts — and the
    abort dumps the flight recorder: crash_dump.json holds the final ring
    with the skip trail and the abort reason."""
    with pytest.raises(FloatingPointError, match="non-finite"):
        _fit(
            tmp_path,
            extra=[
                "--fault-plan", "nan_grad@epoch=1",
                "--health-max-rollbacks", "0",
            ],
        )
    dump = json.loads((tmp_path / "version-0" / "crash_dump.json").read_text())
    assert "non-finite" in dump["reason"]
    ring_kinds = {e["kind"] for e in dump["ring"]}
    assert {"run_start", "skip", "abort"} <= ring_kinds
    for ev in dump["ring"]:
        assert obs.validate_event(ev) == []


@pytest.mark.obs
def test_e2e_run_id_inherited_from_supervisor_env(tmp_path, monkeypatch):
    """The supervisor hands every attempt the run id + restart index via
    the environment; the Trainer's bus, the manifest, and every record
    must carry them verbatim."""
    monkeypatch.setenv(obs.RUN_ID_ENV, "c0ffee0123456789")
    monkeypatch.setenv(obs.ATTEMPT_ENV, "3")
    _fit(tmp_path)
    events = obs.load_events(tmp_path / "version-0" / "events.jsonl")
    assert events
    assert all(
        e["run_id"] == "c0ffee0123456789" and e["attempt"] == 3
        for e in events
    )
    manifest = read_manifest(tmp_path / "version-0" / "last.ckpt")
    assert manifest["run_id"] == "c0ffee0123456789" and manifest["attempt"] == 3


@pytest.mark.obs
def test_no_obs_keeps_ring_but_writes_no_files(tmp_path):
    trainer = _fit(tmp_path, extra=["--no-obs"])
    vdir = tmp_path / "version-0"
    assert not (vdir / "events.jsonl").exists()
    assert not (vdir / "trace.json").exists()
    # the flight recorder still records (a crash would still dump)
    assert trainer.bus.ring_events()


# ------------------------------------------------------------- run_report


def _write_events(path, events):
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")


def _mk_run(root, run_id="ab" * 8, goodput=(6.0, 10.0)):
    """A synthetic two-attempt supervised run layout: supervisor events at
    the root, per-attempt events in the version dirs."""
    sup = EventBus(run_id=run_id)
    for kind, payload in (
        ("attempt_start", {"attempt": 0}),
        ("attempt_end", {"attempt": 0, "returncode": 75, "preempted": True}),
        ("attempt_start", {"attempt": 1}),
        ("attempt_end", {"attempt": 1, "returncode": 0, "preempted": False}),
    ):
        sup.emit(kind, **payload)
    _write_events(root / "events.jsonl", sup.ring_events())
    step_s, wall_s = goodput
    for attempt, n_epochs in ((0, 2), (1, 2)):
        bus = EventBus(run_id=run_id, attempt=attempt)
        bus.emit("run_start", epoch=0)
        for e in range(n_epochs):
            bus.emit("epoch_start", epoch=e)
            bus.emit("epoch_end", epoch=e, secs=1.0)
        if attempt == 0:
            bus.emit("rollback", epoch=1, reason="3 consecutive bad steps")
            bus.emit("skip", epoch=1, count=3)
            bus.emit("preempt", epoch=1, step=36, mid_epoch=False)
        bus.emit("writer", epoch=n_epochs - 1, busy_frac=0.25, queue_depth=1)
        bus.emit(
            "goodput",
            step_s=step_s, wall_s=wall_s,
            step_breakdown={"h2d_wait_s": 0.5},
        )
        _write_events(
            root / "version-0" / obs.events_filename(attempt and 1),
            bus.ring_events(),
        )
    return root


@pytest.mark.obs
def test_run_report_merges_summarizes_and_formats(tmp_path):
    import run_report

    _mk_run(tmp_path)
    events, files = run_report.load_run(tmp_path)
    assert len(files) == 3  # supervisor + two per-attempt files
    walls = [e["t_wall"] for e in events]
    assert walls == sorted(walls)  # one wall-clock-ordered timeline
    s = run_report.summarize(events)
    assert set(s["attempts"]) == {0, 1}
    assert s["epochs"] == 4 and s["rollbacks"] == 1 and s["preemptions"] == 1
    assert s["attempts"][0]["rollback_causes"] == [
        "epoch 1: 3 consecutive bad steps"
    ]
    assert len(s["supervisor"]) == 4
    assert s["goodput_frac"] == pytest.approx(12.0 / 20.0)
    text = run_report.format_summary("x", s)
    assert "2 attempt(s)" in text and "3 consecutive bad steps" in text
    timeline = run_report.format_timeline(events, tail=0)
    assert "preempt" in timeline and "a1/p0" in timeline
    diff = run_report.format_diff("a", s, "b", run_report.summarize(events))
    assert "rollbacks" in diff


@pytest.mark.obs
def test_run_report_summarize_counts_each_multihost_event_once(tmp_path):
    """Every process of a multi-host attempt emits the same trainer and
    watchdog events into its own events-p{i}.jsonl; the merged summary
    must count each occurrence once, not once per process."""
    import run_report

    root = _mk_run(tmp_path)
    # mirror attempt 1's events as a second process of attempt 1
    bus = EventBus(run_id="ab" * 8, attempt=1, process_index=1)
    for e in range(2):
        bus.emit("epoch_start", epoch=e)
        bus.emit("epoch_end", epoch=e, secs=1.0)
    bus.emit("writer", epoch=1, busy_frac=0.25, queue_depth=1)
    bus.emit("goodput", step_s=6.0, wall_s=10.0)
    _write_events(root / "version-0" / "events-a1-p1.jsonl", bus.ring_events())
    events, _ = run_report.load_run(root)
    s = run_report.summarize(events)
    assert s["epochs"] == 4  # not 6: process 1's epoch_ends aren't re-counted
    assert s["attempts"][1]["epochs"] == 2
    assert s["attempts"][1]["processes"] == {0, 1}  # the lane IS recorded
    assert s["rollbacks"] == 1


@pytest.mark.obs
def test_run_report_check_catches_violations(tmp_path):
    import run_report

    good = _mk_run(tmp_path / "good")
    assert run_report.check_run(good) == []
    bad_dir = tmp_path / "bad"
    bad_ev = EventBus(run_id="cd" * 8).emit("ok")
    bad_ev2 = dict(bad_ev, v=99)
    _write_events(bad_dir / "events.jsonl", [bad_ev, bad_ev2])
    with open(bad_dir / "events.jsonl", "a") as f:
        f.write('{"torn')
    problems = run_report.check_run(bad_dir)
    assert any("schema version 99" in p for p in problems)
    assert any("unparseable" in p for p in problems)
    assert run_report.check_run(tmp_path / "missing") != []  # no files = fail
    # the CLI contract bench legs rely on: nonzero exit on violations
    assert run_report.main([str(bad_dir), "--check"]) == 1
    assert run_report.main([str(good), "--check"]) == 0


@pytest.mark.obs
def test_run_report_diff_cli(tmp_path, capsys):
    import run_report

    a = _mk_run(tmp_path / "a", goodput=(6.0, 10.0))
    b = _mk_run(tmp_path / "b", run_id="ef" * 8, goodput=(9.0, 10.0))
    assert run_report.main([str(a), str(b), "--diff"]) == 0
    out = capsys.readouterr().out
    assert "goodput %" in out
    assert run_report.main([str(a), "--diff"]) == 2  # needs exactly two


# ----------------------------------------------------------------- serve


def test_serve_metrics_emit_event_validates():
    from distributed_training_comparison_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics()
    for _ in range(4):
        m.record_request_done(0.003)
    m.record_batch(batch_size=4, queue_depth=2)
    m.record_shed()
    ev = m.emit_event(EventBus(run_id="ad" * 8))
    assert ev["kind"] == "serve"
    assert ev["payload"]["completed"] == 4 and ev["payload"]["shed"] == 1
    assert obs.validate_event(ev) == []
