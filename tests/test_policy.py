"""Closed-loop autopilot tests (ISSUE 13): alert firings drive supervisor
actions, observably and rate-limited, plus the satellites that ride along
(fault-plan conflict rejection, fleet-wide quarantine persistence, the
chaos scenario catalog, ``run_report --policy``).

The load-bearing properties pinned here:

- the ``--policy`` grammar compiles (and malformed rules / rules whose
  trigger names no alert die at the CLI);
- a firing alert runs its bound action exactly once, with per-rule
  cooldowns and the per-attempt budget bounding a flap/storm, and EVERY
  decision — suppressed or acted — lands as a ``policy`` event;
- dry-run mode provably takes no action while logging (and arming the
  same cooldown/budget) as act mode would;
- the supervisor executors write the SAME marker/request files an
  operator/scheduler uses, and ``run_report --policy`` flags a requested
  action that never completed;
- policy events never count as liveness (the PR-7 self-revival flap,
  inverted and pinned for the autopilot);
- the e2e loop: an injected persistent straggler fires its alert, the
  policy drains the host, the world shrinks, and the run completes with
  params allclose to an uninterrupted baseline.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))

import run_report  # noqa: E402

from conftest import assert_trees_within_ulp
from distributed_training_comparison_tpu import obs
from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.obs.heartbeat import (
    FleetWatcher,
    LivenessTracker,
)
from distributed_training_comparison_tpu.ops import policy as P
from distributed_training_comparison_tpu.resilience import (
    CHAOS_SCENARIOS,
    FaultPlan,
    FaultSpecError,
    Supervisor,
    check_chaos_expectations,
    read_manifest,
)
from distributed_training_comparison_tpu.resilience.ckpt_io import (
    quarantine_sidecar_path,
    union_quarantine,
    write_quarantine_sidecar,
)

WORKER = Path(__file__).parent / "fleet_pool_worker.py"


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    monkeypatch.delenv(obs.RUN_ID_ENV, raising=False)
    monkeypatch.delenv(obs.ATTEMPT_ENV, raising=False)
    monkeypatch.delenv("DTC_EMU_SLOW_DISPATCH_S", raising=False)
    obs.reset()
    yield
    obs.reset()


class FakeBus:
    def __init__(self):
        self.events = []

    def emit(self, kind, **payload):
        ev = {"kind": kind, "payload": payload}
        self.events.append(ev)
        return ev

    def states(self, kind="policy"):
        return [
            e["payload"]["state"] for e in self.events if e["kind"] == kind
        ]


def _alert(spec="m:p95>1:for=1", state="firing", source="p1", metric="m"):
    return {
        "kind": "alert",
        "payload": {
            "spec": spec, "metric": metric, "state": state,
            "source": source, "value": 42.0,
        },
    }


# ------------------------------------------------------------- grammar


def test_policy_spec_parse_roundtrip():
    r = P.PolicyRule.parse(
        "step/dispatch_s:p95>30:for=2 -> drain_host:cooldown=120"
    )
    assert r.trigger == "step/dispatch_s:p95>30:for=2"
    assert r.action == "drain_host"
    assert r.cooldown_s == 120.0
    # default cooldown, whitespace tolerated
    r2 = P.PolicyRule.parse("train/loss:p95>50->rollback")
    assert r2.action == "rollback"
    assert r2.cooldown_s == P.DEFAULT_COOLDOWN_S


@pytest.mark.parametrize(
    "bad",
    [
        "no arrow here",
        "-> rollback",
        "m:p95>1 ->",
        "m:p95>1 -> explode",
        "m:p95>1 -> rollback:cooldown=abc",
        "m:p95>1 -> rollback:cooldown=-5",
        "m:p95>1 -> rollback:backoff=3",
    ],
)
def test_policy_spec_rejects_malformed(bad):
    with pytest.raises(P.PolicySpecError):
        P.PolicyRule.parse(bad)


def test_policy_rule_matches_spec_or_metric():
    by_spec = P.PolicyRule.parse("m:p95>1:for=2 -> rollback")
    assert by_spec.matches({"spec": "m:p95>1:for=2", "metric": "m"})
    assert not by_spec.matches({"spec": "m:p95>9", "metric": "m:p95>1"})
    by_metric = P.PolicyRule.parse("train/loss -> rollback")
    assert by_metric.matches({"spec": "train/loss:p95>1", "metric": "train/loss"})
    assert not by_metric.matches({"spec": "x", "metric": "train/grad_norm"})


def test_validate_policy_rules_needs_a_firing_alert():
    from distributed_training_comparison_tpu.obs.alerts import parse_alert_specs

    alerts = parse_alert_specs(["train/loss:p95>50:for=1"])
    P.validate_policy_rules(
        P.parse_policy_specs(["train/loss:p95>50:for=1 -> rollback"]), alerts
    )
    P.validate_policy_rules(  # metric-name trigger also resolves
        P.parse_policy_specs(["train/loss -> rollback"]), alerts
    )
    with pytest.raises(P.PolicySpecError):
        P.validate_policy_rules(
            P.parse_policy_specs(["train/grad_norm:p95>1 -> rollback"]),
            alerts,
        )


def test_config_rejects_policy_without_matching_alert():
    with pytest.raises(SystemExit):
        load_config("tpu", argv=["--policy", "m:p95>1 -> rollback"])
    hp = load_config(
        "tpu",
        argv=[
            "--alert", "m:p95>1", "--policy", "m:p95>1 -> rollback",
            "--policy-mode", "act",
        ],
    )
    assert hp.policy_mode == "act"
    with pytest.raises(SystemExit):
        load_config("tpu", argv=["--policy-max-actions", "0"])


# -------------------------------------------------------------- engine


def test_engine_acts_once_and_emits_requested_completed():
    bus = FakeBus()
    calls = []
    eng = P.PolicyEngine(
        P.parse_policy_specs(["m:p95>1:for=1 -> drain_host:cooldown=60"]),
        bus=bus, mode="act", clock=lambda: 0.0,
    )
    eng.bind("drain_host", lambda d: calls.append(d) or {"host": 1})
    eng.observe_event(_alert(spec="m:p95>1:for=1"))
    assert bus.states() == ["requested", "completed"]
    assert len(calls) == 1
    assert calls[0]["alert_source"] == "p1"
    done = [e for e in bus.events if e["payload"]["state"] == "completed"]
    assert done[0]["payload"]["host"] == 1
    # resolved transitions and foreign kinds never trigger
    eng.observe_event(_alert(spec="m:p95>1:for=1", state="resolved"))
    eng.observe_event({"kind": "metrics", "payload": {}})
    assert len(calls) == 1


def test_engine_cooldown_bounds_a_flapping_alert():
    bus = FakeBus()
    clock = [0.0]
    calls = []
    eng = P.PolicyEngine(
        P.parse_policy_specs(["m -> drain_host:cooldown=100"]),
        bus=bus, mode="act", clock=lambda: clock[0],
    )
    eng.bind("drain_host", lambda d: calls.append(d) or {})
    eng.observe_event(_alert())
    clock[0] = 50.0
    eng.observe_event(_alert())  # flap inside the window: suppressed
    assert bus.states() == ["requested", "completed", "cooldown"]
    cd = bus.events[-1]["payload"]
    assert cd["cooldown_remaining_s"] == pytest.approx(50.0)
    clock[0] = 150.0
    eng.observe_event(_alert())  # window passed: acts again
    assert len(calls) == 2


def test_engine_budget_bounds_a_storm_and_regrants_per_attempt():
    bus = FakeBus()
    calls = []
    eng = P.PolicyEngine(
        # distinct rules so the cooldown cannot be what stops the storm
        P.parse_policy_specs(
            ["a -> rollback:cooldown=0", "b -> rollback:cooldown=0"]
        ),
        bus=bus, mode="act", max_actions=1, clock=lambda: 1e9,
    )
    eng.bind("rollback", lambda d: calls.append(d) or {})
    eng.observe_event(_alert(metric="a"))
    eng.observe_event(_alert(metric="b"))
    assert len(calls) == 1
    assert bus.states()[-1] == "budget"
    # a new attempt re-grants; the same attempt index does NOT (the
    # explicit supervisor call and the tailed attempt_start both land)
    eng.observe_event({"kind": "attempt_start", "payload": {"attempt": 0}})
    eng.observe_event(_alert(metric="b"))
    assert bus.states()[-1] == "budget"
    eng.observe_event({"kind": "attempt_start", "payload": {"attempt": 1}})
    eng.observe_event(_alert(metric="b"))
    assert len(calls) == 2


def test_engine_budget_regrants_on_the_clock_without_attempts():
    """A session with no attempt boundaries (serving, unsupervised runs)
    re-grants the budget every BUDGET_WINDOW_S: the cap rate-limits a
    storm, it must not permanently disable the autopilot — a serve
    session's fifth recompile storm still gets its re-warm."""
    bus = FakeBus()
    calls = []
    clock = [0.0]
    eng = P.PolicyEngine(
        P.parse_policy_specs(["m -> rewarm_serve:cooldown=0"]),
        bus=bus, mode="act", max_actions=1, clock=lambda: clock[0],
    )
    eng.bind("rewarm_serve", lambda d: calls.append(d) or {})
    eng.observe_event(_alert())
    clock[0] = 10.0
    eng.observe_event(_alert())  # inside the window: budget-suppressed
    assert len(calls) == 1 and bus.states()[-1] == "budget"
    clock[0] = P.BUDGET_WINDOW_S + 1.0
    eng.observe_event(_alert())  # window rolled: the budget re-granted
    assert len(calls) == 2 and bus.states()[-1] == "completed"


def test_engine_dry_run_logs_without_acting_and_arms_cooldown():
    bus = FakeBus()
    logged = []
    calls = []
    clock = [0.0]
    eng = P.PolicyEngine(
        P.parse_policy_specs(["m -> drain_host:cooldown=100"]),
        bus=bus, mode="dry-run", clock=lambda: clock[0],
        log=logged.append,
    )
    eng.bind("drain_host", lambda d: calls.append(d) or {})
    eng.observe_event(_alert())
    assert calls == []  # provably no action
    assert bus.states() == ["dry_run"]
    assert bus.events[0]["payload"]["dry_run"] is True
    assert any("would run drain_host" in m for m in logged)
    clock[0] = 50.0
    eng.observe_event(_alert())  # the dry decision armed the cooldown too
    assert bus.states() == ["dry_run", "cooldown"]


def test_engine_mode_off_is_inert():
    bus = FakeBus()
    eng = P.PolicyEngine(
        P.parse_policy_specs(["m -> rollback"]), bus=bus, mode="off"
    )
    eng.observe_event(_alert())
    assert bus.events == []


def test_engine_unbound_failed_and_deferred_states():
    bus = FakeBus()
    eng = P.PolicyEngine(
        P.parse_policy_specs(
            ["a -> drain_host:cooldown=0", "b -> rollback:cooldown=0",
             "c -> rewarm_serve:cooldown=0"]
        ),
        bus=bus, mode="act", clock=lambda: 1e9,
    )

    def boom(decision):
        raise P.PolicyActionError("nope")

    eng.bind("drain_host", boom)
    eng.bind("rollback", lambda d: {"deferred": True})
    # no executor for rewarm_serve in this process
    eng.observe_event(_alert(metric="a"))
    assert bus.states() == ["requested", "failed"]
    assert bus.events[-1]["payload"]["error"] == "nope"
    eng.observe_event(_alert(metric="b"))
    assert bus.states()[-1] == "requested"  # completion comes from afar
    assert [p["action"] for p in eng.pending()] == ["rollback"]
    eng.observe_event(_alert(metric="c"))
    assert bus.states()[-1] == "unbound"
    s = eng.summary()
    assert s["by_state"]["failed"] == 1 and s["by_state"]["unbound"] == 1
    assert s["pending"] and s["mode"] == "act"
    # ... and when the deferred outcome arrives (the watcher tails the
    # applying process's events back through observe_event), the pending
    # ledger converges with the stream
    eng.observe_event({
        "kind": "policy",
        "payload": {"state": "completed", "id": s["pending"][0]},
    })
    assert eng.pending() == [] and eng.summary()["pending"] == []


def test_coalesced_is_terminal_but_not_completed():
    """A decision folded into an already-queued request must close its
    own id (the pending gate passes) WITHOUT counting as a performed
    action — the queued request's id carries the real outcome."""
    bus = FakeBus()
    eng = P.PolicyEngine(
        P.parse_policy_specs(["m -> rollback:cooldown=0"]),
        bus=bus, mode="act", clock=lambda: 1e9,
    )
    eng.bind("rollback", lambda d: {"coalesced": True})
    eng.observe_event(_alert())
    assert bus.states() == ["requested", "coalesced"]
    assert eng.pending() == []
    # offline: coalesced terminates its requested id for the gate too
    evs = [
        _policy_event("requested", "x-1", 1.0),
        _policy_event("coalesced", "x-1", 2.0),
    ]
    assert P.pending_actions(evs) == []


def test_decision_ids_are_unique_across_engines():
    """Two supervisor sessions over one ckpt root must not mint colliding
    ids — the pending gate would pair a new session's 'requested' with an
    old session's terminal event and miss a lost action."""
    a = P.PolicyEngine(P.parse_policy_specs(["m -> rollback"]), mode="act")
    b = P.PolicyEngine(P.parse_policy_specs(["m -> rollback"]), mode="act")
    a.bind("rollback", lambda d: {"deferred": True})
    b.bind("rollback", lambda d: {"deferred": True})
    a.observe_event(_alert())
    b.observe_event(_alert())
    assert a.pending()[0]["id"] != b.pending()[0]["id"]


def test_engine_unbound_spends_neither_budget_nor_cooldown():
    """A rule whose action has no executor here can do nothing — firing
    it must not starve the runnable rules of the shared budget, nor arm
    its own cooldown (binding the executor later must not find a rule
    stuck in a cooldown it never earned)."""
    bus = FakeBus()
    calls = []
    clock = [0.0]
    eng = P.PolicyEngine(
        P.parse_policy_specs(
            ["a -> rewarm_serve:cooldown=100", "b -> rollback:cooldown=0"]
        ),
        bus=bus, mode="act", max_actions=1, clock=lambda: clock[0],
    )
    eng.bind("rollback", lambda d: calls.append(d) or {})
    for _ in range(3):
        eng.observe_event(_alert(metric="a"))  # unbound: free
    eng.observe_event(_alert(metric="b"))
    assert len(calls) == 1  # the runnable rule still had its budget
    assert bus.states() == [
        "unbound", "unbound", "unbound", "requested", "completed",
    ]
    # bind it late: no phantom cooldown from the unbound decisions
    eng.observe_event({"kind": "attempt_start", "payload": {"attempt": 1}})
    eng.bind("rewarm_serve", lambda d: calls.append(d) or {})
    eng.observe_event(_alert(metric="a"))
    assert bus.states()[-1] == "completed" and len(calls) == 2


def test_bad_mode_rejected():
    with pytest.raises(P.PolicySpecError):
        P.PolicyEngine([], mode="yolo")


def test_engine_ignores_replayed_history():
    """The supervisor's watcher tails event files from byte 0: a restart
    over an existing ckpt root replays every old alert firing.  Acting on
    one would drain a now-healthy host or abort a fresh run over a
    previous session's tripwire — events older than the engine are
    history, not findings."""
    import time as _time

    bus = FakeBus()
    calls = []
    eng = P.PolicyEngine(
        P.parse_policy_specs(["m -> drain_host:cooldown=0"]),
        bus=bus, mode="act", clock=lambda: 1e9,
    )
    eng.bind("drain_host", lambda d: calls.append(d) or {})
    stale = dict(_alert(), t_wall=_time.time() - 3600.0)
    eng.observe_event(stale)
    assert calls == [] and bus.events == []
    fresh = dict(_alert(), t_wall=_time.time() + 1.0)
    eng.observe_event(fresh)
    assert len(calls) == 1


def test_engine_dry_run_previews_unbound_without_spending():
    """Executors are bound identically in both modes, so dry-run must
    classify an unbound action exactly as act would — and spend neither
    budget nor cooldown on it, or the previewed suppressions would not
    be the ones act mode applies."""
    bus = FakeBus()
    eng = P.PolicyEngine(
        P.parse_policy_specs(
            ["a -> drain_host:cooldown=0", "b -> rollback:cooldown=0"]
        ),
        bus=bus, mode="dry-run", max_actions=1, clock=lambda: 1e9,
    )
    eng.bind("rollback", lambda d: {})  # drain_host deliberately unbound
    for _ in range(3):
        eng.observe_event(_alert(metric="a"))
    eng.observe_event(_alert(metric="b"))
    assert bus.states() == ["unbound", "unbound", "unbound", "dry_run"]


# ----------------------------------------------------- request channel


def test_request_channel_roundtrip_and_torn_request(tmp_path):
    path = P.write_action_request(
        tmp_path, "rollback", {"id": "a0-1", "rule": "r"}
    )
    assert path.name == "policy-rollback.req"
    poller = P.PolicyRequestPoller(tmp_path)
    reqs = poller.poll()
    assert reqs == [{"id": "a0-1", "rule": "r", "action": "rollback"}]
    assert poller.poll() == []  # consumed
    # torn/garbage request still consumes and names its action
    (tmp_path / "fleet" / "policy-abort_with_evidence.req").write_text("{tor")
    reqs = poller.poll()
    assert reqs == [{"action": "abort_with_evidence"}]
    with pytest.raises(P.PolicyActionError):
        P.write_action_request(tmp_path, "drain_host", {})


def test_supervisor_actions_write_markers_and_requests(tmp_path):
    stops = []
    acts = P.supervisor_actions(
        tmp_path, fleet_hosts=2, request_stop=stops.append
    )
    # rank -> host mapping through the live status file (after a shrink
    # rank 0 may live on host 1)
    fleet = tmp_path / "fleet"
    fleet.mkdir()
    (fleet / "status.json").write_text(json.dumps({"hosts": [1]}))
    res = acts["drain_host"]({"alert_source": "p0", "rule": "r", "id": "x"})
    assert res["host"] == 1
    marker = fleet / "host-1.down"
    assert marker.exists()
    assert json.loads(marker.read_text())["by"] == "policy"
    # no status file: the rank is the host
    (fleet / "status.json").unlink()
    res = acts["drain_host"]({"alert_source": "p0"})
    assert res["host"] == 0
    # a fleet-aggregate alert names no host
    with pytest.raises(P.PolicyActionError):
        acts["drain_host"]({"alert_source": "fleet"})
    # deferred actions land as request files; abort also stops the loop
    assert acts["rollback"]({"id": "a0-2"})["deferred"] is True
    assert (fleet / "policy-rollback.req").exists()
    # an unconsumed request wins: the second decision coalesces into it
    # (completing immediately) instead of overwriting/orphaning its id
    again = acts["rollback"]({"id": "a0-9"})
    assert again == {"coalesced": True}
    assert json.loads(
        (fleet / "policy-rollback.req").read_text()
    )["id"] == "a0-2"
    assert acts["abort_with_evidence"]({"id": "a0-3", "rule": "r"})[
        "deferred"
    ] is True
    assert (fleet / "policy-abort_with_evidence.req").exists()
    assert stops and "abort_with_evidence" in stops[0]
    # rewarm_serve is deliberately ABSENT: an in-process serving action
    # left genuinely unbound supervisor-side, so a misplaced rewarm rule
    # reports unbound without burning cooldown or the shared budget
    assert "rewarm_serve" not in acts
    # and without an elastic fleet there is nothing to drain
    solo = P.supervisor_actions(tmp_path, fleet_hosts=0)
    with pytest.raises(P.PolicyActionError):
        solo["drain_host"]({"alert_source": "p0"})


def test_emit_completion_pairs_with_requested():
    bus = FakeBus()
    P.emit_completion(
        bus, {"action": "rollback", "id": "a0-1", "rule": "r"},
        from_epoch=3, to_epoch=2,
    )
    P.emit_completion(
        bus, {"action": "rollback", "id": "a0-2"}, ok=False, error="why"
    )
    states = bus.states()
    assert states == ["completed", "failed"]
    assert bus.events[1]["payload"]["error"] == "why"


# ------------------------------------------------- watcher + liveness


def test_fleet_watcher_feeds_policy_from_the_tail(tmp_path):
    bus = obs.EventBus(run_id="x" * 16, persist=True)
    bus.bind_dir(tmp_path)
    eng = P.PolicyEngine(
        P.parse_policy_specs(["m -> drain_host:cooldown=0"]),
        bus=None, mode="dry-run", clock=lambda: 1e9,
    )
    eng.bind("drain_host", lambda d: {})
    watcher = FleetWatcher(tmp_path, FakeBus(), policy=eng, poll_s=0.05)
    src = obs.EventBus(run_id="y" * 16, process_index=1)
    src.bind_dir(tmp_path)
    src.emit("alert", spec="s", metric="m", state="firing", source="p1")
    watcher.step()
    assert [d["state"] for d in eng.decisions] == ["dry_run"]
    bus.close()
    src.close()


def test_policy_events_are_not_liveness():
    """The PR-7 flap, inverted for the autopilot: a policy event about a
    host must never count as that host being alive."""
    tracker = LivenessTracker(heartbeat_s=1.0)
    tracker.observe(
        {"kind": "policy", "process_index": 1, "payload": {}}, now=0.0
    )
    assert tracker.states() == {}
    tracker.observe({"kind": "heartbeat", "process_index": 1}, now=0.0)
    tracker.observe(
        {"kind": "chaos", "process_index": 1, "payload": {}}, now=100.0
    )
    # the chaos stamp did not refresh host 1: it is long dead by now
    assert [f["state"] for f in tracker.check(now=100.0)] == ["dead"]


# --------------------------------------------------- supervisor stop


def test_supervisor_request_stop_breaks_without_relaunch():
    rcs = [1, 1, 1]
    seen = []
    events = []

    def runner(cmd, env):
        seen.append(list(cmd))
        return rcs[len(seen) - 1]

    sup = Supervisor(
        ["train"], runner=runner, max_restarts=5,
        sleep=lambda s: None, log=lambda m: None,
        events=lambda kind, **p: events.append((kind, p)),
    )
    sup.request_stop("policy abort_with_evidence (rule)")
    summary = sup.run()
    assert len(seen) == 1  # the in-flight attempt finished; no relaunch
    assert summary["final_rc"] == 1 and summary["restarts"] == 0
    give_up = [p for k, p in events if k == "give_up"]
    assert give_up and "abort_with_evidence" in give_up[0]["reason"]


# ------------------------------------------------------- crash evidence


def test_dump_crash_carries_evidence(tmp_path):
    bus = obs.EventBus(run_id="e" * 16)
    bus.emit("alert", state="firing", spec="s")
    path = bus.dump_crash(
        "policy abort", directory=tmp_path,
        evidence={"alert_timeline": [{"kind": "alert"}], "policy_timeline": []},
    )
    dump = json.loads(Path(path).read_text())
    assert dump["evidence"]["alert_timeline"] == [{"kind": "alert"}]
    bus.close()


# -------------------------------------------------- run_report --policy


def _policy_event(state, pid, t, action="rollback"):
    return {
        "v": 1, "run_id": "r" * 16, "attempt": 0, "process_index": 0,
        "t_wall": t, "t_mono": t, "kind": "policy",
        "payload": {
            "state": state, "id": pid, "action": action, "rule": "m -> x",
        },
    }


def _control_event(state, pid, t, action="rollback", **extra):
    return {
        "v": 1, "run_id": "r" * 16, "attempt": 0, "process_index": 0,
        "t_wall": t, "t_mono": t, "kind": "control",
        "payload": {
            "state": state, "id": pid, "action": action,
            "boundary": "chunk", "mid_epoch": True,
            "t_decide": t - 1.0, "t_apply": t, "ttm_s": 1.0, **extra,
        },
    }


def test_run_report_policy_exit_codes(tmp_path, capsys):
    events = tmp_path / "events.jsonl"
    # completed pair + its applied control event + an informational
    # dry-run: rc 0
    rows = [
        _policy_event("requested", "a0-1", 1.0),
        _policy_event("completed", "a0-1", 2.0),
        _control_event("applied", "a0-1", 2.0, steps_since_decide=2),
        dict(_policy_event("dry_run", "a0-2", 3.0), payload={
            "state": "dry_run", "id": "a0-2", "action": "drain_host",
            "rule": "m -> drain_host", "dry_run": True,
        }),
    ]
    events.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    assert run_report.main([str(tmp_path), "--policy"]) == 0
    out = capsys.readouterr().out
    assert "COMPLETED" in out and "no action taken" in out
    assert "APPLIED" in out and "ttm=1.000s" in out
    # a requested action with no outcome anywhere in the stream: rc 1
    rows.append(_policy_event("requested", "a0-3", 4.0))
    events.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    assert run_report.main([str(tmp_path), "--policy"]) == 1
    assert "STILL PENDING" in capsys.readouterr().out
    # an acted decision that completed but never reached an 'applied'
    # control event: the decide->apply trail broke mid-way, rc 1
    rows = [
        _policy_event("requested", "b0-1", 1.0),
        _policy_event("completed", "b0-1", 2.0),
    ]
    events.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    assert run_report.main([str(tmp_path), "--policy"]) == 1
    assert "NEVER APPLIED" in capsys.readouterr().out
    # no policy events at all is healthy; an empty root is rc 2
    events.write_text(json.dumps(_policy_event("x", "y", 0.0)).replace(
        '"policy"', '"metrics"'
    ) + "\n")
    assert run_report.main([str(tmp_path), "--policy"]) == 0
    empty = tmp_path / "empty"
    empty.mkdir()
    assert run_report.main([str(empty), "--policy"]) == 2


def test_pending_actions_joins_across_processes():
    evs = [
        _policy_event("requested", "a0-1", 1.0),
        dict(_policy_event("completed", "a0-1", 2.0), process_index=1),
        _policy_event("requested", "a0-2", 3.0),
    ]
    pend = P.pending_actions(evs)
    assert [p["id"] for p in pend] == ["a0-2"]
    assert len(P.policy_timeline(evs)) == 3


# ------------------------------------------------ fault-plan conflicts


def test_fault_plan_rejects_same_kind_window_duplicates():
    # step faults: same kind + epoch conflicts whatever the step offsets
    # (the second can only fire on the contractually-clean replay)
    with pytest.raises(FaultSpecError) as e:
        FaultPlan.parse("nan_grad@epoch=1;nan_grad@epoch=1:step=4")
    assert "nan_grad@epoch=1" in str(e.value)
    assert "nan_grad@epoch=1:step=4" in str(e.value)
    with pytest.raises(FaultSpecError):
        FaultPlan.parse("loss_spike@epoch=2,loss_spike@epoch=2:scale=9")
    with pytest.raises(FaultSpecError):
        FaultPlan.parse("desync@epoch=1;desync@epoch=1")
    # boundary faults: duplicates share kind+epoch+step
    with pytest.raises(FaultSpecError):
        FaultPlan.parse("preempt@epoch=2;preempt@epoch=2")
    with pytest.raises(FaultSpecError):
        FaultPlan.parse("stall@epoch=1:secs=1;stall@epoch=1:secs=2")
    # legitimate compositions still parse
    assert FaultPlan.parse(
        "nan_grad@epoch=1;loss_spike@epoch=2;preempt@epoch=3;"
        "preempt@epoch=5:step=2;preempt@epoch=5:step=6;stall@epoch=4:secs=1"
    ) is not None
    # prob-draws are exempt (their windows are not knowable at parse time)
    assert FaultPlan.parse("preempt@prob=0.1;preempt@prob=0.2") is not None


# ---------------------------------------------------- chaos catalog


def test_chaos_catalog_is_well_formed():
    from distributed_training_comparison_tpu.obs.alerts import parse_alert_specs

    assert CHAOS_SCENARIOS, "catalog must not be empty"
    # the matrix covers its advertised axes
    joined = json.dumps(CHAOS_SCENARIOS)
    for axis in ("preempt", "nan_grad", "drain_host", "host-1.up"):
        assert axis in joined, f"matrix lost the {axis} axis"
    for name, sc in CHAOS_SCENARIOS.items():
        for field in (
            "desc", "fault_plan", "alerts", "policies", "policy_mode",
            "driver", "env", "extra_args", "expect", "require_kinds",
        ):
            assert field in sc, f"{name} missing {field}"
        alerts = parse_alert_specs(list(sc["alerts"]))
        rules = P.parse_policy_specs(list(sc["policies"]))
        P.validate_policy_rules(rules, alerts)  # triggers resolve
        if sc["fault_plan"]:
            assert FaultPlan.parse(sc["fault_plan"]) is not None
        assert sc["policy_mode"] in P.MODES
        for kind in sc["require_kinds"]:
            assert kind in obs.KNOWN_KINDS
    # dry-run is proven by a scenario that expects NOTHING to happen
    dry = CHAOS_SCENARIOS["straggler_dryrun"]["expect"]
    assert dry["resizes"] == 0 and dry["policy_completed"] == 0


def test_chaos_matrix_refuses_without_explicit_cpu(monkeypatch, tmp_path):
    """A chip belongs to one process: the matrix's parent imports the
    package and then starts children that need a device, so it must exit
    non-zero with a reason BEFORE it spawns anything, unless the
    environment explicitly asks for the CPU (where its records belong)."""
    import chaos_matrix

    def no_spawn(*a, **kw):
        raise AssertionError("chaos_matrix spawned a child before refusing")

    monkeypatch.setattr(subprocess, "run", no_spawn)
    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    out = tmp_path / "chaos.json"
    with pytest.raises(SystemExit) as ei:
        chaos_matrix.main(["--out", str(out)])
    assert "refused" in str(ei.value.code)
    assert not out.exists()  # no scoreboard written


def test_check_chaos_expectations_bounds():
    obs_row = {
        "final_rc": 0, "resizes": 2, "policy_completed": 1,
        "crash_dump_evidence": False,
    }
    assert check_chaos_expectations(
        {"final_rc": 0, "resizes__min": 1, "policy_completed__max": 2},
        obs_row,
    ) == []
    probs = check_chaos_expectations(
        {"final_rc_nonzero": True, "resizes": 0, "missing__min": 1,
         "crash_dump_evidence": True},
        obs_row,
    )
    assert len(probs) == 4


# ------------------------------------------- quarantine persistence


def test_quarantine_sidecar_roundtrip_and_union(tmp_path):
    assert write_quarantine_sidecar(tmp_path, 0, []) is None  # empty: no file
    p0 = write_quarantine_sidecar(tmp_path, 0, [3, 1])
    p1 = write_quarantine_sidecar(tmp_path, 1, {7, 5})
    assert p0 == quarantine_sidecar_path(tmp_path, 0)
    assert json.loads(p1.read_text()) == [5, 7]
    # manifest base + every rank's sidecar union; torn sidecars skipped
    (tmp_path / "quarantine-p2.json").write_text("{half a reco")
    assert union_quarantine(tmp_path, base=[9, 1]) == [1, 3, 5, 7, 9]
    assert union_quarantine(tmp_path) == [1, 3, 5, 7]
    assert union_quarantine(tmp_path / "nowhere", base=[2]) == [2]
    # valid JSON with drifted entries: bad values dropped, never raised
    (tmp_path / "quarantine-p3.json").write_text('[11, null, "x", "13"]')
    assert union_quarantine(tmp_path) == [1, 3, 5, 7, 11, 13]


@pytest.mark.health
def test_quarantine_union_survives_multihost_relaunch(tmp_path):
    """ROADMAP fleet residue, closed: a relaunch re-applies EVERY rank's
    quarantined example ids — the manifest's (rank 0) unioned with the
    quarantine-p*.json sidecars other ranks left next to the checkpoint —
    not just rank 0's set.  Emulated 2-host shape: a real single-process
    run quarantines its own window (manifest + its sidecar), and rank 1's
    sidecar is written at the file level, exactly what a second host
    leaves on the shared checkpoint root."""
    from distributed_training_comparison_tpu.train import Trainer
    from test_train import TinyNet

    argv = [
        "--synthetic-data", "--limit-examples", "128",
        "--batch-size", "32", "--epoch", "2",
        "--save-last-min-secs", "0", "--no-progress", "--seed", "7",
        "--data-mode", "host", "--workers", "0",
        "--ckpt-path", str(tmp_path),
        "--fault-plan", "nan_grad@epoch=1",
        "--health-quarantine", "--health-bad-steps", "3",
    ]
    trainer = Trainer(load_config("tpu", argv=argv), model=TinyNet(num_classes=100))
    trainer.fit()
    rank0 = set(trainer.train_loader.quarantined)
    trainer.close()
    assert rank0, "the fault must have quarantined rank 0's window"
    vdir = tmp_path / "version-0"
    # rank 0's own set was persisted BOTH ways
    manifest = read_manifest(vdir / "last.ckpt")
    assert set(manifest["quarantined"]) == rank0
    assert set(json.loads(quarantine_sidecar_path(vdir, 0).read_text())) == rank0
    # "host 1" condemned a disjoint window of ITS shard before the relaunch
    rank1 = {101, 102, 103} - rank0
    write_quarantine_sidecar(vdir, 1, rank1)
    resumed = Trainer(
        load_config(
            "tpu",
            argv=[
                "--synthetic-data", "--limit-examples", "128",
                "--batch-size", "32", "--epoch", "3",
                "--save-last-min-secs", "0", "--no-progress", "--seed", "7",
                "--data-mode", "host", "--workers", "0",
                "--ckpt-path", str(tmp_path), "--auto-resume",
                "--health-quarantine",
            ],
        ),
        model=TinyNet(num_classes=100),
    )
    try:
        assert set(resumed.train_loader.quarantined) == rank0 | rank1
    finally:
        resumed.close()


# ----------------------------------------------------- serve rewarm


def test_serve_rewarm_closes_a_recompile_storm():
    from distributed_training_comparison_tpu.serve import ServeEngine
    from test_train import TinyNet

    eng = ServeEngine(
        model=TinyNet(num_classes=10), buckets=(2, 4, 8),
        precision="fp32", image_size=16,
    )
    eng.warmup(buckets=[2])  # the replica's expected traffic
    assert eng.recompiled_buckets == ()
    # a flash crowd lands on an unwarmed bucket: the storm's footprint
    eng.predict_logits(np.zeros((4, 16, 16, 3), np.uint8))
    assert eng.recompiled_buckets == (4,)
    res = eng.rewarm()
    # the affected bucket plus the still-cold remainder of the ladder
    assert res["recompiled"] == [4]
    assert res["warmed"] == [4, 8]
    assert eng.recompiled_buckets == ()
    before = eng.stats()["compiles"]
    eng.predict_logits(np.zeros((8, 16, 16, 3), np.uint8))
    assert eng.stats()["compiles"] == before  # the ladder is fully warm
    assert eng.recompiled_buckets == ()
    # nothing left to warm: rewarm still succeeds (and re-arms)
    assert eng.rewarm() == {"warmed": [], "recompiled": []}


# --------------------------------------------- in-process trainer e2e


def _tiny_argv(tmp_path, extra=()):
    return [
        "--synthetic-data", "--limit-examples", "128",
        "--batch-size", "32", "--epoch", "3",
        "--save-last-min-secs", "0", "--no-progress", "--seed", "7",
        "--device-chunk-steps", "2", "--eval-step", "1000",
        "--ckpt-path", str(tmp_path), *extra,
    ]


@pytest.mark.health
def test_inprocess_policy_rollback_applies_at_epoch_boundary(tmp_path):
    """Unsupervised closed loop, rollback flavor: an in-process alert on
    the (always-breaching) loss metric fires once, the policy engine
    defers a rollback to the epoch boundary, and the trainer replays via
    the existing watchdog path — every decision on the event stream."""
    from distributed_training_comparison_tpu.train import Trainer
    from test_train import TinyNet

    hp = load_config(
        "tpu",
        argv=_tiny_argv(
            tmp_path,
            extra=[
                "--alert", "train/loss:p95>-1:for=1",
                "--policy", "train/loss:p95>-1:for=1 -> rollback:cooldown=9999",
                "--policy-mode", "act",
            ],
        ),
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    try:
        trainer.fit()
    finally:
        trainer.close()
    events = obs.load_events(tmp_path / "version-0" / "events.jsonl")
    states = [
        e["payload"]["state"] for e in events if e["kind"] == "policy"
    ]
    assert "requested" in states and "completed" in states
    rollbacks = [e for e in events if e["kind"] == "rollback"]
    assert rollbacks and "policy action" in rollbacks[0]["payload"]["reason"]
    assert P.pending_actions(events) == []
    assert run_report.main([str(tmp_path), "--policy"]) == 0
    assert run_report.main(
        [str(tmp_path), "--check", "--require-kind", "policy"]
    ) == 0


@pytest.mark.health
def test_inprocess_policy_dry_run_takes_no_action(tmp_path):
    """Same rule in the default dry-run mode: the decision is logged as a
    policy event, and provably nothing happens — no rollback, no request,
    identical epoch count."""
    from distributed_training_comparison_tpu.train import Trainer
    from test_train import TinyNet

    hp = load_config(
        "tpu",
        argv=_tiny_argv(
            tmp_path,
            extra=[
                "--alert", "train/loss:p95>-1:for=1",
                "--policy", "train/loss:p95>-1:for=1 -> rollback:cooldown=9999",
            ],
        ),
    )
    assert hp.policy_mode == "dry-run"  # the default
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    try:
        trainer.fit()
    finally:
        trainer.close()
    events = obs.load_events(tmp_path / "version-0" / "events.jsonl")
    states = [
        e["payload"]["state"] for e in events if e["kind"] == "policy"
    ]
    assert states == ["dry_run"]
    assert not any(e["kind"] == "rollback" for e in events)


@pytest.mark.health
def test_inprocess_policy_abort_attaches_evidence(tmp_path):
    """abort_with_evidence, unsupervised: the run stops orderly at the
    next epoch boundary and crash_dump.json carries the alert + policy
    timelines under 'evidence' — the post-mortem opens on WHY."""
    from distributed_training_comparison_tpu.train import Trainer
    from test_train import TinyNet

    hp = load_config(
        "tpu",
        argv=_tiny_argv(
            tmp_path,
            extra=[
                "--alert", "train/loss:p95>-1:for=1",
                "--policy",
                "train/loss:p95>-1:for=1 -> abort_with_evidence:cooldown=9999",
                "--policy-mode", "act",
            ],
        ),
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    with pytest.raises(P.PolicyAbort):
        try:
            trainer.fit()
        finally:
            trainer.close()
    dump = json.loads((tmp_path / "version-0" / "crash_dump.json").read_text())
    assert "policy abort_with_evidence" in dump["reason"]
    ev = dump["evidence"]
    assert ev["alert_timeline"] and ev["policy_timeline"]
    assert ev["request"]["rule"].endswith("abort_with_evidence:cooldown=9999")
    events = obs.load_events(tmp_path / "version-0" / "events.jsonl")
    assert any(e["kind"] == "abort" for e in events)
    assert P.pending_actions(events) == []


# --------------------------------------------------- supervised e2e


@pytest.mark.elastic
def test_e2e_policy_drains_persistent_straggler(tmp_path):
    """ISSUE 13 acceptance: a supervised 2-host fleet with a persistent
    straggler on host 1 -> the dispatch alert fires -> the POLICY (not an
    operator) writes host-1.down -> the fleet drains and re-renders a
    world-1 attempt that resumes from the verified checkpoint -> the run
    completes with params allclose to an uninterrupted baseline, every
    action traceable to its alert on the merged stream."""
    from distributed_training_comparison_tpu.resilience.faults import (
        EMU_SLOW_DISPATCH_ENV,
    )

    root = tmp_path / "run"
    goodput_json = tmp_path / "GOODPUT.json"
    cmd = [
        sys.executable, str(WORKER), "--supervise",
        "--fleet-hosts", "2", "--fleet-local-devices", "1",
        "--fleet-grace-secs", "3", "--fleet-poll-secs", "0.2",
        "--synthetic-data", "--limit-examples", "256",
        "--batch-size", "32", "--epoch", "10",
        "--no-progress", "--eval-step", "1000",
        "--save-last-min-secs", "0", "--seed", "7",
        "--device-chunk-steps", "2",
        "--heartbeat-secs", "0.2",
        "--ckpt-path", str(root),
        "--goodput-json", str(goodput_json),
        "--alert", "step/dispatch_s:p95>30:for=2",
        "--policy", "step/dispatch_s:p95>30:for=2 -> drain_host:cooldown=120",
        "--policy-mode", "act",
        # the window the chain is given, by construction: host 1 reports
        # its slowed sketch from rank 0's first checkpoint (epoch 0) on,
        # and two flushed windows, one supervisor poll and the drain are
        # about a second — while the ten epochs left take well under one.
        # Rank 0 holds at epoch 1's boundary for fifteen, so the drain
        # lands mid-run on a loaded box as on an idle one; the stall is
        # behind epoch 1's checkpoint and never fires again on resume
        "--fault-plan", "stall@epoch=1:secs=15",
    ]
    env = dict(os.environ)
    env[EMU_SLOW_DISPATCH_ENV] = "60"
    proc = subprocess.run(
        cmd, cwd=WORKER.parent.parent, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=420,
    )
    assert proc.returncode == 0, (proc.stderr or "")[-3000:]
    assert "Traceback" not in (proc.stderr or ""), (proc.stderr or "")[-3000:]

    events, _files = run_report.load_run(root)
    # the policy acted exactly once: requested -> completed, naming host 1
    policy = [e["payload"] for e in events if e["kind"] == "policy"]
    assert [p["state"] for p in policy] == ["requested", "completed"]
    assert policy[1]["host"] == 1
    assert policy[0]["rule"].startswith("step/dispatch_s:p95>30")
    assert policy[0]["alert_source"] == "p1"
    assert policy[0]["dry_run"] is False
    # traceable to its triggering alert on the same stream
    firings = [
        e["payload"] for e in events
        if e["kind"] == "alert" and e["payload"]["state"] == "firing"
    ]
    assert any(
        f["spec"] == policy[0]["trigger"] and f.get("source") == "p1"
        for f in firings
    )
    # the fleet path was the operator path: drain -> shrink -> resume
    resizes = [e["payload"] for e in events if e["kind"] == "resize"]
    assert [(r["from_world"], r["to_world"], r["reason"]) for r in resizes] == [
        (2, 1, "host_lost")
    ]
    run_starts = {
        e["attempt"]: e["payload"] for e in events if e["kind"] == "run_start"
    }
    assert run_starts[1]["resumed"] is True
    # the marker the policy wrote was consumed by the fleet
    assert not (root / "fleet" / "host-1.down").exists()
    assert run_report.main([str(root), "--policy"]) == 0
    assert run_report.main(
        [str(root), "--check", "--require-kind", "policy",
         "--require-kind", "resize"]
    ) == 0
    gp = json.loads(goodput_json.read_text())
    assert gp["supervisor"]["policy"]["by_state"]["completed"] == 1

    # uninterrupted same-seed baseline on this process's devices
    from distributed_training_comparison_tpu.train import Trainer
    from fleet_pool_worker import TinyNet
    from flax import serialization

    clean_root = tmp_path / "clean"
    hp = load_config(
        "tpu",
        argv=[
            "--synthetic-data", "--limit-examples", "256",
            "--batch-size", "32", "--epoch", "10",
            "--no-progress", "--eval-step", "1000",
            "--save-last-min-secs", "0", "--seed", "7",
            "--device-chunk-steps", "2",
            "--ckpt-path", str(clean_root),
        ],
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    trainer.fit()
    trainer.close()

    def final_params(r):
        raw = serialization.msgpack_restore(
            (r / "version-0" / "last.ckpt").read_bytes()
        )
        assert raw["epoch"] == 9  # all 10 epochs completed
        return raw["state"]["params"]

    # world sizes 2 -> 1 against 8 devices: another reduction order, the
    # band measured in test_fleet_pool's kill/shrink/re-expand run
    assert_trees_within_ulp(
        final_params(root), final_params(clean_root), ulp=1 << 15
    )
