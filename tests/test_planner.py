"""Auto-parallel planner tests (ISSUE 14, parallel/planner.py).

Covers: the cost-model fit from synthetic compile/dispatch events, the
feasibility filter against hand-constructed layouts (refusals carrying
``elastic.divisibility_help``-style numbers), plan == hand-flags
trajectory parity through the real Trainer, resize→replan under the
fleet supervisor (scripted FakeProc children), the ``replan`` policy action
(act / dry-run / unavailable), the ``run_report --plan`` stream gate,
and the two satellite knobs (``--device-prefetch auto``,
``--ckpt-comms-residual``).
"""

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.parallel import planner
from distributed_training_comparison_tpu.parallel.planner import (
    Candidate,
    CostModel,
    PlanError,
    bubble_fraction,
    enumerate_candidates,
    fit_ledger,
    model_spec,
    plan_layout,
)

REPO = Path(__file__).parent.parent
sys.path.insert(0, str(REPO / "tools"))
sys.path.insert(0, str(REPO / "benchmark"))
import run_report  # noqa: E402
from harness import flops  # noqa: E402


def _hp(**kw):
    base = dict(
        model="vit_tiny", batch_size=128, grad_accum=1, grad_comms="fp32",
        pipeline_microbatches=0, num_devices=0, image_size=32, patch_size=0,
        parallel_plan="auto",
    )
    base.update(kw)
    return argparse.Namespace(**base)


# ------------------------------------------------- the planner's FLOP table


@pytest.mark.parametrize(
    "config,argv",
    [
        ("resnet18_cifar100", ["--model", "resnet18"]),
        ("vit_small_cifar100_p2", ["--model", "vit_small", "--patch-size", "2"]),
    ],
)
def test_planner_flops_agree_with_the_benchmarks_count(config, argv):
    """The planner's hand-entered ResNet table (``_RESNET_FWD_GFLOPS_32PX``)
    and its ViT formula against ``benchmark/harness/flops.py`` on the image
    configurations' own ``flops`` groups, within 1 %: two counts of one
    forward pass with no common source left to keep them together."""
    group = json.loads(
        (REPO / "benchmark" / "configs" / f"{config}.json").read_text()
    )["flops"]
    spec = model_spec(load_config("tpu", ["--synthetic-data", *argv]))
    assert spec.fwd_flops_per_image == pytest.approx(
        flops.train_flops_per_image(group) / 3, rel=0.01
    )


# ------------------------------------------------------------ feasibility


def test_enumerate_respects_model_divisibility():
    spec = model_spec(_hp())  # vit_tiny: depth 12, heads 3
    cands, refusals = enumerate_candidates(8, spec, batch_size=128)
    keys = {c.key for c in cands}
    # heads=3 never divides by any tp that tiles 8 devices
    assert not any(c.model > 1 for c in cands)
    assert any("attention heads (3)" in r for r in refusals)
    # depth 12: pp2 (v1+v2) and pp4 (v1 only — 12 % 8 != 0) are legal
    assert "dp4xpp2" in keys and "dp4xpp2xv2" in keys
    assert "dp2xpp4" in keys and "dp2xpp4xv2" not in keys
    assert any("12 does not split into" in r for r in refusals)


def test_enumerate_batch_refusal_carries_legal_numbers():
    with pytest.raises(PlanError) as exc:
        plan_layout(_hp(batch_size=6), devices=4, device_kind="unknown")
    msg = str(exc.value)
    assert "legal data-parallel sizes" in msg
    assert "nearest legal batch sizes" in msg
    assert "no plan found" not in msg


def test_generic_model_plans_dp_only():
    plan = plan_layout(
        _hp(model="resnet18", batch_size=32), devices=4,
        device_kind="unknown",
    )
    assert all(c.model == 1 and c.pipe == 1 for c in plan.candidates)
    assert plan.chosen.key == "dp4"


def test_grad_comms_flag_is_the_numerics_ceiling():
    spec = model_spec(_hp())
    fp32, _ = enumerate_candidates(4, spec, batch_size=128)
    assert {c.grad_comms for c in fp32} == {"fp32"}
    int8, _ = enumerate_candidates(
        4, spec, batch_size=128, grad_comms_cap="int8"
    )
    assert {c.grad_comms for c in int8} == {"fp32", "fp16", "int8"}
    # nothing crosses the wire at dp=1: no compressed dp1 candidates
    assert not any(c.data == 1 and c.grad_comms != "fp32" for c in int8)


def test_moe_trunk_refuses_pipeline_allows_expert_parallel():
    spec = model_spec(_hp(model="vit_moe"))  # 8 experts, heads 3
    cands, refusals = enumerate_candidates(8, spec, batch_size=128)
    assert not any(c.pipe > 1 for c in cands)
    assert any("no stageable trunk" in r for r in refusals)
    # expert parallelism: 8 % tp == 0 → tp 2/4/8 legal
    assert {c.model for c in cands} == {1, 2, 4, 8}


# ------------------------------------------------------------- cost model


def _synthetic_ledger(points, *, k=4, devices=1, device_kind="TPU v4",
                      mesh=None, batch=128, hbm_limit=None):
    """Compile + metrics + run_start events for given (flops, secs/dispatch)
    points — the stream shape the real bus commits."""
    events = [
        {
            "kind": "run_start", "t_wall": 1.0, "process_index": 0,
            "attempt": 0,
            "payload": {"mesh": mesh or {"data": devices, "model": 1,
                                         "pipe": 1},
                        "batch_size": batch},
        }
    ]
    metrics = {}
    for i, (flops, secs) in enumerate(points):
        name = f"device_chunk_runner@k{k}" if i == 0 else f"exec{i}"
        fp = f"{i:016x}"
        events.append(
            {
                "kind": "compile", "t_wall": 2.0 + i, "process_index": 0,
                "attempt": 0,
                "payload": {
                    "name": name, "fingerprint": fp, "flops": flops,
                    "devices": devices, "device_kind": device_kind,
                    "argument_bytes": 1000.0, "temp_bytes": 500.0,
                    "peak_bytes": 1500.0,
                },
            }
        )
        metrics[f"exec/{name}:{fp[:8]}/dispatch_s"] = {
            "type": "histogram", "count": 10, "sum": secs * 10,
        }
    if hbm_limit is not None:
        metrics["res/hbm_limit_bytes"] = {"type": "gauge", "value": hbm_limit}
    events.append(
        {
            "kind": "metrics", "t_wall": 9.0, "process_index": 0,
            "attempt": 0, "payload": {"metrics": metrics},
        }
    )
    return events


def test_cost_model_fit_recovers_slope_and_intercept():
    a, b = 2e-12, 0.003
    flops = [1e9, 4e9, 8e9]
    events = _synthetic_ledger([(f, a * f + b) for f in flops])
    ledger = fit_ledger(events)
    assert len(ledger.points) == 3
    cm = CostModel.fit(ledger)
    assert cm.source == "ledger-fit" and cm.n_points == 3
    assert cm.secs_per_flop == pytest.approx(a, rel=1e-6)
    assert cm.overhead_s == pytest.approx(b, rel=1e-6)
    # device kind keyed the wire bandwidth off the planning table
    assert cm.wire_bytes_per_s == planner.WIRE_BYTES_PER_S_BY_DEVICE_KIND[
        "TPU v4"
    ]
    # the train exec's flops are whole-program per K-step dispatch
    assert ledger.step_flops_total == pytest.approx(1e9 / 4)
    assert ledger.measured_step_s == pytest.approx((a * 1e9 + b) / 4)


def test_cost_model_fallbacks():
    cm = CostModel.fit(None, device_kind="TPU v5p")
    assert cm.source == "peak-table"
    assert cm.secs_per_flop == pytest.approx(
        1.0 / (459e12 * planner.ASSUMED_MFU)
    )
    # the flat defaults stay, labelled, for the CPU backend only
    assert CostModel.fit(None, device_kind="cpu").source == "default"


def test_cost_model_refuses_an_unknown_tpu_kind():
    """A TPU the tables do not know is an error, not a silent default: a
    made-up 10 GB/s / 50 GFLOP/s would rank layouts for a chip nobody
    measured."""
    with pytest.raises(planner.PlanError, match="TPU v99"):
        CostModel.fit(None, device_kind="TPU v99")


def test_fit_ledger_mesh_follows_the_chosen_executable_attempt():
    """A resized fleet's stream carries run_starts with DIFFERENT meshes;
    the footprint split must come from the attempt that compiled the
    chosen train executable, not whichever run_start came last — mixing
    them would mis-scale every candidate's predicted activation HBM."""
    events = _synthetic_ledger(
        [(8e9, 0.02)], mesh={"data": 4, "model": 1, "pipe": 1}, batch=128
    )
    # a later, shrunk attempt: new run_start (dp2) + a SMALLER train exec
    events.append(
        {
            "kind": "run_start", "t_wall": 20.0, "process_index": 0,
            "attempt": 1,
            "payload": {"mesh": {"data": 2, "model": 1, "pipe": 1},
                        "batch_size": 128},
        }
    )
    events.append(
        {
            "kind": "compile", "t_wall": 21.0, "process_index": 0,
            "attempt": 1,
            "payload": {
                "name": "device_chunk_runner@k4", "fingerprint": "f" * 16,
                "flops": 4e9, "devices": 2, "device_kind": "TPU v4",
                "temp_bytes": 900.0,
            },
        }
    )
    fit = fit_ledger(events)
    # attempt 0's exec has the larger flops -> ITS mesh (dp4) binds
    assert fit.captured_mesh == {"data": 4, "model": 1, "pipe": 1}
    assert fit.temp_bytes == 500.0


def test_ledger_at_different_batch_is_discarded():
    events = _synthetic_ledger([(1e9, 0.01)], batch=64)
    plan = plan_layout(
        _hp(batch_size=128), devices=4, device_kind="unknown", events=events
    )
    assert plan.ledger is None  # fell back to analytic flops
    assert plan.chosen.terms["flops_source"] == "analytic"


def test_predict_bubble_and_hbm_terms():
    assert bubble_fraction(1, 8) == 0.0
    assert bubble_fraction(2, 8, 1) == pytest.approx(2 / 10)
    assert bubble_fraction(2, 8, 2) == pytest.approx(4 / 20)
    spec = model_spec(_hp())
    cm = CostModel.fit(None, device_kind="unknown")
    plain = planner.predict(
        Candidate(data=2, model=1, pipe=1, devices=4), cm, spec,
        batch_size=128,
    )
    zero = planner.predict(
        Candidate(data=2, model=1, pipe=1, shard_optim=True, devices=4),
        cm, spec, batch_size=128,
    )
    # ZeRO halves the optimizer-state share of predicted HBM at dp=2
    assert zero.predicted_hbm_bytes < plain.predicted_hbm_bytes
    int8 = planner.predict(
        Candidate(data=2, model=1, pipe=1, grad_comms="int8", devices=4),
        cm, spec, batch_size=128,
    )
    # a compressed wire carries the params-shaped fp32 residual
    assert int8.predicted_hbm_bytes > plain.predicted_hbm_bytes
    assert int8.terms["sync_bytes"] == pytest.approx(
        plain.terms["sync_bytes"] / 4
    )
    piped = planner.predict(
        Candidate(data=2, model=1, pipe=2, microbatches=8, devices=4),
        cm, spec, batch_size=128,
    )
    assert piped.terms["bubble_frac"] == pytest.approx(0.2)
    assert piped.terms["compute_s"] > plain.terms["compute_s"]


def test_hbm_gate_refuses_with_numbers():
    # a limit so small every layout busts it → PlanError naming HBM
    events = _synthetic_ledger([(1e9, 0.01)], hbm_limit=1000.0)
    with pytest.raises(PlanError) as exc:
        plan_layout(
            _hp(), devices=4, device_kind="unknown", events=events
        )
    assert "predicted HBM" in str(exc.value)
    assert "device limit" in str(exc.value)


def test_plan_tie_break_prefers_simpler_layout():
    plan = plan_layout(_hp(batch_size=128), devices=4, device_kind="unknown")
    # dp4 and dp4xzero predict the same step seconds; the simpler wins
    assert plan.chosen.key == "dp4"
    assert not plan.chosen.shard_optim


def test_install_plan_writes_hparams():
    hp = _hp(model_parallel=1, pipeline_parallel=1, shard_optim=False,
             pipeline_schedule="gpipe", pipeline_virtual_stages=0,
             parallel_style="tensor")
    plan = plan_layout(hp, devices=4, device_kind="unknown")
    # force a pipeline winner to exercise every installed field
    plan.chosen = next(
        c for c in plan.candidates if c.pipe == 2 and c.virtual == 2
    )
    changed = planner.install_plan(plan, hp)
    assert hp.pipeline_parallel == 2
    assert hp.pipeline_schedule == "interleaved"
    assert hp.pipeline_virtual_stages == 2
    assert hp.pipeline_microbatches == plan.chosen.microbatches
    assert "pipeline_parallel" in changed


def test_plan_payload_is_bounded_and_complete():
    plan = plan_layout(
        _hp(grad_comms="int8"), devices=8, device_kind="unknown"
    )
    payload = plan.payload(installed=True, reason="construction")
    assert len(payload["candidates"]) <= planner.PLAN_EVENT_CANDIDATES
    assert payload["candidates_considered"] == len(plan.candidates)
    assert payload["fit"]["source"] in ("default", "peak-table", "ledger-fit")
    assert payload["layout"]["data"] == plan.chosen.data
    assert payload["flags"][:2] == ["--model-parallel", str(plan.chosen.model)]


# ----------------------------------------------------- staging depth (S2)


def test_auto_staging_depth():
    from distributed_training_comparison_tpu.parallel.planner import (
        auto_staging_depth,
    )

    assert auto_staging_depth(1e6, None, default=2) == 2  # no stats: default
    # 25% of 80MB headroom / 1MB chunks = 20 → capped at 8
    assert auto_staging_depth(1e6, 80_000_000) == 8
    assert auto_staging_depth(10e6, 80_000_000) == 2
    assert auto_staging_depth(1e9, 80_000_000) == 1  # never below 1


# -------------------------------------------------------- config flags


def test_config_parallel_plan_flags(tmp_path):
    hp = load_config("tpu", ["--parallel-plan", "auto",
                             "--ckpt-path", str(tmp_path)])
    assert hp.parallel_plan == "auto"
    hp = load_config("tpu", ["--device-prefetch", "auto",
                             "--ckpt-path", str(tmp_path)])
    assert hp.device_prefetch == "auto"
    hp = load_config("tpu", ["--device-prefetch", "3",
                             "--ckpt-path", str(tmp_path)])
    assert hp.device_prefetch == 3
    with pytest.raises(SystemExit):
        load_config("tpu", ["--device-prefetch", "bogus"])
    with pytest.raises(SystemExit):
        load_config("tpu", ["--parallel-plan", "bogus"])
    hp = load_config("tpu", ["--ckpt-comms-residual",
                             "--ckpt-path", str(tmp_path)])
    assert hp.ckpt_comms_residual is True
    assert load_config("tpu", []).ckpt_comms_residual is False


# ------------------------------------------------- run_report --plan gate


def _write_events(path: Path, events) -> Path:
    path.write_text("".join(json.dumps(e) + "\n" for e in events))
    return path


def _plan_event(layout, *, installed=True, attempt=0, world=None,
                t_wall=10.0):
    payload = {
        "chosen": {"key": "k", **layout},
        "layout": layout,
        "installed": installed,
        "reason": "construction",
        "devices": 4,
        "batch_size": 32,
        "candidates": [
            {"key": "k", "predicted_step_s": 0.01,
             "predicted_hbm_bytes": 1e6, **layout}
        ],
        "fit": {"source": "default"},
        "attempt": attempt,
    }
    if world is not None:
        payload["world"] = world
    return {
        "kind": "plan", "t_wall": t_wall, "process_index": 0,
        "attempt": attempt, "payload": payload,
    }


def _run_start_event(mesh, *, attempt=0, world_size=1, t_wall=11.0,
                     shard_optim=False, grad_comms="fp32"):
    return {
        "kind": "run_start", "t_wall": t_wall, "process_index": 0,
        "attempt": attempt,
        "payload": {
            "mesh": mesh, "world_size": world_size, "batch_size": 32,
            "shard_optim": shard_optim, "grad_comms": grad_comms,
        },
    }


LAYOUT_DP4 = {"data": 4, "model": 1, "pipe": 1, "shard_optim": False,
              "grad_comms": "fp32"}


def test_plan_report_green_on_agreement(tmp_path, capsys):
    _write_events(
        tmp_path / "events.jsonl",
        [
            _plan_event(LAYOUT_DP4),
            _run_start_event({"data": 4, "model": 1, "pipe": 1}),
        ],
    )
    assert run_report.plan_report(tmp_path) == 0
    out = capsys.readouterr().out
    assert "matches its attempt's run_start layout" in out


def test_plan_report_fails_on_silently_ignored_plan(tmp_path, capsys):
    _write_events(
        tmp_path / "events.jsonl",
        [
            _plan_event(LAYOUT_DP4),
            _run_start_event({"data": 2, "model": 2, "pipe": 1}),
        ],
    )
    assert run_report.plan_report(tmp_path) == 1
    assert "PLAN MISMATCH" in capsys.readouterr().out


def test_plan_report_dump_mode_never_gates(tmp_path):
    _write_events(
        tmp_path / "events.jsonl",
        [
            _plan_event(LAYOUT_DP4, installed=False),
            _run_start_event({"data": 2, "model": 2, "pipe": 1}),
        ],
    )
    assert run_report.plan_report(tmp_path) == 0


def test_plan_report_scales_data_axis_by_world_share(tmp_path):
    # the pid-level CPU fleet emulation: the plan sized 4 data shards for
    # 2 hosts, rank 0 joined a 1-host world and ran data=2 — consistent
    _write_events(
        tmp_path / "events.jsonl",
        [
            _plan_event(LAYOUT_DP4, world=2),
            _run_start_event({"data": 2, "model": 1, "pipe": 1},
                             world_size=1),
        ],
    )
    assert run_report.plan_report(tmp_path) == 0
    # but a model-axis disagreement still fails whatever the worlds
    _write_events(
        tmp_path / "events.jsonl",
        [
            _plan_event(
                {**LAYOUT_DP4, "model": 2}, world=2,
            ),
            _run_start_event({"data": 2, "model": 1, "pipe": 1},
                             world_size=1),
        ],
    )
    assert run_report.plan_report(tmp_path) == 1


def test_plan_report_no_events_and_no_plans(tmp_path):
    assert run_report.plan_report(tmp_path / "missing") == 2
    _write_events(
        tmp_path / "events.jsonl",
        [_run_start_event({"data": 4, "model": 1, "pipe": 1})],
    )
    assert run_report.plan_report(tmp_path) == 0


# -------------------------------------------------- replan policy action


class _Bus:
    def __init__(self):
        self.events = []

    def emit(self, kind, **payload):
        ev = {"kind": kind, "payload": payload, "t_wall": time.time()}
        self.events.append(ev)
        return ev


def _alert(metric="compile/peak_hbm_bytes", spec=None):
    return {
        "kind": "alert", "t_wall": time.time() + 60.0,
        "payload": {
            "state": "firing", "metric": metric,
            "spec": spec or f"{metric}:value>1", "source": "p0", "value": 2,
        },
    }


def test_replan_action_acts_and_dry_runs():
    from distributed_training_comparison_tpu.ops import policy as policy_mod

    calls = []
    for mode, expect_called in (("act", True), ("dry-run", False)):
        bus = _Bus()
        engine = policy_mod.PolicyEngine(
            [policy_mod.PolicyRule.parse(
                "compile/peak_hbm_bytes:value>1 -> replan:cooldown=0"
            )],
            bus=bus, mode=mode,
        )
        engine.bind(
            "replan",
            lambda d: calls.append(d) or {"reason": "test"},
        )
        engine.observe_event(_alert())
        states = [e["payload"]["state"] for e in bus.events]
        if expect_called:
            assert states == ["requested", "completed"]
            assert calls and calls[-1]["action"] == "replan"
        else:
            assert states == ["dry_run"]
            assert not calls
        calls.clear()


def test_replan_unavailable_reports_failed():
    from distributed_training_comparison_tpu.ops import policy as policy_mod

    bus = _Bus()
    engine = policy_mod.PolicyEngine(
        [policy_mod.PolicyRule.parse(
            "compile/peak_hbm_bytes:value>1 -> replan"
        )],
        bus=bus, mode="act",
    )
    # supervisor_actions with no planner: the executor raises → 'failed'
    actions = policy_mod.supervisor_actions("/nonexistent", fleet_hosts=2)
    engine.bind("replan", actions["replan"])
    engine.observe_event(_alert())
    states = [e["payload"]["state"] for e in bus.events]
    assert states == ["requested", "failed"]
    assert "--parallel-plan auto" in bus.events[-1]["payload"]["error"]


def test_replan_rule_validates_at_cli(tmp_path):
    hp = load_config(
        "tpu",
        ["--alert", "compile/peak_hbm_bytes:value>1e9",
         "--policy", "compile/peak_hbm_bytes -> replan:cooldown=30",
         "--ckpt-path", str(tmp_path)],
    )
    assert hp.policy
    with pytest.raises(SystemExit):
        load_config(
            "tpu", ["--policy", "compile/peak_hbm_bytes -> replan"]
        )  # trigger names no alert rule


# ------------------------------------- fleet: resize → replan (scripted)


from distributed_training_comparison_tpu.resilience.fleet import (  # noqa: E402
    FleetSupervisor,
)
from distributed_training_comparison_tpu.resilience.preempt import (  # noqa: E402
    EXIT_PREEMPTED,
)


class FakeProc:
    _next_pid = 7000

    def __init__(self, rc, runs_for=3):
        FakeProc._next_pid += 1
        self.pid = FakeProc._next_pid
        self._rc_final = rc
        self._runs_for = runs_for
        self._polls = 0
        self._rc = None
        self._terminated = False

    def poll(self):
        self._polls += 1
        if self._rc is None:
            if self._terminated:
                self._rc = EXIT_PREEMPTED
            elif self._rc_final is not None and self._polls > self._runs_for:
                self._rc = self._rc_final
        return self._rc

    def terminate(self):
        self._terminated = True

    def kill(self):
        self._rc = -9


def _plan_fleet(tmp_path, scripts, events, **kw):
    it = iter(scripts)
    spawned = []

    def spawn(cmd, env):
        rc, runs_for = next(it)
        p = FakeProc(rc, runs_for)
        p.cmd = list(cmd)
        spawned.append(p)
        return p

    kw.setdefault("hosts", 2)
    kw.setdefault("batch_size", 32)
    kw.setdefault("local_devices", 2)
    kw.setdefault("grace_s", 0.0)
    kw.setdefault("poll_s", 0.05)
    kw.setdefault(
        "plan_hparams",
        _hp(model="resnet18", batch_size=32, parallel_plan="auto"),
    )
    sup = FleetSupervisor(
        ["train.py", "--epoch", "3", "--model-parallel", "1",
         "--parallel-plan", "auto"],
        ckpt_root=tmp_path,
        spawn=spawn,
        sleep=lambda s: None,
        log=lambda m: None,
        events=lambda kind, **p: events.append((kind, p)),
        **kw,
    )
    return sup, spawned


def test_fleet_resize_triggers_replan_with_different_layout(tmp_path):
    events: list = []
    # attempt 0 (world 2, 4 devices): host 1 dies by external SIGKILL;
    # attempt 1 (world 1, 2 devices) completes clean
    scripts = [(None, 0), (-9, 1), (0, 2)]
    sup, spawned = _plan_fleet(tmp_path, scripts, events)
    summary = sup.run()
    assert summary["final_rc"] == 0
    kinds = [k for k, _ in events]
    plans = [p for k, p in events if k == "plan"]
    assert len(plans) == 2
    assert [p["reason"] for p in plans] == ["attempt_plan", "resize"]
    # the shrunk fleet re-planned onto a DIFFERENT legal layout: the
    # resize event precedes the new plan, whose data axis halved
    assert kinds.index("resize") < len(kinds) - 1 - kinds[::-1].index("plan")
    assert plans[0]["layout"]["data"] == 4
    assert plans[1]["layout"]["data"] == 2
    assert plans[0]["world"] == 2 and plans[1]["world"] == 1
    assert all(p["installed"] for p in plans)
    # the rendered child argv carries the plan's flags and disables the
    # child-side planner; the caller's own layout flags are stripped
    cmd = spawned[-1].cmd
    assert cmd[cmd.index("--parallel-plan") + 1] == "off"
    assert cmd.count("--parallel-plan") == 1
    assert cmd[cmd.index("--model-parallel") + 1] == "1"
    assert cmd.count("--model-parallel") == 1
    assert "--no-shard-optim" in cmd
    # the compact plan ledger rides the summary (GOODPUT's supervisor)
    assert [p["world"] for p in summary["plans"]] == [2, 1]


def test_policy_replan_drains_and_replans_budget_free(tmp_path):
    events: list = []
    # attempt 0: both ranks healthy until the replan drain; attempt 1 ok
    scripts = [(None, 0), (None, 0), (0, 2), (0, 2)]
    sup, spawned = _plan_fleet(tmp_path, scripts, events, max_restarts=0)
    orig = sup._launch

    def launch(attempt):
        if attempt == 0:
            sup.request_replan("hbm breach (test)")
        return orig(attempt)

    sup._launch = launch
    summary = sup.run()
    assert summary["final_rc"] == 0
    assert "give_up" not in [k for k, _ in events]  # drain was budget-free
    plans = [p for k, p in events if k == "plan"]
    assert [p["reason"] for p in plans] == ["attempt_plan", "policy_replan"]
    assert plans[1]["replan_trigger"] == "hbm breach (test)"
    assert summary["planned_drains"] == 1


def test_fleet_without_plan_hparams_keeps_legacy_selection(tmp_path):
    events: list = []
    scripts = [(0, 2), (0, 2)]
    sup, spawned = _plan_fleet(
        tmp_path, scripts, events, plan_hparams=None
    )
    assert sup.plan_hparams is None
    with pytest.raises(ValueError):
        sup.request_replan("nope")
    summary = sup.run()
    assert summary["final_rc"] == 0
    assert "plan" not in [k for k, _ in events]
    assert "plans" not in summary
    # caller flags survive un-stripped when no plan owns the layout
    assert "--parallel-plan" in spawned[-1].cmd


def test_plan_world_respects_host_batch_divisibility(tmp_path):
    """A per-device-legal candidate can still crash every child: rank
    construction hard-enforces batch % processes == 0
    (host_local_batch_slice).  vit_tiny on 3 hosts x 2 devices admits
    dp2xtp3 (batch 32 % dp 2 == 0) — but 32 % 3 hosts != 0, so world 3
    must be refused and the plan land on world 2."""
    events: list = []
    sup, _ = _plan_fleet(
        tmp_path, [(0, 2)] * 6, events, hosts=3,
        plan_hparams=_hp(model="vit_tiny", batch_size=32,
                         parallel_plan="auto"),
    )
    world, plan, errors = sup._plan_world(3)
    assert world == 2
    assert any("not divisible by 3 host(s)" in e for e in errors)
    assert plan.chosen.data * plan.chosen.model * plan.chosen.pipe == 4


def test_fleet_fallback_to_widest_legal_disables_child_planner(tmp_path):
    """Every world's plan refused (generic model, batch 6 never divides
    the dp-only device counts) but the caller's hand --model-parallel 2
    mesh IS legal at full width: the attempt falls back to the classic
    widest-legal selection, keeps the caller's layout flags, and the
    children get --parallel-plan off — a child-side re-plan would
    re-raise the same refusal at construction and burn the budget."""
    events: list = []
    scripts = [(0, 2)] * 3
    it = iter(scripts)
    spawned = []

    def spawn(cmd, env):
        rc, runs_for = next(it)
        p = FakeProc(rc, runs_for)
        p.cmd = list(cmd)
        spawned.append(p)
        return p

    sup = FleetSupervisor(
        ["train.py", "--model-parallel", "2", "--parallel-plan", "auto"],
        ckpt_root=tmp_path, spawn=spawn, sleep=lambda s: None,
        log=lambda m: None,
        events=lambda kind, **p: events.append((kind, p)),
        hosts=3, batch_size=6, local_devices=4, model_parallel=2,
        grace_s=0.0, poll_s=0.05,
        plan_hparams=_hp(model="resnet18", batch_size=6,
                         parallel_plan="auto"),
    )
    summary = sup.run()
    assert summary["final_rc"] == 0
    assert "plan" not in [k for k, _ in events]  # nothing plannable
    cmd = spawned[-1].cmd
    assert cmd[cmd.index("--parallel-plan") + 1] == "off"
    assert cmd.count("--parallel-plan") == 1
    # the caller's hand layout survived un-stripped
    assert cmd[cmd.index("--model-parallel") + 1] == "2"


def test_fleet_plan_refusal_names_numbers(tmp_path):
    events: list = []
    # batch 30 on 2×2 devices: no dp in {1,2,4} divides 30 evenly at
    # width 4... (30 % 4 != 0, 30 % 2 == 0) — force total refusal with
    # min_hosts=2 so the legal 1-host world is below the floor
    sup, _ = _plan_fleet(
        tmp_path, [(0, 2)], events,
        batch_size=30, min_hosts=2,
        plan_hparams=_hp(model="resnet18", batch_size=30,
                         parallel_plan="auto"),
    )
    from distributed_training_comparison_tpu.resilience.fleet import (
        FleetPlanError,
    )

    with pytest.raises(FleetPlanError) as exc:
        sup.run()
    assert "30" in str(exc.value)


# ------------------------------------ trainer e2e: plan == hand flags


def _trainer_hp(tmp_path, *extra):
    return load_config(
        "tpu",
        [
            "--synthetic-data", "--limit-examples", "96",
            "--batch-size", "16", "--epoch", "1",
            "--no-progress", "--eval-step", "10000",
            "--save-last-min-secs", "0", "--seed", "7",
            "--num-devices", "4",
            "--ckpt-path", str(tmp_path),
            *extra,
        ],
    )


def _fit_losses(hp):
    from distributed_training_comparison_tpu.models.vit import ViT
    from distributed_training_comparison_tpu.train import Trainer

    trainer = Trainer(hp, model=ViT(depth=2, dim=32, heads=2))
    try:
        trainer.fit()
    finally:
        trainer.close()
    events = planner.load_ledger_events(hp.ckpt_path)
    losses = [
        e["payload"]["train_loss"]
        for e in events
        if e.get("kind") == "epoch_end"
    ]
    return trainer, losses, events


@pytest.mark.slow
def test_trainer_plan_matches_hand_flags_trajectory(tmp_path):
    """--parallel-plan auto must install a layout whose trajectory is the
    one the same flags hand-picked produce — and the plan event must agree
    with run_start (run_report --plan green)."""
    planned, p_losses, p_events = _fit_losses(
        _trainer_hp(tmp_path / "plan", "--parallel-plan", "auto")
    )
    assert planned.plan is not None and planned._plan_installed
    plan_evs = [e for e in p_events if e.get("kind") == "plan"]
    assert len(plan_evs) == 1
    chosen = plan_evs[0]["payload"]["chosen"]
    hand, h_losses, _ = _fit_losses(
        _trainer_hp(
            tmp_path / "hand",
            *plan_evs[0]["payload"]["flags"],
        )
    )
    assert dict(hand.mesh.shape) == dict(planned.mesh.shape)
    np.testing.assert_allclose(p_losses, h_losses, rtol=0, atol=0)
    # the stream gate: installed plan == run_start layout
    assert run_report.plan_report(tmp_path / "plan") == 0
    rs = [e for e in p_events if e.get("kind") == "run_start"][0]["payload"]
    assert rs["mesh"]["data"] == chosen["data"]
    assert rs["mesh"]["model"] == chosen["model"]


def test_trainer_dump_mode_survives_plan_refusal(tmp_path, monkeypatch):
    """dump 'scores and logs, never gates': a PlanError must not kill a
    run whose hand flags are legal — auto, with nothing to install,
    still raises the refusal."""
    from distributed_training_comparison_tpu.models.vit import ViT
    from distributed_training_comparison_tpu.train import Trainer

    def refuse(*a, **k):
        raise PlanError("no feasible layout (test)")

    monkeypatch.setattr(planner, "plan_layout", refuse)
    hp = _trainer_hp(tmp_path, "--parallel-plan", "dump")
    trainer = Trainer(hp, model=ViT(depth=2, dim=32, heads=2))
    try:
        assert trainer.plan is None
        assert trainer._plan_refusal == "no feasible layout (test)"
        assert dict(trainer.mesh.shape) == {"data": 4, "model": 1, "pipe": 1}
    finally:
        trainer.close()
    with pytest.raises(PlanError):
        Trainer(
            _trainer_hp(tmp_path / "auto", "--parallel-plan", "auto"),
            model=ViT(depth=2, dim=32, heads=2),
        )


@pytest.mark.slow
def test_trainer_dump_mode_keeps_hand_flags(tmp_path):
    hp = _trainer_hp(tmp_path, "--parallel-plan", "dump")
    from distributed_training_comparison_tpu.models.vit import ViT
    from distributed_training_comparison_tpu.train import Trainer

    trainer = Trainer(hp, model=ViT(depth=2, dim=32, heads=2))
    try:
        assert trainer.plan is not None
        assert not trainer._plan_installed
        # hand flags kept: the default layout, whatever the plan said
        assert dict(trainer.mesh.shape) == {"data": 4, "model": 1, "pipe": 1}
        trainer.bus.emit("run_end", epoch=0)
    finally:
        trainer.close()
    # a dump-mode plan never gates the stream
    assert run_report.plan_report(tmp_path) == 0


# -------------------------------------- comms residual checkpointing (S1)


def _residual_hp(tmp_path, *extra):
    return load_config(
        "tpu",
        [
            "--synthetic-data", "--limit-examples", "96",
            "--batch-size", "16", "--epoch", "2",
            "--no-progress", "--eval-step", "10000",
            "--save-last-min-secs", "0", "--seed", "7",
            "--num-devices", "2",
            "--grad-comms", "int8",
            "--ckpt-path", str(tmp_path),
            *extra,
        ],
    )


def _tiny_model():
    import flax.linen as lnn
    import jax.numpy as jnp

    class TinyNet(lnn.Module):
        num_classes: int = 100

        @lnn.compact
        def __call__(self, x, train: bool = False):
            x = lnn.Conv(8, (3, 3), strides=2, use_bias=False)(x)
            x = lnn.BatchNorm(use_running_average=not train)(x)
            x = lnn.relu(x)
            x = jnp.mean(x, axis=(1, 2))
            return lnn.Dense(self.num_classes)(x)

    return TinyNet()


@pytest.mark.slow
def test_ckpt_comms_residual_roundtrip_and_cross_flag_drop(tmp_path):
    from distributed_training_comparison_tpu.resilience import read_manifest
    from distributed_training_comparison_tpu.train import Trainer

    hp = _residual_hp(tmp_path, "--ckpt-comms-residual")
    trainer = Trainer(hp, model=_tiny_model())
    try:
        trainer.fit()
    finally:
        trainer.close()
    last = Path(trainer.version_dir) / "last.ckpt"
    manifest = read_manifest(last)
    assert manifest["comms_residual"] is True
    # the serialized payload genuinely carries the residual leaves
    from flax import serialization

    raw = serialization.msgpack_restore(last.read_bytes())
    assert "comms_residual" in raw["state"]
    res_leaves = raw["state"]["comms_residual"]
    total = float(
        sum(
            np.abs(np.asarray(l)).sum()
            for l in jax.tree_util.tree_leaves(res_leaves)
        )
    )
    assert total > 0.0  # int8 EF residual after 2 epochs is nonzero

    # same-flag resume restores it (not zeros)
    hp2 = _residual_hp(tmp_path, "--ckpt-comms-residual",
                       "--resume", str(last), "--epoch", "3")
    t2 = Trainer(hp2, model=_tiny_model())
    try:
        restored = float(
            sum(
                np.abs(np.asarray(l)).sum()
                for l in jax.tree_util.tree_leaves(t2.state.comms_residual)
            )
        )
        assert restored == pytest.approx(total, rel=1e-6)
    finally:
        t2.close()

    # cross-flag restore, SAME wire: the restoring run kept --grad-comms
    # int8 but dropped --ckpt-comms-residual — flag-off behavior wins
    # (drop and warn, residual restarts at zero), never a silent restore
    # off an absent flag
    hp2b = _residual_hp(tmp_path, "--resume", str(last), "--epoch", "3")
    t2b = Trainer(hp2b, model=_tiny_model())
    try:
        assert t2b.state.comms_residual is not None  # int8 wire carries one
        dropped = float(
            sum(
                np.abs(np.asarray(l)).sum()
                for l in jax.tree_util.tree_leaves(t2b.state.comms_residual)
            )
        )
        assert dropped == 0.0
    finally:
        t2b.close()

    # cross-flag restore (fp32 wire now): documented drop-and-warn path —
    # the run constructs fine and carries NO residual
    hp3 = load_config(
        "tpu",
        [
            "--synthetic-data", "--limit-examples", "96",
            "--batch-size", "16", "--epoch", "3",
            "--no-progress", "--eval-step", "10000",
            "--save-last-min-secs", "0", "--seed", "7",
            "--num-devices", "2",
            "--ckpt-path", str(tmp_path),
            "--resume", str(last),
        ],
    )
    t3 = Trainer(hp3, model=_tiny_model())
    try:
        assert t3.state.comms_residual is None
    finally:
        t3.close()


def test_ckpt_without_residual_resumes_with_zeros(tmp_path):
    """Flag-off checkpoints keep the old shape; a comms run resuming one
    restarts the residual at zero (the pre-satellite contract)."""
    import jax as _jax
    import jax.numpy as jnp
    from distributed_training_comparison_tpu.parallel import make_mesh
    from distributed_training_comparison_tpu.train import checkpoint as ckpt
    from distributed_training_comparison_tpu.train.state import TrainState
    import optax

    tx = optax.sgd(0.1)
    params = {"w": jnp.ones((4, 4))}
    base = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
        opt_state=tx.init(params), apply_fn=lambda *a, **k: None, tx=tx,
    )
    vdir = tmp_path
    ckpt.save_resume_state(vdir, base, 0, 0.5)
    raw = (vdir / "last.ckpt").read_bytes()
    from flax import serialization

    assert "comms_residual" not in serialization.msgpack_restore(raw)["state"]
    # restoring WITH a residual-carrying state injects zeros, not a crash
    carrying = base.replace(
        comms_residual={"w": jnp.full((4, 4), 7.0)}
    )
    info: dict = {}
    restored, next_epoch, best = ckpt.load_resume_state(
        vdir / "last.ckpt", carrying, info=info
    )
    assert info["comms_residual"] == "absent"
    assert next_epoch == 1 and best == 0.5
    # saving the carrying state DOES serialize the residual, and a
    # wire-layout change on restore drops it
    ckpt.save_resume_state(vdir, carrying, 1, 0.6)
    mismatched = base.replace(
        comms_residual={"w": jnp.zeros((2, 2))}
    )
    info2: dict = {}
    ckpt.load_resume_state(vdir / "last.ckpt", mismatched, info=info2)
    assert info2["comms_residual"] == "dropped:wire-layout-changed"
    info3: dict = {}
    ok, _, _ = ckpt.load_resume_state(
        vdir / "last.ckpt", carrying, info=info3
    )
    assert info3["comms_residual"] == "restored"
    np.testing.assert_array_equal(
        np.asarray(ok.comms_residual["w"]), np.full((4, 4), 7.0)
    )


import jax  # noqa: E402  (used by the residual e2e above)
