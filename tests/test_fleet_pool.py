"""Elastic fleet supervision tests (ISSUE 10): host pool + re-rendered
world size, resharded resume validation, and the satellites that ride
along (adaptive fleet-watcher poll, corrupt-shard quarantine, per-host
partial desync fingerprints, resize reporting).

The load-bearing properties pinned here:

- a host killed by a signal the supervisor did NOT send leaves the pool;
  the next attempt re-renders ``--world-size``/``--rank``/``--dist-url``
  from the survivors and a ``resize`` event prices the shrink;
- a returned host (``fleet/host-i.up``) triggers a deliberate
  drain-checkpoint-and-re-expand whose attempt never consumes the
  restart budget;
- when no legal world size exists the supervisor refuses with the actual
  numbers (batch, widths, nearest legal batches) — never a bare
  divisibility traceback, and never a doomed launch;
- a rollback replay under ``--health-quarantine`` excludes exactly the
  condemned batch window's examples, deterministically, with every other
  batch bit-identical;
- the per-host partial fingerprint matrix is constant down the data axis
  for a healthy sharded state, and any injected drift inside a model
  shard is caught — the case the post-collective scalar check erases.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import serialization

sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))

import goodput_report  # noqa: E402
import run_report  # noqa: E402

from conftest import assert_trees_within_ulp
from distributed_training_comparison_tpu import obs
from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.data.loader import (
    DeviceDataset,
    HostLoader,
    PrefetchLoader,
)
from distributed_training_comparison_tpu.health import (
    HealthConfig,
    Watchdog,
    check_partial_desync,
    partial_fingerprints,
)
from distributed_training_comparison_tpu.obs.bus import EventBus
from distributed_training_comparison_tpu.obs.heartbeat import (
    FleetWatcher,
    LivenessTracker,
)
from distributed_training_comparison_tpu.parallel import make_mesh
from distributed_training_comparison_tpu.parallel.mesh import elastic_mesh_shape
from distributed_training_comparison_tpu.resilience import (
    EXIT_PREEMPTED,
    FleetPlanError,
    FleetSupervisor,
    ReshardError,
    aggregate_goodput,
    divisibility_help,
    read_manifest,
    validate_reshard,
    widest_legal_world,
)
from distributed_training_comparison_tpu.resilience.fleet import strip_flags

WORKER = Path(__file__).parent / "fleet_pool_worker.py"


@pytest.fixture(autouse=True)
def _fresh_obs(monkeypatch):
    monkeypatch.delenv(obs.RUN_ID_ENV, raising=False)
    monkeypatch.delenv(obs.ATTEMPT_ENV, raising=False)
    obs.reset()
    yield
    obs.reset()


# ---------------------------------------------------------- world render


def test_strip_flags_both_forms():
    args = [
        "train.py", "--world-size", "4", "--epoch", "3",
        "--dist-url=127.0.0.1:1", "--rank", "2", "--fleet-hosts=2",
    ]
    out = strip_flags(
        args, ("--world-size", "--rank", "--dist-url", "--fleet-hosts")
    )
    assert out == ["train.py", "--epoch", "3"]


def test_widest_legal_world_shrinks_for_divisibility():
    # 3 hosts x 1 device: batch 32 does not split 3 ways -> widest is 2
    assert widest_legal_world(3, batch_size=32, local_devices=1) == 2
    assert widest_legal_world(2, batch_size=32, local_devices=1) == 2
    # the N/2 case the issue names: batch divisibility forces half width
    assert widest_legal_world(3, batch_size=8, local_devices=2) == 2
    # tensor parallelism: total devices must tile the model axis
    assert widest_legal_world(
        3, batch_size=32, local_devices=1, model_parallel=2
    ) == 2
    # nothing legal: odd batch never splits over 2 devices/host
    assert widest_legal_world(3, batch_size=7, local_devices=2) is None
    # unknown local device count degrades to host granularity
    assert widest_legal_world(4, batch_size=6, local_devices=0) == 3
    # ...and with a model axis it must DEGRADE, not refuse: 4-chip hosts
    # tile model_parallel 4 at any W, which assuming 1 device/host would
    # wrongly reject (the Trainer's validate_reshard stays the authority)
    assert widest_legal_world(
        2, batch_size=32, local_devices=0, model_parallel=4
    ) == 2


def test_elastic_mesh_shape_rederives_axes():
    assert elastic_mesh_shape(8, 2) == (4, 2, 1)
    assert elastic_mesh_shape(4, 1) == (4, 1, 1)
    assert elastic_mesh_shape(3, 2) is None  # devices don't tile the model axis
    assert elastic_mesh_shape(1, 2) is None  # model axis can't shrink below TP
    assert elastic_mesh_shape(0, 1) is None
    # the dedicated pipe axis joins the tiling rule: DP x TP x PP
    assert elastic_mesh_shape(8, 2, 2) == (2, 2, 2)
    assert elastic_mesh_shape(8, 1, 4) == (2, 1, 4)
    assert elastic_mesh_shape(4, 2, 2) == (1, 2, 2)
    assert elastic_mesh_shape(2, 2, 2) is None  # can't shrink below TPxPP
    assert elastic_mesh_shape(6, 2, 2) is None  # doesn't tile TPxPP


def test_divisibility_help_carries_actionable_numbers():
    msg = divisibility_help(32, 3, 1)
    assert "32" in msg and "3" in msg
    assert "[1, 2]" in msg            # legal widths for this batch
    assert "30" in msg and "33" in msg  # nearest legal batches at width 3


def test_validate_reshard_plan_and_refusal():
    mesh = make_mesh(backend="ddp")  # (8, 1, 1) on the test process's devices
    plan = validate_reshard(
        {"mesh": {"data": 4, "model": 1}, "devices": 4},
        mesh, batch_size=32,
    )
    assert plan["changed"] is True
    assert plan["saved_mesh"] == {"data": 4, "model": 1}
    assert plan["mesh"] == {"data": 8, "model": 1, "pipe": 1}
    assert plan["per_device_batch"] == 4
    same = validate_reshard(
        {"mesh": dict(mesh.shape), "devices": jax.device_count()},
        mesh, batch_size=32,
    )
    assert same["changed"] is False
    assert validate_reshard(None, mesh, batch_size=32)["changed"] is False
    with pytest.raises(ReshardError) as exc:
        validate_reshard({}, mesh, batch_size=30)
    assert "30" in str(exc.value) and "8" in str(exc.value)
    assert "nearest legal batch" in str(exc.value)


def test_trainer_batch_error_carries_legal_numbers(tmp_path):
    from distributed_training_comparison_tpu.train import Trainer

    hp = load_config(
        "tpu",
        argv=[
            "--synthetic-data", "--limit-examples", "64",
            "--batch-size", "36",  # 36 % 8 devices != 0
            "--ckpt-path", str(tmp_path), "--no-progress",
        ],
    )
    with pytest.raises(ValueError) as exc:
        Trainer(hp)
    assert "legal data-parallel sizes" in str(exc.value)
    assert "nearest legal batch sizes" in str(exc.value)


# --------------------------------------------------------- the host pool


class FakeProc:
    """A Popen-shaped child whose life is scripted: runs for ``runs_for``
    polls, then exits ``rc`` (None = runs until terminated)."""

    _next_pid = 5000

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


def _fleet(tmp_path, scripts, events, **kw):
    """A FleetSupervisor over scripted fake children.  ``scripts`` is one
    list of FakeProc ctor args per spawn, in spawn order."""
    it = iter(scripts)

    def spawn(cmd, env):
        rc, runs_for = next(it)
        p = FakeProc(rc, runs_for)
        p.cmd = list(cmd)
        return p

    kw.setdefault("hosts", 2)
    kw.setdefault("batch_size", 32)
    kw.setdefault("local_devices", 1)
    kw.setdefault("grace_s", 0.0)
    kw.setdefault("poll_s", 0.05)
    return FleetSupervisor(
        ["train.py", "--epoch", "3"],
        ckpt_root=tmp_path,
        spawn=spawn,
        sleep=lambda s: None,
        log=lambda m: None,
        events=lambda kind, **p: events.append((kind, p)),
        **kw,
    )


def test_external_kill_shrinks_then_up_marker_reexpands(tmp_path):
    events: list = []
    # attempt 0: host 0 wedges (runs forever), host 1 dies by external -9
    # attempt 1: world 1 on host 0, runs until the deliberate drain
    # attempt 2: world 2 again, both exit 0
    scripts = [(None, 0), (-9, 1), (None, 0), (0, 2), (0, 2)]
    sup = _fleet(tmp_path, scripts, events)
    orig = sup._launch

    def launch(attempt):
        if attempt == 1:  # host 1 "returns" mid-attempt
            sup._marker(1, "up").write_text("")
        return orig(attempt)

    sup._launch = launch
    summary = sup.run()
    assert summary["final_rc"] == 0
    assert [
        (r["from_world"], r["to_world"], r["reason"])
        for r in summary["resizes"]
    ] == [(2, 1, "host_lost"), (1, 2, "host_returned")]
    assert summary["resizes"][0]["lost"] == [1]
    assert summary["resizes"][1]["returned"] == [1]
    assert summary["hosts"] == {"0": "alive", "1": "alive"}
    worlds = [
        p["world_size"] for k, p in events if k == "attempt_start"
    ]
    assert worlds == [2, 1, 2]
    hosts = [p["hosts"] for k, p in events if k == "attempt_start"]
    assert hosts == [[0, 1], [0], [0, 1]]
    kinds = [k for k, _ in events]
    assert kinds.count("resize") == 2
    # marker was consumed
    assert not sup._marker(1, "up").exists()


def test_deliberate_reexpand_drain_spares_budget(tmp_path):
    """max_restarts=1: attempt 0 ends by host loss (budget 1/1), attempt 1
    by the deliberate re-expand drain (free), attempt 2 completes — with a
    budget-consuming drain the run would have given up."""
    events: list = []
    scripts = [(None, 0), (-9, 1), (None, 0), (0, 2), (0, 2)]
    sup = _fleet(tmp_path, scripts, events, max_restarts=1)
    orig = sup._launch

    def launch(attempt):
        if attempt == 1:
            sup._marker(1, "up").write_text("")
        return orig(attempt)

    sup._launch = launch
    summary = sup.run()
    assert summary["final_rc"] == 0
    assert "give_up" not in [k for k, _ in events]
    # the planned re-expand drain is not a preemption on the scoreboard:
    # only the host-loss attempt counts
    assert summary["preemptions"] == 1
    assert summary["planned_drains"] == 1


def test_supervisor_sigterm_death_is_not_host_loss(tmp_path):
    """A child that dies from the supervisor's OWN SIGTERM (or the grace
    SIGKILL) must not be marked lost: the supervisor killed the process,
    not the machine."""
    events: list = []
    # attempt 0: host 0 crashes rc=1; host 1 never drains -> grace SIGKILL
    # attempt 1 (after backoff): both exit 0 — world stays 2, no resize
    scripts = [(1, 1), (None, 0), (0, 2), (0, 2)]
    sup = _fleet(tmp_path, scripts, events)
    summary = sup.run()
    assert summary["final_rc"] == 0
    assert summary["resizes"] == []
    assert summary["hosts"] == {"0": "alive", "1": "alive"}
    worlds = [p["world_size"] for k, p in events if k == "attempt_start"]
    assert worlds == [2, 2]


def test_down_marker_drains_and_shrinks(tmp_path):
    events: list = []
    # attempt 0: both run until the down marker triggers the drain
    # attempt 1: world 1 on host 0 completes
    scripts = [(None, 0), (None, 0), (0, 2)]
    sup = _fleet(tmp_path, scripts, events)
    orig = sup._launch

    def launch(attempt):
        if attempt == 0:
            sup._marker(1, "down").write_text("")
        return orig(attempt)

    sup._launch = launch
    summary = sup.run()
    assert summary["final_rc"] == 0
    assert [
        (r["from_world"], r["to_world"], r["reason"])
        for r in summary["resizes"]
    ] == [(2, 1, "host_lost")]
    assert summary["hosts"]["1"] == "lost"


def test_down_marker_for_spare_host_does_not_drain(tmp_path):
    """batch 32 on 3 one-device hosts caps the legal world at 2, so host 2
    is an alive SPARE.  Marking it down changes pool membership but must
    not drain the running ranks or burn budget."""
    events: list = []
    scripts = [(0, 4), (0, 4)]  # ranks 0+1 run a while, then finish clean
    sup = _fleet(tmp_path, scripts, events, hosts=3)
    orig = sup._launch

    def launch(attempt):
        sup._marker(2, "down").write_text("")
        return orig(attempt)

    sup._launch = launch
    summary = sup.run()
    assert summary["final_rc"] == 0
    assert len(summary["attempts"]) == 1  # nobody was drained
    assert summary["resizes"] == []
    assert summary["hosts"] == {"0": "alive", "1": "alive", "2": "lost"}


def test_spare_return_that_cannot_widen_does_not_drain(tmp_path):
    """batch 32 caps 3 one-device hosts at world 2: a spare (host 2)
    cycling down and back up can never widen the legal world, so its
    return must not burn a drain-checkpoint-relaunch cycle."""
    events: list = []
    scripts = [(0, 6), (0, 6)]
    sup = _fleet(tmp_path, scripts, events, hosts=3)
    sup._marker(2, "down").write_text("")  # spare lost before launch
    orig = sup._launch

    def launch(attempt):
        sup._marker(2, "up").write_text("")  # returns mid-attempt
        return orig(attempt)

    sup._launch = launch
    summary = sup.run()
    assert summary["final_rc"] == 0
    assert len(summary["attempts"]) == 1  # no drain fired
    assert summary["hosts"]["2"] == "alive"  # but the pool took it back


def test_crash_during_deliberate_drain_keeps_crash_semantics(tmp_path):
    """A rank that CRASHES while draining for a planned re-expand must not
    be laundered into a budget-free planned drain."""

    class CrashOnDrain(FakeProc):
        def terminate(self):
            self._rc = 1  # the drain's checkpoint write blew up

    events: list = []
    procs = iter([CrashOnDrain(None, 0), FakeProc(0, 2), FakeProc(0, 2)])
    sup = FleetSupervisor(
        ["train.py"], hosts=2, ckpt_root=tmp_path, batch_size=32,
        local_devices=1, grace_s=0.0, poll_s=0.05,
        spawn=lambda c, e: next(procs),
        sleep=lambda s: None, log=lambda m: None,
        events=lambda kind, **p: events.append((kind, p)),
    )
    sup._marker(1, "down").write_text("")  # world 1 on host 0
    orig = sup._launch

    def launch(attempt):
        if attempt == 0:
            sup._marker(1, "up").write_text("")  # triggers the re-expand
        return orig(attempt)

    sup._launch = launch
    summary = sup.run()
    assert summary["final_rc"] == 0
    assert summary["attempts"][0]["returncode"] == 1  # the crash, not 75
    assert summary["preemptions"] == 0
    assert summary["planned_drains"] == 0  # nothing was laundered


def test_pool_exhausted_readmits_everything(tmp_path):
    events: list = []
    scripts = [(0, 1), (0, 1)]
    sup = _fleet(tmp_path, scripts, events)
    sup._marker(0, "down").write_text("")
    sup._marker(1, "down").write_text("")
    summary = sup.run()  # both pre-marked down -> full re-admission
    assert summary["final_rc"] == 0
    assert [p["world_size"] for k, p in events if k == "attempt_start"] == [2]


def test_fleet_refuses_with_numbers_when_no_legal_world(tmp_path):
    events: list = []
    sup = _fleet(
        tmp_path, [], events, batch_size=7, local_devices=2,
    )
    with pytest.raises(FleetPlanError) as exc:
        sup.run()
    msg = str(exc.value)
    assert "7" in msg and "no legal world size" in msg
    assert "nearest legal batch sizes" in msg
    assert [k for k, _ in events] == ["give_up"]


def test_fleet_floor_refusal_names_the_floor_not_the_batch(tmp_path):
    """--fleet-min-hosts refusal: batch 32 divides width 1 fine — the
    message must name the floor, never fabricate a divisibility claim."""
    sup = _fleet(
        tmp_path, [], [], hosts=2, batch_size=32, local_devices=1,
        min_hosts=3,
    )
    with pytest.raises(FleetPlanError) as exc:
        sup.run()
    msg = str(exc.value)
    assert "floor 3" in msg and "widest legal world 2" in msg
    assert "not divisible" not in msg


def test_mid_run_refusal_stops_orderly_with_summary(tmp_path):
    """Losing a host mid-run until no legal world remains (model_parallel
    needs 2 devices, 1 one-device host survives) must end with a give_up
    event and a SUMMARY — not a traceback that loses the completed
    attempts' goodput aggregation."""
    events: list = []
    scripts = [(None, 0), (-9, 1)]  # host 1 dies externally; host 0 drained
    sup = _fleet(
        tmp_path, scripts, events, model_parallel=2, local_devices=1,
    )
    summary = sup.run()  # no exception: the refusal is orderly mid-run
    assert summary["final_rc"] == EXIT_PREEMPTED
    assert len(summary["attempts"]) == 1
    kinds = [k for k, _ in events]
    assert kinds == ["attempt_start", "attempt_end", "give_up"]
    assert "model_parallel 2" in events[-1][1]["reason"]


def test_render_cmd_re_renders_world_flags(tmp_path):
    sup = _fleet(tmp_path, [], [])
    cmd = sup._render_cmd(
        ["w.py", "--world-size", "9", "--rank", "3",
         "--dist-url=10.0.0.1:1", "--fleet-hosts", "2", "--epoch", "3"],
        world=2, rank=1, port=4567,
    )
    assert cmd == [
        "w.py", "--epoch", "3",
        "--world-size", "2", "--rank", "1", "--dist-url", "127.0.0.1:4567",
    ]


# ------------------------------------------- watcher + tracker satellites


def test_tracker_reset_expect_seeds_silent_hosts():
    tr = LivenessTracker(heartbeat_s=1.0)  # slow > 3s, dead > 10s
    tr.reset(expect=range(2), attempt=3, now=0.0)
    assert tr.check(now=2.0) == []  # young silence is fine
    findings = tr.check(now=20.0)
    # both expected hosts are silent past "dead", but neither ever beat:
    # the pre-first-beat cap holds them at "slow" (first-dispatch compile)
    assert [(f["process_index"], f["state"]) for f in findings] == [
        (0, "slow"), (1, "slow"),
    ]
    assert all(f["attempt"] == 3 for f in findings)
    tr.reset()
    assert tr.check(now=30.0) == []  # plain reset forgets the expectation


def test_fleet_watcher_adaptive_poll(tmp_path):
    bus = EventBus(run_id="ab" * 8)
    tr = LivenessTracker(heartbeat_s=1.0)
    w = FleetWatcher(tmp_path, bus, tracker=tr, poll_s=1.0)
    assert w.current_poll_s() == 1.0  # nothing tracked: steady cadence
    tr.observe({"kind": "heartbeat", "process_index": 0, "step": 1}, now=0.0)
    w.step(now=0.5)
    assert w.current_poll_s() == 1.0  # host healthy
    w.step(now=5.0)  # 5s stale -> slow
    assert tr.states()[0] == "slow"
    assert w.current_poll_s() == pytest.approx(0.1)  # tightened
    tr.observe({"kind": "heartbeat", "process_index": 0, "step": 2}, now=6.0)
    w.step(now=6.1)  # recovered
    assert w.current_poll_s() == 1.0


def test_fleet_watcher_fast_poll_never_exceeds_base(tmp_path):
    bus = EventBus(run_id="ab" * 8)
    w = FleetWatcher(
        tmp_path, bus, tracker=LivenessTracker(), poll_s=0.05
    )
    assert w.fast_poll_s == pytest.approx(0.05)


def test_fleet_poll_secs_flag_validation():
    hp = load_config("tpu", ["--synthetic-data"])
    assert hp.fleet_poll_secs == 1.0 and hp.fleet_hosts == 0
    with pytest.raises(SystemExit):
        load_config("tpu", ["--fleet-poll-secs", "0"])
    with pytest.raises(SystemExit):
        load_config("tpu", ["--fleet-hosts", "2"])  # needs --supervise
    with pytest.raises(SystemExit):
        load_config(
            "tpu",
            ["--supervise", "--fleet-hosts", "2", "--world-size", "2"],
        )
    hp = load_config("tpu", ["--supervise", "--fleet-hosts", "2"])
    assert hp.fleet_hosts == 2 and hp.fleet_local_devices == 0


def test_fleet_hosts_on_one_machine_refuse_to_share_its_chips(monkeypatch, tmp_path):
    """The fleet's hosts are processes on this machine; unless they are
    forced onto virtual CPU devices (--fleet-local-devices) or the
    environment says JAX_PLATFORMS=cpu they would all open the same chips
    (one process owns a chip: on a v5e the second host dies on libtpu's
    lockfile and the fleet hangs).  The supervisor stays off JAX, so it
    refuses from the environment alone, before it spawns anything."""
    import subprocess

    from distributed_training_comparison_tpu.resilience.supervisor import (
        run_supervised,
    )

    def no_spawn(*a, **kw):
        raise AssertionError("spawned a host before refusing")

    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    argv = ["--supervise", "--fleet-hosts", "2", "--ckpt-path", str(tmp_path)]
    hp = load_config("tpu", argv)
    for platforms in (None, "tpu,cpu"):
        if platforms is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", platforms)
        with pytest.raises(SystemExit) as ei:
            run_supervised(hp, argv)
        assert "--fleet-local-devices" in str(ei.value.code)
    assert list(tmp_path.iterdir()) == []  # nothing bound, nothing written


# ------------------------------------------------ corrupt-shard quarantine


def _tiny_dataset(n=64):
    rng = np.random.default_rng(0)
    return DeviceDataset(
        rng.integers(0, 255, size=(n, 8, 8, 3)).astype(np.uint8),
        rng.integers(0, 100, size=(n,)).astype(np.int32),
    )


def test_loader_quarantine_substitutes_only_the_bad_window():
    ds = _tiny_dataset()
    loader = HostLoader(ds, 8, shuffle=True, drop_last=True, seed=3)
    before = loader._permutation(2)
    bad = loader.batch_example_indices(2, 1)
    assert len(bad) == 8
    added = loader.quarantine(bad)
    assert added == 8
    after = loader._permutation(2)
    # the condemned examples are gone
    assert not np.isin(after, bad).any()
    # and every untouched position is bit-identical
    untouched = ~np.isin(before, bad)
    np.testing.assert_array_equal(after[untouched], before[untouched])
    # deterministic: a fresh loader with the same quarantine agrees
    twin = HostLoader(ds, 8, shuffle=True, drop_last=True, seed=3)
    twin.quarantine(bad)
    np.testing.assert_array_equal(twin._permutation(2), after)
    # batch count unchanged (substitution, not shortening)
    assert len(after) == len(before)
    # re-quarantining is idempotent
    assert loader.quarantine(bad) == 0


def test_loader_quarantine_refuses_to_exclude_everything():
    ds = _tiny_dataset(8)
    loader = HostLoader(ds, 4, seed=1)
    kept = loader.quarantine(np.arange(2))
    assert kept == 2
    before = loader._permutation(0)
    with pytest.raises(ValueError, match="every example"):
        loader.quarantine(np.arange(8))
    # a refused quarantine leaves the loader EXACTLY as it was — the next
    # epoch's permutation must not see a half-applied set
    assert loader._quarantined == {0, 1}
    np.testing.assert_array_equal(loader._permutation(0), before)


def test_loader_quarantine_substitutes_stay_in_shard():
    """Under multi-host sharding the substitute pool is THIS loader's own
    slice of the epoch — drawing from the whole dataset would hand this
    host examples another host's shard also trains."""
    ds = _tiny_dataset(64)
    shards = [
        HostLoader(ds, 4, shuffle=True, drop_last=True, seed=9,
                   num_shards=2, shard=i)
        for i in (0, 1)
    ]
    epoch = 3
    own = shards[0]._permutation(epoch)
    other = set(shards[1]._permutation(epoch).tolist())
    assert not (set(own.tolist()) & other)  # shards start disjoint
    shards[0].quarantine(shards[0].batch_example_indices(epoch, 0))
    after = shards[0]._permutation(epoch)
    # substitutes were drawn from shard 0's own slice: still disjoint
    assert not (set(after.tolist()) & other)


def test_prefetch_loader_delegates_quarantine():
    ds = _tiny_dataset()
    pf = PrefetchLoader(HostLoader(ds, 8, seed=5), depth=1)
    ids = pf.batch_example_indices(0, 0)
    assert pf.quarantine(ids) == len(set(ids.tolist()))
    assert not np.isin(pf.loader._permutation(0), ids).any()
    pf.close()


def test_watchdog_verdict_carries_bad_steps_and_quarantine_counter():
    wd = Watchdog(HealthConfig(bad_steps=3, quarantine=True))
    losses = np.full(16, 1.0)
    skipped = np.zeros(16)
    skipped[5:8] = 1.0
    verdict = wd.observe_epoch(0, losses, skipped)
    assert verdict.rollback and verdict.bad_steps == [5, 6, 7]
    wd.note_quarantine(0, verdict.bad_steps, examples=96)
    assert wd.counters()["quarantined_examples"] == 96
    assert any(e["kind"] == "quarantine" for e in wd.events)


@pytest.mark.health
def test_trainer_quarantines_bad_window_on_rollback(tmp_path):
    """Host data mode + --health-quarantine: the nan_grad window's batch
    examples are quarantined at rollback, the replay excludes them, and
    the run still completes."""
    from distributed_training_comparison_tpu.train import Trainer
    from test_train import TinyNet

    hp = load_config(
        "tpu",
        argv=[
            "--synthetic-data", "--limit-examples", "128",
            "--batch-size", "32", "--epoch", "2",
            "--save-last-min-secs", "0", "--no-progress", "--seed", "7",
            "--data-mode", "host", "--workers", "0",
            "--ckpt-path", str(tmp_path),
            "--fault-plan", "nan_grad@epoch=1",
            "--health-quarantine", "--health-bad-steps", "3",
        ],
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    trainer.fit()
    counters = trainer.watchdog.counters()
    trainer.close()
    assert counters["rollbacks"] >= 1
    assert counters["quarantined_examples"] > 0
    quarantined = trainer.train_loader.quarantined
    assert len(quarantined) == counters["quarantined_examples"]
    events = obs.load_events(tmp_path / "version-0" / "events.jsonl")
    assert any(e["kind"] == "quarantine" for e in events)
    # the set SURVIVES a relaunch: the resume manifest carries it and the
    # fresh loader re-applies it — a corrupt shard must not re-enter the
    # stream just because the supervisor restarted the process
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
        assert resumed.train_loader.quarantined == quarantined
    finally:
        resumed.close()


# ------------------------------------------- partial desync fingerprints


def test_partial_fingerprints_matrix_and_injected_drift():
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(model_parallel=2, backend="ddp")  # (4, 2)
    repl = jax.device_put(
        jnp.arange(12, dtype=jnp.float32).reshape(3, 4) - 5.0,
        NamedSharding(mesh, P()),
    )
    shard = jax.device_put(
        jnp.arange(16, dtype=jnp.float32).reshape(8, 2) + 1.0,
        NamedSharding(mesh, P("model", None)),
    )
    params = {"a": repl, "b": shard}
    matrix = partial_fingerprints(params, mesh)
    assert matrix.shape == (4, 2, 1)  # (data, model, pipe)
    # replicated across data: every model column is constant down axis 0
    assert (matrix.max(axis=0) == matrix.min(axis=0)).all()
    # the sharded leaf makes the two model columns DIFFER (each holds its
    # own half), which is exactly the per-shard visibility the scalar lacks
    assert matrix[0, 0, 0] != matrix[0, 1, 0]
    # absolute accounting: summing every device's partials recovers the
    # weighted checksums (leaf order: a -> weight 1, b -> weight 2).  The
    # replicated leaf appears once per device (8x1); the model-sharded
    # leaf's halves each appear once per data row (4x, weight 2 -> 8x).
    a_sum = float(np.abs(np.asarray(repl)).sum())
    b_sum = float(np.abs(np.asarray(shard)).sum())
    assert np.isclose(matrix.sum(), 8 * a_sum + 8 * b_sum)

    healthy = check_partial_desync(matrix)
    assert not healthy["mismatch"] and healthy["partial"] is True
    injected = check_partial_desync(matrix, inject=True)
    assert injected["mismatch"] and injected["spread"] > 0

    drifted = matrix.copy()
    drifted[2, 1, 0] += 0.5  # one replica's model-shard 1 drifted
    report = check_partial_desync(drifted)
    assert report["mismatch"]
    assert report["per_model_spread"][0] == 0.0
    assert report["per_model_spread"][1] == pytest.approx(0.5)


# ------------------------------------------------------ resize reporting


def _mk_fleet_run(root, run_id="cd" * 8):
    sup = EventBus(run_id=run_id)
    sup.emit("attempt_start", attempt=0, world_size=2, hosts=[0, 1])
    sup.emit(
        "attempt_end", attempt=0, returncode=75, preempted=True,
        world_size=2, hosts=[0, 1],
    )
    sup.emit(
        "resize", attempt=1, from_world=2, to_world=1,
        reason="host_lost", hosts=[0], lost=[1], returned=[],
    )
    sup.emit("attempt_start", attempt=1, world_size=1, hosts=[0])
    sup.emit(
        "attempt_end", attempt=1, returncode=0, preempted=False,
        world_size=1, hosts=[0],
    )
    root.mkdir(parents=True, exist_ok=True)
    with open(root / "events.jsonl", "w") as f:
        for ev in sup.ring_events():
            f.write(json.dumps(ev) + "\n")
    for attempt in (0, 1):
        bus = EventBus(run_id=run_id, attempt=attempt)
        bus.emit("run_start", epoch=0, world_size=2 - attempt)
        bus.emit("epoch_end", epoch=0, secs=1.0)
        bus.emit("goodput", step_s=4.0, wall_s=5.0)
        (root / "version-0").mkdir(exist_ok=True)
        with open(root / "version-0" / obs.events_filename(0), "a") as f:
            for ev in bus.ring_events():
                f.write(json.dumps(ev) + "\n")
    return root


def test_run_report_renders_resize_and_world_sizes(tmp_path):
    root = _mk_fleet_run(tmp_path / "run")
    events, _files = run_report.load_run(root)
    text = run_report.format_summary("fleet", run_report.summarize(events))
    assert "resize (attempt 1): world 2 -> 1 (host_lost; lost [1])" in text
    assert "world sizes:" in text and "a0=2" in text and "a1=1" in text
    assert run_report.main([str(root), "--check"]) == 0
    assert run_report.main(
        [str(root), "--check", "--require-kind", "resize"]
    ) == 0


def test_run_report_require_kind_resize_fails_without_one(tmp_path):
    root = tmp_path / "run"
    root.mkdir()
    bus = EventBus(run_id="ab" * 8)
    bus.emit("run_start", epoch=0)
    with open(root / "events.jsonl", "w") as f:
        for ev in bus.ring_events():
            f.write(json.dumps(ev) + "\n")
    assert run_report.main(
        [str(root), "--check", "--require-kind", "resize"]
    ) == 1


def test_goodput_aggregate_and_report_carry_resizes():
    resizes = [
        {"attempt": 1, "from_world": 2, "to_world": 1, "reason": "host_lost",
         "lost": [1], "returned": []},
        {"attempt": 2, "from_world": 1, "to_world": 2,
         "reason": "host_returned", "lost": [], "returned": [1]},
    ]
    report = aggregate_goodput(
        [{"step_s": 6.0, "wall_s": 8.0}], resizes=resizes,
    )
    assert report["resizes"] == resizes
    text = goodput_report.format_table([("fleet", report)])
    assert "resize a1 world 2 -> 1 (host_lost; lost [1])" in text
    assert "resize a2 world 1 -> 2 (host_returned; returned [1])" in text
    # reports without resizes render exactly as before
    plain = aggregate_goodput([{"step_s": 6.0, "wall_s": 8.0}])
    assert "resizes" not in plain


# ------------------------------------------------------------------- e2e


@pytest.mark.elastic
def test_e2e_fleet_kill_shrink_readmit_reexpand(tmp_path):
    """ISSUE 10 acceptance: a supervised 2-host fleet loses host 1 to a
    real SIGKILL mid-run -> the supervisor re-renders a world-size-1
    attempt that resumes from the verified checkpoint -> host 1 "returns"
    (fleet/host-1.up) -> a deliberate drain re-expands to 2 hosts -> the
    run completes with final params allclose to an uninterrupted run,
    ``resize`` events on the merged timeline, and ``run_report --check
    --require-kind resize`` green."""
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
        # insurance window: if the world-1 attempt races ahead of the
        # re-admission below, epoch 7 stalls 6s so the drain lands mid-run
        "--fault-plan", "stall@epoch=7:secs=6",
    ]
    proc = subprocess.Popen(
        cmd, cwd=WORKER.parent.parent,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    status = root / "fleet" / "status.json"
    events0 = root / "version-0" / "events.jsonl"

    def wait_for(cond, what, timeout=180.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                out, err = proc.communicate()
                raise AssertionError(
                    f"supervised fleet exited early waiting for {what}: "
                    f"rc={proc.returncode}\n{(err or '')[-3000:]}"
                )
            try:
                if cond():
                    return
            except (OSError, ValueError, KeyError):
                pass
            time.sleep(0.05)
        proc.kill()
        raise AssertionError(f"timed out waiting for {what}")

    def read_status():
        return json.loads(status.read_text())

    # phase 1: attempt 0 at world 2 has a verified checkpoint -> kill host 1
    wait_for(
        lambda: read_status()["attempt"] == 0
        and read_manifest(root / "version-0" / "last.ckpt") is not None,
        "attempt 0's first checkpoint",
    )
    os.kill(int(read_status()["pids"]["1"]), signal.SIGKILL)

    # phase 2: the re-rendered world-1 attempt is up and resumed -> host
    # 1 returns
    wait_for(
        lambda: read_status()["attempt"] == 1
        and any(
            '"kind": "run_start"' in line and '"attempt": 1' in line
            for line in events0.read_text().splitlines()
        ),
        "attempt 1's run_start",
    )
    (root / "fleet" / "host-1.up").write_text("")

    out, err = proc.communicate(timeout=420)
    assert proc.returncode == 0, (err or "")[-3000:]
    assert "Traceback" not in (err or ""), (err or "")[-3000:]

    events, _files = run_report.load_run(root)
    resizes = [
        e["payload"] for e in events if e["kind"] == "resize"
    ]
    assert [
        (r["from_world"], r["to_world"], r["reason"]) for r in resizes
    ] == [(2, 1, "host_lost"), (1, 2, "host_returned")], resizes
    starts = [
        e["payload"] for e in events
        if e["kind"] == "attempt_start" and e["payload"].get("world_size")
    ]
    assert [s["world_size"] for s in starts] == [2, 1, 2]
    # the shrunk attempt RESUMED (verified checkpoint), never retrained
    run_starts = {
        e["attempt"]: e["payload"] for e in events if e["kind"] == "run_start"
    }
    assert run_starts[1]["resumed"] is True
    assert run_starts[2]["resumed"] is True
    # the timeline is schema-clean and carries the required resize kind
    assert run_report.main([str(root), "--check"]) == 0
    assert run_report.main(
        [str(root), "--check", "--require-kind", "resize"]
    ) == 0
    # GOODPUT prices the shrink/expand
    gp = json.loads(goodput_json.read_text())
    assert len(gp["resizes"]) == 2 and gp["goodput_frac"] > 0

    # uninterrupted run, same seed, this process's 8-device mesh
    from distributed_training_comparison_tpu.train import Trainer
    from fleet_pool_worker import TinyNet

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

    # 70 steps at world sizes 2 -> 1 -> 2 against 70 steps on 8 devices:
    # the same trajectory under another reduction order.  Uninterrupted
    # runs of this job at 1, 2, 4 and 8 devices end 1,900-7,400 scale-aware
    # ulps apart (the per-step 2^6-2^8 of --parity-tol's help, compounded);
    # a run that lost one epoch ends 340,000 or more away.  2^15 lies
    # between: it passes reordering and fails a lost or repeated chunk.
    assert_trees_within_ulp(
        final_params(root), final_params(clean_root), ulp=1 << 15
    )
