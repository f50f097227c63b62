"""resilience/ subsystem tests: fault injection, crash-safe checkpoint I/O,
preemption + supervisor restart, elastic restore, goodput accounting.

The headline (ISSUE 2 acceptance) is ``test_e2e_preempt_supervisor_elastic``:
a real child process killed by an injected preemption at epoch K is
restarted by the ``Supervisor``, resumes from epoch K's checkpoint on a
DIFFERENT forced-host device count, and reaches final params allclose to an
uninterrupted run with the same seed; and a torn-write injection is caught
by the manifest check, with restore falling back to the previous good
checkpoint.
"""

import json
import os
import signal
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import serialization

from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.resilience import (
    EXIT_PREEMPTED,
    FaultPlan,
    FaultSpecError,
    GoodputMeter,
    Preempted,
    PreemptionHandler,
    Supervisor,
    aggregate_goodput,
    atomic_write_bytes,
    load_goodput_records,
    previous_path,
    read_manifest,
    verify_checkpoint,
    write_manifest,
)
from distributed_training_comparison_tpu.resilience.faults import tear_file
from distributed_training_comparison_tpu.train import (
    Trainer,
    configure_optimizers,
    create_train_state,
    find_valid_resume,
    find_version_dir,
    load_resume_state,
    save_resume_state,
)
from distributed_training_comparison_tpu.train import checkpoint as ckpt_mod
from distributed_training_comparison_tpu.parallel import make_mesh, replicated_sharding

from conftest import assert_trees_within_ulp, whole_epoch_runner
from test_train import HP, TinyNet

WORKER = Path(__file__).parent / "resil_worker.py"

WORKER_ARGS = [
    "--synthetic-data",
    "--limit-examples", "128",
    "--batch-size", "32",
    "--epoch", "3",
    "--save-last-min-secs", "0",
    "--no-progress",
    "--seed", "7",
]


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(backend="ddp")


def _tiny_state(mesh):
    tx, _ = configure_optimizers(HP, steps_per_epoch=4)
    state = create_train_state(TinyNet(dtype=jnp.float32), jax.random.key(0), tx)
    return jax.device_put(state, replicated_sharding(mesh))


# ------------------------------------------------------------- fault plans


def test_fault_plan_parse_and_triggers():
    plan = FaultPlan.parse(
        "preempt@epoch=2; torn_write@epoch=1, stall@epoch=0:secs=0.25"
    )
    assert plan.preempt_due(2) and not plan.preempt_due(1)
    assert plan.stall_secs(0) == 0.25 and plan.stall_secs(2) == 0.0
    assert plan.ckpt_hook(1) is not None and plan.ckpt_hook(0) is None
    assert FaultPlan.parse(None) is None and FaultPlan.parse("  ") is None


@pytest.mark.parametrize(
    "bad",
    ["explode@epoch=1", "preempt@", "preempt@epoch=x", "stall@epoch=1:mins=9"],
)
def test_fault_plan_rejects_malformed(bad):
    with pytest.raises(FaultSpecError):
        FaultPlan.parse(bad)


def test_fault_plan_prob_draws_are_seeded_and_deterministic():
    a = FaultPlan.parse("preempt@prob=0.5", seed=1)
    b = FaultPlan.parse("preempt@prob=0.5", seed=1)
    draws = [a.preempt_due(e) for e in range(32)]
    assert draws == [b.preempt_due(e) for e in range(32)]  # replayable
    assert any(draws) and not all(draws)  # actually Bernoulli
    c = FaultPlan.parse("preempt@prob=0.5", seed=2)
    assert draws != [c.preempt_due(e) for e in range(32)]  # seed matters


def test_bad_fault_plan_dies_at_the_cli():
    with pytest.raises(SystemExit):
        load_config("tpu", ["--fault-plan", "explode@epoch=1"])


# --------------------------------------------------- crash-safe ckpt I/O


def test_manifest_verify_detects_torn_write(tmp_path):
    path = tmp_path / "blob.ckpt"
    data = os.urandom(4096)
    atomic_write_bytes(path, data)
    write_manifest(path, data, meta={"step": 3})
    ok, reason = verify_checkpoint(path)
    assert ok, reason
    assert read_manifest(path)["step"] == 3

    tear_file(path)  # torn: payload halved, manifest untouched
    ok, reason = verify_checkpoint(path)
    assert not ok and "mismatch" in reason

    # same-size corruption is caught by the checksum (deep) pass
    atomic_write_bytes(path, data)
    write_manifest(path, data, meta={})
    path.write_bytes(os.urandom(len(data)))
    ok, reason = verify_checkpoint(path)
    assert not ok and "checksum" in reason


def test_legacy_checkpoint_without_manifest_is_accepted(tmp_path):
    path = tmp_path / "old.ckpt"
    path.write_bytes(b"pre-manifest era")
    ok, reason = verify_checkpoint(path)
    assert ok and "legacy" in reason


def test_corrupt_manifest_is_rejected_not_legacy(tmp_path):
    """A manifest that exists but doesn't parse is corruption (the same
    event that may have torn the payload) — it must NOT downgrade the
    checkpoint to legacy-accepted, and rotation must not evict a good
    prev copy for it."""
    from distributed_training_comparison_tpu.resilience import (
        manifest_path,
        rotate_previous,
    )

    path = tmp_path / "blob.ckpt"
    data = os.urandom(1024)
    atomic_write_bytes(path, data)
    write_manifest(path, data, meta={})
    manifest_path(path).write_bytes(b"{torn json")
    ok, reason = verify_checkpoint(path)
    assert not ok and "unreadable" in reason
    assert rotate_previous(path) is None  # refuses to rotate unverifiable bytes


def test_resume_rotation_and_fallback(tmp_path, mesh):
    """A torn newest last.ckpt must cost one save interval, not the run:
    find_valid_resume falls back to the rotated previous good checkpoint."""
    state = _tiny_state(mesh)
    vdir = find_version_dir(tmp_path)
    save_resume_state(vdir, state, epoch=0, best_acc=10.0)
    save_resume_state(vdir, state, epoch=1, best_acc=11.0)
    last = vdir / "last.ckpt"
    prev = previous_path(last)
    assert prev.exists() and read_manifest(prev)["epoch"] == 0
    assert read_manifest(last)["epoch"] == 1
    assert find_valid_resume(tmp_path) == last

    tear_file(last)
    assert find_valid_resume(tmp_path) == prev
    restored, next_epoch, best = load_resume_state(prev, _tiny_state(mesh))
    assert next_epoch == 1 and best == 10.0

    tear_file(prev)  # both torn → no resume, fresh start
    assert find_valid_resume(tmp_path) is None


# --------------------------------------------------------- version dirs


def test_find_version_dir_claim_is_race_safe(tmp_path):
    """32 concurrent claims must produce 32 distinct dirs — the mkdir IS
    the claim (the old scan-then-mkdir(exist_ok=True) let two processes
    share a slot)."""
    with ThreadPoolExecutor(8) as ex:
        dirs = list(ex.map(lambda _: find_version_dir(tmp_path), range(32)))
    names = {d.name for d in dirs}
    assert len(names) == 32
    assert all(d.exists() for d in dirs)


def test_agreed_version_dir_rank0_picks_others_follow(tmp_path, monkeypatch):
    from jax.experimental import multihost_utils

    monkeypatch.setattr(jax, "process_count", lambda: 2)

    # rank 1: follows the broadcast pick, creates nothing
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    monkeypatch.setattr(
        multihost_utils, "broadcast_one_to_all", lambda x: np.asarray(3)
    )
    d = ckpt_mod.agreed_version_dir(tmp_path)
    assert d.name == "version-3" and not d.exists()

    # rank 0: claims race-safely and broadcasts its claim
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    sent = {}

    def record_broadcast(x):
        sent["value"] = int(np.asarray(x))
        return np.asarray(x)

    monkeypatch.setattr(multihost_utils, "broadcast_one_to_all", record_broadcast)
    d0 = ckpt_mod.agreed_version_dir(tmp_path)
    assert d0.exists() and sent["value"] == int(d0.name.split("-")[-1])


# ------------------------------------------------------------- preemption


def test_preemption_handler_latches_sigterm_and_restores():
    handler = PreemptionHandler().install()
    try:
        assert not handler.triggered
        os.kill(os.getpid(), signal.SIGTERM)  # latched, not fatal
        assert handler.triggered
    finally:
        handler.restore()
    assert signal.getsignal(signal.SIGTERM) is not handler._on_signal


def test_trainer_preempt_fault_drains_and_raises(tmp_path):
    hp = load_config(
        "tpu",
        argv=WORKER_ARGS
        + ["--ckpt-path", str(tmp_path), "--fault-plan", "preempt@epoch=1"],
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    with pytest.raises(Preempted) as exc:
        trainer.fit()
    trainer.close()
    assert exc.value.epoch == 1
    vdir = tmp_path / "version-0"
    manifest = read_manifest(vdir / "last.ckpt")
    assert manifest["epoch"] == 1  # epoch K's checkpoint landed before exit
    records = load_goodput_records(vdir / "goodput.jsonl")
    assert len(records) == 1 and records[0]["preempted"] is True
    assert records[0]["step_s"] > 0


def test_trainer_ckpt_fail_fault_surfaces_via_writer(tmp_path):
    """An injected checkpoint-write failure must surface as a loud error
    through AsyncCheckpointer.wait(), never a silent no-checkpoint run."""
    hp = load_config(
        "tpu",
        argv=[
            "--synthetic-data", "--limit-examples", "128",
            "--batch-size", "32", "--epoch", "1",
            "--save-last-min-secs", "0", "--no-progress",
            "--ckpt-path", str(tmp_path),
            "--fault-plan", "ckpt_fail@epoch=0",
        ],
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    with pytest.raises(RuntimeError, match="injected checkpoint write failure"):
        trainer.fit()
    trainer.close()


def test_trainer_torn_write_fault_then_auto_resume_falls_back(tmp_path):
    """Acceptance: a torn-write injection is detected by the manifest check
    and restore falls back to the previous good checkpoint."""
    argv = WORKER_ARGS + ["--ckpt-path", str(tmp_path)]
    # The stalls (exercising the stall fault path) double as writer-drain
    # windows: without them three sub-second epochs can queue all three
    # "last" saves before the writer thread runs once, and same-key
    # coalescing would then (legitimately) write only the final, torn one —
    # leaving no prev- fallback to test.
    hp = load_config(
        "tpu",
        argv=argv + [
            "--fault-plan",
            "stall@epoch=0:secs=0.2;stall@epoch=1:secs=0.2;"
            "torn_write@epoch=2;preempt@epoch=2",
        ],
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    with pytest.raises(Preempted):
        trainer.fit()  # epoch 2's last.ckpt lands, then is torn
    trainer.close()
    vdir = tmp_path / "version-0"
    ok, reason = verify_checkpoint(vdir / "last.ckpt")
    assert not ok and "mismatch" in reason
    records = load_goodput_records(vdir / "goodput.jsonl")
    assert records[0]["stall_s"] >= 0.4  # both injected stalls accounted

    resumed = Trainer(
        load_config("tpu", argv=argv + ["--auto-resume"]),
        model=TinyNet(num_classes=100),
    )
    # fell back to the previous good checkpoint.  Its epoch is 0 or 1
    # depending on writer-thread coalescing (a queued epoch-1 save may be
    # superseded by epoch 2's before it starts) — what must hold is that
    # resume continues from exactly the epoch the fallback manifest records.
    assert resumed.hparams.resume.endswith("prev-last.ckpt")
    prev_epoch = read_manifest(vdir / "prev-last.ckpt")["epoch"]
    assert resumed.start_epoch == prev_epoch + 1 <= 3
    version = resumed.fit()
    resumed.close()
    assert version == 0  # continued in place, no new version dir
    assert read_manifest(vdir / "last.ckpt")["epoch"] == 2  # run completed


# ------------------------------------------------------------- supervisor


def test_supervisor_crash_backoff_and_budget():
    rcs = iter([1, 1, 0])
    sleeps = []
    sup = Supervisor(
        ["true"],
        max_restarts=3,
        backoff_base=0.5,
        runner=lambda cmd, env: next(rcs),
        sleep=sleeps.append,
        log=lambda msg: None,
    )
    summary = sup.run()
    assert summary["final_rc"] == 0 and summary["restarts"] == 2
    assert sleeps == [0.5, 1.0]  # exponential
    assert summary["downtime_s"] == 1.5

    sup = Supervisor(
        ["true"],
        max_restarts=2,
        backoff_base=0.1,
        runner=lambda cmd, env: 9,
        sleep=lambda s: None,
        log=lambda msg: None,
    )
    summary = sup.run()
    assert summary["final_rc"] == 9
    assert len(summary["attempts"]) == 3  # initial + 2 budgeted restarts


def test_supervisor_counts_budget_exhausting_preemption():
    """A final preempted attempt that exhausts the budget must still be
    counted — GOODPUT.json's preemptions field must agree with the
    attempt list."""
    sup = Supervisor(
        ["true"],
        max_restarts=1,
        runner=lambda cmd, env: EXIT_PREEMPTED,
        sleep=lambda s: None,
        log=lambda msg: None,
    )
    summary = sup.run()
    assert summary["final_rc"] == EXIT_PREEMPTED
    assert len(summary["attempts"]) == 2
    assert summary["preemptions"] == 2  # both attempts, incl. the last one


def test_supervisor_preemption_restarts_without_backoff():
    rcs = iter([EXIT_PREEMPTED, EXIT_PREEMPTED, 0])
    sleeps = []
    seen_cmds = []
    sup = Supervisor(
        lambda attempt: ["attempt", str(attempt)],
        max_restarts=5,
        runner=lambda cmd, env: (seen_cmds.append(list(cmd)), next(rcs))[1],
        sleep=sleeps.append,
        log=lambda msg: None,
    )
    summary = sup.run()
    assert summary["final_rc"] == 0
    assert summary["preemptions"] == 2 and sleeps == []  # no backoff
    assert seen_cmds == [["attempt", "0"], ["attempt", "1"], ["attempt", "2"]]
    assert [a["preempted"] for a in summary["attempts"]] == [True, True, False]


def test_strip_resume_flag_both_forms():
    """Restart attempts must drop an explicit --resume (attempt 0's
    original-checkpoint pointer) so --auto-resume can pick up the progress
    the previous attempt actually made."""
    from distributed_training_comparison_tpu.resilience.supervisor import (
        strip_resume_flag,
    )

    args = ["--epoch", "5", "--resume", "run/last.ckpt", "--auto-resume"]
    assert strip_resume_flag(args) == ["--epoch", "5", "--auto-resume"]
    args = ["--resume=run/last.ckpt", "--epoch", "5"]
    assert strip_resume_flag(args) == ["--epoch", "5"]
    assert strip_resume_flag(["--epoch", "5"]) == ["--epoch", "5"]


# ---------------------------------------------------------------- goodput


def test_goodput_meter_and_aggregate():
    meter = GoodputMeter()
    meter.add("step", 6.0)
    meter.add("ckpt", 1.0)
    with meter.phase("eval"):
        pass
    summary = meter.summary()
    assert summary["step_s"] == 6.0 and summary["ckpt_s"] == 1.0
    assert summary["wall_s"] >= 0

    report = aggregate_goodput(
        [
            {"step_s": 6.0, "ckpt_s": 1.0, "wall_s": 8.0},
            {"step_s": 3.0, "ckpt_s": 0.5, "wall_s": 4.0},
        ],
        downtime_s=3.0,
        restarts=1,
        preemptions=1,
    )
    assert report["productive_s"] == 9.0
    assert report["total_wall_s"] == 15.0  # 8 + 4 + 3 downtime
    assert report["goodput_frac"] == pytest.approx(9.0 / 15.0, abs=1e-4)
    assert report["restarts"] == 1 and report["attempts"] == 2


def test_collect_goodput_records_spans_version_dirs(tmp_path):
    """An attempt that died before its first save leaves its record in one
    version dir while the relaunch progresses in the next — aggregation
    must see both, and `since` must exclude older runs' records."""
    from distributed_training_comparison_tpu.resilience.goodput import (
        collect_goodput_records,
    )

    for n, (step_s, written_at) in enumerate([(1.0, 50.0), (2.0, 100.0)]):
        d = tmp_path / f"version-{n}"
        d.mkdir()
        (d / "goodput.jsonl").write_text(
            json.dumps({"step_s": step_s, "written_at": written_at}) + "\n"
        )
    assert [r["step_s"] for r in collect_goodput_records(tmp_path)] == [1.0, 2.0]
    assert [
        r["step_s"] for r in collect_goodput_records(tmp_path, since=75.0)
    ] == [2.0]


def test_goodput_records_survive_torn_trailing_line(tmp_path):
    path = tmp_path / "goodput.jsonl"
    path.write_text('{"step_s": 1.0}\n{"step_s": 2.0}\n{"torn...')
    assert [r["step_s"] for r in load_goodput_records(path)] == [1.0, 2.0]


# ------------------------------------------------------------ elastic


def test_elastic_restore_across_device_counts_in_process(tmp_path):
    """Save on an 8-device mesh, restore onto a 4-device mesh: step/epoch
    accounting intact, and the next epoch's trajectory matches the
    8-device continuation (reduction-order tolerance only)."""
    x, y = (
        jnp.asarray(np.random.default_rng(0).normal(size=(64, 32, 32, 3)).astype(np.float32)),
        jnp.asarray(np.random.default_rng(1).integers(0, 10, size=(64,)).astype(np.int32)),
    )
    mesh8 = make_mesh(backend="ddp")
    runner8 = whole_epoch_runner(mesh8, 32, len(x))
    state = _tiny_state(mesh8)
    key = jax.random.key(3)
    state, _ = runner8(state, x, y, key, jnp.asarray(0))
    save_resume_state(find_version_dir(tmp_path), state, epoch=0, best_acc=1.0)

    mesh4 = make_mesh(4, backend="ddp")
    restored, next_epoch, _ = load_resume_state(
        tmp_path / "version-0" / "last.ckpt", _tiny_state(mesh4)
    )
    restored = jax.device_put(restored, replicated_sharding(mesh4))
    assert next_epoch == 1 and int(restored.step) == 2

    state8, s8 = runner8(state, x, y, key, jnp.asarray(1))
    runner4 = whole_epoch_runner(mesh4, 32, len(x))
    state4, s4 = runner4(restored, x, y, key, jnp.asarray(1))
    # one epoch (two steps) under a 4-way against an 8-way gradient
    # reduction: conv-family dp-only fp32 re-associates into 2^6-2^8
    # scale-aware ulps (--parity-tol's help; 221 measured here, on the
    # BatchNorm bias), which the parity rail gates at ulp=1024
    assert_trees_within_ulp(
        {"loss": s4["loss"], "params": jax.device_get(state4.params)},
        {"loss": s8["loss"], "params": jax.device_get(state8.params)},
        ulp=1024,
    )


# ----------------------------------------------------------- e2e headline


@pytest.mark.elastic
def test_e2e_preempt_supervisor_elastic(tmp_path, forced_device_env):
    """ISSUE 2 acceptance: child preempted at epoch 1 (8 devices) →
    supervisor relaunches with --auto-resume on 4 devices → resumes from
    epoch 1's checkpoint → final params allclose to an uninterrupted
    same-seed run."""
    ckpt_root = tmp_path / "faulted"
    args = WORKER_ARGS + [
        "--ckpt-path", str(ckpt_root),
        "--auto-resume",
        "--fault-plan", "preempt@epoch=1",
    ]

    def runner(cmd, env):
        proc = subprocess.run(
            cmd, env=env, cwd=WORKER.parent.parent,
            capture_output=True, text=True, timeout=300,
        )
        assert "Traceback" not in (proc.stderr or ""), proc.stderr[-3000:]
        return proc.returncode

    sup = Supervisor(
        [sys.executable, str(WORKER)] + args,
        env=lambda attempt: forced_device_env(8 if attempt == 0 else 4),
        max_restarts=3,
        backoff_base=0.05,
        runner=runner,
        log=lambda msg: None,
    )
    summary = sup.run()
    assert summary["final_rc"] == 0, summary
    assert summary["restarts"] == 1 and summary["preemptions"] == 1
    assert summary["attempts"][0]["returncode"] == EXIT_PREEMPTED

    vdir = ckpt_root / "version-0"
    records = load_goodput_records(vdir / "goodput.jsonl")
    assert len(records) == 2
    assert records[0]["preempted"] and records[0]["topology"]["devices"] == 8
    assert not records[1]["preempted"] and records[1]["topology"]["devices"] == 4
    assert records[1]["start_epoch"] == 2  # resumed from epoch 1's checkpoint
    report = aggregate_goodput(records, restarts=summary["restarts"])
    assert report["productive_s"] > 0

    # uninterrupted run, same seed, on this process's 8-device mesh
    clean_root = tmp_path / "clean"
    hp = load_config("tpu", argv=WORKER_ARGS + ["--ckpt-path", str(clean_root)])
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    trainer.fit()
    trainer.close()

    def final_params(root):
        raw = serialization.msgpack_restore(
            (root / "version-0" / "last.ckpt").read_bytes()
        )
        assert raw["epoch"] == 2  # all 3 epochs completed
        return raw["state"]["params"]

    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
        ),
        final_params(ckpt_root),
        final_params(clean_root),
    )


# --------------------------------------------------------------- entry


def test_entry_maps_preempted_to_exit_code(tmp_path, monkeypatch):
    from distributed_training_comparison_tpu import entry

    class StubTrainer:
        version = 0

        def __init__(self, hparams):
            pass

        def fit(self):
            raise Preempted(epoch=4, step=40)

        def close(self):
            pass

    monkeypatch.setattr(entry, "Trainer", StubTrainer)
    results = entry.run(
        "single",
        argv=["--synthetic-data", "--ckpt-path", str(tmp_path)],
    )
    assert results["preempted"] is True and results["epoch"] == 4
    assert results["exit_code"] == EXIT_PREEMPTED
