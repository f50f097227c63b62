"""health/ subsystem tests: compiled numerics guards, spike/desync
detection, the watchdog's automatic rollback, and the fault-plan kinds that
drive them — plus the satellite paths (mid-epoch host-mode preemption,
supervisor progress probe, async-writer utilization gauge).

The headline (ISSUE 3 acceptance) is
``test_e2e_nan_and_spike_rollback_matches_clean``: a seeded
``nan_grad@epoch=1;loss_spike@epoch=2`` plan mid-run → the compiled guard
skips the non-finite steps, the median/MAD window flags the spikes, the
watchdog rolls back to the last good checkpoint twice and replays clean →
the final params and eval metrics match (allclose) an uninterrupted run
with the same seed, with every skip/rollback on record in health.jsonl +
HEALTH.json and the wasted epochs charged to goodput's ``rollback`` phase.
"""

import json

import flax.linen as lnn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import serialization

from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.data import synthetic_dataset
from distributed_training_comparison_tpu.health import (
    SpikeDetector,
    Watchdog,
    check_desync,
    global_norm,
    load_health_events,
    param_fingerprint,
)
from distributed_training_comparison_tpu.health.watchdog import HealthConfig
from distributed_training_comparison_tpu.parallel import make_mesh, replicated_sharding
from distributed_training_comparison_tpu.resilience import (
    EXIT_PREEMPTED,
    FaultPlan,
    FaultSpecError,
    GoodputMeter,
    Preempted,
    Supervisor,
    aggregate_goodput,
    load_goodput_records,
    read_manifest,
)
from distributed_training_comparison_tpu.train import (
    Trainer,
    configure_optimizers,
    create_train_state,
    make_train_step,
)

from conftest import whole_epoch_runner
from test_train import HP, TinyNet

BASE_ARGS = [
    "--synthetic-data",
    "--limit-examples", "640",   # 576 train examples -> 18 steps/epoch @32
    "--batch-size", "32",
    "--epoch", "4",
    "--save-last-min-secs", "0",
    "--no-progress",
    "--seed", "7",
    "--eval-step", "1000",
]


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(backend="ddp")


@pytest.fixture(scope="module")
def tiny_data():
    x, y = synthetic_dataset(256, num_classes=10, seed=0)
    return jnp.asarray(x), jnp.asarray(y)


def _fresh_state(mesh):
    tx, _ = configure_optimizers(HP, steps_per_epoch=4)
    state = create_train_state(TinyNet(dtype=jnp.float32), jax.random.key(0), tx)
    return jax.device_put(state, replicated_sharding(mesh))


def _params_equal(a, b):
    flat_a = jax.tree_util.tree_leaves(jax.device_get(a))
    flat_b = jax.tree_util.tree_leaves(jax.device_get(b))
    return all(np.array_equal(x, y) for x, y in zip(flat_a, flat_b))


# ------------------------------------------------------------ fault plans


def test_fault_plan_parses_health_kinds():
    plan = FaultPlan.parse(
        "nan_grad@epoch=1; loss_spike@epoch=2:steps=4:scale=8, "
        "bad_batch@epoch=0:step=5; desync@epoch=3"
    )
    assert plan.has_step_faults()
    scale, start, stop = plan.step_fault(2, steps_per_epoch=20)
    assert (scale, start, stop) == (8.0, 10, 14)
    assert plan.step_fault(2, 20) == (1.0, 0, 0)  # consumed: replay is clean
    scale, start, stop = plan.step_fault(1, 20)
    assert np.isnan(scale) and (start, stop) == (0, 3)  # nan_grad defaults
    assert plan.step_fault(0, 20) == (float("inf"), 5, 6)  # bad_batch @step
    assert plan.desync_due(3) and not plan.desync_due(3)  # one-shot
    assert not plan.desync_due(1)


def test_fault_plan_rejects_malformed_health_args():
    with pytest.raises(FaultSpecError):
        FaultPlan.parse("nan_grad@epoch=1:scale=x")
    with pytest.raises(FaultSpecError):
        FaultPlan.parse("loss_spike@steps=3")  # no trigger
    with pytest.raises(SystemExit):
        load_config("tpu", ["--fault-plan", "nan_grad@epoch=1:mins=2"])


def test_fault_plan_mid_epoch_preempt_semantics():
    plan = FaultPlan.parse("preempt@epoch=1:step=4")
    # boundary: device mode fires it at the epoch's end, host mode must not
    assert plan.preempt_due(1, include_step_events=True)
    assert not plan.preempt_due(1, include_step_events=False)
    # chunk poll: fires once >= step 4 steps are done...
    assert not plan.preempt_step_due(1, done=2)
    assert plan.preempt_step_due(1, done=4)
    # ...but never for an attempt that RESUMED at-or-past it (one-shot)
    assert not plan.preempt_step_due(1, done=14, start_offset=4)
    assert not plan.preempt_step_due(0, done=14)  # wrong epoch
    # an out-of-range step clamps to the epoch's step count (fires at the
    # boundary instead of silently never)
    plan = FaultPlan.parse("preempt@epoch=1:step=99")
    assert not plan.preempt_step_due(1, done=12, cap=14)
    assert plan.preempt_step_due(1, done=14, cap=14)
    # step=0 means "as soon as possible", not "never" (0 < 0 would drop it)
    plan = FaultPlan.parse("preempt@epoch=1:step=0")
    assert plan.preempt_step_due(1, done=2)
    assert not plan.preempt_step_due(1, done=2, start_offset=1)


# ------------------------------------------------- compiled numerics guards


def test_global_norm_flags_nonfinite():
    tree = {"a": jnp.ones((4,)), "b": jnp.full((2,), 2.0)}
    assert float(global_norm(tree)) == pytest.approx(np.sqrt(4 + 8))
    tree["b"] = jnp.array([1.0, np.nan])
    assert not np.isfinite(float(global_norm(tree)))
    tree["b"] = jnp.array([1.0, np.inf])
    assert not np.isfinite(float(global_norm(tree)))
    assert float(global_norm({})) == 0.0


def test_guarded_epoch_skips_nonfinite_and_freezes_state(mesh, tiny_data):
    """NaN-poisoned steps must apply NOTHING (params, BN stats, opt state,
    step counter all frozen) and report per-step skip flags that ride the
    stacked metrics fetch."""
    x, y = tiny_data
    # donate=False: this test deliberately re-reads the INPUT state after
    # the call to prove the guard froze it (the trainer's hot path donates)
    runner = whole_epoch_runner(
        mesh, 64, len(x), fault_injection=True, donate=False
    )
    state = _fresh_state(mesh)
    key = jax.random.key(3)

    # every step poisoned: the epoch is a no-op on the state
    out_state, stacked = runner(
        state, x, y, key, jnp.asarray(0), (float("nan"), 0, 4)
    )
    assert np.all(np.asarray(stacked["skipped"]) == 1.0)
    assert not np.isfinite(np.asarray(stacked["grad_norm"])).any()
    assert int(out_state.step) == int(state.step)
    assert _params_equal(out_state.params, state.params)
    assert _params_equal(out_state.batch_stats, state.batch_stats)

    # partial window: only the poisoned steps skip, the rest train
    out_state, stacked = runner(
        state, x, y, key, jnp.asarray(0), (float("nan"), 1, 3)
    )
    np.testing.assert_array_equal(
        np.asarray(stacked["skipped"]), [0.0, 1.0, 1.0, 0.0]
    )
    assert int(out_state.step) == int(state.step) + 2
    assert not _params_equal(out_state.params, state.params)


def test_fault_scale_injection_is_windowed_and_benign_at_one(mesh, tiny_data):
    """scale=1 must reproduce the unfaulted trajectory exactly, and a spike
    window must scale exactly the targeted step's loss metric."""
    x, y = tiny_data
    state = _fresh_state(mesh)
    key = jax.random.key(3)
    # donate=False: one state feeds three runner calls side by side
    plain = whole_epoch_runner(mesh, 64, len(x), donate=False)
    faulted = whole_epoch_runner(
        mesh, 64, len(x), fault_injection=True, donate=False
    )
    _, s_plain = plain(state, x, y, key, jnp.asarray(0))
    _, s_benign = faulted(state, x, y, key, jnp.asarray(0), (1.0, 0, 0))
    np.testing.assert_allclose(
        np.asarray(s_benign["loss"]), np.asarray(s_plain["loss"]),
        rtol=1e-6, atol=0,
    )
    assert np.all(np.asarray(s_benign["skipped"]) == 0.0)

    _, s_spike = faulted(state, x, y, key, jnp.asarray(0), (64.0, 2, 3))
    losses = np.asarray(s_spike["loss"])
    base = np.asarray(s_plain["loss"])
    np.testing.assert_allclose(losses[:2], base[:2], rtol=1e-6)
    assert losses[2] == pytest.approx(64.0 * base[2], rel=1e-5)
    assert np.all(np.asarray(s_spike["skipped"]) == 0.0)  # finite: applied


def test_moe_metrics_nan_does_not_poison_skip_decision(mesh):
    """Sown dispatch metrics may be NaN (a collapsed router under bf16, a
    non-finite logit) without vetoing a healthy update; a NaN AUX LOSS must
    veto it (it sums into the objective)."""

    class NaNMetricsNet(lnn.Module):
        @lnn.compact
        def __call__(self, x, train=False):
            feats = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
            self.sow("moe_metrics", "expert_load", jnp.full((1, 4), jnp.nan))
            return lnn.Dense(10)(feats)

    class NaNAuxLossNet(lnn.Module):
        @lnn.compact
        def __call__(self, x, train=False):
            feats = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
            self.sow("losses", "aux", jnp.asarray(jnp.nan, jnp.float32))
            return lnn.Dense(10)(feats)

    x, y = synthetic_dataset(64, num_classes=10, seed=1)
    x, y = jnp.asarray(x), jnp.asarray(y)
    tx, _ = configure_optimizers(HP, steps_per_epoch=4)
    step = make_train_step(mesh)

    state = create_train_state(NaNMetricsNet(), jax.random.key(0), tx)
    state = jax.device_put(state, replicated_sharding(mesh))
    new_state, metrics = step(state, x, y, jax.random.key(1))
    assert float(metrics["skipped"]) == 0.0  # NaN diagnostics: still applied
    assert np.isnan(float(metrics["moe_load_max"]))
    assert int(new_state.step) == 1

    state = create_train_state(NaNAuxLossNet(), jax.random.key(0), tx)
    state = jax.device_put(state, replicated_sharding(mesh))
    new_state, metrics = step(state, x, y, jax.random.key(1))
    assert float(metrics["skipped"]) == 1.0  # NaN objective: guarded out
    assert int(new_state.step) == 0
    assert _params_equal(new_state.params, state.params)


# ----------------------------------------------------------- spike detector


def test_spike_detector_flags_outliers_after_warmup():
    det = SpikeDetector(window=32, threshold_mads=8.0, min_baseline=16)
    rng = np.random.default_rng(0)
    base = 2.0 + 0.05 * rng.standard_normal(8)
    # warmup: even a huge value must not flag before the baseline exists
    flags = det.observe(np.append(base, 50.0), np.zeros(9))
    assert not flags.any()
    det.observe(2.0 + 0.05 * rng.standard_normal(16), np.zeros(16))
    losses = 2.0 + 0.05 * rng.standard_normal(10)
    losses[3] = 50.0
    flags = det.observe(losses, np.zeros(10))
    assert flags[3] and flags.sum() == 1
    # the outlier never entered the window: an identical spike still flags
    assert det.observe(np.asarray([50.0]), np.zeros(1))[0]
    # skipped (non-finite) steps are the guard's business, never spikes
    assert not det.observe(np.asarray([np.nan]), np.ones(1))[0]


def test_watchdog_rollback_needs_k_consecutive_bad_steps():
    losses = np.asarray([2.0, 2.0, np.nan, np.nan, np.nan, 2.0])
    skipped = np.asarray([0, 0, 1, 1, 1, 0], np.float32)
    wd = Watchdog(HealthConfig(bad_steps=3, min_baseline=64))
    verdict = wd.observe_epoch(0, losses, skipped)
    assert verdict.rollback and verdict.skipped == 3 and verdict.max_bad_run == 3
    assert verdict.nonfinite
    wd = Watchdog(HealthConfig(bad_steps=4, min_baseline=64))
    verdict = wd.observe_epoch(0, losses, skipped)
    assert not verdict.rollback and wd.skipped_steps == 3
    assert wd.events and wd.events[0]["kind"] == "skip"


# ------------------------------------------------------------------- desync


def test_check_desync_single_process_and_injection(mesh):
    state = _fresh_state(mesh)
    fp = float(jax.jit(param_fingerprint)(state.params))
    assert np.isfinite(fp) and fp > 0
    report = check_desync(fp)
    assert not report["mismatch"] and report["spread"] == 0.0
    report = check_desync(fp, inject=True)
    assert report["mismatch"] and report["injected"]
    assert report["spread"] >= 1.0
    # the injected drift must survive float32 rounding at LARGE fingerprints
    # (a flat +1.0 is absorbed past 2^24)
    report = check_desync(3.4e7, inject=True)
    assert report["mismatch"] and report["spread"] > 0


def test_param_fingerprint_detects_leaf_swaps():
    a = {"x": jnp.full((2,), 1.0), "y": jnp.full((2,), 3.0)}
    b = {"x": jnp.full((2,), 3.0), "y": jnp.full((2,), 1.0)}
    assert float(param_fingerprint(a)) != float(param_fingerprint(b))


# ------------------------------------------------- trainer e2e (acceptance)


def _fit(tmp_path, extra=(), model=None):
    hp = load_config("tpu", argv=BASE_ARGS + ["--ckpt-path", str(tmp_path), *extra])
    trainer = Trainer(hp, model=model or TinyNet(num_classes=100))
    trainer.fit()
    val = trainer.validate(0)
    trainer.close()
    return trainer, val


def _last_ckpt_params(root):
    raw = serialization.msgpack_restore(
        (root / "version-0" / "last.ckpt").read_bytes()
    )
    return raw["epoch"], raw["state"]["params"]


@pytest.mark.health
def test_e2e_nan_and_spike_rollback_matches_clean(tmp_path):
    """ISSUE 3 acceptance: nan_grad + loss_spike injected mid-run → the
    guard skips, the watchdog rolls back twice and replays clean → final
    params and eval metrics allclose an uninterrupted same-seed run, with
    the damage on record (health.jsonl, HEALTH.json, goodput rollback)."""
    health_json = tmp_path / "HEALTH.json"
    clean_t, clean_val = _fit(tmp_path / "clean")
    faulted_t, faulted_val = _fit(
        tmp_path / "faulted",
        extra=[
            "--fault-plan", "nan_grad@epoch=1;loss_spike@epoch=2",
            "--health-json", str(health_json),
        ],
    )
    wd = faulted_t.watchdog
    assert wd.skipped_steps == 3       # nan_grad's 3 poisoned steps
    assert wd.spike_steps >= 3         # the spiked window (damage may extend it)
    assert wd.rollbacks == 2           # one per faulted epoch
    assert wd.desyncs == 0

    # converge-anyway: the replayed trajectory IS the clean trajectory
    epoch, faulted_params = _last_ckpt_params(tmp_path / "faulted")
    clean_epoch, clean_params = _last_ckpt_params(tmp_path / "clean")
    assert epoch == clean_epoch == 3
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
        ),
        faulted_params, clean_params,
    )
    assert faulted_val["val_acc"] == pytest.approx(clean_val["val_acc"], abs=0.5)
    assert faulted_val["val_loss"] == pytest.approx(clean_val["val_loss"], rel=1e-3)

    # the paper trail: events + HEALTH.json + rollback-phase goodput
    events = load_health_events(tmp_path / "faulted" / "version-0" / "health.jsonl")
    assert sum(e["kind"] == "rollback" for e in events) == 2
    report = json.loads(health_json.read_bytes())
    assert report["rollbacks"] == 2 and report["skipped_steps"] == 3
    records = load_goodput_records(
        tmp_path / "faulted" / "version-0" / "goodput.jsonl"
    )
    assert records[0]["rollback_s"] > 0
    assert records[0]["health"]["rollbacks"] == 2
    assert 0.0 <= records[0]["ckpt_writer"]["busy_frac"] <= 1.0


@pytest.mark.health
def test_e2e_desync_detect_rollback_converges(tmp_path):
    """An injected replica desync after a CLEAN epoch rolls back and replays
    — since no damage was ever applied, the final state matches the clean
    run exactly (allclose)."""
    clean_t, _ = _fit(tmp_path / "clean")
    faulted_t, _ = _fit(
        tmp_path / "faulted", extra=["--fault-plan", "desync@epoch=1"]
    )
    assert faulted_t.watchdog.desyncs == 1
    assert faulted_t.watchdog.rollbacks == 1
    _, faulted_params = _last_ckpt_params(tmp_path / "faulted")
    _, clean_params = _last_ckpt_params(tmp_path / "clean")
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
        ),
        faulted_params, clean_params,
    )


@pytest.mark.health
def test_no_health_aborts_on_skipped_steps(tmp_path):
    """--no-health keeps the pre-watchdog contract: the compiled guard
    still holds the state, but non-finite grads (even under a finite loss)
    abort loudly — there is no recovery policy to absorb them."""
    hp = load_config(
        "tpu",
        argv=BASE_ARGS + [
            "--ckpt-path", str(tmp_path), "--no-health",
            "--fault-plan", "nan_grad@epoch=0:steps=1",
        ],
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    assert trainer.watchdog is None
    with pytest.raises(FloatingPointError, match="non-finite train loss"):
        trainer.fit()
    trainer.close()


@pytest.mark.health
def test_rollback_falls_back_to_resume_source_before_first_save(tmp_path):
    """An explicit --resume trains in a FRESH version dir: a bad epoch
    before its first save must roll back to the (read-only) source
    checkpoint, not give up — and still converge to the clean trajectory."""
    _fit(tmp_path / "src")  # donor run: version-0 with last.ckpt at epoch 3
    src_last = tmp_path / "src" / "version-0" / "last.ckpt"
    argv = BASE_ARGS[:]
    argv[argv.index("--epoch") + 1] = "6"
    hp = load_config(
        "tpu",
        argv=argv + [
            "--ckpt-path", str(tmp_path / "dst"),
            "--resume", str(src_last),
            "--fault-plan", "nan_grad@epoch=4",
        ],
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    assert trainer.start_epoch == 4
    trainer.fit()
    trainer.close()
    assert trainer.watchdog.rollbacks == 1  # via the source fallback
    epoch, _ = _last_ckpt_params(tmp_path / "dst")
    assert epoch == 5  # run completed in its own fresh dir
    assert src_last.exists()  # source untouched


@pytest.mark.health
def test_e2e_single_bad_batch_absorbed_without_rollback(tmp_path):
    """One corrupt batch (Inf loss) is the cheap case: the compiled guard
    skips its update, the run keeps training — no rollback, one skip on
    record."""
    trainer, _ = _fit(
        tmp_path, extra=["--fault-plan", "bad_batch@epoch=1"]
    )
    assert trainer.watchdog.skipped_steps == 1
    assert trainer.watchdog.rollbacks == 0
    epoch, _ = _last_ckpt_params(tmp_path)
    assert epoch == 3  # completed


# ------------------------------------- mid-epoch preemption (host data mode)


HOST_ARGS = [
    "--synthetic-data",
    "--limit-examples", "512",   # 460 train examples -> 14 steps/epoch @32
    "--batch-size", "32",
    "--epoch", "2",
    "--data-mode", "host",
    "--host-chunk-steps", "2",
    "--workers", "0",
    "--save-last-min-secs", "0",
    "--no-progress",
    "--seed", "7",
    "--eval-step", "1000",
]


def test_host_mode_mid_epoch_preempt_drains_and_resumes_exactly(tmp_path):
    """Chunk-boundary preemption polling (ROADMAP follow-on from PR 2): the
    drain no longer waits for the epoch boundary, the checkpoint records the
    in-progress epoch's step count, and the resumed attempt fast-forwards
    past it — final params match an uninterrupted run."""
    root = tmp_path / "faulted"
    argv = HOST_ARGS + [
        "--ckpt-path", str(root), "--fault-plan", "preempt@epoch=0:step=4",
    ]
    trainer = Trainer(
        load_config("tpu", argv=argv), model=TinyNet(num_classes=100)
    )
    with pytest.raises(Preempted) as exc:
        trainer.fit()
    trainer.close()
    assert exc.value.epoch == 0 and exc.value.step == 4
    manifest = read_manifest(root / "version-0" / "last.ckpt")
    assert manifest["epoch"] == -1  # no epoch completed yet
    assert manifest["epoch_in_progress"] == 0
    assert manifest["epoch_steps_done"] == 4
    records = load_goodput_records(root / "version-0" / "goodput.jsonl")
    assert records[0]["preempted"] is True

    # relaunch (fault plan intact, as a supervisor would): resumes INTO
    # epoch 0 at step 4, does not re-fire the consumed preemption
    resumed = Trainer(
        load_config("tpu", argv=argv + ["--auto-resume"]),
        model=TinyNet(num_classes=100),
    )
    assert resumed.start_epoch == 0
    assert resumed._resume_step_offset == 4
    resumed.fit()
    resumed.close()
    assert read_manifest(root / "version-0" / "last.ckpt")["epoch"] == 1

    clean_root = tmp_path / "clean"
    clean = Trainer(
        load_config("tpu", argv=HOST_ARGS + ["--ckpt-path", str(clean_root)]),
        model=TinyNet(num_classes=100),
    )
    clean.fit()
    clean.close()
    _, resumed_params = _last_ckpt_params(root)
    _, clean_params = _last_ckpt_params(clean_root)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        ),
        resumed_params, clean_params,
    )


DEVICE_ARGS = [
    "--synthetic-data",
    "--limit-examples", "512",   # 460 train examples -> 14 steps/epoch @32
    "--batch-size", "32",
    "--epoch", "2",
    "--device-chunk-steps", "2",
    "--save-last-min-secs", "0",
    "--no-progress",
    "--seed", "7",
    "--eval-step", "1000",
]


def test_device_mode_mid_epoch_preempt_drains_and_resumes_exactly(tmp_path):
    """ISSUE 4 acceptance: device data mode gains the same chunk-boundary
    preemption drain host mode has — with ``--device-chunk-steps`` set, a
    mid-epoch ``preempt@epoch=K:step=S`` drains at the next chunk boundary
    (grace window = one chunk, not one epoch), the manifest records the
    steps done, the relaunch fast-forwards the epoch permutation past them,
    and final params match an uninterrupted run."""
    root = tmp_path / "faulted"
    argv = DEVICE_ARGS + [
        "--ckpt-path", str(root), "--fault-plan", "preempt@epoch=0:step=4",
    ]
    trainer = Trainer(
        load_config("tpu", argv=argv), model=TinyNet(num_classes=100)
    )
    with pytest.raises(Preempted) as exc:
        trainer.fit()
    trainer.close()
    assert exc.value.epoch == 0 and exc.value.step == 4
    manifest = read_manifest(root / "version-0" / "last.ckpt")
    assert manifest["epoch"] == -1  # no epoch completed yet
    assert manifest["epoch_in_progress"] == 0
    assert manifest["epoch_steps_done"] == 4

    # relaunch (fault plan intact, as a supervisor would): resumes INTO
    # epoch 0 at step 4, does not re-fire the consumed preemption
    resumed = Trainer(
        load_config("tpu", argv=argv + ["--auto-resume"]),
        model=TinyNet(num_classes=100),
    )
    assert resumed.start_epoch == 0
    assert resumed._resume_step_offset == 4
    resumed.fit()
    resumed.close()
    assert read_manifest(root / "version-0" / "last.ckpt")["epoch"] == 1

    clean_root = tmp_path / "clean"
    clean = Trainer(
        load_config("tpu", argv=DEVICE_ARGS + ["--ckpt-path", str(clean_root)]),
        model=TinyNet(num_classes=100),
    )
    clean.fit()
    clean.close()
    _, resumed_params = _last_ckpt_params(root)
    _, clean_params = _last_ckpt_params(clean_root)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        ),
        resumed_params, clean_params,
    )


def test_host_mode_final_chunk_preempt_fires_at_boundary(tmp_path):
    """A step event landing in the epoch's FINAL chunk (the mid-epoch poll
    stops one boundary early) must fire at the epoch boundary as a normal
    end-of-epoch preemption — never be silently dropped."""
    argv = HOST_ARGS + [
        "--ckpt-path", str(tmp_path), "--fault-plan", "preempt@epoch=0:step=13",
    ]
    trainer = Trainer(
        load_config("tpu", argv=argv), model=TinyNet(num_classes=100)
    )
    with pytest.raises(Preempted) as exc:
        trainer.fit()
    trainer.close()
    assert exc.value.epoch == 0  # whole epoch completed, boundary drain
    manifest = read_manifest(tmp_path / "version-0" / "last.ckpt")
    assert manifest["epoch"] == 0
    assert "epoch_in_progress" not in manifest


def test_resume_progress_marker_is_manifest_only(tmp_path, mesh):
    """The supervisor's per-attempt probe must not read/hash the payload:
    the marker comes from the manifest, and moves when the checkpoint
    does."""
    from distributed_training_comparison_tpu.train import save_resume_state
    from distributed_training_comparison_tpu.train.checkpoint import (
        find_version_dir,
        resume_progress_marker,
    )

    assert resume_progress_marker(tmp_path) is None
    state = _fresh_state(mesh)
    vdir = find_version_dir(tmp_path)
    save_resume_state(vdir, state, epoch=0, best_acc=1.0)
    m0 = resume_progress_marker(tmp_path)
    assert m0 is not None and m0[3] == 0  # manifest epoch
    save_resume_state(vdir, state, epoch=1, best_acc=1.0)
    m1 = resume_progress_marker(tmp_path)
    assert m1 != m0 and m1[3] == 1  # marker moved with progress


# ------------------------------------------------- supervisor progress probe


def test_supervisor_progress_spares_budget_and_resets_backoff():
    """Crashed attempts whose durable checkpoint ADVANCED (health rollbacks
    kept writing progress) must not consume --max-restarts, and the crash
    backoff restarts from its base instead of compounding."""
    rcs = iter([1, 1, 1, 0])
    markers = iter([None, ("ck", 1), ("ck", 2), ("ck", 3)])
    sleeps = []
    sup = Supervisor(
        ["true"],
        max_restarts=1,  # would die after 1 restart without the probe
        backoff_base=0.5,
        runner=lambda cmd, env: next(rcs),
        sleep=sleeps.append,
        log=lambda msg: None,
        progress=lambda: next(markers),
    )
    summary = sup.run()
    assert summary["final_rc"] == 0 and summary["restarts"] == 3
    assert summary["progress_restarts"] == 3
    assert sleeps == [0.5, 0.5, 0.5]  # backoff never compounded
    assert all(a["progress"] for a in summary["attempts"][:3])


def test_supervisor_without_progress_still_budgets():
    """A run stuck at the same checkpoint exhausts the budget as before."""
    sup = Supervisor(
        ["true"],
        max_restarts=1,
        backoff_base=0.01,
        runner=lambda cmd, env: 9,
        sleep=lambda s: None,
        log=lambda msg: None,
        progress=lambda: ("ck", 1),  # never moves
    )
    summary = sup.run()
    assert summary["final_rc"] == 9
    assert len(summary["attempts"]) == 2  # initial + 1 budgeted restart
    assert summary["progress_restarts"] == 0


def test_supervisor_preempt_budget_unchanged_with_probe():
    """Preemptions keep PR-2 semantics (budgeted, no backoff) even when a
    progress probe is wired."""
    markers = iter([None, ("ck", 1), ("ck", 2)])
    sup = Supervisor(
        ["true"],
        max_restarts=1,
        runner=lambda cmd, env: EXIT_PREEMPTED,
        sleep=lambda s: None,
        log=lambda msg: None,
        progress=lambda: next(markers),
    )
    summary = sup.run()
    assert len(summary["attempts"]) == 2 and summary["preemptions"] == 2


# ------------------------------------------------ goodput/writer satellites


def test_goodput_transfer_and_rollback_aggregation():
    meter = GoodputMeter()
    meter.add("step", 10.0)
    moved = meter.transfer("step", "rollback", 4.0)
    assert moved == 4.0
    assert meter.seconds["step"] == 6.0 and meter.seconds["rollback"] == 4.0
    assert meter.transfer("step", "rollback", 100.0) == 6.0  # clamped
    summary = meter.summary()
    assert summary["rollback_s"] == 10.0 and summary["step_s"] == 0.0

    report = aggregate_goodput(
        [
            {
                "step_s": 6.0, "rollback_s": 2.0, "wall_s": 10.0,
                "ckpt_writer": {"busy_s": 1.5},
                "health": {"rollbacks": 2, "skipped_steps": 3},
            },
            {"step_s": 4.0, "wall_s": 5.0},  # pre-health record: still sums
        ]
    )
    assert report["phase_totals_s"]["rollback"] == 2.0
    assert report["ckpt_writer_busy_s"] == 1.5
    assert report["health"]["rollbacks"] == 2
    assert report["health"]["skipped_steps"] == 3
    assert report["goodput_frac"] == pytest.approx(10.0 / 15.0, abs=1e-4)


def test_async_checkpointer_busy_gauge():
    import time as _time

    from distributed_training_comparison_tpu.train import AsyncCheckpointer

    writer = AsyncCheckpointer()
    try:
        writer.submit(lambda: _time.sleep(0.05), key="a")
        writer.wait()
        stats = writer.stats()
        assert stats["busy_s"] >= 0.04
        assert 0.0 < stats["busy_frac"] <= 1.0
        assert stats["alive_s"] >= stats["busy_s"]
    finally:
        writer.close()


# --------------------------------------------------------- config + tooling


def test_health_flags_defaults_and_validation():
    hp = load_config("tpu", ["--synthetic-data"])
    assert hp.health is True and hp.health_window == 64
    assert hp.health_bad_steps == 3 and hp.health_desync_every == 1
    hp = load_config("tpu", ["--no-health"])
    assert hp.health is False
    for bad in (
        ["--health-bad-steps", "0"],
        ["--health-window", "2"],
        ["--health-max-rollbacks", "-1"],
        ["--health-desync-every", "-1"],
    ):
        with pytest.raises(SystemExit):
            load_config("tpu", bad)


def test_health_report_tool_summarizes_events(tmp_path):
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))
    import health_report

    events = [
        {"kind": "skip", "epoch": 1, "count": 3},
        {"kind": "spike", "epoch": 2, "count": 2},
        {"kind": "rollback", "epoch": 2, "to_epoch": 2,
         "wasted_steps": 18, "wasted_s": 1.5},
        {"kind": "desync", "epoch": 3},
    ]
    summary = health_report.summarize_events(events)
    assert summary["skipped_steps"] == 3 and summary["spike_steps"] == 2
    assert summary["rollbacks"] == 1 and summary["desyncs"] == 1
    assert summary["rollback_wasted_steps"] == 18
    path = tmp_path / "health.jsonl"
    path.write_text("\n".join(json.dumps(e) for e in events) + "\n{torn")
    table = health_report.format_table([("run", health_report.load_report(path))])
    assert "rollbk" in table and "run" in table
