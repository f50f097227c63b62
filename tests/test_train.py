"""Train-core tests on the 8-device CPU mesh: step semantics, scanned epoch
runner, eval masking, checkpoint/resume roundtrip, determinism.

ResNet-18 is far too heavy for the single-core CI host, so these use a tiny
BN-bearing convnet — it exercises every train-state path (params, mutable
batch_stats, optimizer state, bf16 policy) at toy cost.
"""

import flax.linen as lnn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_comparison_tpu.data import synthetic_dataset
from distributed_training_comparison_tpu.parallel import (
    batch_sharding,
    make_mesh,
    replicated_sharding,
)
from distributed_training_comparison_tpu.train import (
    configure_optimizers,
    create_train_state,
    load_checkpoint,
    load_resume_state,
    make_eval_runner,
    make_eval_step,
    make_train_step,
    save_checkpoint,
    save_resume_state,
)
from distributed_training_comparison_tpu.train.checkpoint import (
    find_best_checkpoint,
    find_version_dir,
)

from conftest import whole_epoch_runner


class TinyNet(lnn.Module):
    """Minimal conv+BN+dense classifier sharing the ResNet interface."""

    num_classes: int = 10
    dtype: jnp.dtype = jnp.float32

    @lnn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        x = lnn.Conv(8, (3, 3), strides=2, use_bias=False, dtype=self.dtype)(x)
        x = lnn.BatchNorm(use_running_average=not train, dtype=self.dtype)(x)
        x = lnn.relu(x)
        x = jnp.mean(x, axis=(1, 2))
        return lnn.Dense(self.num_classes, dtype=self.dtype)(x).astype(jnp.float32)


class HP:
    lr = 0.05
    weight_decay = 1e-4
    lr_decay_step_size = 25
    lr_decay_gamma = 0.1


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(backend="ddp")


@pytest.fixture(scope="module")
def tiny_data():
    x, y = synthetic_dataset(256, num_classes=10, seed=0)
    return jnp.asarray(x), jnp.asarray(y)


def _fresh_state(mesh, dtype=jnp.float32):
    tx, _ = configure_optimizers(HP, steps_per_epoch=4)
    state = create_train_state(TinyNet(dtype=dtype), jax.random.key(0), tx)
    return jax.device_put(state, replicated_sharding(mesh))


def test_train_step_updates_everything(mesh, tiny_data):
    x, y = tiny_data
    state = _fresh_state(mesh)
    p0 = jax.device_get(state.params)
    bs0 = jax.device_get(state.batch_stats)
    step = make_train_step(mesh)
    shard = batch_sharding(mesh)
    new_state, metrics = step(
        state,
        jax.device_put(x[:64], shard),
        jax.device_put(y[:64], shard),
        jax.random.key(1),
    )
    assert int(new_state.step) == 1
    assert float(metrics["loss"]) > 0
    assert 0 <= float(metrics["top1_count"]) <= 64
    p1 = jax.device_get(new_state.params)
    bs1 = jax.device_get(new_state.batch_stats)
    diff = jax.tree_util.tree_map(lambda a, b: float(np.abs(a - b).max()), p0, p1)
    assert max(jax.tree_util.tree_leaves(diff)) > 0  # params moved
    bdiff = jax.tree_util.tree_map(lambda a, b: float(np.abs(a - b).max()), bs0, bs1)
    assert max(jax.tree_util.tree_leaves(bdiff)) > 0  # BN stats moved


def test_epoch_runner_convergence_and_determinism(mesh, tiny_data):
    """Two runs from the same seed produce identical losses; loss decreases
    over epochs on learnable synthetic data (the convergence smoke test the
    reference never had, SURVEY.md §4)."""
    x, y = tiny_data
    runner = whole_epoch_runner(mesh, 64, len(x))

    def run(n_epochs):
        state = _fresh_state(mesh)
        key = jax.random.key(7)
        losses = []
        for e in range(n_epochs):
            state, stacked = runner(state, x, y, key, jnp.asarray(e))
            losses.append(np.asarray(stacked["loss"]))
        return np.concatenate(losses)

    l1 = run(3)
    l2 = run(3)
    np.testing.assert_array_equal(l1, l2)
    assert l1[-4:].mean() < l1[:4].mean()  # learning happened


@pytest.mark.slow
def test_epoch_runner_epochs_differ(mesh, tiny_data):
    x, y = tiny_data
    runner = whole_epoch_runner(mesh, 64, len(x))
    state = _fresh_state(mesh)
    key = jax.random.key(7)
    _, s0 = runner(_fresh_state(mesh), x, y, key, jnp.asarray(0))
    _, s1 = runner(_fresh_state(mesh), x, y, key, jnp.asarray(1))
    assert not np.array_equal(np.asarray(s0["loss"]), np.asarray(s1["loss"]))


def test_eval_step_weight_mask(mesh, tiny_data):
    """Padded examples must contribute nothing to loss/acc/count."""
    x, y = tiny_data
    state = _fresh_state(mesh)
    ev = make_eval_step(mesh)
    shard = batch_sharding(mesh)
    w_full = np.ones(64, np.float32)
    w_half = w_full.copy()
    w_half[32:] = 0.0
    xb, yb = jax.device_put(x[:64], shard), jax.device_put(y[:64], shard)
    m_half = ev(state, xb, yb, jax.device_put(jnp.asarray(w_half), shard))
    m_sub = ev(
        state,
        jax.device_put(jnp.concatenate([x[:32], x[:32]]), shard),
        jax.device_put(jnp.concatenate([y[:32], y[:32]]), shard),
        jax.device_put(jnp.asarray(w_half), shard),
    )
    assert float(m_half["count"]) == 32.0
    # masked half is ignored: metrics equal whatever occupies the padded slots
    np.testing.assert_allclose(
        float(m_half["loss_sum"]), float(m_sub["loss_sum"]), rtol=1e-5
    )


def test_eval_runner_matches_per_batch_eval(mesh, tiny_data):
    """The scanned whole-split eval must produce exactly the per-batch
    step's totals (same core, one dispatch instead of nb)."""
    x, y = tiny_data
    state = _fresh_state(mesh)
    bs = 64
    ev = make_eval_step(mesh)
    runner = make_eval_runner(mesh, bs)
    shard = batch_sharding(mesh)
    w = np.ones(len(x), np.float32)
    w[-16:] = 0.0  # padding mask in the last batch

    totals = {"loss_sum": 0.0, "top1_count": 0.0, "top5_count": 0.0, "count": 0.0}
    for b in range(len(x) // bs):
        sl = slice(b * bs, (b + 1) * bs)
        m = ev(
            state,
            jax.device_put(x[sl], shard),
            jax.device_put(y[sl], shard),
            jax.device_put(jnp.asarray(w[sl]), shard),
        )
        for k in totals:
            totals[k] += float(m[k])

    scanned = runner(state, x, y, jnp.asarray(w))
    for k in totals:
        np.testing.assert_allclose(float(scanned[k]), totals[k], rtol=1e-5)


def test_bf16_policy_keeps_fp32_state(mesh, tiny_data):
    x, y = tiny_data
    state = _fresh_state(mesh, dtype=jnp.bfloat16)
    for leaf in jax.tree_util.tree_leaves(state.params):
        assert leaf.dtype == jnp.float32
    step = make_train_step(mesh, precision="bf16")
    shard = batch_sharding(mesh)
    new_state, metrics = step(
        state,
        jax.device_put(x[:64], shard),
        jax.device_put(y[:64], shard),
        jax.random.key(1),
    )
    assert metrics["loss"].dtype == jnp.float32  # loss computed on fp32 logits
    for leaf in jax.tree_util.tree_leaves(new_state.params):
        assert leaf.dtype == jnp.float32


# ------------------------------------------------------------------ ckpt


def test_version_dir_scan(tmp_path):
    d0 = find_version_dir(tmp_path)
    assert d0.name == "version-0" and d0.exists()
    assert find_version_dir(tmp_path).name == "version-1"


def test_best_checkpoint_policy_and_roundtrip(tmp_path, mesh):
    state = _fresh_state(mesh)
    vdir = find_version_dir(tmp_path)
    save_checkpoint(vdir, state, epoch=0, val_acc=50.0)
    save_checkpoint(vdir, state, epoch=3, val_acc=62.5)
    files = list(vdir.glob("best_model_*.ckpt"))
    assert len(files) == 1  # old best deleted (reference policy)
    assert "epoch_3" in files[0].name and "62.5" in files[0].name
    assert find_best_checkpoint(vdir) == files[0]

    other = _fresh_state(mesh)  # same init => perturb before restore
    other = other.replace(
        params=jax.tree_util.tree_map(lambda a: a + 1.0, other.params)
    )
    restored = load_checkpoint(files[0], other)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        restored.params,
        state.params,
    )


def test_best_checkpoint_numeric_epoch_sort(tmp_path):
    """Crash-window scenario: two best files coexist; ``epoch_10`` must win
    over ``epoch_9`` (lexicographic order picks the stale one) and the stale
    file is cleaned up (VERDICT r2 weak #4)."""
    vdir = tmp_path / "version-0"
    vdir.mkdir()
    stale = vdir / "best_model_epoch_9_acc_60.0000.ckpt"
    fresh = vdir / "best_model_epoch_10_acc_61.0000.ckpt"
    stale.write_bytes(b"stale")
    fresh.write_bytes(b"fresh")
    assert sorted(vdir.glob("*.ckpt"))[-1] == stale  # the old bug's pick
    assert find_best_checkpoint(vdir) == fresh
    assert stale.exists()  # lookup never mutates by default (advisor r3)
    assert fresh.exists()

    # same-epoch tie breaks on accuracy
    a = vdir / "best_model_epoch_10_acc_59.0000.ckpt"
    a.write_bytes(b"a")
    assert find_best_checkpoint(vdir) == fresh
    # opt-in cleanup: unparseable stray names never beat a well-formed
    # file — and cleanup never deletes a file the naming scheme doesn't
    # account for (nor one whose acc field regex-matches but isn't a float)
    stray = vdir / "best_model_backup.ckpt"
    stray.write_bytes(b"s")
    bad_acc = vdir / "best_model_epoch_3_acc_1.2.3.ckpt"
    bad_acc.write_bytes(b"b")
    assert find_best_checkpoint(vdir, cleanup=True) == fresh
    assert stray.exists() and bad_acc.exists()
    assert not a.exists() and not stale.exists()  # parseable losers cleaned


def _write_ckpt(path, payload):
    from flax import serialization

    path.write_bytes(serialization.msgpack_serialize(payload))


def test_old_fmt_vit_checkpoint_raises_documented_error(tmp_path):
    """A format-1/2 packed-qkv ViT checkpoint must fail with the documented
    migration error, not a shape mismatch deep inside from_state_dict."""
    from distributed_training_comparison_tpu.train import load_eval_variables
    from distributed_training_comparison_tpu.train.checkpoint import CKPT_FMT

    old_vit = {
        # fmt key absent → format 1 (pre-versioning packed-qkv era)
        "params": {"blocks": {"qkv": {"kernel": np.zeros((4, 12), np.float32)}}},
        "batch_stats": {},
        "epoch": 3,
        "val_acc": 50.0,
    }
    path = tmp_path / "old_vit.ckpt"
    _write_ckpt(path, old_vit)
    vit_template = {
        "params": {"blocks": {"q_proj": {"kernel": np.zeros((4, 4), np.float32)}}},
        "batch_stats": {},
    }
    with pytest.raises(ValueError, match="format-1 ViT checkpoint"):
        load_eval_variables(path, vit_template)

    # an explicit format-2 (head-major packed) file names its own format
    old_vit["fmt"] = 2
    _write_ckpt(path, old_vit)
    with pytest.raises(ValueError, match=f"format-2.*current format {CKPT_FMT}"):
        load_eval_variables(path, vit_template)


def test_old_fmt_non_vit_checkpoint_still_loads(tmp_path):
    """The format gate is ViT-specific: a pre-versioning ResNet-style
    checkpoint (no packed qkv to migrate) must keep loading."""
    from distributed_training_comparison_tpu.train import load_eval_variables

    kernel = np.arange(4, dtype=np.float32).reshape(2, 2)
    payload = {
        "params": {"dense": {"kernel": kernel}},  # fmt absent → format 1
        "batch_stats": {},
        "epoch": 7,
        "val_acc": 61.0,
    }
    path = tmp_path / "old_resnet.ckpt"
    _write_ckpt(path, payload)
    template = {
        "params": {"dense": {"kernel": np.zeros((2, 2), np.float32)}},
        "batch_stats": {},
    }
    restored, info = load_eval_variables(path, template)
    np.testing.assert_array_equal(restored["params"]["dense"]["kernel"], kernel)
    assert info == {"epoch": 7, "acc": 61.0}


def test_fwd_bwd_hook_rejects_bn_models(mesh, tiny_data):
    """Wiring the 1F1B fwd_bwd hook with a BN model must fail loudly at the
    hook boundary (trace time), not silently freeze running statistics
    (advisor r3 / VERDICT r3 weak #5)."""
    x, y = tiny_data

    def fake_fwd_bwd(params, xb, yb):  # pragma: no cover - must not run
        raise AssertionError("fwd_bwd must not be invoked for BN models")

    step = make_train_step(mesh, fwd_bwd=fake_fwd_bwd)
    state = _fresh_state(mesh)  # TinyNet has BatchNorm → non-empty stats
    with pytest.raises(ValueError, match="BN-free"):
        step(state, x[:8], y[:8], jax.random.key(0))


def test_resume_roundtrip(tmp_path, mesh, tiny_data):
    x, y = tiny_data
    step = make_train_step(mesh)
    shard = batch_sharding(mesh)
    state = _fresh_state(mesh)
    for i in range(2):
        state, _ = step(
            state,
            jax.device_put(x[:64], shard),
            jax.device_put(y[:64], shard),
            jax.random.key(i),
        )
    vdir = find_version_dir(tmp_path)
    save_resume_state(vdir, state, epoch=5, best_acc=41.0)

    fresh = _fresh_state(mesh)
    restored, next_epoch, best = load_resume_state(vdir / "last.ckpt", fresh)
    assert next_epoch == 6 and best == 41.0
    assert int(restored.step) == 2
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        restored.opt_state,
        state.opt_state,
    )


class TinyNoBN(lnn.Module):
    """BN-free variant: grad-accum equivalence is exact only without
    batch-dependent normalization statistics."""

    num_classes: int = 10

    @lnn.compact
    def __call__(self, x, train: bool = False):
        x = lnn.Conv(8, (3, 3), strides=2, use_bias=False)(x)
        x = lnn.relu(x)
        x = jnp.mean(x, axis=(1, 2))
        return lnn.Dense(self.num_classes)(x)


def test_grad_accum_matches_single_step(mesh, tiny_data):
    """Mean of micro-batch grads == grad of the whole-batch mean loss, so
    with augmentation off and no BN the accumulated update must match the
    one-shot update to float tolerance."""
    x, y = tiny_data
    shard = batch_sharding(mesh)
    bx, by = jax.device_put(x[:64], shard), jax.device_put(y[:64], shard)
    states = {}
    for accum in (1, 4):
        tx, _ = configure_optimizers(HP, steps_per_epoch=4)
        state = create_train_state(TinyNoBN(), jax.random.key(0), tx)
        state = jax.device_put(state, replicated_sharding(mesh))
        step = make_train_step(mesh, augment=False, grad_accum=accum)
        new_state, metrics = step(state, bx, by, jax.random.key(1))
        states[accum] = (jax.device_get(new_state.params), float(metrics["loss"]))
    p1, l1 = states[1]
    p4, l4 = states[4]
    assert l1 == pytest.approx(l4, rel=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6), p1, p4
    )


def test_grad_accum_with_bn_trains(mesh, tiny_data):
    """BN path under accumulation: stats thread through the micro-scan and
    the step still updates params/stats/step."""
    x, y = tiny_data
    shard = batch_sharding(mesh)
    state = _fresh_state(mesh)
    step = make_train_step(mesh, grad_accum=2)
    new_state, metrics = step(
        state,
        jax.device_put(x[:64], shard),
        jax.device_put(y[:64], shard),
        jax.random.key(1),
    )
    assert int(new_state.step) == 1
    assert np.isfinite(float(metrics["loss"]))
    bdiff = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
        jax.device_get(state.batch_stats),
        jax.device_get(new_state.batch_stats),
    )
    assert max(jax.tree_util.tree_leaves(bdiff)) > 0


def test_grad_accum_keeps_data_parallel_sharding(mesh, tiny_data):
    """Micro-batches must stay sharded on the data axis: an unconstrained
    (b,)→(a, b/a) reshape makes GSPMD replicate each micro-batch to every
    device (each chip redundantly computing all of it).  With real data
    parallelism the compiled program must carry gradient all-reduces."""
    x, y = tiny_data
    shard = batch_sharding(mesh)
    state = _fresh_state(mesh)
    step = make_train_step(mesh, augment=False, grad_accum=2)
    bx, by = jax.device_put(x[:64], shard), jax.device_put(y[:64], shard)
    compiled = step.lower(state, bx, by, jax.random.key(1)).compile()
    assert "all-reduce" in compiled.as_text()
