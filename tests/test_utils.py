"""Unit tests for utils: seeding, meters, metrics.

Covers the semantics of the reference's ``src/single/utils.py`` symbols
(fix_seed / AverageMeter / accuracy) under the JAX rebuild.
"""

import contextlib
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from distributed_training_comparison_tpu.utils import (
    AverageMeter,
    accuracy,
    fix_seed,
    topk_correct,
)


class TestFixSeed:
    def test_returns_prng_key(self):
        key = fix_seed(42)
        # keys are typed scalars in new-style jax.random
        assert jax.random.bits(key, (2,)).shape == (2,)

    def test_deterministic(self):
        k1, k2 = fix_seed(42), fix_seed(42)
        assert jnp.array_equal(jax.random.bits(k1, (4,)), jax.random.bits(k2, (4,)))

    def test_seeds_numpy(self):
        fix_seed(7)
        a = np.random.rand(3)
        fix_seed(7)
        b = np.random.rand(3)
        assert np.array_equal(a, b)

    def test_different_seeds_differ(self):
        a = jax.random.bits(fix_seed(1), (8,))
        b = jax.random.bits(fix_seed(2), (8,))
        assert not jnp.array_equal(a, b)


class TestAverageMeter:
    def test_weighted_average(self):
        m = AverageMeter()
        m.update(1.0, n=2)
        m.update(4.0, n=1)
        assert m.val == 4.0
        assert m.sum == 6.0
        assert m.count == 3
        assert abs(m.avg - 2.0) < 1e-9

    def test_reset(self):
        m = AverageMeter()
        m.update(5.0)
        m.reset()
        assert m.val == 0.0 and m.sum == 0.0 and m.count == 0 and m.avg == 0.0


class TestAccuracy:
    def test_top1_perfect(self):
        logits = jnp.eye(4) * 10.0
        labels = jnp.arange(4)
        (top1,) = accuracy(logits, labels, topk=(1,))
        assert float(top1) == 100.0

    def test_top1_top5_known(self):
        # one sample: true class is rank 3 in the logits -> top1 miss, top5 hit
        logits = jnp.array([[5.0, 4.0, 3.0, 2.0, 1.0, 0.0]])
        labels = jnp.array([2])
        top1, top5 = accuracy(logits, labels, topk=(1, 5))
        assert float(top1) == 0.0
        assert float(top5) == 100.0

    def test_matches_numpy_reference(self):
        rng = np.random.default_rng(0)
        logits = rng.normal(size=(64, 100)).astype(np.float32)
        labels = rng.integers(0, 100, size=(64,))
        for k in (1, 5):
            order = np.argsort(-logits, axis=1)[:, :k]
            expected = float(np.mean([l in o for l, o in zip(labels, order)]) * 100)
            (got,) = accuracy(jnp.asarray(logits), jnp.asarray(labels), topk=(k,))
            assert abs(float(got) - expected) < 1e-4

    def test_topk_correct_is_jittable(self):
        f = jax.jit(lambda lg, lb: topk_correct(lg, lb, 5))
        logits = jnp.ones((8, 10))
        labels = jnp.zeros((8,), dtype=jnp.int32)
        assert f(logits, labels).shape == ()


def test_collective_census_parser():
    """The HLO census must count collectives once (-start/-done pairs are
    one op) and size payloads from result shapes, tuples included."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent.parent))
    from tools.collective_census import census_from_hlo

    hlo = """
  %all-reduce.1 = f32[12,192]{1,0} all-reduce(f32[12,192]{1,0} %p), replica_groups={{0,1,2,3},{4,5,6,7}}
  %ag = bf16[4,64,128]{2,1,0} all-gather(bf16[4,32,128]{2,1,0} %x), replica_groups=[4,2]<=[2,4]T(1,0), dimensions={1}
  %cp-start = (bf16[2,8]{1,0}, bf16[2,8]{1,0}) collective-permute-start(bf16[2,8]{1,0} %y), source_target_pairs={{0,1},{3,4}}
  %cp-done = bf16[2,8]{1,0} collective-permute-done(%cp-start)
  %add.5 = f32[2]{0} add(f32[2]{0} %a, f32[2]{0} %b)
"""
    c = census_from_hlo(hlo)  # host_size=4: devices 0-3 host A, 4-7 host B
    # explicit groups confined to one host each → no DCN share
    assert c["all-reduce"] == (1, 12 * 192 * 4, 0)
    # transposed-iota groups {0,4},{1,5},... all span hosts → full payload
    ag_bytes = 4 * 64 * 128 * 2
    assert c["all-gather"] == (1, ag_bytes, ag_bytes)
    # -start counted once; tuple result = 2 * (2*8) bf16; one of the two
    # point-to-point pairs (3→4) crosses hosts → half the payload
    cp_bytes = 2 * 2 * 8 * 2
    assert c["collective-permute"] == (1, cp_bytes, cp_bytes // 2)
    assert "add" not in c and len(c) == 3


# ------------------------------------------------- compile-cache placement


@contextlib.contextmanager
def _restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_dir_from_the_environment_and_no_other(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR places the cache from outside: that
    directory is configured, and the in-checkout default is neither
    configured nor created."""
    from distributed_training_comparison_tpu.utils import compile_cache

    checkout = tmp_path / "checkout" / ".jax_cache"
    monkeypatch.setattr(compile_cache, "_CHECKOUT_CACHE", checkout)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    with _restore_cache_dir():
        compile_cache.enable_persistent_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "outside")
    assert (tmp_path / "outside").is_dir()
    assert not checkout.parent.exists()


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch, tmp_path):
    """Unset, the cache lives at <checkout>/.jax_cache — the same path for
    every call and every process, whatever its working directory (the path
    is part of the cache's key: a directory that moves never hits)."""
    from distributed_training_comparison_tpu.utils import compile_cache

    repo = Path(__file__).resolve().parent.parent
    want = str(repo / ".jax_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with _restore_cache_dir():
        for _ in range(2):
            compile_cache.enable_persistent_compilation_cache()
            assert jax.config.jax_compilation_cache_dir == want
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()

    script = (
        "import jax\n"
        "from distributed_training_comparison_tpu.utils import "
        "enable_persistent_compilation_cache as on\n"
        "on(); print(jax.config.jax_compilation_cache_dir)\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = f"{repo}{os.pathsep}" + env.get("PYTHONPATH", "")
    seen = {
        subprocess.run(
            [sys.executable, "-c", script], cwd=cwd, env=env,
            capture_output=True, text=True, check=True,
        ).stdout.strip()
        for cwd in (str(tmp_path), str(repo))
    }
    assert seen == {want}
