"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference never tests its distributed path (SURVEY.md §4 — no tests at
all).  Here every SPMD code path runs in CI on 8 virtual CPU devices via
``--xla_force_host_platform_device_count``, the JAX-native analogue of
"test multi-GPU without GPUs".

Both settings must land before jax instantiates its CPU client, hence the
module-scope environ writes: ``JAX_PLATFORMS=cpu`` (the tier-1 command sets
it too; a bare ``pytest tests/`` on a machine with a chip must not take the
chip) and the forced host device count.  Subprocess workers inherit both.
The chip is reached through ``chip_smoke.py`` and ``tests_tpu/``, never
from here.
"""

import os
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import pytest  # noqa: E402

from distributed_training_comparison_tpu.utils import (  # noqa: E402
    enable_persistent_compilation_cache,
)

# Persistent executable cache: the fast gate (`pytest -m "not slow"`) is
# dominated by CPU compiles of the zoo models; with the cache warm a repeat
# run skips nearly all of them.
enable_persistent_compilation_cache()


def whole_epoch_runner(mesh, batch_size, n, **kw):
    """A device-resident epoch of ``n`` examples as ONE dispatch of the
    runner the product calls: ``make_device_chunk_runner`` with ``K =
    steps``, called with ``start = 0``.  Signature of the result:
    ``(state, images, labels, key, epoch[, fault])``."""
    from distributed_training_comparison_tpu.train import (
        make_device_chunk_runner,
    )

    runner = make_device_chunk_runner(
        mesh, batch_size, chunk_steps=n // batch_size, **kw
    )

    def run(state, images, labels, key, epoch, *fault):
        return runner(state, images, labels, key, epoch, jnp.asarray(0), *fault)

    return run


def assert_trees_within_ulp(actual, desired, ulp):
    """Every leaf pair within ``ulp`` of ``parity/diff.py``'s scale-aware
    ulp distance (max |a - b| in float32 ulps at the leaf's largest
    magnitude): the metric built for comparing one trajectory across
    device counts, where the reduction order differs and elementwise
    ``allclose`` mistakes noise-floor elements for divergence."""
    from distributed_training_comparison_tpu.parity.diff import ulp_distance

    flat_a = jax.tree_util.tree_leaves_with_path(actual)
    flat_d = jax.tree_util.tree_leaves(desired)
    assert len(flat_a) == len(flat_d)
    dist = {
        jax.tree_util.keystr(path): ulp_distance(a, d)
        for (path, a), d in zip(flat_a, flat_d)
    }
    worst = [k for k, v in dist.items() if v is None or not v <= ulp]
    assert not worst, {k: dist[k] for k in worst}


@pytest.fixture(scope="session", autouse=True)
def _assert_virtual_mesh():
    devs = jax.devices()
    assert len(devs) == 8 and devs[0].platform == "cpu", (
        f"test suite must run on 8 virtual CPU devices, got {devs}"
    )


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture
def forced_device_env():
    """Factory for subprocess environments with a FORCED virtual CPU device
    count (``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

    The elastic-restore tests need children running under *different*
    device counts than this process's 8-device mesh.  The flag must land
    before jax instantiates its CPU client, so it can only apply to fresh
    subprocesses — and it is passed via a per-child env COPY, never by
    mutating ``os.environ``, so nothing leaks into other tests (or into
    this process, whose backend is already up).
    """
    from distributed_training_comparison_tpu.resilience.elastic import (
        forced_host_device_env,
    )

    repo = Path(__file__).parent.parent

    def make(n: int) -> dict[str, str]:
        env = forced_host_device_env(n)
        env["PYTHONPATH"] = f"{repo}{os.pathsep}" + env.get("PYTHONPATH", "")
        return env

    return make
