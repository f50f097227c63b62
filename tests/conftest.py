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

import pytest  # noqa: E402

from distributed_training_comparison_tpu.utils import (  # noqa: E402
    enable_persistent_compilation_cache,
)

# Persistent executable cache: the fast gate (`pytest -m "not slow"`) is
# dominated by CPU compiles of the zoo models; with the cache warm a repeat
# run skips nearly all of them.
enable_persistent_compilation_cache()


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
