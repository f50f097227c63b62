"""The jax-pin seam: the private or backend-dependent jax surfaces this
package leans on, written for the one installation there is
(``requirements.txt``: jax/jaxlib 0.9.0, libtpu 0.0.34).

Everything public and stable (``jax.shard_map``, ``jax.lax.axis_size``,
``pltpu.CompilerParams``) is imported from jax where it is used.  What
lives here is what a jax upgrade must re-check in one place:

- the persistent compile cache's write bar for donated executables
  (a private config ``State``);
- the compile-observability reads (``obs/compilation.py``,
  ``obs/resource.py``): executable cost/memory analysis, the private
  monitoring listener the persistent cache reports hits through, and
  device memory stats.  These differ by BACKEND on this jax — the CPU
  backend reports no device memory stats, a deserialized executable may
  refuse an analysis — so they return ``None`` for "no data" instead of
  raising into the train path.
"""

from __future__ import annotations

from contextlib import nullcontext

import jax
from jax._src import monitoring
from jax._src.config import (
    persistent_cache_min_compile_time_secs as _min_compile_secs,
)


def donated_cache_write_barred(platform: str):
    """Context under which freshly-compiled executables are NEVER written to
    the persistent on-disk cache (the min-compile-time write threshold is
    raised past any real compile; the threshold is read at write time, so a
    thread-scoped override works — unlike ``enable_compilation_cache``,
    whose read path latches globally on first use).

    Exists because buffer-DONATED executables round-tripped through the
    on-disk cache misbehave on this jax's CPU backend: a warm-cache process
    re-running the donated scanned runners segfaults or silently corrupts
    the carried train state (reproduced while developing
    tests/test_overlap.py; cold-cache and cache-off runs are correct, as
    are non-donated programs).  ``platform`` is the platform the executable
    is compiled FOR (its mesh's devices, not the default backend).  The
    fault was never seen on ``"tpu"`` — there the donated train programs
    are cached like any other (``chip_smoke.py`` run twice checks it: warm
    cache hits, bit-identical losses) — so the bar stays up everywhere
    else: the donated runners' executables exist only in process memory,
    and no process can ever deserialize one.
    """
    if platform == "tpu":
        return nullcontext()
    return _min_compile_secs(1e18)


# ---------------------------------------------------------------- compiler
#
# The compile-observability hook (obs/compilation.py) reads the AOT
# executable's cost/memory analyses and the internal monitoring stream the
# persistent compile cache reports hits on.  The analyses return None for
# "this executable reports nothing" — compile telemetry must never be the
# reason a run fails to train.


def executable_cost_analysis(compiled) -> dict | None:
    """``Compiled.cost_analysis()`` (one flat dict on this jax); ``None``
    when the executable raises or reports nothing."""
    try:
        out = compiled.cost_analysis()
    except Exception:
        return None
    return out or None


def executable_memory_analysis(compiled) -> dict | None:
    """``Compiled.memory_analysis()`` flattened to the byte counts the HBM
    ledger wants (``{argument,output,temp,generated_code,alias}_bytes``);
    ``None`` when the executable raises or reports nothing."""
    try:
        stats = compiled.memory_analysis()
    except Exception:
        return None
    if stats is None:
        return None
    return {
        "argument_bytes": stats.argument_size_in_bytes,
        "output_bytes": stats.output_size_in_bytes,
        "temp_bytes": stats.temp_size_in_bytes,
        "alias_bytes": stats.alias_size_in_bytes,
        "generated_code_bytes": stats.generated_code_size_in_bytes,
    }


def register_monitoring_listener(callback) -> None:
    """Attach ``callback(event, **metadata)`` to jax's internal monitoring
    stream (the persistent compile cache announces hits there as
    ``/jax/compilation_cache/cache_hits``).  Private API."""
    monitoring.register_event_listener(callback)


def compilation_cache_dir() -> str | None:
    """The configured persistent compile-cache directory, or None when
    caching is off (then a compile can be neither a hit nor a miss)."""
    return jax.config.jax_compilation_cache_dir or None


def device_memory_stats(device) -> dict | None:
    """``device.memory_stats()``: a dict with at least ``bytes_in_use`` on
    allocator-backed devices (TPU), ``None`` on the CPU backend.  Callers
    treat None as "no HBM gauge here", never as an error."""
    if device is None:
        return None
    return device.memory_stats() or None


__all__ = [
    "donated_cache_write_barred", "device_memory_stats",
    "executable_cost_analysis", "executable_memory_analysis",
    "register_monitoring_listener", "compilation_cache_dir",
]
