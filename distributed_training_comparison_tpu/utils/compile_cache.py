"""Persistent XLA-executable caches.

The reference has nothing comparable (PyTorch eager needs no compilation);
under XLA every (program, shape) pair compiles once per process, and the
whole-epoch scanned programs are the costliest part of a cold start.

Two layers live here:

- :func:`enable_persistent_compilation_cache` — jax's own on-disk HLO
  cache, enabled by every entry point (CLI ``entry.run``,
  ``benchmark/run.py``, ``chip_smoke.py``, the test workers).  ``JAX_COMPILATION_CACHE_DIR``
  places it from outside; unset, it lives at one fixed path inside the
  checkout (``.jax_cache/``, git-ignored).  The path is part of the
  cache's key, so it is never a temporary or per-process name.  It caches
  *compilations* — a fresh process still pays lowering plus the cache
  lookup per executable.
- :class:`PersistedServeCache` — whole-**executable** persistence for
  the serving fast path: the serve engine's AOT-compiled bucket
  programs, serialized via ``jax.experimental.serialize_executable``
  and keyed on the CompileMonitor's stable cross-process fingerprint
  (``obs/compilation.py``), so a cold replica deserializes its warmed
  ladder in milliseconds instead of recompiling it — first-response in
  seconds even when the jax cache is cold.

Safety bar: the jax-pin bug behind ``_compat.donated_cache_write_barred``
— buffer-DONATED executables round-tripped through a persistent cache
segfault or silently corrupt their carries on this jax's CPU backend —
applies to ANY deserialized donated program, so :meth:`store` refuses
donated executables outright.  Serve executables donate nothing (the
fp32 logits could never alias the uint8 request batch, so donation was
always unusable there; the engine dropped it), which is asserted at the
store site rather than assumed.
"""

from __future__ import annotations

import os
import pickle
import time
from pathlib import Path

# <checkout>/.jax_cache, resolved from this file's own location
_CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_persistent_compilation_cache() -> None:
    """Idempotently turn on JAX's on-disk executable cache:
    ``JAX_COMPILATION_CACHE_DIR`` where the environment sets it (and then
    no other directory is created or configured), else the fixed
    in-checkout ``.jax_cache/``.  Safe to call before or after device
    initialization.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        _CHECKOUT_CACHE
    )
    Path(cache_dir).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # default threshold (1 s) skips small programs; the dispatch-heavy ones
    # here (eval runners, chunk runners at several sizes) are all worth it.
    # An explicit JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS wins, like the
    # cache-dir env var above.
    if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


# ------------------------------------------------- persisted serve AOT


class DonatedExecutableError(ValueError):
    """Refused: a donated executable must never be persisted (the
    ``_compat.donated_cache_write_barred`` jax-pin bug — deserialized
    donated programs segfault/corrupt their carries)."""


class PersistedServeCache:
    """On-disk store of serialized serve executables, keyed by the
    CompileMonitor's cross-process fingerprint.

    ``load`` returns a ready-to-dispatch ``Compiled`` (or None on any
    miss/decode/device mismatch — the caller falls back to compiling);
    ``store`` refuses donated executables (see module docstring) and
    writes rename-atomically so a concurrent replica never reads a torn
    blob.  Every failure degrades to "no cache": warm-start is a perf
    lever, never a correctness dependency.
    """

    SUFFIX = ".aotexe"

    def __init__(self, directory: str | os.PathLike) -> None:
        self.dir = Path(directory)
        self.loads = 0
        self.stores = 0
        self.errors = 0
        self.rejected = 0  # blobs that failed the store-time round-trip
        try:
            self.dir.mkdir(parents=True, exist_ok=True)
            self._usable = True
        except OSError:
            self._usable = False

    def path_for(self, fingerprint: str) -> Path:
        return self.dir / f"{fingerprint}{self.SUFFIX}"

    def load(self, fingerprint: str):
        """Deserialize the executable stored under ``fingerprint``, or
        None.  Returns ``(compiled, load_seconds)``."""
        if not self._usable:
            return None, 0.0
        path = self.path_for(fingerprint)
        t0 = time.perf_counter()
        try:
            blob = path.read_bytes()
        except OSError:
            return None, 0.0
        try:
            from jax.experimental.serialize_executable import (
                deserialize_and_load,
            )

            payload, in_tree, out_tree = pickle.loads(blob)
            compiled = deserialize_and_load(payload, in_tree, out_tree)
        except Exception:
            # torn blob, jax/topology mismatch, moved API — all degrade
            # to a recompile; a poisoned entry must not wedge cold starts
            self.errors += 1
            try:
                path.unlink(missing_ok=True)
            except OSError:
                pass
            return None, 0.0
        self.loads += 1
        return compiled, time.perf_counter() - t0

    def store(
        self, fingerprint: str, compiled, donated=(), verify: bool = True
    ) -> Path | None:
        """Serialize ``compiled`` under ``fingerprint``.  ``donated`` is
        the executable's donated-argument set — non-empty REFUSES with
        :class:`DonatedExecutableError` (never silently skips: a serve
        engine that starts donating again must fail its tests, not
        quietly lose warm-start).

        ``verify`` round-trips the blob through ``deserialize_and_load``
        before committing it: on the pinned jaxlib's CPU backend an
        executable that was itself materialized from jax's persistent
        HLO cache (compile outcome ``"hit"``) serializes into a blob
        whose jitted fusion symbols are missing — deserialization in the
        next process dies with ``Symbols not found``.  Only genuinely
        compiled executables round-trip; storing an unverified blob
        would hand every cold replica a poisoned entry (each one paying
        a failed load + unlink + recompile instead of a warm start), so
        a blob that cannot round-trip is counted ``rejected`` and never
        written."""
        if donated:
            raise DonatedExecutableError(
                f"executable {fingerprint} donates arguments {tuple(donated)}"
                ": donated executables deserialized from a persistent cache"
                " corrupt their carries on the pinned jax "
                "(_compat.donated_cache_write_barred) — serve programs "
                "must donate nothing to be persisted"
            )
        if not self._usable:
            return None
        try:
            from jax.experimental.serialize_executable import serialize

            payload, in_tree, out_tree = serialize(compiled)
            blob = pickle.dumps((payload, in_tree, out_tree))
        except Exception:
            self.errors += 1
            return None
        if verify:
            try:
                from jax.experimental.serialize_executable import (
                    deserialize_and_load,
                )

                deserialize_and_load(payload, in_tree, out_tree)
            except Exception:
                self.rejected += 1
                return None
        path = self.path_for(fingerprint)
        tmp = path.with_suffix(self.SUFFIX + ".tmp")
        try:
            tmp.write_bytes(blob)
            tmp.replace(path)
        except OSError:
            self.errors += 1
            try:
                tmp.unlink(missing_ok=True)
            except OSError:
                pass
            return None
        self.stores += 1
        return path

    def stats(self) -> dict:
        return {
            "dir": str(self.dir),
            "loads": self.loads,
            "stores": self.stores,
            "errors": self.errors,
            "rejected": self.rejected,
            "entries": (
                len(list(self.dir.glob(f"*{self.SUFFIX}")))
                if self._usable else 0
            ),
        }
