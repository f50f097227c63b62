"""Resource telemetry: device HBM, host RSS, fds, checkpoint-root disk.

The failure modes that kill long runs slowly — an HBM footprint creeping
toward the cap, a host process leaking memory or file descriptors, a
checkpoint volume filling up — are invisible to the work telemetry until
the step that finally dies.  ``ResourceSampler`` reads the gauges at
metric-flush boundaries, self-rate-limited to one read per
``min_interval_s`` (a ``/proc`` + ``statvfs`` pass costs ~1 ms — cheap at
a 10 s cadence, most of the 25 µs/step obs budget if done every
50-step flush), and records them into the registry, where they ride the
same ``metrics`` events, the exporter, and the alert engine as everything
else (registry gauges are not reset by a flush, so every flush event
carries the latest sampled values regardless of the cadence)::

    res/hbm_used_bytes · res/hbm_limit_bytes   (device.memory_stats()
        through _compat — absent on backends that report none,
        e.g. the CPU CI backend)
    res/host_rss_bytes                          (/proc/self/statm)
    res/open_fds                                (/proc/self/fd)
    res/disk_free_bytes                         (statvfs of the ckpt root)
    res/live_arrays · res/live_array_bytes      (jax.live_arrays() census
        — with the per-executable analysis totals
        of the compile ledger this answers "where did HBM go": arrays the
        program still holds vs what the executables themselves reserve)

Every read is wrapped: a missing /proc, an unreadable mount, or a backend
without memory stats silently drops that gauge — resource telemetry must
never kill (or slow) training.
"""

from __future__ import annotations

import os
import shutil
import time
from pathlib import Path

from .._compat import device_memory_stats

_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def host_rss_bytes() -> int | None:
    """Current resident set size (linux /proc; None elsewhere)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * _PAGE_SIZE
    except (OSError, ValueError, IndexError):
        return None


def open_fd_count() -> int | None:
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return None


def disk_free_bytes(path: str | Path) -> int | None:
    try:
        return shutil.disk_usage(str(path)).free
    except OSError:
        return None


def live_array_census() -> tuple[int, int]:
    """``(count, total_bytes)`` over ``jax.live_arrays()`` — the array
    side of the HBM ledger.  Donated buffers linger in the list as
    deleted arrays whose attribute reads raise; they hold no memory and
    are skipped, not counted."""
    import jax

    count = 0
    total = 0
    for a in jax.live_arrays():
        try:
            nbytes = a.nbytes
        except Exception:  # deleted (donated) array — owns nothing
            continue
        count += 1
        total += int(nbytes)
    return count, total


class ResourceSampler:
    """Read the gauges above into a metric registry.

    ``device=None`` picks the first local jax device lazily at the first
    sample (so constructing a sampler never imports or touches jax's
    backend); ``ckpt_root=None`` skips the disk gauge.
    """

    def __init__(
        self, ckpt_root: str | Path | None = None, device=None,
        min_interval_s: float = 10.0,
    ) -> None:
        self.ckpt_root = ckpt_root
        self.min_interval_s = float(min_interval_s)
        self._device = device
        self._device_resolved = device is not None
        self._last_sample = -float("inf")
        self.samples = 0

    def _resolve_device(self):
        if not self._device_resolved:
            self._device_resolved = True
            try:
                import jax

                self._device = jax.local_devices()[0]
            except Exception:
                self._device = None
        return self._device

    def read(self) -> dict[str, float]:
        """One pass over every available gauge, name → value."""
        out: dict[str, float] = {}
        rss = host_rss_bytes()
        if rss is not None:
            out["res/host_rss_bytes"] = float(rss)
        fds = open_fd_count()
        if fds is not None:
            out["res/open_fds"] = float(fds)
        if self.ckpt_root is not None:
            free = disk_free_bytes(self.ckpt_root)
            if free is not None:
                out["res/disk_free_bytes"] = float(free)
        count, nbytes = live_array_census()
        out["res/live_arrays"] = float(count)
        out["res/live_array_bytes"] = float(nbytes)
        stats = device_memory_stats(self._resolve_device())
        if stats:
            used = stats.get("bytes_in_use")
            if used is not None:
                out["res/hbm_used_bytes"] = float(used)
            limit = stats.get("bytes_limit")
            if limit is not None:
                out["res/hbm_limit_bytes"] = float(limit)
        return out

    def sample(self, registry) -> dict[str, float]:
        """Record every available gauge into ``registry``; returns what
        was read (empty when the rate limit skipped the read — the
        registry still holds the previous sample's gauges).  Call at
        flush boundaries; the values ride the flush's ``metrics``
        event."""
        now = time.monotonic()
        if now - self._last_sample < self.min_interval_s:
            return {}
        self._last_sample = now
        values = self.read()
        for name, value in values.items():
            registry.gauge(name).set(value)
        self.samples += 1
        return values
