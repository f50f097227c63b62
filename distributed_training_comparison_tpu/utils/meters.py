"""Host-side scalar accumulators.

Parity: reference ``src/single/utils.py:33-47`` (AverageMeter with
val/sum/count/avg and an n-weighted ``update``).  Used by the Trainer for
epoch-level aggregation of per-step metrics that were computed on device and
fetched in bulk (never one ``.item()`` per step — that device sync each step
is a reference bottleneck we do not replicate, see
``src/single/trainer.py:147``).
"""

from __future__ import annotations

import time
from contextlib import contextmanager, nullcontext


class AverageMeter:
    """Tracks the latest value and a running (weighted) average."""

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self.val = 0.0
        self.sum = 0.0
        self.count = 0
        self.avg = 0.0

    def update(self, val: float, n: int = 1) -> None:
        val = float(val)
        self.val = val
        self.sum += val * n
        self.count += n
        self.avg = self.sum / self.count if self.count else 0.0


class StepTimeMeter:
    """Wall-clock breakdown of the chunked train loop's MAIN thread.

    Three phases, chosen to expose what overlapped execution hides and what
    it cannot:

    - ``h2d_wait``  — blocked on the staged-chunk queue (``DevicePrefetcher``
      pop): >0 means batch assembly + H2D transfer are NOT fully hidden
      behind compute and the chip will idle for that long;
    - ``dispatch``  — building + enqueueing the chunk program (async, so
      this is host-side launch latency, not device compute);
    - ``compute``   — blocked on device results (the bulk metrics fetch at
      the epoch boundary, where all remaining device work drains).

    Everything outside the three phases (preemption polls, tqdm, python loop
    glue) is the residual against the epoch wall-clock the caller tracks.
    An epoch whose time is dominated by ``compute`` is overlap working as
    designed; time migrating into ``h2d_wait`` means the input pipeline is
    the bottleneck (raise ``--workers`` / prefetch depth or shrink the
    host-side batch work).
    """

    PHASES = ("h2d_wait", "dispatch", "compute")

    def __init__(self, tracer=None, metrics=None) -> None:
        # optional span recorder (obs/spans.py): when set, every phase()
        # interval is ALSO recorded as a host span, so the Chrome-trace
        # export shows the same h2d_wait/dispatch/compute breakdown the
        # scalar totals summarize.  Optional metric registry
        # (obs/metrics.py): every phase interval additionally lands in a
        # per-phase histogram sketch, so the periodic `metrics` flush
        # events carry the step-phase DISTRIBUTION (p50/p95/p99), not just
        # the epoch totals — a straggler chunk is visible even when the
        # totals look healthy.
        self.tracer = tracer
        self.metrics = metrics
        self.reset()

    def reset(self) -> None:
        self.seconds = {p: 0.0 for p in self.PHASES}
        self.chunks = 0
        # whether the most recent accounted sample carried a compile —
        # read by derived per-dispatch accounting (the trainer's pipeline
        # per-stage sketches) that must mirror the compile-taint split
        self.last_compiled = False

    def add(self, phase: str, secs: float, compiled: bool = False) -> None:
        """Account one phase interval.  ``compiled=True`` marks a sample
        whose span contained a jit compile: it still counts into the
        epoch totals (the wall clock really passed), but lands in a
        separate ``step/{phase}_compile_s`` sketch so the cross-host
        straggler scoring — which reads ``step/{phase}_s`` only — never
        judges a host by its compiles.  Without the exclusion a
        warm-resumed host (persistent cache serves its first dispatch)
        reads as faster than peers that genuinely compiled."""
        secs = max(0.0, float(secs))
        self.seconds[phase] += secs
        self.last_compiled = bool(compiled)
        if self.metrics is not None:
            suffix = "_compile_s" if compiled else "_s"
            self.metrics.histogram(f"step/{phase}{suffix}").record(secs)

    @contextmanager
    def phase(self, name: str, taint=None, **attrs):
        # attrs ride into the span's args — the trainer stamps the chunk's
        # global step onto `dispatch`.
        # ``taint`` — optional zero-arg read-and-clear callable (the
        # compile monitor's take_taint): consulted once on ENTRY to drop
        # any stale flag (an eval/snapshot compile between phases must
        # not taint the next dispatch) and once when the span closes —
        # True then means a compile happened INSIDE this span, and the
        # sample reroutes to the compile-bearing sketch (see ``add``).
        ctx = (
            self.tracer.span(name, **attrs)
            if self.tracer is not None
            else nullcontext()
        )
        if taint:
            taint()
        t0 = time.perf_counter()
        try:
            with ctx:
                yield
        finally:
            dt = time.perf_counter() - t0
            self.add(name, dt, compiled=bool(taint()) if taint else False)

    def note_chunk(self) -> None:
        self.chunks += 1

    def merge(self, other: "StepTimeMeter") -> None:
        """Fold another meter's totals in (per-epoch → per-run aggregation)."""
        for p in self.PHASES:
            self.seconds[p] += other.seconds[p]
        self.chunks += other.chunks

    def summary(self) -> dict:
        out = {f"{p}_s": round(self.seconds[p], 4) for p in self.PHASES}
        out["chunks"] = self.chunks
        return out
