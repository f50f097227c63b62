"""The watchdog policy layer: per-epoch verdicts, counters, HEALTH records.

Detection is layered by cost of the response:

- a **skipped step** (non-finite loss/grads, caught by the compiled guard)
  costs nothing beyond the lost update — the guard already kept the state
  clean, so isolated skips are absorbed and only counted;
- **K consecutive bad steps** (skips or spikes) mean the run is *stuck* bad
  — a clean state exists only behind us, so the Trainer rolls back to the
  last verified checkpoint and replays;
- **any desync** rolls back immediately: there is no "mildly" diverged
  replica set, and every step trained past it is wasted.

Rollbacks are budgeted (``max_rollbacks``): a fault that deterministically
re-fires on replay (diverged hyperparameters, a persistently corrupt shard)
must abort loudly, not loop.  Every event is appended to the run dir's
``health.jsonl`` and aggregated into the summary that ``--health-json``'s
report and the goodput records carry.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from .spike import SpikeDetector

EVENTS_NAME = "health.jsonl"


@dataclass
class HealthConfig:
    """Watchdog thresholds; one source of truth for flags and defaults."""

    window: int = 64          # spike-detector rolling window (good steps)
    spike_mads: float = 8.0   # MADs above rolling median that flag a spike
    bad_steps: int = 3        # K consecutive bad steps trigger rollback
    max_rollbacks: int = 3    # rollback budget per attempt; then abort
    desync_every: int = 1     # fingerprint check every N epochs (0 = off)
    min_baseline: int = 16    # good steps required before spikes can flag
    phase_baselines: bool = True  # one baseline per LR phase, not global
    quarantine: bool = False  # rollback replay skips the bad batch indices

    @classmethod
    def from_hparams(cls, hparams) -> "HealthConfig":
        return cls(
            window=getattr(hparams, "health_window", 64),
            spike_mads=getattr(hparams, "health_spike_mads", 8.0),
            bad_steps=getattr(hparams, "health_bad_steps", 3),
            max_rollbacks=getattr(hparams, "health_max_rollbacks", 3),
            desync_every=getattr(hparams, "health_desync_every", 1),
            phase_baselines=getattr(hparams, "health_phase_baselines", True),
            quarantine=getattr(hparams, "health_quarantine", False),
        )


@dataclass
class EpochVerdict:
    """One epoch's health assessment (pre-checkpoint, pre-validation)."""

    rollback: bool
    reason: str | None
    skipped: int        # non-finite steps the compiled guard rejected
    spikes: int         # finite steps flagged by the median/MAD detector
    max_bad_run: int    # longest consecutive run of bad steps
    nonfinite: bool     # any non-finite loss this epoch
    # within-epoch indices of every bad step (skip|spike) — the window the
    # corrupt-shard quarantine hands to the loader on rollback
    bad_steps: list = field(default_factory=list)


def _max_run(flags: np.ndarray) -> int:
    run = best = 0
    for f in flags:
        run = run + 1 if f else 0
        best = max(best, run)
    return best


class Watchdog:
    """Accumulates health events for one training attempt."""

    def __init__(
        self, config: HealthConfig | None = None, logger=None, bus=None
    ) -> None:
        self.cfg = config or HealthConfig()
        self.logger = logger
        # run-event bus (obs/): when set, every health event ALSO emits on
        # the unified timeline, and the health.jsonl records carry the
        # bus's run_id/attempt/process_index/t_wall stamp — back-compatibly
        # (old records stay parseable; tools accept both shapes)
        self.bus = bus
        self.detector = SpikeDetector(
            window=self.cfg.window,
            threshold_mads=self.cfg.spike_mads,
            min_baseline=self.cfg.min_baseline,
        )
        # per-phase baselines: losses shift with the LR schedule (a decay
        # drops the whole distribution), so spike thresholds are kept per
        # schedule phase — the default detector above serves phase=None
        # (callers without a schedule, and cfg.phase_baselines=False)
        self._phase_detectors: dict[str, SpikeDetector] = {}
        self.skipped_steps = 0
        self.spike_steps = 0
        self.rollbacks = 0
        self.desyncs = 0
        self.rollback_wasted_steps = 0
        self.rollback_wasted_s = 0.0
        self.quarantined_examples = 0
        self.events: list[dict] = []
        self._unflushed = 0

    # ------------------------------------------------------------ detection

    def _detector_for(self, phase: str | None) -> SpikeDetector:
        """The spike detector judging ``phase`` (an opaque label the caller
        derives from the LR schedule — e.g. ``"lr=0.1"``).  Each phase gets
        its own median/MAD window so a post-decay epoch is never judged
        against pre-decay losses; ``None`` keeps the single global window."""
        if phase is None or not self.cfg.phase_baselines:
            return self.detector
        det = self._phase_detectors.get(phase)
        if det is None:
            det = self._phase_detectors[phase] = SpikeDetector(
                window=self.cfg.window,
                threshold_mads=self.cfg.spike_mads,
                min_baseline=self.cfg.min_baseline,
            )
        return det

    def observe_epoch(
        self,
        epoch: int,
        losses: np.ndarray,
        skipped: np.ndarray,
        phase: str | None = None,
    ) -> EpochVerdict:
        """Judge one epoch's per-step loss/skip series (device arrays already
        fetched by the trainer's per-epoch metrics read)."""
        losses = np.asarray(losses)
        skip_flags = np.asarray(skipped) > 0.5
        spike_flags = self._detector_for(phase).observe(losses, skip_flags)
        bad = skip_flags | spike_flags
        n_skip, n_spike = int(skip_flags.sum()), int(spike_flags.sum())
        self.skipped_steps += n_skip
        self.spike_steps += n_spike
        max_bad = _max_run(bad)
        if n_skip:
            self._event(
                "skip", epoch,
                steps=np.flatnonzero(skip_flags)[:16].tolist(), count=n_skip,
            )
        if n_spike:
            self._event(
                "spike", epoch,
                steps=np.flatnonzero(spike_flags)[:16].tolist(), count=n_spike,
                losses=[round(float(x), 4) for x in losses[spike_flags][:16]],
                **({"phase": phase} if phase is not None else {}),
            )
        rollback = max_bad >= self.cfg.bad_steps
        reason = None
        if rollback:
            kinds = ("skip" if n_skip else "") + ("+spike" if n_spike else "")
            reason = (
                f"{max_bad} consecutive bad steps "
                f"({kinds.strip('+')}) in epoch {epoch}"
            )
        return EpochVerdict(
            rollback=rollback,
            reason=reason,
            skipped=n_skip,
            spikes=n_spike,
            max_bad_run=max_bad,
            nonfinite=not bool(np.isfinite(losses).all()),
            bad_steps=np.flatnonzero(bad).tolist(),
        )

    def note_desync(self, epoch: int, report: dict) -> None:
        self.desyncs += 1
        self._event(
            "desync", epoch,
            spread=report.get("spread"),
            injected=report.get("injected", False),
            **(
                {"per_host": True}
                if report.get("partial") else {}
            ),
        )

    def note_quarantine(
        self, epoch: int, steps: list[int], examples: int
    ) -> None:
        """Record a corrupt-shard quarantine: the replay of ``epoch`` will
        exclude the bad step window's batch examples (loader cooperation —
        ``data/loader.py HostLoader.quarantine``)."""
        self.quarantined_examples += int(examples)
        self._event(
            "quarantine", epoch,
            steps=[int(s) for s in steps[:16]], examples=int(examples),
        )

    # ------------------------------------------------------------- rollback

    def exhausted(self) -> bool:
        return self.rollbacks >= self.cfg.max_rollbacks

    def record_rollback(
        self, epoch: int, to_epoch: int, wasted_steps: int,
        wasted_s: float, reason: str,
    ) -> None:
        self.rollbacks += 1
        self.rollback_wasted_steps += int(wasted_steps)
        self.rollback_wasted_s += float(wasted_s)
        self._event(
            "rollback", epoch,
            to_epoch=to_epoch, wasted_steps=int(wasted_steps),
            wasted_s=round(float(wasted_s), 4), reason=reason,
        )

    # ------------------------------------------------------------ reporting

    def _event(self, kind: str, epoch: int, **extra) -> None:
        record = {"kind": kind, "epoch": int(epoch), **extra}
        if self.bus is not None:
            # stamp the legacy record so health.jsonl rows join the
            # unified timeline on run_id/attempt, and mirror the event
            # onto the bus itself
            record.update(self.bus.stamp(), t_wall=time.time())
            self.bus.emit(kind, epoch=epoch, **extra)
        self.events.append(record)
        self._unflushed += 1
        if self.logger is not None and kind != "rollback":
            self.logger.warning(f"health: {kind} at epoch {epoch}: {extra}")

    def counters(self) -> dict:
        return {
            "skipped_steps": self.skipped_steps,
            "spike_steps": self.spike_steps,
            "rollbacks": self.rollbacks,
            "desyncs": self.desyncs,
            "rollback_wasted_steps": self.rollback_wasted_steps,
            "rollback_wasted_s": round(self.rollback_wasted_s, 4),
            "quarantined_examples": self.quarantined_examples,
        }

    def summary(self) -> dict:
        return {
            "metric": "train_health",
            **self.counters(),
            "config": {
                "window": self.cfg.window,
                "spike_mads": self.cfg.spike_mads,
                "bad_steps": self.cfg.bad_steps,
                "max_rollbacks": self.cfg.max_rollbacks,
                "desync_every": self.cfg.desync_every,
            },
            "events": self.events,
        }

    def flush_events(self, version_dir: str | Path | None) -> None:
        """Append events accumulated since the last flush to the run dir's
        ``health.jsonl`` (process-0 only — the caller gates)."""
        if version_dir is None or not self._unflushed:
            return
        path = Path(version_dir) / EVENTS_NAME
        try:
            with open(path, "a") as f:
                for ev in self.events[-self._unflushed:]:
                    f.write(json.dumps(ev) + "\n")
        except OSError:
            return  # accounting must never kill training
        self._unflushed = 0


def write_health(path: str | Path, summary: dict) -> Path:
    """Write the trainer's ``--health-json`` report.  Same report-file
    shape as the supervisor's ``--goodput-json``, so it shares the writer."""
    from ..resilience.goodput import write_goodput

    return write_goodput(path, summary)


def load_health_events(path: str | Path) -> list[dict]:
    """Parse a run dir's ``health.jsonl``.  Shares the goodput jsonl loader
    (one copy of the torn-trailing-line tolerance rule)."""
    from ..resilience.goodput import load_goodput_records

    return load_goodput_records(path)
