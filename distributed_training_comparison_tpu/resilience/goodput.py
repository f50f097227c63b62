"""Goodput accounting: productive step time vs. everything else.

A resilient system is only as good as the fraction of wall-clock it spends
actually training.  The meter splits a process's lifetime into phases —

- ``init``  — process start through restore/compile readiness (recovery
  cost: every restart pays it again),
- ``step``  — productive epoch compute (the only phase that makes progress),
- ``eval``  — validation/test,
- ``ckpt``  — *main-thread blocking* checkpoint work: the symmetric
  collective fetch and ``AsyncCheckpointer.wait()`` drains.  The write-
  behind worker's overlapped fetch+serialize is deliberately NOT counted —
  overlap is the design, and charging it would double-book time the chip
  spent stepping,
- ``stall`` — injected or detected step-time stalls,
- ``rollback`` — step time the health watchdog later invalidated: when a
  bad epoch rolls back to the last good checkpoint (``health/``), its
  wall-clock moves from ``step`` to here via ``transfer`` — wasted compute
  must not inflate goodput,

plus untracked remainder.  Each training attempt appends one record to the
run dir's ``goodput.jsonl``; the supervisor aggregates records + its own
restart downtime into the file ``--goodput-json`` names —
goodput = productive seconds / (wall seconds across attempts + downtime).
Attempt records may also carry a ``ckpt_writer`` gauge (the async writer
thread's busy seconds/fraction, ``train/async_ckpt.py``) — visible when
write-behind stops hiding the device→host fetch cost.
"""

from __future__ import annotations

import json
import time
from collections import defaultdict
from contextlib import contextmanager, nullcontext
from pathlib import Path

PHASES = ("init", "step", "eval", "ckpt", "stall", "rollback")


class GoodputMeter:
    """Accumulates per-phase wall-clock for one training attempt."""

    def __init__(self, tracer=None) -> None:
        # optional span recorder (obs/spans.py): ``phase(name, span=...)``
        # then also records the interval as a host span, as
        # ``StepTimeMeter.phase`` does — one call books the total and draws
        # the span, so the two cannot drift apart in extent
        self.tracer = tracer
        self.seconds: dict[str, float] = defaultdict(float)
        self._t0 = time.monotonic()
        self.written = False

    def add(self, phase: str, secs: float) -> None:
        self.seconds[phase] += max(0.0, float(secs))

    def transfer(self, src: str, dst: str, secs: float) -> float:
        """Re-attribute up to ``secs`` already booked under ``src`` to
        ``dst`` (health rollback: a bad epoch's 'step' time becomes
        'rollback' waste once invalidated).  Clamped to what ``src``
        actually holds; returns the amount moved."""
        moved = min(max(0.0, float(secs)), self.seconds[src])
        self.seconds[src] -= moved
        self.seconds[dst] += moved
        return moved

    @contextmanager
    def phase(self, name: str, span: str | None = None):
        ctx = (
            self.tracer.span(span)
            if span is not None and self.tracer is not None
            else nullcontext()
        )
        t0 = time.monotonic()
        try:
            with ctx:
                yield
        finally:
            self.add(name, time.monotonic() - t0)

    def wall_seconds(self) -> float:
        return time.monotonic() - self._t0

    def productive_frac(self) -> float:
        wall = self.wall_seconds()
        return self.seconds["step"] / wall if wall > 0 else 0.0

    def summary(self) -> dict:
        wall = self.wall_seconds()
        tracked = sum(self.seconds.values())
        out = {f"{k}_s": round(self.seconds[k], 4) for k in PHASES}
        out["wall_s"] = round(wall, 4)
        out["untracked_s"] = round(max(0.0, wall - tracked), 4)
        out["productive_frac"] = round(self.productive_frac(), 4)
        return out


def append_goodput_record(path: str | Path, record: dict) -> None:
    """Append one attempt record to the run dir's ``goodput.jsonl``."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")


def load_goodput_records(path: str | Path) -> list[dict]:
    path = Path(path)
    if not path.exists():
        return []
    records = []
    for line in path.read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            records.append(json.loads(line))
        except ValueError:
            continue  # a torn trailing line must not void the good records
    return records


def collect_goodput_records(
    ckpt_root: str | Path, since: float | None = None
) -> list[dict]:
    """Attempt records from EVERY version dir under ``ckpt_root`` — an
    attempt that died before its first checkpoint save leaves its record in
    one version dir while the relaunch progresses in the next, and the
    wasted wall-clock of the failed attempt is exactly what goodput exists
    to charge.  ``since`` (unix time, compared to each record's
    ``written_at``) restricts aggregation to one supervised run's own
    attempts when the ckpt_root also holds older runs' dirs; records
    without a timestamp (pre-timestamp writers) are excluded by a
    ``since`` filter."""
    records = []
    for path in sorted(Path(ckpt_root).glob("version-*/goodput.jsonl")):
        records.extend(load_goodput_records(path))
    if since is not None:
        records = [r for r in records if r.get("written_at", 0.0) >= since]
    records.sort(key=lambda r: r.get("written_at", 0.0))
    return records


def aggregate_goodput(
    records: list[dict],
    *,
    downtime_s: float = 0.0,
    restarts: int = 0,
    preemptions: int = 0,
    resizes: list[dict] | None = None,
) -> dict:
    """Fold per-attempt records + supervisor downtime into the GOODPUT.json
    shape: totals per phase, overall goodput, and the attempt list.
    ``resizes`` (the elastic fleet supervisor's world-size changes) ride
    into the report so the scoreboard prices every shrink/expand next to
    the goodput it cost."""
    totals = {f"{k}_s": 0.0 for k in PHASES}
    totals["wall_s"] = 0.0
    totals["untracked_s"] = 0.0
    writer_busy = 0.0
    health = {
        "skipped_steps": 0, "spike_steps": 0, "rollbacks": 0, "desyncs": 0,
        "quarantined_examples": 0,
    }
    for rec in records:
        for key in totals:
            totals[key] += float(rec.get(key, 0.0))
        writer_busy += float(rec.get("ckpt_writer", {}).get("busy_s", 0.0))
        for key in health:
            health[key] += int(rec.get("health", {}).get(key, 0))
    total_wall = totals["wall_s"] + downtime_s
    goodput = totals["step_s"] / total_wall if total_wall > 0 else 0.0
    # records written since the obs bus exist carry the run identity; the
    # aggregate surfaces it when every stamped record agrees (old,
    # unstamped records aggregate exactly as before)
    run_ids = {r["run_id"] for r in records if r.get("run_id")}
    out = {
        "metric": "train_goodput",
        "goodput_frac": round(goodput, 4),
        "productive_s": round(totals["step_s"], 3),
        "total_wall_s": round(total_wall, 3),
        "restart_downtime_s": round(downtime_s, 3),
        "restarts": restarts,
        "preemptions": preemptions,
        "attempts": len(records),
        "phase_totals_s": {k: round(totals[f"{k}_s"], 3) for k in PHASES},
        "untracked_s": round(totals["untracked_s"], 3),
        "ckpt_writer_busy_s": round(writer_busy, 3),
        "health": health,
        "attempt_records": records,
    }
    if resizes is not None:
        out["resizes"] = list(resizes)
    if len(run_ids) == 1:
        out["run_id"] = next(iter(run_ids))
    return out


def write_goodput(path: str | Path, report: dict) -> Path:
    path = Path(path)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    return path
