"""Write-behind checkpointing.

The reference's ``save_checkpoint`` blocks the epoch loop while it
serializes (``src/single/trainer.py:96-107``); here a synchronous save
would stall the chip for the device→host fetch of the train state plus
the serialize and the write.  ``AsyncCheckpointer`` moves
fetch+serialize+write to a single worker thread: the epoch loop hands
over a *reference* to the on-device state and continues; the transfer
overlaps the next epoch's compute.

Correctness notes:
- the scanned runners DONATE their input state buffers (the next dispatch
  reuses them), so the Trainer hands this writer a device-side snapshot —
  an HBM→HBM copy taken only on epochs that actually save — never a live
  reference the next dispatch would invalidate mid-fetch;
- ``wait()`` drains the queue — called before reading a checkpoint back
  (test phase, end of fit) and on ``close()``;
- writes for the same target are serialized by the single worker, so
  ``last.ckpt`` is always a complete, most-recent snapshot;
- ``hold()`` / ``release()`` pace the worker: between them it STARTS no
  job, and a job at work stops wherever it calls ``pace()`` (the saves do,
  before every 32 MiB they write).  The Trainer holds it over the epoch
  boundary and releases it once the next epoch's train program is
  dispatched — a job starts with the device→host fetch of the whole
  state, which stalls the submitting thread for as long as a large leaf's
  transfer takes (~0.26 s of a boundary at a 6.3 GB state), and a job
  filling the page cache at memory speed beside a boundary stretches the
  host's small transfers there (PERF.md, Findings, PR 40); after the
  dispatch that thread only waits for the chip.  ``wait()``, ``close()``
  and a full queue release, so no drain can meet a held worker.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable

from ..obs import open_span as _obs_open_span, span as _obs_span


class AsyncCheckpointer:
    """One background writer thread executing queued checkpoint jobs.

    Jobs submitted under the same ``key`` coalesce: if a newer snapshot for
    that key is queued before the old one started writing, the old one is
    dropped — only the most recent state of each checkpoint target ever hits
    disk (a best.ckpt made obsolete two epochs later need not be written at
    all, which matters when the device→host fetch is the expensive part).
    """

    def __init__(self, max_pending: int = 16, metrics=None) -> None:
        self._q: queue.Queue = queue.Queue(maxsize=max_pending)
        # key -> (job, the span open on the submitting thread), or None
        self._latest: dict[str, tuple[Callable[[], object], object] | None] = {}
        self._lock = threading.Lock()
        self._errors: list[BaseException] = []
        self._busy_s = 0.0  # wall-clock the worker spent executing jobs
        self._depth = 0     # jobs submitted but not yet finished
        # optional metric registry (obs/metrics.py): the queue depth as a
        # live gauge + a write-seconds histogram, so the periodic
        # `metrics` flush events track the writer BETWEEN the per-epoch
        # `writer` gauges
        self._metrics = metrics
        self._born = time.monotonic()
        self._go = threading.Event()  # clear between hold() and release()
        self._go.set()
        self._thread = threading.Thread(
            target=self._worker, name="dtc-ckpt-writer", daemon=True
        )
        self._thread.start()

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            key = item
            self.pace()  # held: start nothing yet (the newest job still wins)
            with self._lock:
                queued = self._latest.get(key)
                self._latest[key] = None
            job, cause = queued or (None, None)
            t0 = time.monotonic()
            try:
                if job is not None:  # None => superseded, already written
                    # the one cross-thread edge of the span tree: this write
                    # is the child of the trainer's span that queued it
                    with _obs_span("ckpt_write", parent=cause, key=key):
                        job()
            except BaseException as e:  # surfaced on wait()/close()
                with self._lock:
                    self._errors.append(e)
            finally:
                took = time.monotonic() - t0
                with self._lock:
                    self._busy_s += took
                    self._depth -= 1
                    depth = self._depth
                if self._metrics is not None:
                    self._metrics.gauge("ckpt/queue_depth").set(depth)
                    if job is not None:
                        self._metrics.histogram("ckpt/write_s").record(took)
                self._q.task_done()

    def stats(self) -> dict:
        """Writer-thread utilization gauges for goodput records and the
        periodic ``writer`` events: busy seconds (fetch+serialize+write
        inside jobs) over thread lifetime, plus the instantaneous queue
        depth (jobs submitted and not yet finished).  A busy fraction
        approaching 1.0 — or a depth that climbs epoch over epoch — means
        write-behind has stopped hiding the checkpoint cost: saves queue
        faster than they drain, and the next ``wait()`` will block the
        epoch loop for real."""
        alive = max(time.monotonic() - self._born, 1e-9)
        with self._lock:
            busy, depth = self._busy_s, self._depth
        return {
            "busy_s": round(busy, 4),
            "alive_s": round(alive, 4),
            "busy_frac": round(min(busy / alive, 1.0), 4),
            "queue_depth": depth,
        }

    def hold(self) -> None:
        """Start no further job until ``release()`` (or a drain)."""
        self._go.clear()

    def release(self) -> None:
        self._go.set()

    def pace(self) -> None:
        """For a job to call where it can stop: returns once not held."""
        self._go.wait()

    def submit(self, job: Callable[[], object], key: str = "default") -> None:
        """Enqueue a checkpoint job; newer jobs with the same key supersede
        queued-but-unstarted ones.  The span open on the calling thread
        (the trainer's ``ckpt_submit``) rides with the job and becomes the
        parent of the writer's ``ckpt_write``."""
        cause = _obs_open_span()
        with self._lock:
            self._latest[key] = (job, cause)
            self._depth += 1
            depth = self._depth
        if self._metrics is not None:
            self._metrics.gauge("ckpt/queue_depth").set(depth)
            self._metrics.counter("ckpt/jobs").inc()
        if self._q.full():  # a held worker frees no slot: pacing yields
            self.release()
        self._q.put(key)

    def _raise_collected(self) -> None:
        """Surface worker failures: a background save that failed must never
        be silently swallowed — the run would end believing its checkpoints
        exist.  Raises the FIRST collected error (chained), noting how many
        followed; clears the list so a handled failure isn't re-raised by a
        later drain."""
        with self._lock:
            err, self._errors = self._errors[:], []
        if err:
            extra = f" (+{len(err) - 1} more)" if len(err) > 1 else ""
            raise RuntimeError(
                f"async checkpoint write failed: {err[0]!r}{extra}"
            ) from err[0]

    def wait(self) -> None:
        """Block until every queued job has finished; re-raise any failure."""
        self.release()
        self._q.join()
        self._raise_collected()

    def close(self) -> None:
        self.release()
        if self._thread.is_alive():
            self._q.put(None)
            self._thread.join()
        self._raise_collected()
