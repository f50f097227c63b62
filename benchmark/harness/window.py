"""The benchmark's own clock on a ``Trainer.fit()``: a subscriber on the
run's event bus that opens and closes the measured window, starts and stops
the profiler, and stops the run — without touching the program.

``fit()`` has no stop-after-seconds (listed in PERF.md for the ``tracing``
issue), but it re-reads ``hparams.epoch`` at every pass of its loop.  So:

- the window opens at the ``epoch_start`` after ``warmup_epochs`` epochs
  (epoch 0 compiles, runs the first validation and the first saves);
- the first ``epoch_end`` after ``seconds`` have passed arms the close; the
  window closes at the *next* ``epoch_start``, so that it holds whole epochs
  each with its boundary (validation, snapshot, saves, bookkeeping);
- at that event ``hparams.epoch`` is lowered to end the run with the epoch
  that is just starting — it runs outside the window and ends as a job's
  last epoch does (final save, writer drain), which keeps that one-off cost
  out of a steady-state rate;
- with a trace directory, the profiler runs from the ``epoch_start`` of the
  window's first epoch to the one ``trace_epochs`` later, and every bus
  event in between leaves a ``bench/<kind>/<epoch>`` mark in the trace.

The bus swallows a subscriber's exceptions, so the first one is kept in
``error`` and the caller re-raises it after ``fit()``.
"""

from __future__ import annotations

import time


class WindowClock:
    def __init__(self, trainer, seconds: float, warmup_epochs: int = 1,
                 trace_dir: str | None = None, trace_epochs: int = 2):
        self.trainer = trainer
        self.seconds = float(seconds)
        self.first_epoch = int(warmup_epochs)
        self.trace_dir = trace_dir
        self.trace_epochs = int(trace_epochs)
        self.events: list[dict] = []  # every bus event, on perf_counter
        self.opened = self.closed = None  # perf_counter at open / close
        self.close_epoch = None  # the first epoch outside the window
        self.goodput_open = self.goodput_close = None
        self.profiler_s = 0.0  # seconds of the window spent in stop_trace
        self.tracing = False
        self.traced = trace_dir is None  # nothing left to trace
        self.armed = False
        self.error: Exception | None = None

    def __call__(self, ev: dict) -> None:
        try:
            self._on_event(ev)
        except Exception as e:  # the bus would swallow it: kept for the caller
            if self.error is None:
                self.error = e

    def _mark(self, kind: str, epoch) -> None:
        import jax

        with jax.profiler.TraceAnnotation(
            f"bench/{kind}/{-1 if epoch is None else epoch}"
        ):
            pass

    def _profiler(self, start: bool) -> None:
        import jax

        t0 = time.perf_counter()
        if start:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # device and TraceMe lines only
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        else:
            jax.profiler.stop_trace()
        self.tracing = start
        if self.opened is not None:  # the start call precedes the window
            self.profiler_s += time.perf_counter() - t0

    def _on_event(self, ev: dict) -> None:
        kind, epoch = ev.get("kind"), ev.get("epoch")
        if kind == "epoch_start":
            if epoch == self.first_epoch and self.trace_dir is not None:
                self._profiler(start=True)
            elif self.tracing and epoch == self.first_epoch + self.trace_epochs:
                self._mark(kind, epoch)
                self._profiler(start=False)
                self.traced = True
        t = time.perf_counter()
        self.events.append({
            "t": t, "kind": kind, "epoch": epoch,
            "payload": ev.get("payload") or {},
        })
        if self.tracing:
            self._mark(kind, epoch)
        if kind == "epoch_start":
            if epoch == self.first_epoch:
                self.opened = t
                self.goodput_open = dict(self.trainer.goodput.seconds)
            elif self.armed and self.closed is None:
                self.closed, self.close_epoch = t, epoch
                self.goodput_close = dict(self.trainer.goodput.seconds)
                self.trainer.hparams.epoch = epoch + 1
        elif kind == "epoch_end" and self.opened is not None:
            if self.traced and t - self.opened - self.profiler_s >= self.seconds:
                self.armed = True

    # ------------------------------------------------------------ results

    def window(self) -> dict:
        """What the window held.  Its length leaves out the seconds spent
        inside the profiler's start and stop calls of a traced run."""
        epochs = self.close_epoch - self.first_epoch
        steps = epochs * self.trainer.steps_per_epoch
        return {
            "seconds": self.closed - self.opened - self.profiler_s,
            "first_epoch": self.first_epoch,
            "epochs": epochs,
            "steps": steps,
            "images": steps * self.trainer.hparams.batch_size,
            "steps_per_epoch": self.trainer.steps_per_epoch,
            "batch_size": self.trainer.hparams.batch_size,
        }

    def in_window(self, kind: str) -> list[dict]:
        return [
            e for e in self.events
            if e["kind"] == kind and self.opened <= e["t"] <= self.closed
        ]

    def goodput_window(self) -> dict:
        """Seconds the program booked under each goodput phase inside the
        window (``resilience/goodput.py``: step, eval, ckpt, ...)."""
        return {
            k: v - self.goodput_open.get(k, 0.0)
            for k, v in self.goodput_close.items()
        }
