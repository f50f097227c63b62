"""Trainer: milliseconds a traced epoch the first device sits idle while
the host keeps its own records (``health``, ``policy``, ``step_log``,
``epoch_log``, ``epoch_end_emit``, ``metrics_flush``, ``heartbeat``,
``moe_log``, ``resilience``): the device's idle intervals cut at the
program's span edges and booked to the innermost span open
(``harness/host_spans.py``, group ``bookkeeping``).  ``None`` where the
program draws no ``boundary`` span."""

from harness import host_spans


def read(run):
    return host_spans.idle_ms_per_epoch(run, "bookkeeping")
