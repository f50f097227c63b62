"""Trainer: milliseconds a traced epoch the first device sits idle while
the host validates (``eval``: the call, ``eval_dispatch``, and the blocking
fetch of its totals, ``eval_fetch``): the device's idle intervals cut at the
program's span edges and booked to the innermost span open
(``harness/host_spans.py``, group ``eval``).  ``None`` where the program
draws no ``boundary`` span."""

from harness import host_spans


def read(run):
    return host_spans.idle_ms_per_epoch(run, "eval")
