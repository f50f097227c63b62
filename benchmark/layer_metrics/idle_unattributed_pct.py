"""Trainer: the share (%) of the first device's idle time in the traced
span that no group of ``harness/host_spans.py GROUPS`` takes: idle under
``boundary``'s own time (the loop's glue), under no span, or under a span
the table does not name.  Above 10, a span is missing.  ``None`` where the
program draws no ``boundary`` span."""

from harness import host_spans


def read(run):
    return host_spans.idle_unattributed_pct(run)
