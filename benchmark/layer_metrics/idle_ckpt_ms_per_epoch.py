"""Checkpoint: milliseconds a traced epoch the first device sits idle while
the host saves (``ckpt_decide``, ``ckpt_snapshot`` or ``ckpt_fetch``,
``ckpt_submit``, ``writer_stats``): the device's idle intervals cut at the
program's span edges and booked to the innermost span open
(``harness/host_spans.py``, group ``ckpt``).  ``None`` where the program
draws no ``boundary`` span."""

from harness import host_spans


def read(run):
    return host_spans.idle_ms_per_epoch(run, "ckpt")
