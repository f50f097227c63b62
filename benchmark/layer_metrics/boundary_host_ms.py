"""Trainer: the epoch boundary as the program times it — mean duration of
the ``boundary`` spans (``train/trainer.py fit``: from the train program's
results reaching the host to just before the next ``epoch_start``) in the
traced span.  The second witness beside ``epoch_boundary_ms``, which times
the same thing from the device's side and reads a whole epoch where a train
execution is missing from its marks.  ``None`` where the program draws no
``boundary`` span."""

from harness import host_spans


def read(run):
    return host_spans.boundary_host_ms(run)
