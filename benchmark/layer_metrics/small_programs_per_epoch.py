"""Trainer: executions an epoch of every program other than the train and
validation programs and the state snapshot — the schedule evaluated on the
device for a log line, the epoch's scalars, the parameter fingerprint: each
a host dispatch.  Counted over whole epochs in the device's own order
(``harness/scopes.py programs_per_epoch``), so the count repeats exactly;
the snapshot is left out because saves throttle on the wall clock."""

from harness import scopes


def read(run):
    return scopes.small_programs_per_epoch(run)
