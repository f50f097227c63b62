"""Trainer: from the end of an epoch's last train execution to the start
of the next epoch's first (validation, snapshot, bookkeeping), mean over
the traced epochs."""


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace_mod.boundary_seconds(
        run.trace, run.clock.first_epoch, run.clock.trace_epochs
    )
    return None if seconds is None else 1e3 * seconds
