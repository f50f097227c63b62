"""Input pipeline: device idle between the first and last train execution
of an epoch / that span — the device waiting for the host's next chunk."""


def read(run):
    if run.trace is None:
        return None
    out = run.trace_mod.idle_inside_epochs(
        run.trace, run.clock.first_epoch, run.clock.trace_epochs
    )
    return None if out is None or not out[1] else 100.0 * out[0] / out[1]
