"""Kernels: share of the device's op time spent in Pallas custom calls."""


def read(run):
    if run.trace_span is None:
        return None
    share = run.trace_mod.share_of_busy(run.trace, *run.trace_span)
    return None if share is None else 100.0 * share
