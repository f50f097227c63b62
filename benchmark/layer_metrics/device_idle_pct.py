"""Device: 1 - union of device-op intervals / traced span (two epochs with
their boundaries), averaged over the chips."""


def read(run):
    if run.trace_span is None:
        return None
    lo, hi = run.trace_span
    busy = run.trace_mod.busy_seconds(run.trace, lo, hi)
    return None if busy is None else 100.0 * (1.0 - busy / ((hi - lo) / 1e9))
