"""Parallel layouts: share of the collectives' time in which the core runs
nothing else (a synchronous collective: all of it; an asynchronous one:
its ``-start`` and ``-done`` ops only)."""


def read(run):
    if run.trace_span is None:
        return None
    out = run.trace_mod.collectives(run.trace, *run.trace_span)
    return None if out is None or not out[0] else 100.0 * out[1] / out[0]
