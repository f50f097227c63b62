"""Parallel layouts: summed duration of the all-reduce / reduce-scatter /
all-gather ops on the first device in the traced span / its steps."""


def read(run):
    if run.trace_span is None:
        return None
    out = run.trace_mod.collectives(run.trace, *run.trace_span)
    return None if out is None else 1e3 * out[0] / run.traced_steps
