"""Step programs: device time of the train program's executions in the
traced span / the steps they ran."""


def read(run):
    if run.trace_span is None:
        return None
    seconds = run.trace_mod.train_seconds(run.trace, *run.trace_span)
    return None if seconds is None else 1e3 * seconds / run.traced_steps
