"""Trainer: device time of the validation program (``jit_eval_runner``) in
the traced span, per traced epoch."""

from harness import scopes


def read(run):
    runs = scopes.program_runs(run, scopes.EVAL_PROGRAM)
    if not runs:
        return None
    return sum(e - s for s, e in runs) / 1e6 / run.clock.trace_epochs
