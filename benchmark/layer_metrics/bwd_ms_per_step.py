"""Step programs: self-time of the train program's backward ops a step — an
``op_name`` that holds ``transpose(`` (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.train_ms_per_step(
        run, lambda op_name: scopes.phase_of(op_name) == "backward"
    )
