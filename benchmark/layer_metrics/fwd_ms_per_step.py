"""Step programs: self-time of the train program's forward ops a step — an
``op_name`` under ``jvp(`` and no ``transpose(``, or under the scopes
``augment`` or ``loss`` (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.train_ms_per_step(
        run, lambda op_name: scopes.phase_of(op_name) == "forward"
    )
