"""Step programs: self-time of the train program's ops under the scopes
``guards`` (gradient norm, finite flag, the select that keeps the old state)
and ``optimizer`` (``tx.update`` and ``apply_updates``) a step."""

from harness import scopes


def read(run):
    return scopes.train_ms_per_step(
        run, lambda op_name: scopes.phase_of(op_name) == "update"
    )
