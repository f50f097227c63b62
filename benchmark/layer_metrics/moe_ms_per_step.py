"""Models: self-time a step of the train program's ops under the module
``moe`` (``models/moe.py TopKMoE``: router, selection, dispatch, the
grouped matmuls, combine), forward (the recomputed one too) and backward."""

from harness import scopes


def read(run):
    return scopes.train_ms_per_step(
        run, lambda op_name: scopes.under(op_name, "moe")
    )
