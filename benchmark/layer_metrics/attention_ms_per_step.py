"""Kernels: self-time a step of the train program's ops under the scope
``attention`` (``ops/attention.py``: whichever implementation runs),
forward and backward."""

from harness import scopes


def read(run):
    return scopes.train_ms_per_step(
        run, lambda op_name: scopes.under(op_name, "attention")
    )
