"""Kernels: self-time a step of the train program's ops under the scope
``ssd_scan`` (``ops/ssd.py``: the state-space scan, whichever
implementation runs it), forward, recomputed forward and backward.
``None`` where the program has no such scope."""

from harness import scopes


def read(run):
    return scopes.train_ms_per_step(
        run, lambda op_name: scopes.under(op_name, "ssd_scan")
    ) or None
