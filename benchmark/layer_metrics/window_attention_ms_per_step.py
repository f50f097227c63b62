"""Kernels: self-time a step of the train program's ops under the scope
``attention_window`` (``ops/attention.py``: a call with a sliding window,
whichever implementation runs it — the sliding layers' share of the scope
``attention``), forward, recomputed forward and backward.  ``None`` where
no call has a window."""

from harness import scopes


def read(run):
    return scopes.train_ms_per_step(
        run, lambda op_name: scopes.under(op_name, "attention_window")
    ) or None
