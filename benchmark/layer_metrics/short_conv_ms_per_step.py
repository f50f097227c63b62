"""Models: self-time a step of the train program's ops under the module
``short_conv`` (``models/lfm2.py ShortConv``: in-projection, gates, the
depthwise causal taps, out-projection), forward and backward."""

from harness import scopes


def read(run):
    return scopes.train_ms_per_step(
        run, lambda op_name: scopes.under(op_name, "short_conv")
    )
