"""Models: self-time a step of the train program's ops under the module
``mamba`` (``models/nemotron_h.py Mamba2Mixer``: the fused in-projection,
the depthwise convolution, the scan, the gated norm, the output
projection), forward, recomputed forward and backward.  ``None`` where no
layer has one."""

from harness import scopes


def read(run):
    return scopes.train_ms_per_step(
        run, lambda op_name: scopes.under(op_name, "mamba")
    ) or None
