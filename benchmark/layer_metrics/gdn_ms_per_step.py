"""Models: self-time a step of the train program's ops under the module
``gdn`` (``models/qwen3_next.py GatedDeltaNet``: the fused projections, the
depthwise convolution, the scan, the gated norm, the output projection),
forward, recomputed forward and backward.  ``None`` where no layer has
one."""

from harness import scopes


def read(run):
    return scopes.train_ms_per_step(
        run, lambda op_name: scopes.under(op_name, "gdn")
    ) or None
