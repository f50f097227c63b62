"""Models: self-time a step of the train program's ops under a flax module
whose name starts with ``stage1_`` — ResNet-18's two 64-channel blocks at
32x32 — forward and backward.  By stage and not by layer kind: XLA fuses
BatchNorm's reductions into the convolution that feeds them, so a split of
convolution from norm would be the compiler's choice of a fusion's root."""

from harness import scopes


def read(run):
    return scopes.train_ms_per_step(
        run, lambda op_name: scopes.under(op_name, "stage1_*")
    )
