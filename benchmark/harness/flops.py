"""Operations a training step needs, from shapes alone.

Copied from ``bench.py`` (``forward_flops_per_image``,
``vit_forward_flops_per_image``, ``train_flops_per_image``), with one change:
the architecture comes from the configuration's own file (``flops`` group),
not from the program's model zoo, so a change to ``models/`` cannot move the
yardstick.  Multiply-accumulates count as two operations; normalisation,
activation, pooling and softmax are left out (under 1% of the total at these
shapes); the backward pass counts twice the forward (gradient with respect
to the input and to the weights), and nothing recomputed counts.
"""

from __future__ import annotations


def resnet_forward_flops(p: dict) -> float:
    """CIFAR-form ResNet (He et al. 2015): 3x3 stem at stride 1, no max-pool,
    stages of basic (two 3x3) or bottleneck (1x1, 3x3, 1x1 x4) blocks, a 1x1
    projection wherever the stride or the width changes, a linear head."""
    hw = p["image_size"]
    macs = 3 * 3 * p["in_channels"] * p["stem_width"] * hw * hw
    cin = p["stem_width"]
    exp = 1 if p["block"] == "basic" else 4
    for planes, stride, blocks in zip(p["widths"], p["strides"], p["depths"]):
        for i in range(blocks):
            s = stride if i == 0 else 1
            out = hw // s
            if p["block"] == "basic":
                macs += 3 * 3 * cin * planes * out * out
                macs += 3 * 3 * planes * planes * out * out
            else:
                macs += cin * planes * hw * hw
                macs += 3 * 3 * planes * planes * out * out
                macs += planes * planes * exp * out * out
            if s != 1 or cin != planes * exp:
                macs += cin * planes * exp * out * out
            cin, hw = planes * exp, out
    macs += cin * p["num_classes"]
    return 2.0 * macs


def vit_forward_flops(p: dict) -> float:
    """Pre-LN ViT: per token and block 4 d^2 (q, k, v, output projection)
    + 2 d m (the MLP, m its hidden width) + 2 S d (scores and their product
    with the values) multiply-accumulates; patch embedding and head once."""
    s = (p["image_size"] // p["patch_size"]) ** 2
    d, m = p["dim"], p["mlp_dim"]
    per_token = p["depth"] * (4 * d * d + 2 * d * m + 2 * s * d)
    macs = s * (per_token + p["patch_size"] ** 2 * p["in_channels"] * d)
    macs += d * p["num_classes"]
    return 2.0 * macs


FAMILIES = {"resnet": resnet_forward_flops, "vit": vit_forward_flops}


def train_flops_per_image(flops_cfg: dict) -> float:
    """Forward plus backward for one image: three forwards."""
    return 3.0 * FAMILIES[flops_cfg["family"]](flops_cfg)
