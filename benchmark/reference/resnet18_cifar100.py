"""Plain reference for ``resnet18_cifar100``: ResNet-18 (He et al. 2015,
arXiv:1512.03385) in the CIFAR form of the source repository's ``net.py``
(3x3 stem at stride 1, no max-pool, stages 64/128/256/512 at strides
1/2/2/2 of two basic blocks each, 4x4 average pool, linear head).

Departures from the source, each because the program under test states it:
- NHWC tensors and HWIO kernels (the source is NCHW torch); same arithmetic.
- the running variance is updated with the batch's biased variance (flax);
  torch uses the unbiased one.  It enters no loss or gradient of the step.
- BatchNorm reduces over the whole global batch, as under GSPMD; torch DDP
  without SyncBatchNorm reduces per replica.

Takes the program's parameter tree as plain arrays:
``stem_conv/kernel``, ``stem_bn/{scale,bias}``,
``stage<S>_block<I>/{Conv_0,Conv_1[,Conv_2]}/kernel`` and
``.../{BatchNorm_0,BatchNorm_1[,BatchNorm_2]}/{scale,bias}``,
``head/{kernel,bias}``; statistics under the same names with ``mean``/``var``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BN_EPS = 1e-5
BN_DECAY = 0.9  # torch momentum 0.1
STRIDES = (1, 2, 2, 2)
BLOCKS = (2, 2, 2, 2)


def conv(x, kernel, stride):
    pad = (kernel.shape[0] - 1) // 2
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def batch_norm(x, p, stats):
    """Training-mode BatchNorm over (N, H, W); returns the new running stats."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) / jnp.sqrt(var + BN_EPS) * p["scale"] + p["bias"]
    return y, {
        "mean": BN_DECAY * stats["mean"] + (1 - BN_DECAY) * mean,
        "var": BN_DECAY * stats["var"] + (1 - BN_DECAY) * var,
    }


def forward(params, batch_stats, x):
    new_stats = {}
    x = conv(x, params["stem_conv"]["kernel"], 1)
    x, new_stats["stem_bn"] = batch_norm(
        x, params["stem_bn"], batch_stats["stem_bn"]
    )
    x = jax.nn.relu(x)
    for stage, (stride, blocks) in enumerate(zip(STRIDES, BLOCKS)):
        for i in range(blocks):
            name = f"stage{stage + 1}_block{i}"
            p, s, ns = params[name], batch_stats[name], {}
            st = stride if i == 0 else 1
            out = conv(x, p["Conv_0"]["kernel"], st)
            out, ns["BatchNorm_0"] = batch_norm(
                out, p["BatchNorm_0"], s["BatchNorm_0"]
            )
            out = jax.nn.relu(out)
            out = conv(out, p["Conv_1"]["kernel"], 1)
            out, ns["BatchNorm_1"] = batch_norm(
                out, p["BatchNorm_1"], s["BatchNorm_1"]
            )
            shortcut = x
            if "Conv_2" in p:  # projection where stride or width changes
                shortcut = conv(x, p["Conv_2"]["kernel"], st)
                shortcut, ns["BatchNorm_2"] = batch_norm(
                    shortcut, p["BatchNorm_2"], s["BatchNorm_2"]
                )
            x = jax.nn.relu(out + shortcut)
            new_stats[name] = ns
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["head"]["kernel"] + params["head"]["bias"], new_stats
