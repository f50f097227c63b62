"""What every plain reference shares: input normalisation, the loss, and
the optimizer step of the paper's recipe.  Straightforward ``jax.numpy`` in
float32; callers run it under ``jax.default_matmul_precision("highest")``,
because on a TPU a float32 matrix product otherwise runs as bf16 passes.

Written from the source repository's ``trainer.py`` (torch
``SGD(momentum=0.9, nesterov=True, weight_decay=wd)``) and torchvision's
``ToTensor`` + ``Normalize``; no module of the program is imported.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MOMENTUM = 0.9  # the source's SGD(momentum=0.9, nesterov=True)


def normalize(images_u8, mean, std):
    """uint8 NHWC -> float32: scale to [0, 1], standardise per channel."""
    x = images_u8.astype(jnp.float32) / 255.0
    return (x - jnp.asarray(mean, jnp.float32)) / jnp.asarray(std, jnp.float32)


def cross_entropy(logits, labels):
    """Mean over the batch of -log softmax(logits)[label]."""
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def sgd_nesterov(params, grads, momentum_buf, lr, weight_decay):
    """torch SGD with coupled weight decay and Nesterov momentum:
    d = g + wd p;  buf = 0.9 buf + d;  p <- p - lr (d + 0.9 buf)."""
    tree_map = jax.tree_util.tree_map
    d = tree_map(lambda g, p: g + weight_decay * p, grads, params)
    new_buf = tree_map(lambda buf, d: MOMENTUM * buf + d, momentum_buf, d)
    new_params = tree_map(
        lambda p, d, buf: p - lr * (d + MOMENTUM * buf), params, d, new_buf
    )
    return new_params, new_buf


def reference_step(forward, params, batch_stats, images_u8, labels, recipe):
    """One training step from zero momentum: loss, logits, the gradient's
    global norm, the parameters and the normalisation statistics after it.

    ``forward(params, batch_stats, x) -> (logits, new_batch_stats)`` is the
    configuration's own file.  ``recipe``: ``lr``, ``weight_decay``,
    ``input_mean``, ``input_std``.
    """
    x = normalize(images_u8, recipe["input_mean"], recipe["input_std"])

    def loss_fn(p):
        logits, new_stats = forward(p, batch_stats, x)
        return cross_entropy(logits, labels), (logits, new_stats)

    (loss, (logits, new_stats)), grads = jax.value_and_grad(
        loss_fn, has_aux=True
    )(params)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    new_params, _ = sgd_nesterov(
        params, grads, zeros, recipe["lr"], recipe["weight_decay"]
    )
    grad_norm = jnp.sqrt(
        sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads))
    )
    return {
        "loss": loss, "logits": logits, "grad_norm": grad_norm,
        "params": new_params, "batch_stats": new_stats,
    }
