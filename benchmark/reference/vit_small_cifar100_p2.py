"""Plain reference for ``vit_small_cifar100_p2``: DeiT-S (Touvron et al.
2021, arXiv:2012.12877, Table 1: 12 layers, width 384, 6 heads of 64, MLP
1536), pre-LN blocks as in ViT (Dosovitskiy et al. 2021), on 32x32 inputs
cut into 2x2 patches (256 tokens), 100 classes.

Departures from the source, each because the program under test states it:
- no class or distillation token: LayerNorm, then the mean over tokens,
  then the linear head (the repo's ``ViT``);
- learned position embedding added to every token, no dropout, no
  stochastic depth, no label smoothing or mixing (the recipe is the paper
  repo's SGD recipe, not DeiT's AdamW one);
- GELU in its tanh approximation; LayerNorm epsilon 1e-6.

Takes the program's parameter tree as plain arrays: ``patch_embed``,
``pos_emb``, ``blocks/<name>/...`` stacked on a leading depth axis,
``ln_head``, ``head``.  The blocks are a Python loop over that axis, no scan.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HEADS = 6
LN_EPS = 1e-6


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def forward(params, batch_stats, x):
    b, h, w, c = x.shape
    kernel = params["patch_embed"]["kernel"]  # (p, p, c, dim)
    ps, dim = kernel.shape[0], kernel.shape[-1]
    x = x.reshape(b, h // ps, ps, w // ps, ps, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, (h // ps) * (w // ps), ps * ps * c)
    x = x @ kernel.reshape(ps * ps * c, dim) + params["patch_embed"]["bias"]
    x = x + params["pos_emb"]
    s, hd = x.shape[1], dim // HEADS
    blocks = params["blocks"]
    for layer in range(blocks["q_proj"]["kernel"].shape[0]):
        p = jax.tree_util.tree_map(lambda a: a[layer], blocks)
        y = layer_norm(x, p["ln_attn"])
        q = dense(y, p["q_proj"]).reshape(b, s, HEADS, hd)
        k = dense(y, p["k_proj"]).reshape(b, s, HEADS, hd)
        v = dense(y, p["v_proj"]).reshape(b, s, HEADS, hd)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        attn = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(b, s, dim)
        x = x + dense(o, p["proj"])
        y = layer_norm(x, p["ln_mlp"])
        y = jax.nn.gelu(dense(y, p["mlp_up"]), approximate=True)
        x = x + dense(y, p["mlp_down"])
    x = jnp.mean(layer_norm(x, params["ln_head"]), axis=1)
    return dense(x, params["head"]), batch_stats
