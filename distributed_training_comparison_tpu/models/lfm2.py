"""LFM2-MoE: a token decoder of gated short convolutions, grouped-query
attention and sigmoid-routed experts (Liquid AI; ``model_type`` ``lfm2_moe``).

``LFM2_24B_A2B`` is the published ``config.json`` of ``LiquidAI/LFM2-24B-A2B``
whole (https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json);
no width is ever cut.  What one chip holds of it is a ``--model-cut``
(``parse_cut``): how many layers, how many of the leading dense layers,
which experts, how many rows of the vocabulary — one rank's share of an
expert-parallel deployment, the layers left out lying on further chips as
pipeline stages.  The router keeps its published width and top-k; the
expert layer computes its own experts' part of the result
(``models/moe.py TopKMoE``); the vocabulary slice is a smaller vocabulary.

The equations (``eps`` = ``norm_eps``; no bias anywhere):

- RMSNorm: ``y = x * rsqrt(mean(x^2) + eps) * g``, statistics in float32.
- Layer: ``h = h + mixer(operator_norm(h))``; ``h = h + ffn(ffn_norm(h))``.
  After the last layer ``norm_out``, then logits ``= h @ E^T`` with the
  tied embedding ``E``.
- Short convolution: ``(B, C, X) = split3(h W_in)``; ``u = B * X``; ``v_t =
  sum_j w[:, j] * u_{t - (L-1) + j}`` per channel, causal, zeros before the
  sequence (``L`` = ``conv_L_cache``); ``out = (C * v) W_out``.
- Attention: query heads and fewer key-value heads of ``hidden / heads``;
  per-head RMSNorm on q and k; RoPE in the rotate-half form; causal
  softmax through ``ops/attention.py``'s dispatcher, each key-value head
  repeated for the query heads it serves.
- FFN: ``W_2(silu(W_1 x) * W_3 x)``; the leading ``num_dense_layers`` at
  ``intermediate_size``, every later layer ``TopKMoE`` at
  ``moe_intermediate_size``.

``parse_cut`` / ``cut_config``, ``RMSNorm``, ``rope``, ``SwiGLU`` and the
bias-free ``_dense`` live in ``models/token_parts.py`` since the second
token decoder (``models/afmoe.py``) shares them.

Scopes a device trace shows: ``embed``, ``short_conv``, ``attn`` (with
``attention`` inside it), ``mlp``, ``moe`` (with ``moe_gmm`` inside it),
``lm_head`` — module names and ``jax.named_scope``s alike.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attention
from .moe import TopKMoE
from .token_parts import (  # noqa: F401  (the cut is this module's interface too)
    CUT_KEYS,
    RMSNorm,
    SwiGLU,
    _dense,
    cut_config,
    parse_cut,
    rope,
    zoo_entry,
)

_PERIOD = ("full_attention", "conv", "conv", "conv")
LFM2_24B_A2B = {
    "conv_L_cache": 3,
    "conv_bias": False,
    "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv", *_PERIOD * 9, "full_attention", "conv"],
    "max_position_embeddings": 128000,
    "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536,
    "norm_eps": 1e-05,
    "norm_topk_prob": True,
    "num_attention_heads": 32,
    "num_dense_layers": 2,
    "num_experts": 64,
    "num_experts_per_tok": 4,
    "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1,
    "use_expert_bias": True,
    "vocab_size": 65536,
}
# the same pattern at test widths (tests/, rehearsals): never a cell
LFM2_TINY = {
    **LFM2_24B_A2B,
    "hidden_size": 64,
    "intermediate_size": 160,
    "layer_types": ["conv", "conv", *_PERIOD, "full_attention", "conv"],
    "moe_intermediate_size": 48,
    "num_attention_heads": 4,
    "num_experts": 16,
    "num_hidden_layers": 8,
    "num_key_value_heads": 2,
    "vocab_size": 512,
}
class ShortConv(nn.Module):
    """The gated short convolution (five steps in the module docstring)."""

    dim: int
    kernel: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        b_gate, c_gate, x = jnp.split(_dense(3 * self.dim, self.dtype, "in_proj")(h), 3, axis=-1)
        w = self.param(
            "conv_kernel", nn.initializers.normal(stddev=0.02),
            (self.dim, self.kernel), jnp.float32,
        ).astype(self.dtype)
        u = b_gate * x
        s, pad = u.shape[1], self.kernel - 1
        u = jnp.pad(u, ((0, 0), (pad, 0), (0, 0)))
        v = sum(w[:, j] * u[:, j:j + s] for j in range(self.kernel))
        return _dense(self.dim, self.dtype, "out_proj")(c_gate * v)


class GQAttention(nn.Module):
    dim: int
    heads: int
    kv_heads: int
    eps: float
    theta: float
    dtype: Any = jnp.float32
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, h):
        b, s, _ = h.shape
        hd = self.dim // self.heads
        q = _dense(self.dim, self.dtype, "q_proj")(h).reshape(b, s, self.heads, hd)
        k = _dense(self.kv_heads * hd, self.dtype, "k_proj")(h).reshape(b, s, self.kv_heads, hd)
        v = _dense(self.kv_heads * hd, self.dtype, "v_proj")(h).reshape(b, s, self.kv_heads, hd)
        q = rope(RMSNorm(self.eps, self.dtype, name="q_norm")(q), self.theta)
        k = rope(RMSNorm(self.eps, self.dtype, name="k_norm")(k), self.theta)
        # each key-value head serves heads // kv_heads consecutive queries:
        # the dispatcher takes the heads as they are (the flash kernels read
        # head h // group by index, every other path repeats them there)
        o = attention(q, k, v, causal=True, layout="bshd", impl=self.attn_impl)
        return _dense(self.dim, self.dtype, "o_proj")(o.reshape(b, s, self.dim))


class LFM2Layer(nn.Module):
    config: Any  # the cut config, frozen
    kind: str
    dense: bool
    dtype: Any = jnp.float32
    moe_gmm: str = "auto"
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, h):
        c = self.config
        x = RMSNorm(c["norm_eps"], self.dtype, name="operator_norm")(h)
        if self.kind == "conv":
            mixed = ShortConv(
                c["hidden_size"], c["conv_L_cache"], self.dtype, name="short_conv"
            )(x)
        else:
            mixed = GQAttention(
                c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"], c["norm_eps"],
                float(c["rope_parameters"]["rope_theta"]), self.dtype,
                self.attn_impl, name="attn",
            )(x)
        h = h + mixed
        x = RMSNorm(c["norm_eps"], self.dtype, name="ffn_norm")(h)
        if self.dense:
            y = SwiGLU(c["hidden_size"], c["intermediate_size"], self.dtype, name="mlp")(x)
        else:
            y = TopKMoE(
                c["hidden_size"], c["moe_intermediate_size"], c["num_experts"],
                c["num_experts_per_tok"], c["num_experts_held"],
                c["first_expert"], float(c["routed_scaling_factor"]),
                c["norm_topk_prob"], c["use_expert_bias"], self.dtype,
                self.moe_gmm, name="moe",
            )(x)
        return h + y


class LFM2(nn.Module):
    """``tokens (B, S) int32 -> logits (B, S, vocab) float32``."""

    config: Any
    dtype: Any = jnp.float32
    remat: bool = False
    moe_gmm: str = "auto"
    attn_impl: str = "auto"

    task = "next_token"  # train/task.py: what this family trains on

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        c = self.config
        embedding = self.param(
            "embedding", nn.initializers.normal(stddev=0.02),
            (c["vocab_size"], c["hidden_size"]), jnp.float32,
        ).astype(self.dtype)
        with jax.named_scope("embed"):
            h = embedding[tokens]
        # prevent_cse stays on: the layers are a Python loop, so forward and
        # backward share one program body and XLA would merge the
        # recomputation back into the forward pass
        layer = nn.remat(LFM2Layer) if self.remat else LFM2Layer
        for i, kind in enumerate(c["layer_types"]):
            h = layer(
                c, kind, i < c["num_dense_layers"], self.dtype, self.moe_gmm,
                self.attn_impl, name=f"layers_{i}",
            )(h)
        h = RMSNorm(c["norm_eps"], self.dtype, name="norm_out")(h)
        with jax.named_scope("lm_head"):
            return jnp.einsum(
                "bsd,vd->bsv", h, embedding, preferred_element_type=jnp.float32
            )


LFM2_24B_A2B_MODEL = zoo_entry(LFM2, LFM2_24B_A2B)
LFM2_TINY_MODEL = zoo_entry(LFM2, LFM2_TINY)
