"""AFMoE: a token decoder of gated grouped-query attention, sliding-window
and full layers mixed, and a shared expert beside sigmoid-routed experts
whose selection bias the training step moves (Arcee; ``model_type``
``afmoe``).

``TRINITY_MINI`` is the published ``config.json`` of ``arcee-ai/Trinity-Mini``
whole (https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json);
no width is ever cut.  What one chip holds of it is a ``--model-cut``
(``models/token_parts.py parse_cut``), as for ``models/lfm2.py``: how many
layers, how many of the leading dense layers, which experts, how many rows
of the vocabulary.

The equations (``eps`` = ``rms_norm_eps``; no bias anywhere; what the
config's keys do not carry is from the reference implementation,
``transformers`` ``models/afmoe/modeling_afmoe.py``, and the bias rule from
``torchtitan`` ``models/moe.py``, whose argument names the config's keys
are):

1. ``h0 = E[tokens] * sqrt(hidden_size)`` (``mup_enabled``).
2. ``a = input_norm(h)``; ``q = a W_q`` as ``num_attention_heads`` heads of
   ``head_dim`` (published apart from ``hidden / heads``), ``k = a W_k``,
   ``v = a W_v`` as ``num_key_value_heads`` heads, ``g = a W_g`` as wide as
   ``q``; per-head RMSNorm with a learned scale on q and k.
3. A ``sliding_attention`` layer: RoPE (rotate-half, ``rope_theta``) on q
   and k; key ``j`` is visible to query ``i`` iff ``j <= i and i - j <
   sliding_window``.  A ``full_attention`` layer: no position encoding at
   all; ``j <= i``.
4. ``o = softmax(q k^T / sqrt(head_dim) + mask) v`` through
   ``ops/attention.py``'s dispatcher, each key-value head serving
   consecutive query heads; ``attn = (o * sigmoid(g)) W_o``; ``h = h +
   post_attn_norm(attn)``.
5. ``m = pre_mlp_norm(h)``.  The leading ``num_dense_layers``: a SwiGLU at
   ``intermediate_size``.  Every later layer ``models/moe.py TopKMoE``: ``s
   = sigmoid(m W_r)``, ``sel = top_k(s + b)``, ``w = s[sel] / sum(s[sel]) *
   route_scale``, ``y = shared(m) + sum over sel of w_e expert_e(m)``, the
   shared expert and every routed one a SwiGLU at ``moe_intermediate_size``.
6. ``h = h + post_mlp_norm(y)``.  After the last layer ``norm_out``, then
   ``logits = h W_head`` with an untied head.
7. Training only, once a step and a layer, after the step's routing: the
   selection bias moves by ``load_balance_coeff * sign(mean(c) - c)``,
   centred (``TopKMoE``'s docstring); it starts at zero, lives in
   ``batch_stats`` and never meets the optimizer.

Scopes a device trace shows: ``embed``, ``attn`` (with ``attn_gate`` —
the gate's projection and multiply — and ``attention`` inside it, and
``attention_window`` inside that on a sliding layer), ``mlp``, ``moe``
(with ``moe_gmm`` and ``shared_expert`` inside it), ``lm_head``.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attention
from .moe import TopKMoE
from .token_parts import (
    RMSNorm,
    SwiGLU,
    _dense,
    cut_config,
    parse_cut,
    rope,
    zoo_entry,
)

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)
TRINITY_MINI = {
    "global_attn_every_n_layers": 4,
    "head_dim": 128,
    "hidden_act": "silu",
    "hidden_size": 2048,
    "intermediate_size": 6144,
    "layer_types": list(_PERIOD * 8),
    "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072,
    "model_type": "afmoe",
    "moe_intermediate_size": 1024,
    "mup_enabled": True,
    "n_group": 1,
    "num_attention_heads": 32,
    "num_dense_layers": 2,
    "num_expert_groups": 1,
    "num_experts": 128,
    "num_experts_per_tok": 8,
    "num_hidden_layers": 32,
    "num_key_value_heads": 4,
    "num_limited_groups": 1,
    "num_shared_experts": 1,
    "rms_norm_eps": 1e-05,
    "rope_scaling": None,
    "rope_theta": 10000,
    "route_norm": True,
    "route_scale": 2.826,
    "score_func": "sigmoid",
    "sliding_window": 2048,
    "tie_word_embeddings": False,
    "topk_group": 1,
    "use_grouped_mm": True,
    "vocab_size": 200192,
}
# the same pattern at test widths (tests/, rehearsals): never a cell.  The
# head size stays apart from hidden / heads, the window inside a test's
# sequence, and the bias moves fast enough for three steps to show it.
AFMOE_TINY = {
    **TRINITY_MINI,
    "head_dim": 32,
    "hidden_size": 64,
    "intermediate_size": 160,
    "layer_types": list(_PERIOD * 2),
    "moe_intermediate_size": 48,
    "num_attention_heads": 4,
    "num_experts": 16,
    "num_experts_per_tok": 4,
    "num_hidden_layers": 8,
    "num_key_value_heads": 2,
    "sliding_window": 16,
    "vocab_size": 512,
}


class GatedAttention(nn.Module):
    """Steps 2-4 of the module docstring, up to ``W_o``."""

    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float | None  # None: no position encoding (a full layer)
    window: int | None  # None: every earlier key (a full layer)
    dtype: Any = jnp.float32
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, h):
        b, s, _ = h.shape
        hd, wide = self.head_dim, self.heads * self.head_dim
        q = _dense(wide, self.dtype, "q_proj")(h).reshape(b, s, self.heads, hd)
        k = _dense(self.kv_heads * hd, self.dtype, "k_proj")(h).reshape(b, s, self.kv_heads, hd)
        v = _dense(self.kv_heads * hd, self.dtype, "v_proj")(h).reshape(b, s, self.kv_heads, hd)
        with jax.named_scope("attn_gate"):
            gate = _dense(wide, self.dtype, "gate_proj")(h)
        q = RMSNorm(self.eps, self.dtype, name="q_norm")(q)
        k = RMSNorm(self.eps, self.dtype, name="k_norm")(k)
        if self.theta is not None:
            q, k = rope(q, self.theta), rope(k, self.theta)
        o = attention(
            q, k, v, causal=True, layout="bshd", impl=self.attn_impl,
            window=self.window,
        )
        with jax.named_scope("attn_gate"):
            o = o.reshape(b, s, wide) * jax.nn.sigmoid(gate)
        return _dense(self.dim, self.dtype, "o_proj")(o)


class AfmoeLayer(nn.Module):
    config: Any  # the cut config, frozen
    kind: str
    dense: bool
    dtype: Any = jnp.float32
    moe_gmm: str = "auto"
    attn_impl: str = "auto"
    train: bool = False

    @nn.compact
    def __call__(self, h):
        c = self.config
        norm = functools.partial(RMSNorm, c["rms_norm_eps"], self.dtype)
        sliding = self.kind == "sliding_attention"
        attn = GatedAttention(
            c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["rms_norm_eps"],
            float(c["rope_theta"]) if sliding else None,
            c["sliding_window"] if sliding else None,
            self.dtype, self.attn_impl, name="attn",
        )(norm(name="input_norm")(h))
        h = h + norm(name="post_attn_norm")(attn)
        x = norm(name="pre_mlp_norm")(h)
        if self.dense:
            y = SwiGLU(c["hidden_size"], c["intermediate_size"], self.dtype, name="mlp")(x)
        else:
            y = TopKMoE(
                c["hidden_size"], c["moe_intermediate_size"], c["num_experts"],
                c["num_experts_per_tok"], c["num_experts_held"],
                c["first_expert"], float(c["route_scale"]), c["route_norm"],
                dtype=self.dtype, gmm=self.moe_gmm,
                bias_update_rate=float(c["load_balance_coeff"]),
                shared_hidden=c["num_shared_experts"] * c["moe_intermediate_size"],
                name="moe",
            )(x, train=self.train)
        return h + norm(name="post_mlp_norm")(y)


class Afmoe(nn.Module):
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
        init = nn.initializers.normal(stddev=0.02)
        shape = (c["vocab_size"], c["hidden_size"])
        embedding = self.param("embedding", init, shape, jnp.float32)
        head = self.param("lm_head", init, shape, jnp.float32)
        with jax.named_scope("embed"):
            h = embedding.astype(self.dtype)[tokens]
            if c["mup_enabled"]:
                h = h * jnp.asarray(math.sqrt(c["hidden_size"]), self.dtype)
        # prevent_cse stays on, as in models/lfm2.py: the layers are a
        # Python loop
        layer = nn.remat(AfmoeLayer) if self.remat else AfmoeLayer
        for i, kind in enumerate(c["layer_types"]):
            h = layer(
                c, kind, i < c["num_dense_layers"], self.dtype, self.moe_gmm,
                self.attn_impl, train, name=f"layers_{i}",
            )(h)
        h = RMSNorm(c["rms_norm_eps"], self.dtype, name="norm_out")(h)
        with jax.named_scope("lm_head"):
            return jnp.einsum(
                "bsd,vd->bsv", h, head.astype(self.dtype),
                preferred_element_type=jnp.float32,
            )


TRINITY_MINI_MODEL = zoo_entry(Afmoe, TRINITY_MINI)
AFMOE_TINY_MODEL = zoo_entry(Afmoe, AFMOE_TINY)
