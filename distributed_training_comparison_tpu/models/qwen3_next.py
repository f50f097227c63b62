"""Qwen3-Next: a token decoder of Gated DeltaNet (linear-attention) layers,
three to one gated softmax-attention layer, every layer's MLP 512
softmax-routed experts beside a gated shared expert (Qwen; ``model_type``
``qwen3_next``).

``QWEN3_NEXT_80B_A3B`` is the published ``config.json`` of
``Qwen/Qwen3-Next-80B-A3B-Instruct`` whole
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json);
no width is ever cut.  What one chip holds of it is a ``--model-cut``
(``models/token_parts.py parse_cut``), as for ``models/lfm2.py`` and
``models/afmoe.py``: how many layers, which experts, how many rows of the
vocabulary.  The config publishes no ``layer_types`` and has no leading
dense layer: ``derived`` adds ``layer_types`` (from
``full_attention_interval``) and ``num_dense_layers`` 0, the keys
``cut_config`` reads.

The equations (``eps`` = ``rms_norm_eps``; no bias on any projection; what
the config's keys do not carry is from ``transformers``
``models/qwen3_next/modeling_qwen3_next.py``, written from knowledge of it):

1. ``h0 = E[tokens]``, not scaled.  Layer ``i`` is a ``full_attention``
   layer iff ``(i + 1) % full_attention_interval == 0``, else a
   ``linear_attention`` (Gated DeltaNet) layer; every layer's MLP is the
   expert layer.  ``h = h + mixer(norm_in(h))``; ``h = h +
   moe(norm_post(h))``.
2. Every norm but step 6's is zero-centred: ``y = x * rsqrt(mean(x^2) + eps)
   * (1 + w)``, ``w`` from zero, statistics in float32.
3. Gated DeltaNet (``linear_num_key_heads`` key and ``linear_num_value_heads``
   value heads of ``linear_key_head_dim`` / ``linear_value_head_dim``).  From
   ``a = norm_in(h)``: ``q | k | v | z`` by one projection (``in_proj_qkvz``:
   key width, key width, value width, value width, in that order, heads
   contiguous in each), ``b | alpha`` by one of ``2 x value heads``
   (``in_proj_ba``).
4. ``q | k | v`` go through a causal depthwise convolution of
   ``linear_conv_kernel_dim`` taps, no bias, then SiLU.  ``q`` and ``k`` are
   L2-normalised per head (``x * rsqrt(sum x^2 + 1e-6)``), ``q`` scaled by
   ``key head size^-1/2``; each key head serves ``value heads / key heads``
   consecutive value heads.  (``ops/gdn_pointwise.py short_conv_l2norm``: on
   a TPU, at head sizes of whole lanes and a length of whole token tiles,
   one fused pass over the ``(B, S, H d)`` columns of ``qkvz`` as the
   projection wrote them — convolution, SiLU and the norms in float32 in
   VMEM, q, k and v written flat for the scan's kernels; every other call,
   the CPU's and ``qwen3_next_tiny``'s among them, composed XLA: the
   convolution and SiLU in the activations' dtype, the norms in float32
   over ``(B, S, H, d)``.  The call shows which.)
5. Per value head and token in float32: ``beta = sigmoid(b)``, ``g =
   -exp(A_log) * softplus(alpha + dt_bias)`` (``A_log = log(u)``, ``u ~ U(0,
   16)``; ``dt_bias`` = 1), and the gated delta rule ``S <- exp(g) S``; ``d
   = beta (v - S^T k)``; ``S <- S + k d^T``; ``o = S^T q``
   (``ops/gated_delta.py``, the chunked form: on a TPU, at head sizes of
   whole lanes and a length of whole chunks, a Pallas kernel pair that reads
   q, k, v as the projections leave them; every other call, the CPU's and
   ``qwen3_next_tiny``'s among them, composed XLA.  The call shows which).
6. ``y = (w * o * rsqrt(mean(o^2) + eps)) * silu(z)`` per head (``w`` from
   one), then ``out_proj`` (``ops/gdn_pointwise.py gated_rms_norm``: on the
   same calls as step 4 one fused pass that reads ``o`` as the scan wrote it
   and ``z`` in ``qkvz``'s last columns; else composed XLA in float32).
7. Full attention (``num_attention_heads`` / ``num_key_value_heads`` heads of
   ``head_dim``): ``q_proj`` gives each head its query and its gate, side
   by side; ``k_proj``, ``v_proj``.  Zero-centred per-head RMSNorm on q and
   k; rotate-half RoPE at ``rope_theta`` on the first ``head_dim *
   partial_rotary_factor`` elements of each head; ``o = softmax(q k^T /
   sqrt(head_dim) + causal) v`` through ``ops/attention.py``'s dispatcher;
   ``attn = (o * sigmoid(gate)) W_o``.
8. Expert layer (``models/moe.py TopKMoE``): ``p = softmax(m W_r)`` over all
   experts in float32, ``sel = top_k(p)``, ``w = p[sel] / sum p[sel]``, ``y =
   sum over sel of w_e expert_e(m) + sigmoid(m w_sg) * shared(m)``, every
   expert a SwiGLU at ``moe_intermediate_size`` and the shared one at
   ``shared_expert_intermediate_size``.  No selection bias, no scaling
   factor, no auxiliary loss, no multi-token-prediction module (the config
   has no key for either).
9. ``norm_out``, then ``logits = h W_head``, untied.

Scopes a device trace shows: ``embed``, ``gdn`` (the whole DeltaNet mixer)
with ``gdn_conv``, ``gdn_scan`` and ``gdn_gate_norm`` inside it, ``attn``
with ``attn_gate`` (the gate's sigmoid and multiply; its projection is
``q_proj``'s other half) and ``attention`` inside it, ``moe`` with ``moe_gmm`` and
``shared_expert`` inside it, ``lm_head``.  No scope of the DeltaNet mixer
has ``attention`` as a path element.  On the fused path ``gdn_conv`` holds
all of step 4 — the kernels ``gdn_conv_fwd`` and ``gdn_conv_bwd`` (q, k
and v in one call), the l2-norms inside them — and ``gdn_gate_norm`` the
kernels ``gdn_gate_norm_fwd`` and ``gdn_gate_norm_bwd``; on the composed
path ``gdn_conv`` is the convolution and SiLU alone and the l2-norms are
ops of ``gdn``.  A compile event counts a program's mixer call sites by
form (``gdn_pointwise: {"fused": n, "composed": m}``).
``gdn_scan`` holds every op of the
scan on either path: the kernels ``gated_delta_fwd`` and ``gated_delta_bwd``
with the running sums of ``g`` around them, or the composed form's loop.
For its backward the kernel path keeps each chunk's start state and solved
``T`` (268 + 34 MB a layer at 8,192 tokens), which live inside one
rematerialised layer's backward.  A training call sows
``moe_metrics/gdn_decay_mean``, the mean of ``exp(g)`` over a DeltaNet
layer's tokens and heads (the ``metrics`` event's gauge ``gdn/decay_mean``).
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attention
from ..ops.gated_delta import gated_delta_rule
from ..ops.gdn_pointwise import gated_rms_norm, short_conv_l2norm
from .moe import TopKMoE
from .token_parts import RMSNorm, _dense, rope, zoo_entry

QWEN3_NEXT_80B_A3B = {
    "decoder_sparse_step": 1,
    "full_attention_interval": 4,
    "head_dim": 256,
    "hidden_act": "silu",
    "hidden_size": 2048,
    "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128,
    "linear_num_key_heads": 16,
    "linear_num_value_heads": 32,
    "linear_value_head_dim": 128,
    "max_position_embeddings": 262144,
    "mlp_only_layers": [],
    "model_type": "qwen3_next",
    "moe_intermediate_size": 512,
    "norm_topk_prob": True,
    "num_attention_heads": 16,
    "num_experts": 512,
    "num_experts_per_tok": 10,
    "num_hidden_layers": 48,
    "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06,
    "rope_scaling": None,
    "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False,
    "use_sliding_window": False,
    "vocab_size": 151936,
}
# the same pattern at test widths (tests/, rehearsals): never a cell.  Two
# value heads a key head, a head size apart from hidden / heads, rotary on a
# quarter of it.
QWEN3_NEXT_TINY = {
    **QWEN3_NEXT_80B_A3B,
    "head_dim": 32,
    "hidden_size": 64,
    "linear_key_head_dim": 16,
    "linear_num_key_heads": 2,
    "linear_num_value_heads": 4,
    "linear_value_head_dim": 24,
    "moe_intermediate_size": 48,
    "num_attention_heads": 4,
    "num_experts": 16,
    "num_experts_per_tok": 4,
    "num_hidden_layers": 8,
    "num_key_value_heads": 2,
    "shared_expert_intermediate_size": 40,
    "vocab_size": 512,
}
GDN_CHUNK = 64  # tokens a sequential step of the scan (ops/gated_delta.py)


def derived(config: dict) -> dict:
    """``config`` with the keys ``token_parts.cut_config`` reads and the
    published file leaves to the code: the kind of every layer from
    ``full_attention_interval``, and no leading dense layer (``mlp_only_layers``
    is empty and ``decoder_sparse_step`` 1: every MLP is the expert layer)."""
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1:
        raise ValueError("a dense MLP layer: this decoder builds none")
    every = config["full_attention_interval"]
    return {
        **config,
        "layer_types": [
            "full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(config["num_hidden_layers"])
        ],
        "num_dense_layers": 0,
    }


class GatedDeltaNet(nn.Module):
    """Steps 3-6 of the module docstring."""

    dim: int
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int
    eps: float
    dtype: Any = jnp.float32
    chunk: int = GDN_CHUNK

    @nn.compact
    def __call__(self, h):
        hk, hv, dk, dv = self.key_heads, self.value_heads, self.key_dim, self.value_dim
        keys, values = hk * dk, hv * dv
        qkvz = _dense(2 * keys + 2 * values, self.dtype, "in_proj_qkvz")(h)
        ba = _dense(2 * hv, self.dtype, "in_proj_ba")(h).astype(jnp.float32)
        # HF leaves its Conv1d at torch's default, U(+-1/sqrt(taps))
        taps = self.conv_kernel
        w = self.param(
            "conv_kernel",
            lambda key, shape, dtype: jax.random.uniform(
                key, shape, dtype, -taps ** -0.5, taps ** -0.5
            ),
            (2 * keys + values, taps), jnp.float32,
        )
        a_log = self.param(
            "A_log",
            lambda key, shape, dtype: jnp.log(
                jax.random.uniform(key, shape, dtype, 0.0, 16.0)
            ),
            (hv,), jnp.float32,
        )
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,), jnp.float32)
        q, k, v = short_conv_l2norm(
            qkvz, w, key_heads=hk, value_heads=hv, key_dim=dk, value_dim=dv
        )
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
        self.sow("moe_metrics", "gdn_decay_mean", jnp.mean(jnp.exp(g)))
        o = gated_delta_rule(q, k, v, g, beta, chunk=self.chunk)
        scale = self.param("norm_scale", nn.initializers.ones, (dv,), jnp.float32)
        o = gated_rms_norm(o, qkvz, scale, key_dim=dk, eps=self.eps)
        return _dense(self.dim, self.dtype, "out_proj")(o)


class GatedAttention(nn.Module):
    """Step 7 of the module docstring."""

    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary: int
    eps: float
    theta: float
    dtype: Any = jnp.float32
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, h):
        b, s, _ = h.shape
        hd, wide = self.head_dim, self.heads * self.head_dim
        norm = functools.partial(RMSNorm, self.eps, self.dtype, True)
        # one matrix gives each head its query and its gate, side by side
        q, gate = jnp.split(
            _dense(2 * wide, self.dtype, "q_proj")(h).reshape(
                b, s, self.heads, 2 * hd
            ), 2, axis=-1,
        )
        k = _dense(self.kv_heads * hd, self.dtype, "k_proj")(h).reshape(b, s, self.kv_heads, hd)
        v = _dense(self.kv_heads * hd, self.dtype, "v_proj")(h).reshape(b, s, self.kv_heads, hd)
        q = rope(norm(name="q_norm")(q), self.theta, self.rotary)
        k = rope(norm(name="k_norm")(k), self.theta, self.rotary)
        o = attention(q, k, v, causal=True, layout="bshd", impl=self.attn_impl)
        with jax.named_scope("attn_gate"):
            o = (o * jax.nn.sigmoid(gate)).reshape(b, s, wide)
        return _dense(self.dim, self.dtype, "o_proj")(o)


class Qwen3NextLayer(nn.Module):
    config: Any  # the cut config, frozen
    kind: str
    dtype: Any = jnp.float32
    moe_gmm: str = "auto"
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, h):
        c = self.config
        norm = functools.partial(RMSNorm, c["rms_norm_eps"], self.dtype, True)
        x = norm(name="norm_in")(h)
        if self.kind == "linear_attention":
            mixed = GatedDeltaNet(
                c["hidden_size"], c["linear_num_key_heads"],
                c["linear_num_value_heads"], c["linear_key_head_dim"],
                c["linear_value_head_dim"], c["linear_conv_kernel_dim"],
                c["rms_norm_eps"], self.dtype, name="gdn",
            )(x)
        else:
            mixed = GatedAttention(
                c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"], c["head_dim"],
                int(c["head_dim"] * c["partial_rotary_factor"]),
                c["rms_norm_eps"], float(c["rope_theta"]), self.dtype,
                self.attn_impl, name="attn",
            )(x)
        h = h + mixed
        y = TopKMoE(
            c["hidden_size"], c["moe_intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], c["num_experts_held"], c["first_expert"],
            renormalise=c["norm_topk_prob"], use_bias=False, dtype=self.dtype,
            gmm=self.moe_gmm, shared_hidden=c["shared_expert_intermediate_size"],
            score="softmax", shared_gate=True, name="moe",
        )(norm(name="norm_post")(h))
        return h + y


class Qwen3Next(nn.Module):
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
        # prevent_cse stays on, as in models/lfm2.py: the layers are a
        # Python loop
        layer = nn.remat(Qwen3NextLayer) if self.remat else Qwen3NextLayer
        for i, kind in enumerate(c["layer_types"]):
            h = layer(
                c, kind, self.dtype, self.moe_gmm, self.attn_impl,
                name=f"layers_{i}",
            )(h)
        h = RMSNorm(c["rms_norm_eps"], self.dtype, True, name="norm_out")(h)
        with jax.named_scope("lm_head"):
            return jnp.einsum(
                "bsd,vd->bsv", h, head.astype(self.dtype),
                preferred_element_type=jnp.float32,
            )


QWEN3_NEXT_MODEL = zoo_entry(Qwen3Next, derived(QWEN3_NEXT_80B_A3B))
QWEN3_NEXT_TINY_MODEL = zoo_entry(Qwen3Next, derived(QWEN3_NEXT_TINY))
