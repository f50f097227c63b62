"""Nemotron-H: a token decoder whose every layer is one mixer alone — a
Mamba-2 state-space mixer, an expert layer of squared-ReLU experts beside a
shared expert, or grouped-query attention without positions — in the order
its config spells out (NVIDIA; ``model_type`` ``nemotron_h``).

``NEMOTRON_3_NANO_30B_A3B`` is the published ``config.json`` of
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` whole
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json);
no width is ever cut.  What one chip holds of it is a ``--model-cut``
(``models/token_parts.py parse_cut``), as for the other token decoders: how
many layers, which experts, how many rows of the vocabulary.  The config
publishes ``hybrid_override_pattern`` (``M`` Mamba-2, ``E`` experts, ``*``
attention; this one has no ``-``, a dense MLP) and no leading dense layer:
``derived`` adds ``layer_types``, ``num_dense_layers`` 0 and ``num_experts``,
the keys ``cut_config`` and the expert layer read.

The equations (``eps`` = ``layer_norm_epsilon``; no bias on any projection;
float32 statistics, decays and state, the activations' dtype as operands;
what the config's keys do not carry is from ``transformers``
``models/nemotron_h/modeling_nemotron_h.py`` and ``mamba_ssm``'s ``Mamba2``,
written from knowledge of them):

1. ``h = E[tokens]``; for layer ``i``: ``h = h + mixer_i(norm_i(h))``, one
   norm a layer (``RMSNorm``, ``w`` from one); ``logits = norm_f(h)
   W_head^T``, the head untied.
2. Mamba-2 (``H = mamba_num_heads`` heads of ``P = mamba_head_dim``,
   ``d_inner = H P`` — ``expand`` is not read; ``G = n_groups``, ``N =
   ssm_state_size``): ``z | xBC | dt = u W_in`` (``d_inner`` | ``d_inner +
   2 G N`` | ``H``); ``xBC = silu(conv(xBC) + b_conv)``, a causal depthwise
   convolution of ``conv_kernel`` taps, zeros before the sequence (scope
   ``ssm_conv``); ``x | B | C = xBC`` (``d_inner`` | ``G N`` | ``G N``), head
   ``h`` using group ``h // (H / G)``'s ``B_t`` and ``C_t``; ``dt =
   softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per head from ``S = 0``:
   ``S <- exp(dt A) S + (dt x) B^T``; ``y = S C + D x`` (``ops/ssd.py
   ssd_scan``, the chunked form at ``chunk_size`` tokens a chunk, scope
   ``ssd_scan``: a Pallas kernel pair on a TPU where ``N`` and ``(H / G) P``
   are whole lane tiles and the length whole chunks — its backward keeps
   each chunk's start states, nothing ``chunk x chunk`` — else composed XLA
   with autodiff through it, the tiny models on a CPU among them;
   ``kernel_paths`` says which as ``ssd``); ``y = y * silu(z)``, then RMS-normalised over each of the
   ``G`` groups of ``d_inner / G`` channels and scaled by ``w`` — gate
   first, norm second (scope ``ssm_gate_norm``); ``out = y W_out``.
3. Attention (``num_attention_heads`` / ``num_key_value_heads`` heads of
   ``head_dim``): ``q, k, v`` by three projections, **no rotary embedding and
   no other position signal** (``rope_theta`` and ``partial_rotary_factor``
   are read by nothing), causal softmax at ``head_dim^-1/2`` through
   ``ops/attention.py``'s dispatcher, ``W_o``.  No gate, no head norm.
4. Expert layer (``models/moe.py TopKMoE``, ``mlp="relu2"``): ``s =
   sigmoid(u W_r)`` in float32; ``sel = top_k(s + b)``; ``w =
   routed_scaling_factor * s[sel] / sum s[sel]``; ``y = sum over sel held
   here of w_e W_2e relu(W_1e u)^2 + W_2s relu(W_1s u)^2``, the shared expert
   ``moe_shared_expert_intermediate_size`` wide, unweighted.  ``b`` is a
   buffer the training call moves by the auxiliary-loss-free rule at
   ``BIAS_UPDATE_RATE``.  No auxiliary loss, no multi-token prediction (the
   config has no key for either).
5. Initialisers: matrices normal 0.02, a mixer's out-projection (``W_out``,
   ``W_o``) divided by ``sqrt(num_hidden_layers)`` of the published depth
   (``rescale_prenorm_residual``); ``A_log = log U(1, 16)``; ``dt_bias =
   softplus^-1(dt)``, ``dt`` log-uniform in ``[time_step_min,
   time_step_max]``, floored at ``time_step_floor``; ``D`` = 1; the
   convolution's taps and bias ``U(-1/sqrt(taps), 1/sqrt(taps))``.

Scopes a device trace shows: ``embed``, ``mamba`` (the whole state-space
mixer) with ``ssm_conv``, ``ssd_scan`` and ``ssm_gate_norm`` inside it,
``attn`` with ``attention`` inside it, ``moe`` with ``moe_gmm`` and
``shared_expert`` inside it, ``lm_head``.  A training call sows
``moe_metrics/ssm_decay_mean``, the mean of ``exp(dt A)`` over a Mamba-2
layer's tokens and heads (the ``metrics`` event's gauge ``ssm/decay_mean``).
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attention
from ..ops.ssd import ssd_scan
from .moe import TopKMoE
from .token_parts import RMSNorm, _dense, zoo_entry

NEMOTRON_3_NANO_30B_A3B = {
    "attention_bias": False,
    "chunk_size": 128,
    "conv_kernel": 4,
    "expand": 2,
    "head_dim": 128,
    "hidden_size": 2688,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64,
    "mamba_hidden_act": "silu",
    "mamba_num_heads": 64,
    "mamba_proj_bias": False,
    "max_position_embeddings": 262144,
    "mlp_bias": False,
    "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712,
    "n_group": 1,
    "n_groups": 8,
    "n_routed_experts": 128,
    "n_shared_experts": 1,
    "norm_eps": 1e-05,
    "norm_topk_prob": True,
    "num_attention_heads": 32,
    "num_experts_per_tok": 6,
    "num_hidden_layers": 52,
    "num_key_value_heads": 2,
    "num_logits_to_keep": 1,
    "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True,
    "residual_in_fp32": False,
    "rope_theta": 10000,
    "routed_scaling_factor": 2.5,
    "sliding_window": None,
    "ssm_state_size": 128,
    "tie_word_embeddings": False,
    "time_step_floor": 0.0001,
    "time_step_max": 0.1,
    "time_step_min": 0.001,
    "topk_group": 1,
    "use_bias": False,
    "use_conv_bias": True,
    "use_mamba_kernels": True,
    "vocab_size": 131072,
}
# the same pattern at test widths (tests/, rehearsals): never a cell.  Two
# groups of four heads, a chunk inside a test's sequence, a head size apart
# from hidden / heads.
NEMOTRON_H_TINY = {
    **NEMOTRON_3_NANO_30B_A3B,
    "chunk_size": 16,
    "head_dim": 32,
    "hidden_size": 64,
    "hybrid_override_pattern": "MEMEM*EMEMEM*E",
    "intermediate_size": 48,
    "mamba_head_dim": 8,
    "mamba_num_heads": 8,
    "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 40,
    "n_groups": 2,
    "n_routed_experts": 16,
    "num_attention_heads": 4,
    "num_experts_per_tok": 4,
    "num_hidden_layers": 14,
    "num_key_value_heads": 2,
    "ssm_state_size": 16,
    "vocab_size": 512,
}
LAYER_KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
# the selection bias's step (the Nemotron 3 Nano report's, as recalled; the
# config has no key for it)
BIAS_UPDATE_RATE = 1e-3


def derived(config: dict) -> dict:
    """``config`` with the keys ``token_parts.cut_config`` and the layers
    read and the published file leaves to the code: the kind of every layer
    from ``hybrid_override_pattern``, no leading dense layer, the routed
    experts under the name the cut knows, and the published depth (the cut
    overwrites ``num_hidden_layers``; the initialiser divides by this)."""
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"] or set(pattern) - set(LAYER_KINDS):
        raise ValueError(
            f"hybrid_override_pattern {pattern!r}: {config['num_hidden_layers']} "
            f"layers of {sorted(LAYER_KINDS)} (a dense MLP layer, '-', is not built)"
        )
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("group-limited routing: this decoder builds none")
    return {
        **config,
        "layer_types": [LAYER_KINDS[c] for c in pattern],
        "num_dense_layers": 0,
        "num_experts": config["n_routed_experts"],
        "published_num_hidden_layers": config["num_hidden_layers"],
    }


def _uniform(low, high):
    return lambda key, shape, dtype: jax.random.uniform(key, shape, dtype, low, high)


class Mamba2Mixer(nn.Module):
    """Step 2 of the module docstring."""

    dim: int
    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int
    chunk: int
    eps: float
    dt_limits: tuple  # (time_step_min, time_step_max, time_step_floor)
    out_std: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        b, s, _ = u.shape
        h, p, g, n, taps = self.heads, self.head_dim, self.groups, self.state, self.conv_kernel
        inner, bc = h * p, g * n
        zxbcdt = _dense(2 * inner + 2 * bc + h, self.dtype, "in_proj")(u)
        z, xbc, dt = jnp.split(zxbcdt, (inner, 2 * inner + 2 * bc), axis=-1)
        w = self.param(
            "conv_kernel", _uniform(-taps ** -0.5, taps ** -0.5),
            (inner + 2 * bc, taps), jnp.float32,
        )
        b_conv = self.param(
            "conv_bias", _uniform(-taps ** -0.5, taps ** -0.5),
            (inner + 2 * bc,), jnp.float32,
        )
        a_log = self.param(
            "A_log", lambda *a: jnp.log(_uniform(1.0, 16.0)(*a)), (h,), jnp.float32
        )

        def dt_bias_init(key, shape, dtype):
            low, high, floor = self.dt_limits
            step = jnp.exp(_uniform(math.log(low), math.log(high))(key, shape, dtype))
            step = jnp.maximum(step, floor)
            return step + jnp.log(-jnp.expm1(-step))  # softplus^-1

        dt_bias = self.param("dt_bias", dt_bias_init, (h,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        scale = self.param("norm_scale", nn.initializers.ones, (inner,), jnp.float32)

        with jax.named_scope("ssm_conv"):
            padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
            w_t = w.astype(self.dtype)
            xbc = jax.nn.silu(
                sum(w_t[:, j] * padded[:, j:j + s] for j in range(taps))
                + b_conv.astype(self.dtype)
            )
        x, B, C = jnp.split(xbc, (inner, inner + bc), axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        A = -jnp.exp(a_log)
        self.sow("moe_metrics", "ssm_decay_mean", jnp.mean(jnp.exp(dt * A)))
        y = ssd_scan(
            x.reshape(b, s, h, p), dt, A, B.reshape(b, s, g, n),
            C.reshape(b, s, g, n), skip, chunk=self.chunk,
        )
        with jax.named_scope("ssm_gate_norm"):
            y = y.reshape(b, s, inner).astype(jnp.float32)
            y = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(b, s, g, inner // g)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + self.eps)
            y = (y.reshape(b, s, inner) * scale).astype(self.dtype)
        return _dense(self.dim, self.dtype, "out_proj", self.out_std)(y)


class Attention(nn.Module):
    """Step 3 of the module docstring."""

    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    out_std: float
    dtype: Any = jnp.float32
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, u):
        b, s, _ = u.shape
        hd = self.head_dim
        q = _dense(self.heads * hd, self.dtype, "q_proj")(u).reshape(b, s, self.heads, hd)
        k = _dense(self.kv_heads * hd, self.dtype, "k_proj")(u).reshape(b, s, self.kv_heads, hd)
        v = _dense(self.kv_heads * hd, self.dtype, "v_proj")(u).reshape(b, s, self.kv_heads, hd)
        o = attention(q, k, v, causal=True, layout="bshd", impl=self.attn_impl)
        return _dense(self.dim, self.dtype, "o_proj", self.out_std)(
            o.reshape(b, s, self.heads * hd)
        )


class NemotronHLayer(nn.Module):
    config: Any  # the cut config, frozen
    kind: str
    dtype: Any = jnp.float32
    moe_gmm: str = "auto"
    attn_impl: str = "auto"
    train: bool = False

    @nn.compact
    def __call__(self, h):
        c = self.config
        u = RMSNorm(c["layer_norm_epsilon"], self.dtype, name="norm")(h)
        out_std = 0.02
        if c["rescale_prenorm_residual"]:
            out_std /= math.sqrt(c["published_num_hidden_layers"])
        if self.kind == "mamba":
            mixed = Mamba2Mixer(
                c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
                c["n_groups"], c["ssm_state_size"], c["conv_kernel"],
                c["chunk_size"], c["layer_norm_epsilon"],
                (c["time_step_min"], c["time_step_max"], c["time_step_floor"]),
                out_std, self.dtype, name="mamba",
            )(u)
        elif self.kind == "attention":
            mixed = Attention(
                c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"], c["head_dim"], out_std, self.dtype,
                self.attn_impl, name="attn",
            )(u)
        else:
            mixed = TopKMoE(
                c["hidden_size"], c["moe_intermediate_size"], c["num_experts"],
                c["num_experts_per_tok"], c["num_experts_held"],
                c["first_expert"], float(c["routed_scaling_factor"]),
                c["norm_topk_prob"], dtype=self.dtype, gmm=self.moe_gmm,
                bias_update_rate=BIAS_UPDATE_RATE,
                shared_hidden=c["n_shared_experts"]
                * c["moe_shared_expert_intermediate_size"],
                mlp=c["mlp_hidden_act"], name="moe",
            )(u, train=self.train)
        return h + mixed


class NemotronH(nn.Module):
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
        layer = nn.remat(NemotronHLayer) if self.remat else NemotronHLayer
        for i, kind in enumerate(c["layer_types"]):
            h = layer(
                c, kind, self.dtype, self.moe_gmm, self.attn_impl, train,
                name=f"layers_{i}",
            )(h)
        h = RMSNorm(c["layer_norm_epsilon"], self.dtype, name="norm_f")(h)
        with jax.named_scope("lm_head"):
            return jnp.einsum(
                "bsd,vd->bsv", h, head.astype(self.dtype),
                preferred_element_type=jnp.float32,
            )


NEMOTRON_H_MODEL = zoo_entry(NemotronH, derived(NEMOTRON_3_NANO_30B_A3B))
NEMOTRON_H_TINY_MODEL = zoo_entry(NemotronH, derived(NEMOTRON_H_TINY))
