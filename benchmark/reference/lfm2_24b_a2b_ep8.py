"""Plain reference for ``lfm2_24b_a2b_ep8``: one chip's share of
LFM2-24B-A2B (Liquid AI, ``model_type`` ``lfm2_moe``;
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json) under
eight-way expert parallelism, written from the published configuration and
the family's description: a decoder of gated short convolutions and
grouped-query attention layers, RMSNorm before each mixer and each FFN,
two leading dense SwiGLU layers and sigmoid-routed top-4 experts after
them, tied embedding.  Straightforward ``jax.numpy`` in float32 (the caller
sets ``highest`` matmul precision); no kernel, no sort, no dispatch buffer;
no module of the program is imported.

Takes the program's parameter tree as plain arrays: ``embedding``,
``layers_<i>`` holding ``operator_norm``, ``ffn_norm``, a mixer
(``short_conv`` or ``attn``) and an FFN (``mlp`` or ``moe``), ``norm_out``;
and its ``batch_stats`` tree, whose only leaves are the expert layers'
selection bias ``layers_<i>/moe/expert_bias``.  A layer's kind is read off
its keys, the heads off ``q_norm``'s width, the experts held off the
expert stack's leading axis.

Departures from the published description, each because the configuration
under test states it (``configs/lfm2_24b_a2b_ep8.json``, ``reduced`` and
``assumed``):
- the share of a deployment: experts ``first_expert`` .. of the 64 are held
  and only they add to an expert layer's output, the normaliser of the
  routing weights running over all four selected; the vocabulary is its
  first rows, and logits, softmax and loss are over that slice; five of the
  forty layers;
- the embedding is tied to the output head (the family ties them; the
  catalog's ``config`` has no key for it);
- the selection bias is a constant drawn at initialisation: the config
  publishes no update rule for it, so none is applied;
- one document a sequence: positions ``0 .. S-1``, a plain causal mask;
- attention's scores are materialised one key-value head at a time, under
  ``jax.checkpoint``, so that a 4,096-token sequence fits beside the
  program on the chip; the arithmetic is the unblocked one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# what the parameter tree's shapes do not say (the published ``config``)
ARCH = {
    "norm_eps": 1e-5,
    "rope_theta": 1e6,
    "num_experts_per_tok": 4,
    "routed_scaling_factor": 1.0,
    "norm_topk_prob": True,
    "first_expert": 0,
}


def operand(x):
    """Every matrix product's operands pass through here.  The identity: the
    reference is float32.  ``tools/precision_below.py`` puts a rounding to a
    lower precision here, to read what computing in it would cost (the
    router stays float32 there, as the program keeps it)."""
    return x


def mm(a, b):
    return operand(a) @ operand(b)


def make_batch(config, n, rng):
    """``n`` seeded token rows from the held vocabulary slice and, as
    labels, the next token of each."""
    spec = config["compare"]
    rows = rng.integers(
        0, int(spec["vocab"]), (n, int(spec["tokens"]) + 1), dtype=np.int32
    )
    return rows[:, :-1], rows[:, 1:]


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def short_conv(h, p):
    """``(B, C, X) = split3(h W_in)``; ``u = B * X``; the depthwise causal
    convolution over the last ``L`` positions; ``out = (C * v) W_out``."""
    b_gate, c_gate, x = jnp.split(mm(h, p["in_proj"]["kernel"]), 3, axis=-1)
    u = b_gate * x
    w = p["conv_kernel"]  # (channels, L): w[:, L-1] weighs the current token
    taps, s = w.shape[1], u.shape[1]
    v = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j  # u_{t - back}, zeros before the sequence
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :s]
        v = v + w[:, j] * shifted
    return mm(c_gate * v, p["out_proj"]["kernel"])


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rope(x, theta):
    """``x`` is ``(B, S, heads, d)``; position ``t`` turns pair ``(i, i +
    d/2)`` by ``t * theta^(-2i/d)``."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    return x * jnp.cos(angles) + rotate_half(x) * jnp.sin(angles)


def attention(h, p, arch):
    b, s, _ = h.shape
    d = p["q_norm"]["scale"].shape[0]
    q = mm(h, p["q_proj"]["kernel"]).reshape(b, s, -1, d)
    k = mm(h, p["k_proj"]["kernel"]).reshape(b, s, -1, d)
    v = mm(h, p["v_proj"]["kernel"]).reshape(b, s, -1, d)
    q = rope(rms_norm(q, p["q_norm"]["scale"], arch["norm_eps"]), arch["rope_theta"])
    k = rope(rms_norm(k, p["k_norm"]["scale"], arch["norm_eps"]), arch["rope_theta"])
    group = q.shape[2] // k.shape[2]  # query heads a key-value head serves
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one_kv_head(qg, kh, vh):  # (b, s, group, d), (b, s, d), (b, s, d)
        scores = jnp.einsum("bqgd,bkd->bgqk", operand(qg), operand(kh)) / jnp.sqrt(jnp.float32(d))
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum(
            "bgqk,bkd->bqgd", operand(jax.nn.softmax(scores, axis=-1)), operand(vh)
        )

    out = [
        one_kv_head(q[:, :, i * group:(i + 1) * group], k[:, :, i], v[:, :, i])
        for i in range(k.shape[2])
    ]
    return mm(jnp.concatenate(out, axis=2).reshape(b, s, -1), p["o_proj"]["kernel"])


def swiglu(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def top_k_by_argmax(values, k):
    """The indices of the ``k`` largest entries of each row, largest first
    (the lowest index on a tie): ``k`` rounds of argmax, no sort."""
    picked = []
    for _ in range(k):
        i = jnp.argmax(values, axis=-1)
        picked.append(i)
        values = jnp.where(
            jax.nn.one_hot(i, values.shape[-1], dtype=bool), -jnp.inf, values
        )
    return jnp.stack(picked, axis=-1)


def moe(x, p, bias, arch):
    """Sigmoid scores over every expert; ``top4(s + b)``; weights ``s[sel]
    / (sum + 1e-6) * scale``; the sum, over the experts held here, of weight
    times expert — each held expert on every token, masked."""
    scores = jax.nn.sigmoid(x @ p["router"])  # (b, s, experts)
    sel = top_k_by_argmax(scores + bias, arch["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if arch["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * arch["routed_scaling_factor"]
    out = jnp.zeros_like(x)
    for e in range(p["w1"].shape[0]):
        mine = jnp.sum(jnp.where(sel == arch["first_expert"] + e, w, 0.0), axis=-1)
        out = out + mine[..., None] * swiglu(x, p["w1"][e], p["w3"][e], p["w2"][e])
    return out


def forward(params, batch_stats, tokens, arch=None):
    """``tokens (B, S) int32 -> (logits (B, S, vocab), batch_stats)``."""
    arch = {**ARCH, **(arch or {})}
    eps = arch["norm_eps"]
    h = params["embedding"][tokens]
    for i in range(sum(k.startswith("layers_") for k in params)):
        p = params[f"layers_{i}"]

        @jax.checkpoint
        def layer(h, p=p, i=i):
            x = rms_norm(h, p["operator_norm"]["scale"], eps)
            if "short_conv" in p:
                h = h + short_conv(x, p["short_conv"])
            else:
                h = h + attention(x, p["attn"], arch)
            x = rms_norm(h, p["ffn_norm"]["scale"], eps)
            if "mlp" in p:
                m = p["mlp"]
                return h + swiglu(
                    x, m["w1"]["kernel"], m["w3"]["kernel"], m["w2"]["kernel"]
                )
            bias = batch_stats[f"layers_{i}"]["moe"]["expert_bias"]
            return h + moe(x, p["moe"], bias, arch)

        h = layer(h)
    h = rms_norm(h, params["norm_out"]["scale"], eps)
    return mm(h, params["embedding"].T), batch_stats


def next_token_loss(logits, labels):
    """Mean over every token of the batch of -log softmax(logits)[next]."""
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def step(params, batch_stats, tokens, labels, recipe):
    """One AdamW step (Loshchilov & Hutter 2019) from a fresh optimizer
    state: ``m = (1 - b1) g``, ``v = (1 - b2) g^2``, bias-corrected to ``g``
    and ``g^2``; decoupled decay on matrices (two axes or more) only.  The
    selection bias is a buffer: no gradient reaches it, no rule moves it.
    ``recipe``: ``lr``, ``beta1``, ``beta2``, ``eps``, ``weight_decay``,
    optionally ``arch`` (test widths)."""
    arch = recipe.get("arch")

    def loss_fn(p):
        logits, _ = forward(p, batch_stats, tokens, arch)
        return next_token_loss(logits, labels)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    b1, b2 = recipe["beta1"], recipe["beta2"]

    def adamw(p, g):
        m_hat = (1 - b1) * g / (1 - b1)
        v_hat = (1 - b2) * g * g / (1 - b2)
        decay = recipe["weight_decay"] * p if p.ndim >= 2 else 0.0
        return p - recipe["lr"] * (m_hat / (jnp.sqrt(v_hat) + recipe["eps"]) + decay)

    grad_norm = jnp.sqrt(
        sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads))
    )
    return {
        "loss": loss, "grad_norm": grad_norm,
        "params": jax.tree_util.tree_map(adamw, params, grads),
        "batch_stats": batch_stats,
    }
