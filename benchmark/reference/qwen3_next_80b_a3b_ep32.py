"""Plain reference for ``qwen3_next_80b_a3b_ep32``: one chip's share of
Qwen3-Next-80B-A3B (Qwen, ``model_type`` ``qwen3_next``;
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json)
under 32-way expert parallelism, written from the published configuration
and, for what its keys do not carry, from knowledge of the reference
implementation (``transformers`` ``models/qwen3_next/modeling_qwen3_next.py``:
``Qwen3NextGatedDeltaNet``, ``torch_recurrent_gated_delta_rule``,
``Qwen3NextAttention``, ``Qwen3NextSparseMoeBlock``, ``Qwen3NextRMSNorm``,
``Qwen3NextRMSNormGated``).  Straightforward ``jax.numpy`` in float32 (the
caller sets ``highest`` matmul precision); no kernel, no chunk algebra, no
sort, no dispatch buffer; no module of the program is imported.

The equations (``eps`` = ``rms_norm_eps`` 1e-6; no bias on any projection):

1. ``h0 = E[tokens]``.  ``h = h + mixer(norm_in(h))``; ``h = h +
   moe(norm_post(h))``; which mixer a layer has is ``ARCH["layer_types"]``.
2. Every norm but step 6's: ``y = x * rsqrt(mean(x^2) + eps) * (1 + w)``.
3. Gated DeltaNet, from ``a = norm_in(h)``: ``q | k | v | z = a W_qkvz``
   (key width, key width, value width, value width), ``b | alpha = a W_ba``.
4. ``q | k | v`` through a causal depthwise convolution (``taps`` rows of
   the kernel, zeros before the sequence), then SiLU.  ``q`` and ``k``
   L2-normalised per head (``x * rsqrt(sum x^2 + 1e-6)``), ``q`` scaled by
   ``key head size^-1/2``; key head ``j`` serves value heads ``j * r .. j *
   r + r - 1``, ``r = value heads / key heads``.
5. Per value head, **token by token**: ``beta_t = sigmoid(b_t)``; ``g_t =
   -exp(A_log) * softplus(alpha_t + dt_bias)``; from ``S = 0``: ``S <-
   exp(g_t) S``; ``d_t = beta_t (v_t - S^T k_t)``; ``S <- S + k_t d_t^T``;
   ``o_t = S^T q_t``.
6. ``y = (w * o * rsqrt(mean(o^2) + eps)) * silu(z)`` per head; ``W_out``.
7. Full attention: ``W_q`` gives each head its query and its gate side by
   side; zero-centred per-head RMSNorm on q and k; rotate-half RoPE on the
   first ``rotary`` elements of each head; ``o = softmax(q k^T /
   sqrt(head_dim) + causal) v``, each key-value head serving consecutive
   query heads; ``attn = (o * sigmoid(gate)) W_o``.
8. Expert layer: ``p = softmax(m W_r)``; ``sel = top_k(p)``; ``w = p[sel] /
   sum p[sel]``; ``y = sum over sel held here of w_e expert_e(m) + sigmoid(m
   w_sg) * shared(m)``.
9. ``norm_out``; ``logits = h W_head`` (untied).

Takes the program's parameter tree as plain arrays: ``embedding``,
``lm_head``, ``norm_out``, ``layers_<i>`` holding ``norm_in``, ``norm_post``,
a mixer (``gdn``: ``in_proj_qkvz``, ``in_proj_ba``, ``conv_kernel``,
``A_log``, ``dt_bias``, ``norm_scale``, ``out_proj``; or ``attn``:
``q_proj``, ``k_proj``, ``v_proj``, ``q_norm``, ``k_norm``, ``o_proj``) and
``moe`` (``router``, ``w1``, ``w3``, ``w2``, ``shared_expert``,
``shared_gate``).  There is no ``batch_stats`` leaf (no selection bias).
Head counts are read off the shapes (``in_proj_ba``, ``norm_scale``,
``q_norm``, ``k_proj``); the key head size of the DeltaNet layers and the
rotary width are ``ARCH``'s (the tree does not say).

Departures from the published description, each because the configuration
under test states it (``configs/qwen3_next_80b_a3b_ep32.json``, ``reduced``
and ``assumed``):
- the share of a deployment: experts ``first_expert`` .. of the 512 are held
  and only they and the gated shared expert add to an expert layer's output,
  the normaliser of the routing weights running over all ten selected; the
  vocabulary is its first rows, and logits, softmax and loss are over that
  slice; four of the forty-eight layers;
- the columns of the two fused DeltaNet projections are ``q | k | v | z``
  and ``b | alpha`` with heads contiguous in each, where the source
  interleaves them a key head at a time: the weights are seeded, no
  checkpoint is read, and the program has the same layout;
- no multi-token-prediction module and no auxiliary load-balancing loss (the
  configuration has no key for either);
- one document a sequence: positions ``0 .. S-1``, the state and the
  convolution start from zeros, no boundary resets them;
- the recurrence runs ``scan_block`` tokens at a time under
  ``jax.checkpoint`` (kept whole, the gradient of 8,192 steps holds 8,192 x
  32 states of 64 KiB, 17 GB), attention's scores one key-value head and
  one block of queries at a time, the logits one block of tokens at a
  time, each in a loop the compiler sees once; the arithmetic is the
  unblocked one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# what the parameter tree's shapes do not say (the published ``config``)
ARCH = {
    "rms_norm_eps": 1e-6,
    "rope_theta": 1e7,
    "rotary": 64,  # head_dim 256 * partial_rotary_factor 0.25
    "linear_key_head_dim": 128,
    "layer_types": ("linear_attention",) * 3 + ("full_attention",),
    "num_experts_per_tok": 10,
    "first_expert": 0,
    "query_block": 1024,  # rows of scores and of logits alive at a time
    "scan_block": 64,  # tokens of the recurrence between two checkpoints
}


def operand(x):
    """Every matrix product's operands pass through here.  The identity: the
    reference is float32.  ``tools/precision_below.py`` puts a rounding to a
    lower precision here (the router stays float32 there, as the program
    keeps it)."""
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


def rms_norm(x, w, eps):
    """Zero-centred: the learned scale is ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rope(x, theta, rotary):
    """``x`` is ``(B, S, heads, d)``; position ``t`` turns pair ``(i, i +
    rotary/2)`` of the first ``rotary`` elements by ``t * theta^(-2i /
    rotary)``; the rest pass."""
    s = x.shape[1]
    turned, passed = x[..., :rotary], x[..., rotary:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    turned = turned * jnp.cos(angles) + rotate_half(turned) * jnp.sin(angles)
    return jnp.concatenate([turned, passed], axis=-1)


def l2_normalised(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def recurrence(q, k, v, g, beta, block):
    """Step 5, token by token.  ``q``, ``k``: ``(B, S, H, dk)``; ``v``:
    ``(B, S, H, dv)``; ``g``, ``beta``: ``(B, S, H)`` (one key head a value
    head already).  The products are sums over an axis, exact in float32."""
    b, s, h, dv = v.shape
    if s % block:
        raise ValueError(f"{s} tokens are not whole blocks of {block}")

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x  # (B, H, d) and (B, H)
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.sum(operand(state) * operand(k_t)[..., None], axis=-2)
        d_t = beta_t[..., None] * (v_t - read)
        state = state + operand(k_t)[..., None] * operand(d_t)[..., None, :]
        return state, jnp.sum(operand(state) * operand(q_t)[..., None], axis=-2)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    def in_blocks(x):  # (B, S, ...) -> (S / block, block, B, ...)
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(s // block, block, *x.shape[1:])

    state = jnp.zeros((b, h, q.shape[-1], dv), jnp.float32)
    _, o = jax.lax.scan(tokens, state, tuple(in_blocks(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(s, b, h, dv), 0, 1)


def gated_delta_net(a, p, arch):
    """Steps 3-6."""
    b, s, _ = a.shape
    hv = p["in_proj_ba"]["kernel"].shape[1] // 2
    dv = p["norm_scale"].shape[0]
    dk = arch["linear_key_head_dim"]
    values = hv * dv
    keys = (p["conv_kernel"].shape[0] - values) // 2
    hk = keys // dk
    # a W_qkvz and a W_ba as one product against the two matrices side by
    # side (the compiler takes about a second for every float32 product at
    # ``highest`` precision, and the columns are the same)
    sides = [p["in_proj_qkvz"]["kernel"], p["in_proj_ba"]["kernel"]]
    qkvz, ba = jnp.split(
        mm(a, jnp.concatenate(sides, axis=1)), [sides[0].shape[1]], axis=-1
    )
    taps = p["conv_kernel"].shape[1]
    u = jnp.pad(qkvz[..., : 2 * keys + values], ((0, 0), (taps - 1, 0), (0, 0)))
    mixed = jax.nn.silu(
        sum(p["conv_kernel"][:, j] * u[:, j:j + s] for j in range(taps))
    )
    q, k, v = jnp.split(mixed, (keys, 2 * keys), axis=-1)
    z = qkvz[..., 2 * keys + values:].reshape(b, s, hv, dv)
    q = l2_normalised(q.reshape(b, s, hk, dk)) / jnp.sqrt(jnp.float32(dk))
    k = l2_normalised(k.reshape(b, s, hk, dk))
    q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    o = recurrence(
        q, k, v.reshape(b, s, hv, dv), g, beta, min(arch["scan_block"], s)
    )
    o = p["norm_scale"] * o * jax.lax.rsqrt(
        jnp.mean(o * o, axis=-1, keepdims=True) + arch["rms_norm_eps"]
    )
    return mm((o * jax.nn.silu(z)).reshape(b, s, values), p["out_proj"]["kernel"])


def attention(a, p, arch):
    """Step 7."""
    b, s, _ = a.shape
    d = p["q_norm"]["scale"].shape[0]
    eps = arch["rms_norm_eps"]
    sides = [p[n]["kernel"] for n in ("q_proj", "k_proj", "v_proj")]
    ends = np.cumsum([w.shape[1] for w in sides])[:-1]
    q, k, v = jnp.split(mm(a, jnp.concatenate(sides, axis=1)), ends, axis=-1)
    q, gate = jnp.split(q.reshape(b, s, -1, 2 * d), 2, axis=-1)
    k, v = k.reshape(b, s, -1, d), v.reshape(b, s, -1, d)
    q = rope(rms_norm(q, p["q_norm"]["scale"], eps), arch["rope_theta"], arch["rotary"])
    k = rope(rms_norm(k, p["k_norm"]["scale"], eps), arch["rope_theta"], arch["rotary"])
    heads = k.shape[2]
    group = q.shape[2] // heads  # query heads a key-value head serves
    rows = min(arch["query_block"], s)
    if s % rows:
        raise ValueError(f"{s} tokens are not whole blocks of {rows} queries")
    key_at = jnp.arange(s)[None, :]

    @jax.checkpoint
    def one_block(block):
        """Key-value head ``i`` under the ``rows`` queries from
        ``first_row`` of the ``group`` query heads it serves."""
        qg, i, first_row = block  # (b, rows, group, d)
        kh, vh = k[:, :, i], v[:, :, i]  # (b, s, d)
        scores = jnp.einsum(
            "bqgd,bkd->bgqk", operand(qg), operand(kh)
        ) / jnp.sqrt(jnp.float32(d))
        seen = first_row + jnp.arange(rows)[:, None] - key_at >= 0
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum(
            "bgqk,bkd->bqgd", operand(jax.nn.softmax(scores, axis=-1)), operand(vh)
        )

    # one (query block, key-value head) at a time, in a loop the compiler
    # sees once: (blocks, heads, b, rows, group, d) flattened over the first two
    blocks = s // rows
    qb = q.reshape(b, blocks, rows, heads, group, d).transpose(1, 3, 0, 2, 4, 5)
    head_of = jnp.tile(jnp.arange(heads), blocks)
    first_row_of = jnp.repeat(jnp.arange(blocks) * rows, heads)
    out = jax.lax.map(
        one_block, (qb.reshape(-1, b, rows, group, d), head_of, first_row_of)
    )
    out = out.reshape(blocks, heads, b, rows, group, d).transpose(2, 0, 3, 1, 4, 5)
    gated = out.reshape(b, s, -1, d) * jax.nn.sigmoid(gate)
    return mm(gated.reshape(b, s, -1), p["o_proj"]["kernel"])


def swiglu(x, w1, w3, w2):
    """``W_2(silu(W_1 x) * W_3 x)``, with ``W_1`` and ``W_3`` side by side
    in one product."""
    gate, up = jnp.split(mm(x, jnp.concatenate([w1, w3], axis=1)), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, w2)


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


def moe(x, p, arch):
    """Step 8."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)  # (b, s, experts)
    sel = top_k_by_argmax(probs, arch["num_experts_per_tok"])
    w = jnp.take_along_axis(probs, sel, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    @jax.checkpoint
    def add_expert(out, held):  # one held expert on every token, masked
        e, w1, w3, w2 = held
        mine = jnp.sum(jnp.where(sel == arch["first_expert"] + e, w, 0.0), axis=-1)
        return out + mine[..., None] * swiglu(x, w1, w3, w2), None

    s = p["shared_expert"]
    shared = jax.nn.sigmoid(mm(x, p["shared_gate"])) * swiglu(
        x, s["w1"]["kernel"], s["w3"]["kernel"], s["w2"]["kernel"]
    )
    # a loop the compiler sees once
    out, _ = jax.lax.scan(
        add_expert, shared,
        (jnp.arange(p["w1"].shape[0]), p["w1"], p["w3"], p["w2"]),
    )
    return out


def trunk(params, tokens, arch):
    """Steps 1-9 up to ``norm_out``."""
    eps = arch["rms_norm_eps"]
    h = params["embedding"][tokens]
    for i, kind in enumerate(arch["layer_types"]):

        @jax.checkpoint
        def layer(h, p, kind=kind):
            a = rms_norm(h, p["norm_in"]["scale"], eps)
            if kind == "linear_attention":
                h = h + gated_delta_net(a, p["gdn"], arch)
            else:
                h = h + attention(a, p["attn"], arch)
            return h + moe(rms_norm(h, p["norm_post"]["scale"], eps), p["moe"], arch)

        h = layer(h, params[f"layers_{i}"])
    return rms_norm(h, params["norm_out"]["scale"], eps)


def forward(params, batch_stats, tokens, arch=None):
    """``tokens (B, S) int32 -> (logits (B, S, vocab), batch_stats)``."""
    arch = {**ARCH, **(arch or {})}
    return mm(trunk(params, tokens, arch), params["lm_head"].T), batch_stats


def next_token_loss(logits, labels):
    """Mean over every token of the batch of -log softmax(logits)[next]."""
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def step(params, batch_stats, tokens, labels, recipe):
    """One AdamW step (Loshchilov & Hutter 2019) from a fresh optimizer
    state: ``m = (1 - b1) g``, ``v = (1 - b2) g^2``, bias-corrected to ``g``
    and ``g^2``; decoupled decay on matrices (two axes or more) only.  The
    loss is ``next_token_loss`` taken a block of tokens at a time.
    ``recipe``: ``lr``, ``beta1``, ``beta2``, ``eps``, ``weight_decay``,
    optionally ``arch`` (test widths)."""
    arch = {**ARCH, **(recipe.get("arch") or {})}

    def loss_fn(p):
        h = trunk(p, tokens, arch)

        @jax.checkpoint
        def block_loss(block):  # summed over the block's tokens
            hb, y = block
            logits = mm(hb, p["lm_head"].T)
            logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
            return -jnp.sum(jnp.take_along_axis(logp, y[..., None], axis=-1))

        b, s = labels.shape
        rows = min(arch["query_block"], s)
        in_blocks = lambda x: jnp.moveaxis(  # noqa: E731
            x.reshape(b, s // rows, rows, *x.shape[2:]), 1, 0
        )
        total = jnp.sum(jax.lax.map(block_loss, (in_blocks(h), in_blocks(labels))))
        return total / labels.size

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
