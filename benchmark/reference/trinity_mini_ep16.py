"""Plain reference for ``trinity_mini_ep16``: one chip's share of
Trinity-Mini (Arcee, ``model_type`` ``afmoe``;
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json) under
sixteen-way expert parallelism, written from the published configuration
and, for what its keys do not carry, from the reference implementation
(``transformers`` ``models/afmoe/modeling_afmoe.py``: ``AfmoeAttention``,
``AfmoeDecoderLayer``, ``AfmoeTokenChoiceRouter``, ``AfmoeMoE``) and
``torchtitan`` ``models/moe.py`` (the selection bias's rule).
Straightforward ``jax.numpy`` in float32 (the caller sets ``highest`` matmul
precision); no kernel, no sort, no dispatch buffer; no module of the program
is imported.

The seven equations (``eps`` = ``rms_norm_eps``; no bias anywhere):

1. ``h0 = E[tokens] * sqrt(hidden)``.
2. ``a = input_norm(h)``; ``q = a W_q`` as heads of ``head_dim``, ``k = a
   W_k``, ``v = a W_v`` as fewer heads, ``g = a W_g`` as wide as ``q``;
   per-head RMSNorm with a learned scale on q and k.
3. A ``sliding_attention`` layer: rotate-half RoPE on q and k; key ``j`` is
   visible to query ``i`` iff ``j <= i and i - j < sliding_window``.  A
   ``full_attention`` layer: no position encoding; ``j <= i``.
4. ``o = softmax(q k^T / sqrt(head_dim) + mask) v``, each key-value head
   serving consecutive query heads; ``attn = (o * sigmoid(g)) W_o``; ``h = h
   + post_attn_norm(attn)``.
5. ``m = pre_mlp_norm(h)``; the leading dense layers ``W_2(silu(W_1 m) * W_3
   m)``; every later layer ``s = sigmoid(m W_r)``, ``sel = top_k(s + b)``,
   ``w = s[sel] / (sum s[sel] + 1e-6) * route_scale``, ``y = shared(m) +
   sum over sel held here of w_e expert_e(m)``.
6. ``h = h + post_mlp_norm(y)``; after the last layer ``norm_out``, then
   ``logits = h W_head`` (untied).
7. In ``step`` only, once a layer: ``c_e`` the pairs that selected expert
   ``e``; ``delta = load_balance_coeff * sign(mean(c) - c)``; ``b += delta -
   mean(delta)``.

Takes the program's parameter tree as plain arrays: ``embedding``,
``lm_head``, ``layers_<i>`` holding ``input_norm``, ``post_attn_norm``,
``pre_mlp_norm``, ``post_mlp_norm``, ``attn`` and an FFN (``mlp`` or
``moe``, the latter with ``shared_expert`` inside), ``norm_out``; and its
``batch_stats`` tree, whose only leaves are the expert layers' selection
bias ``layers_<i>/moe/expert_bias``.  The head size is read off ``q_norm``'s
width, the experts held off the expert stack's leading axis; which layers
slide is ``ARCH["layer_types"]`` (the tree does not say).

Departures from the published description, each because the configuration
under test states it (``configs/trinity_mini_ep16.json``, ``reduced`` and
``assumed``):
- the share of a deployment: experts ``first_expert`` .. of the 128 are held
  and only they and the shared expert add to an expert layer's output, the
  normaliser of the routing weights running over all eight selected; the
  vocabulary is its first rows, and logits, softmax and loss are over that
  slice; five of the thirty-two layers;
- the routing weights' denominator carries 1e-6 where the source has 1e-20:
  a relative 1e-6 on a sum of eight sigmoids;
- the bias rule counts this chip's tokens (in the deployment the counts are
  summed over the data-parallel ranks);
- one document a sequence: positions ``0 .. S-1``, no boundary mask;
- attention's scores are materialised one key-value head and one block of
  queries at a time, and the logits one block of tokens at a time, under
  ``jax.checkpoint`` inside a ``lax.map`` (a loop the compiler sees once:
  160 unrolled blocks took it five minutes), so that an 8,192-token
  sequence fits beside the program on the chip; the arithmetic is the
  unblocked one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HELD = ("sliding_attention", "sliding_attention", "full_attention",
         "sliding_attention", "sliding_attention")
# what the parameter tree's shapes do not say (the published ``config``)
ARCH = {
    "rms_norm_eps": 1e-5,
    "rope_theta": 1e4,
    "sliding_window": 2048,
    "layer_types": _HELD,
    "num_experts_per_tok": 8,
    "route_scale": 2.826,
    "route_norm": True,
    "load_balance_coeff": 0.001,
    "first_expert": 0,
    "query_block": 1024,  # rows of scores and of logits alive at a time
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


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


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


def attention(h, p, arch, sliding):
    """Steps 2-4 up to ``W_o``; ``sliding`` says which mask and whether
    positions are encoded at all."""
    b, s, _ = h.shape
    d = p["q_norm"]["scale"].shape[0]
    eps = arch["rms_norm_eps"]
    # h W_q, h W_k, h W_v, h W_g as one product against the four matrices
    # side by side: the compiler takes about a second for every float32
    # product at ``highest`` precision, and the columns are the same
    sides = [p[n]["kernel"] for n in ("q_proj", "k_proj", "v_proj", "gate_proj")]
    ends = np.cumsum([w.shape[1] for w in sides])[:-1]
    q, k, v, gate = jnp.split(mm(h, jnp.concatenate(sides, axis=1)), ends, axis=-1)
    q, k, v = (x.reshape(b, s, -1, d) for x in (q, k, v))
    q = rms_norm(q, p["q_norm"]["scale"], eps)
    k = rms_norm(k, p["k_norm"]["scale"], eps)
    if sliding:
        q, k = rope(q, arch["rope_theta"]), rope(k, arch["rope_theta"])
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
        ahead = first_row + jnp.arange(rows)[:, None] - key_at
        seen = ahead >= 0
        if sliding:
            seen = seen & (ahead < arch["sliding_window"])
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
    return mm(out.reshape(b, s, -1) * jax.nn.sigmoid(gate), p["o_proj"]["kernel"])


def swiglu(x, w1, w3, w2):
    """``W_2(silu(W_1 x) * W_3 x)``, with ``W_1`` and ``W_3`` side by side
    in one product (as the attention's four)."""
    gate, up = jnp.split(mm(x, jnp.concatenate([w1, w3], axis=1)), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, w2)


def swiglu_of(x, p):
    return swiglu(x, p["w1"]["kernel"], p["w3"]["kernel"], p["w2"]["kernel"])


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
    """Step 5's expert layer.  Returns ``(y, counts)``: the pairs that
    selected each of the experts, held here or not, for the bias rule."""
    scores = jax.nn.sigmoid(x @ p["router"])  # (b, s, experts)
    sel = top_k_by_argmax(scores + bias, arch["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if arch["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * arch["route_scale"]

    @jax.checkpoint
    def add_expert(out, held):  # one held expert on every token, masked
        e, w1, w3, w2 = held
        mine = jnp.sum(jnp.where(sel == arch["first_expert"] + e, w, 0.0), axis=-1)
        return out + mine[..., None] * swiglu(x, w1, w3, w2), None

    # a loop the compiler sees once (a float32 product at ``highest``
    # precision takes it about a second, and there are 24 a layer)
    out, _ = jax.lax.scan(
        add_expert, swiglu_of(x, p["shared_expert"]),
        (jnp.arange(p["w1"].shape[0]), p["w1"], p["w3"], p["w2"]),
    )
    counts = jnp.sum(
        jax.nn.one_hot(sel, scores.shape[-1], dtype=jnp.float32), axis=(0, 1, 2)
    )
    return out, counts


def moved_bias(bias, counts, arch):
    """Step 7: an expert selected less than its even share rises."""
    delta = arch["load_balance_coeff"] * jnp.sign(jnp.mean(counts) - counts)
    return bias + (delta - jnp.mean(delta))


def trunk(params, batch_stats, tokens, arch):
    """Steps 1-6 up to ``norm_out``.  Returns ``(h, batch_stats after the
    bias rule)``: ``forward`` drops the second, ``step`` keeps it."""
    eps = arch["rms_norm_eps"]
    h = params["embedding"][tokens] * jnp.sqrt(
        jnp.float32(params["embedding"].shape[1])
    )
    new_stats = {}
    for i, kind in enumerate(arch["layer_types"]):
        p = params[f"layers_{i}"]

        @jax.checkpoint
        def layer(h, p=p, i=i, kind=kind):
            a = rms_norm(h, p["input_norm"]["scale"], eps)
            attn = attention(a, p["attn"], arch, kind == "sliding_attention")
            h = h + rms_norm(attn, p["post_attn_norm"]["scale"], eps)
            m = rms_norm(h, p["pre_mlp_norm"]["scale"], eps)
            if "mlp" in p:
                y, counts = swiglu_of(m, p["mlp"]), None
            else:
                bias = batch_stats[f"layers_{i}"]["moe"]["expert_bias"]
                y, counts = moe(m, p["moe"], bias, arch)
            return h + rms_norm(y, p["post_mlp_norm"]["scale"], eps), counts

        h, counts = layer(h)
        if counts is not None:
            bias = batch_stats[f"layers_{i}"]["moe"]["expert_bias"]
            new_stats[f"layers_{i}"] = {"moe": {"expert_bias": moved_bias(
                bias, jax.lax.stop_gradient(counts), arch
            )}}
    return rms_norm(h, params["norm_out"]["scale"], eps), new_stats


def forward(params, batch_stats, tokens, arch=None):
    """``tokens (B, S) int32 -> (logits (B, S, vocab), batch_stats)``: an
    evaluation, which leaves the selection bias alone."""
    arch = {**ARCH, **(arch or {})}
    h, _ = trunk(params, batch_stats, tokens, arch)
    return mm(h, params["lm_head"].T), batch_stats


def next_token_loss(logits, labels):
    """Mean over every token of the batch of -log softmax(logits)[next]."""
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def step(params, batch_stats, tokens, labels, recipe):
    """One AdamW step (Loshchilov & Hutter 2019) from a fresh optimizer
    state: ``m = (1 - b1) g``, ``v = (1 - b2) g^2``, bias-corrected to ``g``
    and ``g^2``; decoupled decay on matrices (two axes or more) only.  The
    selection bias moves by its own rule (step 7), outside the optimizer.
    The loss is ``next_token_loss`` taken a block of tokens at a time.
    ``recipe``: ``lr``, ``beta1``, ``beta2``, ``eps``, ``weight_decay``,
    optionally ``arch`` (test widths)."""
    arch = {**ARCH, **(recipe.get("arch") or {})}

    def loss_fn(p):
        h, new_stats = trunk(p, batch_stats, tokens, arch)

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
        return total / labels.size, new_stats

    (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
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
        "batch_stats": new_stats,
    }
