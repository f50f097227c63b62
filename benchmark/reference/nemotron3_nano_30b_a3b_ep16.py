"""Plain reference for ``nemotron3_nano_30b_a3b_ep16``: one chip's share of
NVIDIA-Nemotron-3-Nano-30B-A3B (NVIDIA, ``model_type`` ``nemotron_h``;
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json)
under sixteen-way expert parallelism, written from the published
configuration and, for what its keys do not carry, from knowledge of the
reference implementations (``transformers``
``models/nemotron_h/modeling_nemotron_h.py``: ``NemotronHMamba2Mixer``'s
``torch_forward``, ``NemotronHAttention``, ``NemotronHMOE``,
``NemotronHTopkRouter``, ``MambaRMSNormGated``; ``mamba_ssm``'s ``Mamba2``
and ``selective_state_update``; the selection bias's rule from the
Nemotron 3 Nano report and DeepSeek-V3's).  Straightforward ``jax.numpy`` in
float32 (the caller sets ``highest`` matmul precision); no kernel, no chunk
algebra, no sort, no dispatch buffer; no module of the program is imported.

The equations (``eps`` = ``layer_norm_epsilon`` 1e-5; no bias on any
projection; ``rms(x, w) = x rsqrt(mean x^2 + eps) w``):

1. ``h = E[tokens]``; for every layer ``h = h + mixer(rms(h, w_norm))``, the
   mixer the one whose parameters the layer holds (``mamba``, ``attn`` or
   ``moe``); ``logits = rms(h, w_f) W_head^T`` (untied).
2. Mamba-2, from ``u``: ``z | xBC | dt = u W_in`` (``d_inner`` | ``d_inner
   + 2 G N`` | ``H``); ``xBC = silu(conv(xBC) + b_conv)``, a causal depthwise
   convolution over the taps of ``conv_kernel``, zeros before the sequence;
   ``x | B | C = xBC``; head ``h`` of ``P = d_inner / H`` channels reads
   group ``h // (H / G)``'s ``B_t`` and ``C_t``; ``dt = softplus(dt +
   dt_bias)``; ``A = -exp(A_log)``; per head, **token by token** from ``S =
   0``: ``S <- exp(dt_t A) S + (dt_t x_t) B_t^T``; ``y_t = S C_t + D x_t``.
3. ``y = y * silu(z)``; RMS-normalised over each of the ``G`` groups of
   ``d_inner / G`` channels, scaled by ``w`` (gate first, norm second);
   ``out = y W_out``.
4. Attention: ``q, k, v = u W_q, u W_k, u W_v`` as heads of ``head_dim``,
   **no position encoding of any kind**, ``o = softmax(q k^T /
   sqrt(head_dim) + causal) v``, each key-value head serving consecutive
   query heads; ``out = o W_o``.
5. Expert layer: ``s = sigmoid(u W_r)``; ``sel = top_k(s + b)``; ``w =
   routed_scaling_factor * s[sel] / sum s[sel]``; ``y = W_2s relu(W_1s u)^2
   + sum over sel held here of w_e W_2e relu(W_1e u)^2``.
6. In ``step`` only, once an expert layer: ``c_e`` the pairs that selected
   expert ``e``; ``delta = bias_update_rate * sign(mean(c) - c)``; ``b +=
   delta - mean(delta)``.

Takes the program's parameter tree as plain arrays: ``embedding``,
``lm_head``, ``norm_f``, ``layers_<i>`` holding ``norm`` and one mixer
(``mamba``: ``in_proj``, ``conv_kernel``, ``conv_bias``, ``A_log``,
``dt_bias``, ``D``, ``norm_scale``, ``out_proj``; ``attn``: ``q_proj``,
``k_proj``, ``v_proj``, ``o_proj``; ``moe``: ``router``, ``w1``, ``w2``,
``shared_expert``); and its ``batch_stats`` tree, whose only leaves are the
expert layers' selection bias ``layers_<i>/moe/expert_bias``.  Head counts
and the inner width are read off the shapes (``A_log``, ``norm_scale``,
``conv_kernel``); the groups of B and C and attention's head size are
``ARCH``'s (the tree does not say).

Departures from the published description, each because the configuration
under test states it (``configs/nemotron3_nano_30b_a3b_ep16.json``,
``reduced`` and ``assumed``):
- the share of a deployment: experts ``first_expert`` .. of the 128 are held
  and only they and the shared expert add to an expert layer's output, the
  normaliser of the routing weights running over all six selected; the
  vocabulary is its first rows, and logits, softmax and loss are over that
  slice; seven of the fifty-two layers;
- the bias rule counts this chip's tokens (in the deployment the counts are
  summed over the data-parallel ranks);
- one document a sequence: the state and the convolution start from zeros,
  no boundary resets them;
- the recurrence runs ``scan_block`` tokens at a time under
  ``jax.checkpoint`` (kept whole, the gradient of 8,192 steps holds 8,192
  states of 2 MiB a layer), attention's scores one key-value head and one
  block of queries at a time, the logits one block of tokens at a time,
  each in a loop the compiler sees once; the arithmetic is the unblocked
  one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# what the parameter tree's shapes do not say (the published ``config``)
ARCH = {
    "layer_norm_epsilon": 1e-5,
    "n_groups": 8,
    "head_dim": 128,
    "num_experts_per_tok": 6,
    "routed_scaling_factor": 2.5,
    "bias_update_rate": 0.001,
    "first_expert": 0,
    "query_block": 512,  # rows of scores and of logits alive at a time
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
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def recurrence(x, dt, A, B, C, D, block):
    """Step 2's state, token by token.  ``x``: ``(B, S, H, P)``; ``dt``:
    ``(B, S, H)``; ``A``, ``D``: ``(H,)``; ``B``, ``C``: ``(B, S, H, N)``
    (a group's, repeated for its heads already).  The products are an outer
    product and a sum over an axis, exact in float32."""
    b, s, h, p = x.shape
    if s % block:
        raise ValueError(f"{s} tokens are not whole blocks of {block}")

    def token(state, v):
        x_t, dt_t, b_t, c_t = v  # (B, H, P), (B, H), (B, H, N) twice
        written = operand(dt_t[..., None] * x_t)[..., :, None] * operand(b_t)[..., None, :]
        state = jnp.exp(dt_t * A)[..., None, None] * state + written
        read = jnp.sum(operand(state) * operand(c_t)[..., None, :], axis=-1)
        return state, read + D[:, None] * x_t

    @jax.checkpoint
    def tokens(state, vs):
        return jax.lax.scan(token, state, vs)

    def in_blocks(v):  # (B, S, ...) -> (S / block, block, B, ...)
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(s // block, block, *v.shape[1:])

    state = jnp.zeros((b, h, p, B.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(tokens, state, tuple(in_blocks(v) for v in (x, dt, B, C)))
    return jnp.moveaxis(y.reshape(s, b, h, p), 0, 1)


def mamba2(u, p, arch):
    """Steps 2-3."""
    b, s, _ = u.shape
    h = p["A_log"].shape[0]
    inner = p["norm_scale"].shape[0]
    g = arch["n_groups"]
    n = (p["conv_kernel"].shape[0] - inner) // (2 * g)
    z, xbc, dt = jnp.split(
        mm(u, p["in_proj"]["kernel"]), (inner, 2 * inner + 2 * g * n), axis=-1
    )
    taps = p["conv_kernel"].shape[1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(
        sum(p["conv_kernel"][:, j] * padded[:, j:j + s] for j in range(taps))
        + p["conv_bias"]
    )
    x, B, C = jnp.split(xbc, (inner, inner + g * n), axis=-1)
    by_head = lambda v: jnp.repeat(v.reshape(b, s, g, n), h // g, axis=2)  # noqa: E731
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(
        x.reshape(b, s, h, inner // h), dt, -jnp.exp(p["A_log"]), by_head(B),
        by_head(C), p["D"], min(arch["scan_block"], s),
    )
    y = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(b, s, g, inner // g)
    y = y * jax.lax.rsqrt(
        jnp.mean(y * y, axis=-1, keepdims=True) + arch["layer_norm_epsilon"]
    )
    return mm(y.reshape(b, s, inner) * p["norm_scale"], p["out_proj"]["kernel"])


def attention(u, p, arch):
    """Step 4."""
    b, s, _ = u.shape
    d = arch["head_dim"]
    sides = [p[n]["kernel"] for n in ("q_proj", "k_proj", "v_proj")]
    ends = np.cumsum([w.shape[1] for w in sides])[:-1]
    # one product against the three matrices side by side (the compiler
    # takes about a second for every float32 product at ``highest``)
    q, k, v = jnp.split(mm(u, jnp.concatenate(sides, axis=1)), ends, axis=-1)
    q, k, v = (x.reshape(b, s, -1, d) for x in (q, k, v))
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
    return mm(out.reshape(b, s, -1), p["o_proj"]["kernel"])


def relu2(x, w1, w2):
    """``W_2 relu(W_1 x)^2``, ``w1`` ``(d, hidden)`` and ``w2`` ``(hidden,
    d)``."""
    return mm(jnp.square(jax.nn.relu(mm(x, w1))), w2)


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
    """Step 5.  Returns ``(y, counts)``: the pairs that selected each of the
    experts, held here or not, for the bias rule."""
    scores = jax.nn.sigmoid(x @ p["router"])  # (b, s, experts)
    sel = top_k_by_argmax(scores + bias, arch["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = arch["routed_scaling_factor"] * w / jnp.sum(w, axis=-1, keepdims=True)

    @jax.checkpoint
    def add_expert(out, held):  # one held expert on every token, masked
        e, w1, w2 = held  # both (hidden, d): a row a hidden unit
        mine = jnp.sum(jnp.where(sel == arch["first_expert"] + e, w, 0.0), axis=-1)
        return out + mine[..., None] * relu2(x, w1.T, w2), None

    s = p["shared_expert"]
    # a loop the compiler sees once
    out, _ = jax.lax.scan(
        add_expert, relu2(x, s["w1"]["kernel"], s["w2"]["kernel"]),
        (jnp.arange(p["w1"].shape[0]), p["w1"], p["w2"]),
    )
    counts = jnp.sum(
        jax.nn.one_hot(sel, scores.shape[-1], dtype=jnp.float32), axis=(0, 1, 2)
    )
    return out, counts


def moved_bias(bias, counts, arch):
    """Step 6: an expert selected less than its even share rises."""
    delta = arch["bias_update_rate"] * jnp.sign(jnp.mean(counts) - counts)
    return bias + (delta - jnp.mean(delta))


def trunk(params, batch_stats, tokens, arch):
    """Step 1 up to ``norm_f``.  Returns ``(h, batch_stats after the bias
    rule)``: ``forward`` drops the second, ``step`` keeps it."""
    eps = arch["layer_norm_epsilon"]
    h = params["embedding"][tokens]
    new_stats = {}
    layers = sorted(
        (k for k in params if k.startswith("layers_")), key=lambda k: int(k[7:])
    )
    for name in layers:

        @jax.checkpoint
        def layer(h, p, bias):
            u = rms_norm(h, p["norm"]["scale"], eps)
            if "mamba" in p:
                return h + mamba2(u, p["mamba"], arch), None
            if "attn" in p:
                return h + attention(u, p["attn"], arch), None
            y, counts = moe(u, p["moe"], bias, arch)
            return h + y, counts

        bias = batch_stats.get(name, {}).get("moe", {}).get("expert_bias")
        h, counts = layer(h, params[name], bias)
        if counts is not None:
            new_stats[name] = {"moe": {"expert_bias": moved_bias(
                bias, jax.lax.stop_gradient(counts), arch
            )}}
    return rms_norm(h, params["norm_f"]["scale"], eps), new_stats


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
    selection bias moves by its own rule (step 6), outside the optimizer.
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
