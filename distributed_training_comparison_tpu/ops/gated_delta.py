"""The gated delta rule (Gated DeltaNet, Yang et al. 2024): a linear-attention
state that every token decays, corrects and reads.

Per value head, with ``S_0 = 0`` in ``R^{dk x dv}``, in float32::

    S   <- exp(g_t) S
    d_t  = beta_t (v_t - S^T k_t)
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

:func:`gated_delta_rule_sequential` is that recurrence token by token, the
semantics contract (as ``ops/attention.py mha_reference`` is attention's):
``S`` sequential steps of vector work.  :func:`gated_delta_rule` computes the
same in the **chunked form** (the WY representation), so that a sequence is
``S / chunk`` sequential steps of matrix products:

Within a chunk that starts from the state ``S_0``, with ``gamma_i = sum_{j <=
i} g_j`` the cumulative log-decay inside the chunk, the corrections of the
chunk's tokens obey ``d_i = beta_i (v_i - e^{gamma_i} S_0^T k_i - sum_{j < i}
e^{gamma_i - gamma_j} (k_i . k_j) d_j)``: a unit lower-triangular system
``(I + L) D = beta (V - e^gamma K S_0)`` with ``L_ij = beta_i e^{gamma_i -
gamma_j} (k_i . k_j)``, ``j < i``.  Its solution is linear in the state, ``D =
U - W S_0``, and ``T = (I + L)^{-1}``, ``U = T (beta V)``, ``W = T (beta
e^gamma K)`` do not depend on it: they are computed for every chunk at once,
outside the loop.  The loop over chunks (a ``lax.scan``) is then four
products a chunk and head::

    D   = U - W S_0
    O   = (e^gamma Q) S_0 + ((Q K^T) * Gamma) D      Gamma_ij = e^{gamma_i - gamma_j}, j <= i
    S_C = e^{gamma_C} S_0 + (e^{gamma_C - gamma} K)^T D

Every decay factor is a ratio ``e^{gamma_i - gamma_j}`` with ``j <= i``, at
most one: nothing is ever divided by a decay, so a head that forgets within
a token (``g`` = -20) underflows to zero and not to ``inf``.

Precision: the cumulative log-decays, the ratios, ``T`` and the carried
state are float32 whatever the inputs' dtype; the matrix products take the
inputs' dtype as operands (bf16 under ``--amp``) and accumulate in float32.
``T`` is a triangular solve, by substitution on 16 x 16 diagonal blocks and
``[[A, 0], [B, C]]^{-1} = [[A^-1, 0], [-C^-1 B A^-1, C^-1]]`` above them, its
products at ``highest`` precision (on a TPU a float32 product otherwise runs
as bf16 passes).  A Neumann series ``(I - L)(I + L^2)(I + L^4)..`` would be
five products and is not used: with keys alike its terms grow binomially and
cancel.

The backward is autodiff through the scan (the caller's ``--remat``
recomputes the layer; the carried states of a layer at 8,192 tokens, 32
heads of 128 x 128 and chunk 64 are 268 MB).  Composed XLA: no Pallas kernel
yet (``PERF.md`` §7).  Ops of both functions carry the scope ``gdn_scan``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs.compilation import note_kernel_path

_HIGHEST = jax.lax.Precision.HIGHEST
_SUBSTITUTION_ROWS = 16  # the diagonal blocks solved row by row


def _heads_of(q, k, v):
    """``q`` and ``k`` with one head a value head: each key head serves
    ``Hv // Hk`` consecutive value heads."""
    group, rest = divmod(v.shape[2], k.shape[2])
    if rest or q.shape != k.shape:
        raise ValueError(
            f"{v.shape[2]} value heads over q {q.shape} / k {k.shape}"
        )
    if group > 1:
        q, k = jnp.repeat(q, group, axis=2), jnp.repeat(k, group, axis=2)
    return q, k


def gated_delta_rule_sequential(q, k, v, g, beta, *, block: int | None = None):
    """The recurrence of the module docstring token by token, in float32.
    ``q``, ``k``: ``(B, S, Hk, dk)`` (``q`` scaled and both normalised by
    the caller); ``v``: ``(B, S, Hv, dv)``; ``g`` (log-decay, <= 0) and
    ``beta``: ``(B, S, Hv)``.  Returns ``o (B, S, Hv, dv)`` in ``v``'s
    dtype.  ``block`` (a divisor of ``S``) keeps, for the gradient, the
    state of every ``block``-th token only and recomputes between them: a
    state a token is ``S x Hv x dk x dv`` floats."""
    with jax.named_scope("gdn_scan"):
        q, k = _heads_of(q, k, v)
        b, s, h, dv = v.shape
        block = block or s
        if s % block:
            raise ValueError(f"{s} tokens are not whole blocks of {block}")

        def f32(x):  # (B, S, ...) -> (S / block, block, B, ...)
            x = jnp.moveaxis(x.astype(jnp.float32), 1, 0)
            return x.reshape(s // block, block, *x.shape[1:])

        def token(state, x):
            q_t, k_t, v_t, g_t, beta_t = x  # (B, H, d) and (B, H)
            state = state * jnp.exp(g_t)[..., None, None]
            d_t = beta_t[..., None] * (
                v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST)
            )
            state = state + k_t[..., :, None] * d_t[..., None, :]
            return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=_HIGHEST)

        @jax.checkpoint
        def tokens(state, xs):
            return jax.lax.scan(token, state, xs)

        state = jnp.zeros((b, h, k.shape[-1], dv), jnp.float32)
        _, o = jax.lax.scan(tokens, state, (f32(q), f32(k), f32(v), f32(g), f32(beta)))
        return jnp.moveaxis(o.reshape(s, b, h, dv), 0, 1).astype(v.dtype)


def _unit_lower_inverse(m):
    """``(I + strict_lower(m))^{-1}`` over the last two axes, in float32:
    row-by-row substitution up to ``_SUBSTITUTION_ROWS`` rows, the block
    formula above that (the module docstring says why no series)."""
    n = m.shape[-1]
    if n > _SUBSTITUTION_ROWS and n % 2 == 0:
        half = n // 2
        a = _unit_lower_inverse(m[..., :half, :half])
        c = _unit_lower_inverse(m[..., half:, half:])
        lower = -jnp.matmul(
            jnp.matmul(c, m[..., half:, :half], precision=_HIGHEST), a,
            precision=_HIGHEST,
        )
        top = jnp.concatenate([a, jnp.zeros_like(lower)], axis=-1)
        return jnp.concatenate(
            [top, jnp.concatenate([lower, c], axis=-1)], axis=-2
        )
    eye = jnp.eye(n, dtype=m.dtype)
    rows = [jnp.broadcast_to(eye[0], m.shape[:-2] + (n,))]
    for i in range(1, n):  # row i of the inverse from the rows above it
        above = jnp.stack(rows, axis=-2)  # (..., i, n)
        rows.append(eye[i] - jnp.sum(m[..., i, :i, None] * above, axis=-2))
    return jnp.stack(rows, axis=-2)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64):
    """:func:`gated_delta_rule_sequential`'s result in the chunked form
    (module docstring): same arguments, ``chunk`` tokens a sequential step.
    A length that is no multiple of ``chunk`` is padded with tokens that
    neither decay nor write (``g`` = 0, ``beta`` = 0)."""
    note_kernel_path("gated_delta", "composed")
    with jax.named_scope("gdn_scan"):
        return _chunked(q, k, v, g, beta, chunk)


def _chunked(q, k, v, g, beta, chunk):
    q, k = _heads_of(q, k, v)
    b, s, h, dv = v.shape
    dtype, f32 = v.dtype, jnp.float32
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta)
        )
    n = (s + pad) // chunk

    def chunks(x):  # (B, S, H, ...) -> (B, H, N, C, ...)
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v = chunks(q.astype(dtype)), chunks(k.astype(dtype)), chunks(v)
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))  # (B, H, N, C)

    def mm(eq, x, y):
        return jnp.einsum(
            eq, x.astype(dtype), y.astype(dtype), preferred_element_type=f32
        )

    # ---- every chunk at once: what does not depend on the state
    gamma = jnp.cumsum(g, axis=-1)
    row = jnp.arange(chunk)
    below = row[:, None] >= row[None, :]
    ratio = jnp.exp(jnp.where(
        below, gamma[..., :, None] - gamma[..., None, :], -jnp.inf
    ))  # Gamma: e^{gamma_i - gamma_j} for j <= i, else 0
    lower = beta[..., :, None] * ratio * mm("bhnid,bhnjd->bhnij", k, k)
    t = _unit_lower_inverse(jnp.where(below & ~jnp.eye(chunk, dtype=bool), lower, 0.0))
    decay = jnp.exp(gamma)  # e^{gamma_i}, the chunk's start to token i
    # operands of the loop's products: kept in their dtype, not float32
    u = mm("bhnij,bhnjd->bhnid", t, beta[..., None] * v.astype(f32)).astype(dtype)
    w = mm(
        "bhnij,bhnjd->bhnid", t, (beta * decay)[..., None] * k.astype(f32)
    ).astype(dtype)
    scores = (ratio * mm("bhnid,bhnjd->bhnij", q, k)).astype(dtype)
    q_in = (decay[..., None] * q.astype(f32)).astype(dtype)
    to_end = jnp.exp(gamma[..., -1:] - gamma)  # e^{gamma_C - gamma_j}
    k_out = (to_end[..., None] * k.astype(f32)).astype(dtype)
    end = decay[..., -1]  # e^{gamma_C}

    # ---- the loop over chunks: four products a chunk
    def step(state, x):  # state (B, H, dk, dv) float32
        u_c, w_c, scores_c, q_c, k_c, end_c = x
        d = u_c - mm("bhik,bhkv->bhiv", w_c, state)
        o = mm("bhik,bhkv->bhiv", q_c, state) + mm("bhij,bhjv->bhiv", scores_c, d)
        state = end_c[..., None, None] * state + mm("bhik,bhiv->bhkv", k_c, d)
        return state, o.astype(dtype)

    first = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731  (N to the front)
    state = jnp.zeros((b, h, k.shape[-1], dv), f32)
    _, o = jax.lax.scan(
        step, state,
        tuple(first(x) for x in (u, w, scores, q_in, k_out, end)),
    )
    # (N, B, H, C, dv) -> (B, S, H, dv)
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(b, n * chunk, h, dv)
    return o[:, :s]
