"""The selective state-space scan of Mamba-2 (Dao & Gu 2024, "state-space
duality"): a state that every token decays by a scalar a head and writes an
outer product into — the gated delta rule of ``ops/gated_delta.py`` without
its delta correction, so nothing is solved.

Per head ``h`` of ``P`` channels, with ``S_0 = 0`` in ``R^{P x N}``, in
float32; head ``h`` reads group ``h // (H / G)``'s ``B_t`` and ``C_t``::

    a_t  = dt_t A                      (A < 0, dt_t > 0: the log-decay)
    S_t  = exp(a_t) S_{t-1} + (dt_t x_t) B_t^T
    y_t  = S_t C_t + D x_t

:func:`ssd_scan_sequential` is that recurrence token by token, the semantics
contract of every implementation.  :func:`ssd_scan` computes the same in the
**chunked form**, so that a sequence is ``S / chunk`` sequential steps and
everything else matrix products.  Within a chunk of ``L`` tokens that starts
from the state ``S_prev``, with ``gamma_i`` the running sum of ``a`` inside
the chunk and ``X~ = dt x``::

    Y      = ((C B^T) * Gamma) X~ + e^gamma * (C S_prev^T)     Gamma_ij = e^{gamma_i - gamma_j}, j <= i
    S_next = e^{gamma_L} S_prev + sum_j e^{gamma_L - gamma_j} X~_j B_j^T

``C B^T`` is a group's, taken once for the ``H / G`` heads it serves; the
decays are a head's.  The decay factors are ``ops/gated_delta.py
chunk_decays``, the arithmetic both rules share: every one a ratio of at
most one.  Work: ``G L N + H L P + 2 H N P`` multiply-accumulates a token.

Precision: ``a``, the ratios and the carried state are float32 whatever the
inputs' dtype; the matrix products take ``x``'s dtype as operands (bf16
under ``--amp``) and accumulate in float32.

One path today, composed XLA with autodiff through it (``kernel_paths``
``ssd: composed``), every op under the scope ``ssd_scan``: ``C B^T``, the
masked ratios and the chunks' own state increments for every chunk at once,
a ``lax.scan`` over the chunks that carries the ``(B, H, P, N)`` float32
state (one multiply-add a chunk: the products are outside it), and the
states' read-out for every chunk at once.  A chunk's ``Gamma`` is ``H L^2``
floats: 268 MB a layer call at 8,192 tokens, 64 heads and ``L`` = 128, and
the scores and each one's gradient as much again, alive inside one
rematerialised layer's backward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs.compilation import note_kernel_path
from .gated_delta import chunk_decays

_HIGHEST = jax.lax.Precision.HIGHEST


def _heads_a_group(x, B) -> int:
    heads, groups = x.shape[2], B.shape[2]
    if heads % groups:
        raise ValueError(f"{heads} heads over {groups} groups of B and C")
    return heads // groups


def ssd_scan_sequential(x, dt, A, B, C, D, *, block: int | None = None):
    """The recurrence of the module docstring token by token, in float32.
    ``x``: ``(B, S, H, P)``; ``dt``: ``(B, S, H)``, positive (the caller's
    softplus); ``A`` (negative) and ``D``: ``(H,)``; ``B``, ``C``: ``(B, S,
    G, N)``.  Returns ``y (B, S, H, P)`` in ``x``'s dtype.  ``block`` (a
    divisor of ``S``) keeps, for the gradient, the state of every
    ``block``-th token only and recomputes between them."""
    with jax.named_scope("ssd_scan"):
        r = _heads_a_group(x, B)
        b, s, h, p = x.shape
        block = block or s
        if s % block:
            raise ValueError(f"{s} tokens are not whole blocks of {block}")
        A, D = A.astype(jnp.float32), D.astype(jnp.float32)

        def f32(v):  # (B, S, ...) -> (S / block, block, B, ...)
            v = jnp.moveaxis(v.astype(jnp.float32), 1, 0)
            return v.reshape(s // block, block, *v.shape[1:])

        def token(state, v):
            x_t, dt_t, b_t, c_t = v  # (B, H, P), (B, H), (B, H, N) twice
            state = state * jnp.exp(dt_t * A)[..., None, None] + (
                (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
            )
            y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=_HIGHEST)
            return state, y_t + D[:, None] * x_t

        @jax.checkpoint
        def tokens(state, vs):
            return jax.lax.scan(token, state, vs)

        by_head = lambda v: jnp.repeat(v, r, axis=2)  # noqa: E731
        state = jnp.zeros((b, h, p, B.shape[-1]), jnp.float32)
        _, y = jax.lax.scan(
            tokens, state, (f32(x), f32(dt), f32(by_head(B)), f32(by_head(C)))
        )
        return jnp.moveaxis(y.reshape(s, b, h, p), 0, 1).astype(x.dtype)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 128):
    """:func:`ssd_scan_sequential`'s result in the chunked form (module
    docstring): same arguments, ``chunk`` tokens a sequential step.  A
    length that is no multiple of ``chunk`` is padded with tokens that
    neither decay nor write (``dt`` = 0).  Differentiable in all six."""
    note_kernel_path("ssd", "composed")
    with jax.named_scope("ssd_scan"):
        s = x.shape[1]
        pad = -s % chunk
        if pad:
            x, dt, B, C = (
                jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                for v in (x, dt, B, C)
            )
        return _chunked(x, dt, B, C, A, D, chunk)[:, :s]


def _chunked(x, dt, B, C, A, D, chunk):
    r = _heads_a_group(x, B)
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    dtype, f32 = x.dtype, jnp.float32
    nc = s // chunk

    def chunks(v, *tail):  # (B, S, ...) -> (B, NC, L, *tail)
        return v.reshape(b, nc, chunk, *tail)

    def mm(eq, u, v):
        return jnp.einsum(
            eq, u.astype(dtype), v.astype(dtype), preferred_element_type=f32
        )

    x32 = chunks(x.astype(f32), g, r, p)
    dt = chunks(dt.astype(f32), g, r)
    Bc, Cc = chunks(B.astype(dtype), g, n), chunks(C.astype(dtype), g, n)
    # ---- every chunk at once: what does not depend on the state
    a = jnp.moveaxis(dt * A.astype(f32).reshape(g, r), 2, -1)  # (B, NC, G, R, L)
    _, ratio, decay, to_end = chunk_decays(a)
    tokens_first = lambda v: jnp.moveaxis(v, -1, 2)[..., None]  # noqa: E731
    written = dt[..., None] * x32  # X~, (B, NC, L, G, R, P)
    scores = (ratio * mm("bclgn,bcsgn->bcgls", Cc, Bc)[:, :, :, None]).astype(dtype)
    y = mm("bcgrls,bcsgrp->bclgrp", scores, written)
    # a chunk's own tokens in the state at its end
    added = mm("bclgrp,bclgn->bcgrpn", tokens_first(to_end) * written, Bc)
    end = decay[..., -1]  # e^{gamma_L}, (B, NC, G, R)

    # ---- the loop over chunks: the state each one starts from
    def step(state, v):
        added_c, end_c = v
        return end_c[..., None, None] * state + added_c, state

    _, starts = jax.lax.scan(
        step, jnp.zeros((b, g, r, p, n), f32),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(end, 1, 0)),
    )
    y = y + tokens_first(decay) * mm(
        "bclgn,bcgrpn->bclgrp", Cc, jnp.moveaxis(starts, 0, 1)
    )
    y = y + D.astype(f32).reshape(g, r)[:, :, None] * x32
    return y.reshape(b, s, h, p).astype(dtype)
