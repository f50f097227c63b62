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
as one bf16 pass).  A Neumann series ``(I - L)(I + L^2)(I + L^4)..`` would be
five products and is not used: with keys alike its terms grow binomially and
cancel.

**One algorithm on two paths**, chosen by what a call shows
(:func:`gated_delta_plan`: backend, dtype, head sizes, group, length, chunk;
no flag and no model name), noted on the compile event as ``gated_delta:
pallas | composed``, every op of both under the scope ``gdn_scan``:

*The Pallas kernel pair* (a TPU, head sizes in whole lanes, a length of
whole chunks and whole grid steps).  Grid ``(B, key heads, N / 8)``, the
last axis sequential: a grid step takes one key head with the ``Hv / Hk``
value heads it serves — they share ``Q K^T`` and ``K K^T``, and dk leaves
the kernel summed over them — and walks eight chunks, the group's ``dk x
dv`` float32 states a VMEM scratch that the first step zeroes and no step
writes back.  q, k and v, o are read and written as ``(B, S, H d)`` — a
``(8 C, d)`` block at lane offset ``h d``: the reshape from ``(B, S, H, d)``
is free, so no transpose, no float32 copy, a key head read by index and
never repeated — and gamma (``g``'s running sum inside a chunk, the one
thing made outside, with its transpose) and ``beta`` as ``(B, S, Hv)``, a
head's column picked by a masked sum.  A chunk's ``Q K^T``, ``K K^T``,
``Gamma``, ``T``, ``D`` and output exist only in the step that uses them:
``D = T (beta (V - e^gamma K S_0))`` in one product, ``[Q; K] S_0`` in one
of 128 rows.  **The solve is in the kernel** (:func:`_solve_chunks`), as
:func:`_unit_lower_inverse` does it: the 16 x 16 diagonal blocks by
substitution — over every block of the step at once, row ``i`` of all ``8
group C / 16`` blocks one strided read, so the 16 sequential rows are paid a
grid step and not a chunk — and the block formula above them on the MXU, in
float32 at ``highest`` whatever the operands' dtype (the configuration's
stated precision of the solve).  No ``vmem_limit_bytes``: blocks and
scratch stay under 10 MiB of the 16 MiB a kernel gets unasked.

*What the backward keeps* (``jax.custom_vjp``, by hand): the forward kernel
then also writes ``T`` (in the operands' dtype, which is how every product
uses it) and the state at each chunk's start (``(B, Hk, group, N, dk, dv)``
float32, 268 MB a layer at 8,192 tokens and 32 heads of 128 x 128, alive
inside one layer's backward under the caller's ``--remat``); the backward
kernel walks the chunks in reverse with the state's gradient in VMEM,
recomputes the chunk's values from q, k, v, ``T`` and the saved state, and
writes dq, dk (the group summed, in float32 before the one rounding), dv and
the gradients of gamma and beta.  Nothing differentiates the solve: ``T = (I
+ L)^-1`` gives ``dL = -T^T dT T^T``, and with ``dT = dD R^T`` that is
``-(T^T dD) (T R)^T`` — one product of two values the backward has anyway.
What meets a state takes the group's states side by side (one product of
``group dv`` columns, or a sum over ``group dv`` terms).

*The composed form* (:func:`_chunked`; every other call: a CPU, a head size
that is no lane multiple, a length that would need padding): ``T``, ``U``,
``W`` and the scores for every chunk at once, a ``lax.scan`` over the
chunks, autodiff through it (the carried states of a layer are the same 268
MB, and the score-sized float32 tensors beside them).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.compilation import note_kernel_path

_HIGHEST = jax.lax.Precision.HIGHEST
_SUBSTITUTION_ROWS = 16  # the diagonal blocks solved row by row


def _group_of(q, k, v) -> int:
    """Value heads a key head: each key head serves ``Hv // Hk``
    consecutive value heads."""
    group, rest = divmod(v.shape[2], k.shape[2])
    if rest or q.shape != k.shape:
        raise ValueError(
            f"{v.shape[2]} value heads over q {q.shape} / k {k.shape}"
        )
    return group


def _heads_of(q, k, v):
    """``q`` and ``k`` with one head a value head (the composed form and the
    recurrence; the kernels read a key head by index)."""
    group = _group_of(q, k, v)
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


def gated_delta_rule(
    q, k, v, g, beta, *, chunk: int = 64, interpret: bool = False
):
    """:func:`gated_delta_rule_sequential`'s result in the chunked form
    (module docstring): same arguments, ``chunk`` tokens a sequential step.
    Where :func:`gated_delta_plan` takes the call the Pallas kernel pair runs
    it, else the composed form, which pads a length that is no multiple of
    ``chunk`` with tokens that neither decay nor write (``g`` = 0, ``beta``
    = 0).  ``interpret=True`` runs the kernels through the Pallas
    interpreter whatever the backend (the tier-1 tests, on a CPU)."""
    group = _group_of(q, k, v)
    step_chunks = gated_delta_plan(
        "tpu" if interpret else jax.default_backend(), v.dtype,
        k.shape[-1], v.shape[-1], group, v.shape[1], chunk,
    )
    if step_chunks is None:
        note_kernel_path("gated_delta", "composed")
        with jax.named_scope("gdn_scan"):
            return _chunked(q, k, v, g, beta, chunk)
    note_kernel_path("gated_delta", "pallas-interpret" if interpret else "pallas")
    with jax.named_scope("gdn_scan"):
        q, k = q.astype(v.dtype), k.astype(v.dtype)
        g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    return _kernel_rule(q, k, v, g, beta, chunk, step_chunks, interpret)


def chunk_decays(g):
    """The decay factors of a chunked scan from ``g (..., C)``, the float32
    log-decays (<= 0) of a chunk's tokens along the last axis, with ``gamma``
    their running sum inside the chunk: ``(below, ratio, decay, to_end)`` —
    the mask ``i >= j``; ``Gamma_ij = e^{gamma_i - gamma_j}`` for ``j <= i``,
    else 0, ``(..., C, C)``; ``e^{gamma_i}``, the chunk's start to token
    ``i``; ``e^{gamma_C - gamma_j}``, token ``j`` to the chunk's end.  Every
    factor is a ratio of at most one: nothing is divided by a decay.  What
    the gated delta rule here and the state-space scan (``ops/ssd.py``)
    share."""
    gamma = jnp.cumsum(g, axis=-1)
    row = jnp.arange(g.shape[-1])
    below = row[:, None] >= row[None, :]
    ratio = jnp.exp(jnp.where(
        below, gamma[..., :, None] - gamma[..., None, :], -jnp.inf
    ))
    return below, ratio, jnp.exp(gamma), jnp.exp(gamma[..., -1:] - gamma)


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
    below, ratio, decay, to_end = chunk_decays(g)
    lower = beta[..., :, None] * ratio * mm("bhnid,bhnjd->bhnij", k, k)
    t = _unit_lower_inverse(jnp.where(below & ~jnp.eye(chunk, dtype=bool), lower, 0.0))
    # operands of the loop's products: kept in their dtype, not float32
    u = mm("bhnij,bhnjd->bhnid", t, beta[..., None] * v.astype(f32)).astype(dtype)
    w = mm(
        "bhnij,bhnjd->bhnid", t, (beta * decay)[..., None] * k.astype(f32)
    ).astype(dtype)
    scores = (ratio * mm("bhnid,bhnjd->bhnij", q, k)).astype(dtype)
    q_in = (decay[..., None] * q.astype(f32)).astype(dtype)
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


# ------------------------------------------------------- the Pallas kernel pair

# what a kernel's blocks (twice: the pipeline's two buffers) and its scratch
# may hold of the 16 MiB of scoped VMEM a kernel gets unasked; the rest is
# the compiler's own temporaries (a chunk's C x C and C x d float32 values)
_KERNEL_VMEM_LIMIT = 10 * 2**20
# chunks a grid step: whole sublane tiles of the (chunks, C) blocks that
# hold a chunk's tokens along the lanes; a shorter sequence is one step
_STEP_CHUNKS = 8


def _kernel_vmem_bytes(dtype, dk, dv, group, chunk, step_chunks) -> int:
    """What the larger of the two kernels holds.  Backward: q, k, v, dO in,
    dq, dk, dv out, ``T`` and the saved states, gamma and beta of every head
    (a token a row of 128 lanes), two buffers each, and the carried state
    gradients.  Forward: the solve's three scratches instead of dO and the
    gradients."""
    item, rows = jnp.dtype(dtype).itemsize, chunk * step_chunks
    shared = (
        group * step_chunks * (chunk * chunk * item + dk * dv * 4)
        + 2 * rows * 128 * 4
    )
    backward = 2 * (4 * rows * dk * item + 3 * rows * group * dv * item + shared)
    forward = 2 * (
        2 * rows * dk * item + 2 * rows * group * dv * item + shared
    ) + group * step_chunks * chunk * (chunk + chunk + 128) * 4
    return max(forward, backward) + group * dk * dv * 4


def gated_delta_plan(
    backend: str, dtype, dk: int, dv: int, group: int, seq_len: int, chunk: int
) -> int | None:
    """The chunks a grid step of the kernel pair works through, or ``None``
    for the composed form: a pure function of what the call shows.  The
    kernels take a TPU, head sizes in whole lanes (a ``(rows, d)`` block at
    lane offset ``h d`` of ``(B, S, H d)`` is then a tile), a chunk of 64
    tokens, a length of whole chunks and whole grid steps — up to
    ``_STEP_CHUNKS`` chunks, or a multiple of them: nothing is padded — and a
    group whose blocks fit ``_KERNEL_VMEM_LIMIT``.  The model's calls are
    bf16 at chunk 64 and 8,192 tokens; float32 operands, a chunk of 16 (one
    diagonal block) and a single short step are taken for the tests' sake
    (exactness against the recurrence; tiny interpreted shapes), and cost
    ``_solve_chunks`` its early return and ``_each`` its whole unroll.  A
    grid step works through ``_STEP_CHUNKS`` chunks because its fixed cost,
    ~0.35 us, is as much as half a chunk's forward work, and the solve's
    substitution runs over a step's blocks at once."""
    if backend != "tpu" or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return None
    if dk % 128 or dv % 128 or chunk not in (16, 64) or seq_len % chunk:
        return None
    chunks = seq_len // chunk
    if chunks > _STEP_CHUNKS and chunks % _STEP_CHUNKS:
        return None
    step_chunks = min(chunks, _STEP_CHUNKS)
    held = _kernel_vmem_bytes(dtype, dk, dv, group, chunk, step_chunks)
    return step_chunks if held <= _KERNEL_VMEM_LIMIT else None


def _mm(x, y, dims, dtype):
    """A product on the MXU: operands in ``dtype``, float32 accumulation;
    float32 operands at ``highest`` (Mosaic's default rounds them to bf16)."""
    return jax.lax.dot_general(
        x.astype(dtype), y.astype(dtype), (dims, ((), ())),
        precision=_HIGHEST if dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32,
    )


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))  # x y, x y^T, x^T y


def _chunk_vectors(gamma_ref, beta_ref, rows_ref, c, tiling, head):
    """A chunk's per-token vectors for the value heads of key head ``head``
    (the step's place on the grid's head axis), each a ``(C, 1)`` column —
    read from ``(B, S, Hv)`` as it is, tokens down the sublanes, the head's
    lane picked by a masked sum (its index follows the grid) — and the
    cumulative log-decay as a ``(1, C)`` row too, from its transpose."""
    t = tiling
    first = head * t.group
    gamma, beta = gamma_ref[0, t.rows(c), :], beta_ref[0, t.rows(c), :]  # (C, Hv)
    lane = jax.lax.broadcasted_iota(jnp.int32, gamma.shape, 1)
    col = lambda x, e: jnp.sum(  # noqa: E731
        jnp.where(lane == first + e, x, 0.0), axis=1, keepdims=True
    )
    vectors = []
    for e in range(t.group):
        gamma_e = col(gamma, e)
        at_end = gamma_e[t.chunk - 1:t.chunk]  # (1, 1)
        vectors.append(dict(
            gamma=gamma_e, gamma_row=rows_ref[0, 0, e, pl.ds(c, 1), :],
            beta=col(beta, e), decay=jnp.exp(gamma_e),
            to_end=jnp.exp(at_end - gamma_e),
            # e^{gamma_C} on every lane of a state's row: over the lanes
            # before the exp, so that nothing folds the two into the one
            # broadcast over sublanes and lanes that Mosaic does not have
            end=jnp.exp(jnp.broadcast_to(at_end, (1, t.dv))),
        ))
    return vectors


def _ratios(gamma, gamma_row, chunk):
    """``Gamma_ij = e^{gamma_i - gamma_j}`` for ``j <= i``, else 0, and the
    mask of ``j < i``."""
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    ratio = jnp.where(i >= j, jnp.exp(jnp.where(i >= j, gamma - gamma_row, 0.0)), 0.0)
    return ratio, i > j


def _aligned(start, size):
    """``size`` rows from ``start``, a multiple of ``size``."""
    if not isinstance(start, int):
        start = pl.multiple_of(start, size)
    return pl.ds(start, size)


def _each(count, body, unroll=1):
    """``body(i)`` for ``i`` in ``range(count)``, ``unroll`` of them an
    iteration of the loop (one block of code that the scheduler interleaves;
    Pallas unrolls a ``fori_loop`` whole or not at all)."""
    unroll = max(1, min(unroll, count))
    while count % unroll:
        unroll -= 1
    if unroll == count:
        for i in range(count):
            body(i)
        return

    def some(n, carry):
        for u in range(unroll):
            body(n * unroll + u)
        return carry

    jax.lax.fori_loop(0, count // unroll, some, 0)


class _Tiling(NamedTuple):
    """A grid step's sizes, as the kernels index with them."""

    group: int  # value heads of the step's key head
    chunk: int
    chunks: int  # chunks a step
    dv: int

    def rows(self, c):
        return _aligned(c * self.chunk, self.chunk)

    def values(self, e):
        return slice(e * self.dv, (e + 1) * self.dv)

    def solved(self, e, c):  # where chunk c's T stands in the solve's scratch
        n = e * self.chunks + c
        return n, _aligned(n * self.chunk, self.chunk)


def _solve_chunks(
    k_ref, gamma_ref, beta_ref, rows_ref, lower, folded, solved, tiling, head
):
    """``T = (I + L)^-1`` of every chunk and value head of a grid step, into
    ``solved`` (``(group m C, C)``, a chunk ``C`` rows), as
    :func:`_unit_lower_inverse` does it: substitution on the 16 x 16 diagonal
    blocks, the block formula above them.  The substitution runs over all
    ``group m C / 16`` blocks of the step at once — row ``i`` of every block
    is one strided read — so its 16 sequential rows are 16 steps a grid
    step, not a chunk."""
    t = tiling
    f32, dtype, chunk = jnp.float32, k_ref.dtype, t.chunk
    per_chunk = chunk // _SUBSTITUTION_ROWS
    blocks = t.group * t.chunks * per_chunk

    def lower_of(c):  # L, and its diagonal blocks side by side
        k = k_ref[0, t.rows(c), :]
        gram = _mm(k, k, _NT, dtype)
        for e, x in enumerate(_chunk_vectors(gamma_ref, beta_ref, rows_ref, c, t, head)):
            ratio, strict = _ratios(x["gamma"], x["gamma_row"], chunk)
            low = jnp.where(strict, x["beta"] * ratio * gram, 0.0)
            n, at = t.solved(e, c)
            lower[n] = low
            diagonal = [
                slice(b * _SUBSTITUTION_ROWS, (b + 1) * _SUBSTITUTION_ROWS)
                for b in range(per_chunk)
            ]
            folded[at, :] = jnp.concatenate([low[x, x] for x in diagonal], axis=0)

    for c in range(t.chunks):
        lower_of(c)
    # row i of every diagonal block's inverse from the rows above it
    block = jax.lax.broadcasted_iota(jnp.int32, (blocks, chunk), 0) % per_chunk
    lane = jax.lax.broadcasted_iota(jnp.int32, (blocks, chunk), 1)
    rows = []
    for i in range(_SUBSTITUTION_ROWS):
        row = (lane == block * _SUBSTITUTION_ROWS + i).astype(f32)
        if i:
            factors = folded[pl.ds(i, blocks, stride=_SUBSTITUTION_ROWS), :]
            for j in range(i):
                row = row - factors[:, j:j + 1] * rows[j]
        rows.append(row)
        solved[pl.ds(i, blocks, stride=_SUBSTITUTION_ROWS), :] = row
    if per_chunk == 1:
        return
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)

    def product(x, y):  # float32 tiles whatever the operands' dtype
        return jnp.dot(x, y, precision=_HIGHEST, preferred_element_type=f32)

    def above(c):  # [[A, 0], [B, C]]^-1 = [[A^-1, 0], [-C^-1 B A^-1, C^-1]]
        for e in range(t.group):
            n, at = t.solved(e, c)
            x, low = solved[at, :], lower[n]
            size = _SUBSTITUTION_ROWS
            while size < chunk:
                lower_left = (
                    (i // (2 * size) == j // (2 * size))
                    & (i // size % 2 == 1) & (j // size % 2 == 0)
                )
                x = x - product(product(x, jnp.where(lower_left, low, 0.0)), x)
                size *= 2
            solved[at, :] = x

    # the chunks' chains of products are independent: one block of code, so
    # that the scheduler interleaves them (a loop ran them 1.6 x slower)
    for c in range(t.chunks):
        above(c)


def _fwd_kernel(
    q_ref, k_ref, v_ref, gamma_ref, beta_ref, rows_ref, o_ref, *rest, tiling, save
):
    """One key head's value heads over ``tiling.chunks`` chunks; ``save``
    also writes what the backward reads: ``T`` and the state at each chunk's
    start."""
    t = tiling
    t_ref, states_ref = rest[:2] if save else (None, None)
    state, lower, folded, solved = rest[-4:]
    dtype, chunk = v_ref.dtype, t.chunk

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    head = pl.program_id(1)
    _solve_chunks(k_ref, gamma_ref, beta_ref, rows_ref, lower, folded, solved, t, head)

    def one_chunk(c):
        q, k = q_ref[0, t.rows(c), :], k_ref[0, t.rows(c), :]
        qk = jnp.concatenate([q, k], axis=0)  # (2 C, dk)
        scores = _mm(q, k, _NT, dtype)  # Q K^T, shared by the group
        for e, x in enumerate(_chunk_vectors(gamma_ref, beta_ref, rows_ref, c, t, head)):
            lanes = t.values(e)
            s0 = state[e]
            solution = solved[t.solved(e, c)[1], :]
            if save:
                states_ref[0, 0, e, c] = s0
                t_ref[0, 0, e, c] = solution.astype(t_ref.dtype)
            on_state = _mm(qk, s0, _NN, dtype)  # [Q; K] S_0
            qs, ks = on_state[:chunk], on_state[chunk:]
            r = x["beta"] * (
                v_ref[0, t.rows(c), lanes].astype(jnp.float32) - x["decay"] * ks
            )
            d = _mm(solution, r, _NN, dtype)
            ratio, _ = _ratios(x["gamma"], x["gamma_row"], chunk)
            o = x["decay"] * qs + _mm(scores * ratio, d, _NN, dtype)
            o_ref[0, t.rows(c), lanes] = o.astype(o_ref.dtype)
            state[e] = x["end"] * s0 + _mm(k, x["to_end"] * d, _TN, dtype)

    # two chunks a loop iteration: what does not wait for the state (scores,
    # ratios, the vectors) of the second overlaps the first's chain
    _each(t.chunks, one_chunk, 2)


def _bwd_kernel(
    q_ref, k_ref, v_ref, gamma_ref, beta_ref, rows_ref, t_ref, states_ref, do_ref,
    dq_ref, dk_ref, dv_ref, drows_ref, dstate, *, tiling,
):
    """The chunks in reverse, ``dstate`` the gradient of the state a chunk
    leaves: recomputes the chunk's ``D`` and scores from q, k, v, the saved
    state and ``T``, and takes ``T``'s own cotangent back to k, g and beta
    in closed form (``T = (I + L)^-1``: ``dL = -T^T dT T^T``)."""
    t = tiling
    dtype, chunk, f32 = v_ref.dtype, t.chunk, jnp.float32
    head = pl.program_id(1)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    row_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    row_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    side = lambda xs: jnp.concatenate(xs, axis=1)  # noqa: E731  (heads along the lanes)

    def row_of(col):  # (C, 1) -> (1, C), exactly
        return jnp.sum(jnp.where(row_i == row_j, col, 0.0), axis=0, keepdims=True)

    def one_chunk(i):
        c = t.chunks - 1 - i
        at, heads = t.rows(c), range(t.group)
        q, k = q_ref[0, at, :], k_ref[0, at, :]
        qk = jnp.concatenate([q, k], axis=0)
        both = _mm(qk, k, _NT, dtype)  # [Q K^T; K K^T]
        scores, gram = both[:chunk], both[chunk:]
        lanes = [t.values(e) for e in heads]
        # what meets a state takes the group's states side by side: one
        # product of g dv columns, or of g dv terms a sum
        s0 = side([states_ref[0, 0, e, c].astype(dtype) for e in heads])
        ds = side([dstate[e].astype(dtype) for e in heads])
        on_state = _mm(qk, s0, _NN, dtype)  # [Q; K] S_0
        d_scaled = _mm(k, ds, _NN, dtype)  # dL/d(to_end D), from S_C = .. + K^T (to_end D)
        dscores, dgram = jnp.zeros_like(scores), jnp.zeros_like(gram)
        scaled, d_on_state, d_rows = [], [], []
        vectors = _chunk_vectors(gamma_ref, beta_ref, rows_ref, c, t, head)
        for e, x in enumerate(vectors):
            beta, decay, to_end, end = x["beta"], x["decay"], x["to_end"], x["end"]
            ratio, strict = _ratios(x["gamma"], x["gamma_row"], chunk)
            solution = t_ref[0, 0, e, c]
            do = do_ref[0, at, lanes[e]]
            # ---- the forward's values again
            qs, ks = on_state[:chunk, lanes[e]], on_state[chunk:, lanes[e]]
            y = v_ref[0, at, lanes[e]].astype(f32) - decay * ks
            r = beta * y
            d = _mm(solution, r, _NN, dtype)
            p = scores * ratio
            # ---- S_C = end S_0 + K^T (to_end D)
            scaled.append(to_end * d)
            dd = to_end * d_scaled[:, lanes[e]]
            d_to_end = jnp.sum(d * d_scaled[:, lanes[e]], axis=1, keepdims=True)
            d_end = jnp.sum(dstate[e] * states_ref[0, 0, e, c], keepdims=True)
            # ---- O = decay (Q S_0) + P D
            do32 = do.astype(f32)
            d_decay = jnp.sum(do32 * qs, axis=1, keepdims=True)
            dp = _mm(do, d, _NT, dtype)
            dd += _mm(p, do, _TN, dtype)
            dscores += dp * ratio
            # ---- D = T R, R = beta (V - decay K S_0)
            dr = _mm(solution, dd, _TN, dtype)
            d_beta = jnp.sum(dr * y, axis=1, keepdims=True)
            dv_ref[0, at, lanes[e]] = (beta * dr).astype(dv_ref.dtype)
            d_decay -= jnp.sum(beta * ks * dr, axis=1, keepdims=True)
            d_on_state.append(
                jnp.concatenate([decay * do32, -beta * decay * dr], axis=0)
            )
            # ---- T = (I + L)^-1, L_ij = beta_i Gamma_ij (k_i . k_j), j < i:
            # dL = -T^T dT T^T with dT = dD R^T, which is -(T^T dD) (T R)^T
            dl_ratio = jnp.where(strict, -_mm(dr, d, _NT, dtype) * ratio, 0.0)
            d_beta += jnp.sum(dl_ratio * gram, axis=1, keepdims=True)
            dgram_e = beta * dl_ratio
            dgram += dgram_e
            # ---- the decays: Gamma_ij, e^gamma_i, e^{gamma_C - gamma_j}, e^gamma_C
            d_log_ratio = dp * p + dgram_e * gram  # dL/dGamma_ij Gamma_ij
            d_gamma = (
                jnp.sum(d_log_ratio, axis=1, keepdims=True)
                + d_decay * decay - d_to_end * to_end
            )
            at_end = jnp.sum(d_to_end * to_end, keepdims=True) + d_end * end[:, :1]
            d_gamma = d_gamma + jnp.where(last, at_end, 0.0)
            d_rows.append((
                row_of(d_gamma) - jnp.sum(d_log_ratio, axis=0, keepdims=True),
                row_of(d_beta),
            ))
        d_on_state = side(d_on_state)  # (2 C, g dv): rows of dQ S_0, then of dK S_0
        new = _mm(qk, d_on_state, _TN, dtype)  # (dk, g dv)
        for e, x in enumerate(vectors):
            dstate[e] = x["end"] * dstate[e] + new[:, lanes[e]]
        dq = _mm(dscores, k, _NN, dtype) + _mm(d_on_state[:chunk], s0, _NT, dtype)
        dk = (
            _mm(dscores, q, _TN, dtype)
            + _mm(dgram, k, _NN, dtype) + _mm(dgram, k, _TN, dtype)
            + _mm(side(scaled + [d_on_state[chunk:]]), side([ds, s0]), _NT, dtype)
        )
        dq_ref[0, at, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, at, :] = dk.astype(dk_ref.dtype)
        for e, (d_gamma, d_beta) in enumerate(d_rows):
            drows_ref[0, 0, e, pl.ds(c, 1), :] = d_gamma
            drows_ref[0, 0, t.group + e, pl.ds(c, 1), :] = d_beta

    _each(t.chunks, one_chunk, 2)


def _running_sum(x, chunk, *, back=False):
    """Over the tokens of each chunk, ``(B, S, H)``; ``back``: from the
    chunk's end.  A product with a triangle of ones: a few MB of work that a
    ``cumsum`` over a middle axis would make a window reduction of."""
    b, s, h = x.shape
    row = jnp.arange(chunk)
    ones = (row[:, None] <= row[None, :] if back else row[:, None] >= row[None, :])
    return jnp.einsum(
        "ij,bnjh->bnih", ones.astype(x.dtype), x.reshape(b, s // chunk, chunk, h),
        precision=_HIGHEST,
    ).reshape(b, s, h)


def _prepare(q, k, v, g, beta, chunk):
    """What the kernels read: q, k, v as ``(B, S, H d)``, the cumulative
    log-decay ``gamma`` and ``beta`` as ``(B, S, Hv)`` and ``gamma`` also
    with a chunk's tokens along the lanes, ``(B, Hk, group, N, C)``
    (:func:`_chunk_vectors`)."""
    b, s, hv, dv = v.shape
    hk, dk = k.shape[2:]
    gamma = _running_sum(g, chunk)
    rows = jnp.swapaxes(gamma, 1, 2).reshape(b, hk, hv // hk, s // chunk, chunk)
    return (
        q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk),
        v.reshape(b, s, hv * dv), gamma, beta, rows,
    )


def _call(kernel, step_chunks, operands, outputs, scratch, interpret, *, backward):
    """One of the two kernels on the grid ``(B, Hk, N / step_chunks)``, the
    last axis sequential; the backward walks the chunk blocks from the last.
    ``scratch`` follows the carried states' own.  ``operands`` and
    ``outputs`` are ``(array or shape, kind)``: ``keys`` and ``values`` are
    ``(B, S, H d)``, ``tokens`` ``(B, S, Hv)`` (every head in a block),
    ``rows`` ``(B, Hk, r, N, C)`` and ``head`` ``(B, Hk, group, N, x, y)``."""
    q2, rows = operands[0][0], operands[5][0]
    b, hk, group, n, chunk = rows.shape
    m = step_chunks
    dk = q2.shape[-1] // hk
    dv = operands[2][0].shape[-1] // (hk * group)
    at = (lambda j: n // m - 1 - j) if backward else (lambda j: j)

    def spec(x, kind):
        if kind == "keys":
            return pl.BlockSpec((1, m * chunk, dk), lambda b, h, j: (b, at(j), h))
        if kind == "values":
            return pl.BlockSpec((1, m * chunk, group * dv), lambda b, h, j: (b, at(j), h))
        if kind == "tokens":
            return pl.BlockSpec((1, m * chunk, x.shape[2]), lambda b, h, j: (b, at(j), 0))
        if kind == "rows":
            return pl.BlockSpec(
                (1, 1, x.shape[2], m, chunk), lambda b, h, j: (b, h, 0, at(j), 0)
            )
        return pl.BlockSpec(
            (1, 1, group, m) + x.shape[4:], lambda b, h, j: (b, h, 0, at(j), 0, 0)
        )

    return pl.pallas_call(
        functools.partial(kernel, tiling=_Tiling(group, chunk, m, dv)),
        grid=(b, hk, n // m),
        in_specs=[spec(x, kind) for x, kind in operands],
        out_specs=[spec(x, kind) for x, kind in outputs],
        out_shape=[x for x, _ in outputs],
        scratch_shapes=[
            pltpu.VMEM((group, dk, dv), jnp.float32),  # the carried states
            *scratch,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="gated_delta_bwd" if backward else "gated_delta_fwd",
    )(*(x for x, _ in operands))


def _kernel_forward(q2, k2, v2, gamma, beta, rows, step_chunks, interpret, *, save):
    b, hk, group, n, chunk = rows.shape
    dk, dv = q2.shape[-1] // hk, v2.shape[-1] // (hk * group)
    shape = jax.ShapeDtypeStruct
    outputs = [(shape(v2.shape, v2.dtype), "values")]
    if save:
        outputs += [
            (shape((b, hk, group, n, chunk, chunk), v2.dtype), "head"),
            (shape((b, hk, group, n, dk, dv), jnp.float32), "head"),
        ]

    per_step = group * step_chunks  # chunks of the group's heads a grid step
    solve_scratch = [
        pltpu.VMEM((per_step, chunk, chunk), jnp.float32),  # L
        pltpu.VMEM((per_step * chunk, _SUBSTITUTION_ROWS), jnp.float32),
        pltpu.VMEM((per_step * chunk, chunk), jnp.float32),  # T
    ]
    return _call(
        functools.partial(_fwd_kernel, save=save), step_chunks,
        [
            (q2, "keys"), (k2, "keys"), (v2, "values"),
            (gamma, "tokens"), (beta, "tokens"), (rows, "rows"),
        ],
        outputs, solve_scratch, interpret, backward=False,
    )


def _kernel_backward(
    q2, k2, v2, gamma, beta, rows, t, states, do2, step_chunks, interpret
):
    shape = jax.ShapeDtypeStruct
    b, hk, group, n, chunk = rows.shape
    return _call(
        _bwd_kernel, step_chunks,
        [
            (q2, "keys"), (k2, "keys"), (v2, "values"),
            (gamma, "tokens"), (beta, "tokens"), (rows, "rows"),
            (t, "head"), (states, "head"), (do2, "values"),
        ],
        [
            (shape(q2.shape, q2.dtype), "keys"), (shape(k2.shape, k2.dtype), "keys"),
            (shape(v2.shape, v2.dtype), "values"),
            (shape((b, hk, 2 * group, n, chunk), jnp.float32), "rows"),
        ],
        [], interpret, backward=True,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kernel_rule(q, k, v, g, beta, chunk, step_chunks, interpret):
    """``g`` and ``beta`` in float32, q and k in ``v``'s dtype."""
    with jax.named_scope("gdn_scan"):
        (o,) = _kernel_forward(
            *_prepare(q, k, v, g, beta, chunk), step_chunks, interpret, save=False
        )
        return o.reshape(v.shape)


def _kernel_rule_fwd(q, k, v, g, beta, chunk, step_chunks, interpret):
    with jax.named_scope("gdn_scan"):
        operands = _prepare(q, k, v, g, beta, chunk)
        o, t, states = _kernel_forward(*operands, step_chunks, interpret, save=True)
        return o.reshape(v.shape), (operands, t, states)


def _kernel_rule_bwd(chunk, step_chunks, interpret, residuals, do):
    (q2, k2, v2, gamma, beta, rows), t, states = residuals
    b, s, hv, dv = do.shape
    hk, group = rows.shape[1:3]
    with jax.named_scope("gdn_scan"):
        dq, dk, dv_, drows = _kernel_backward(
            q2, k2, v2, gamma, beta, rows, t, states,
            do.astype(v2.dtype).reshape(b, s, hv * dv), step_chunks, interpret,
        )
        # (B, Hk, 2 group, N, C): d gamma, d beta of a key head's value heads
        drows = drows.reshape(b, hk, 2, group, s)
        dgamma, dbeta = (
            jnp.swapaxes(drows[:, :, i].reshape(b, hv, s), 1, 2) for i in (0, 1)
        )
        # gamma is g's running sum inside the chunk: g_j reaches gamma_i, i >= j
        dg = _running_sum(dgamma, chunk, back=True)
        return (
            dq.reshape(b, s, hk, -1), dk.reshape(b, s, hk, -1),
            dv_.reshape(do.shape), dg, dbeta,
        )


_kernel_rule.defvjp(_kernel_rule_fwd, _kernel_rule_bwd)
