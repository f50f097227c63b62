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

**One algorithm on two paths**, chosen by what a call shows
(:func:`ssd_plan`: backend, dtype, heads, head size, groups, state, length,
chunk; no flag and no model name), noted on the compile event as ``ssd:
pallas | composed``, every op of both under the scope ``ssd_scan``:

*The Pallas kernel pair* (a TPU, a state and a group's ``(H / G) P``
channels in whole lane tiles, a length of whole chunks).  Grid ``(B, G, NC /
4)``, the last axis sequential: a grid step takes one group of B and C with
the ``H / G`` heads it serves and walks four chunks in a loop, the group's
states a VMEM scratch — ``(N, (H / G) P)`` float32, the heads' ``S^T`` side
by side — that the first step zeroes and no step writes back.  x and y are
read and written as ``(B, S, H P)`` — a group's ``(4 L, (H / G) P)`` block at
lane offset ``g (H / G) P`` — and B, C as ``(B, S, G N)``: the reshapes are
free, so no transpose, no float32 copy, no B or C repeated by head.  ``dt``
and ``gamma`` (``dt A``'s running sum inside a chunk, the one thing made
outside, with its transpose) arrive a token a row and a head a column, ``(B,
G, S, 2 H / G)``.  A chunk's ``C B^T`` is one product a group; a head's
``Gamma``, its masked scores and ``X~`` exist only in the step that uses
them; the heads' channels are worked a 128-lane tile at a time, two heads
of 64 side by side: what meets the states (``C S^T``, the increment ``B^T
(to_end X~)``) is one product of 128 columns a tile — whole lane tiles
though a head is half of one; the group's 512 at once kept 128 vector
registers alive across the tiles and spilled them — and a head's own scores
meet its ``X~`` at the tile's width with its neighbour's lanes zeroed.  ``D
x`` is added in the kernel.  No
``vmem_limit_bytes``: blocks and scratch stay under 10 MiB of the 16 MiB a
kernel gets unasked.

*What the backward keeps* (``jax.custom_vjp``, by hand): the forward kernel
then also writes the state at each chunk's start (``(B, G, NC, N, (H / G)
P)`` float32, 134 MB a layer call at 8,192 tokens and 64 heads of 64 x 128,
alive inside one layer's backward under the caller's ``--remat``) — nothing
``L x L`` goes to HBM.  The backward kernel walks the chunks in reverse with
the states' gradient in VMEM, recomputes the chunk's ``C B^T``, ratios and
scores from x, dt, B, C and the saved state, and writes dx, dB and dC (the
group's heads summed in float32 before the one rounding) and, a head a row
of a chunk's tokens, the gradients of ``dt`` through ``X~`` and of ``gamma``
and ``sum_p dy x``; outside, ``gamma``'s gradient runs back through the
running sum to ``dt`` and ``A``, and ``D``'s is summed over the tokens.
``dL/dgamma_i`` is taken as ``sum_p dy_ip y_ip - sum_p X~_ip dX~_ip`` over
the strictly earlier tokens' scores (a token's own ratio is one whatever
``gamma``), so no ``L x L`` row or column sums and no pair of equal terms
that cancels to rounding.

*The composed form* (:func:`_chunked`; every other call: a CPU, a padded
length, a head size or state that is no lane multiple), autodiff through it:
``C B^T``, the masked ratios and the chunks' own state increments for every
chunk at once, a ``lax.scan`` over the chunks that carries the ``(B, H, P,
N)`` float32 state (one multiply-add a chunk: the products are outside it),
and the states' read-out for every chunk at once.  A chunk's ``Gamma`` is
``H L^2`` floats: 268 MB a layer call at 8,192 tokens, 64 heads and ``L`` =
128, and the scores and each one's gradient as much again.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.compilation import note_kernel_path
from .gated_delta import (
    _NN, _NT, _TN, _aligned, _each, _mm, _running_sum, chunk_decays,
)

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


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 128, interpret: bool = False):
    """:func:`ssd_scan_sequential`'s result in the chunked form (module
    docstring): same arguments, ``chunk`` tokens a sequential step.  Where
    :func:`ssd_plan` takes the call the Pallas kernel pair runs it, else the
    composed form, which pads a length that is no multiple of ``chunk`` with
    tokens that neither decay nor write (``dt`` = 0).  Differentiable in all
    six.  ``interpret=True`` runs the kernels through the Pallas interpreter
    whatever the backend (the tier-1 tests, on a CPU)."""
    _heads_a_group(x, B)  # refused before either path
    step_chunks = ssd_plan(
        "tpu" if interpret else jax.default_backend(), x.dtype,
        x.shape[2], x.shape[3], B.shape[2], B.shape[3], x.shape[1], chunk,
    )
    if step_chunks is None:
        note_kernel_path("ssd", "composed")
        return _composed(x, dt, A, B, C, D, chunk)
    note_kernel_path("ssd", "pallas-interpret" if interpret else "pallas")
    with jax.named_scope("ssd_scan"):
        f32 = jnp.float32
        operands = (
            x, dt.astype(f32), A.astype(f32), B.astype(x.dtype),
            C.astype(x.dtype), D.astype(f32),
        )
    return _kernel_scan(*operands, chunk, step_chunks, interpret)


def _composed(x, dt, A, B, C, D, chunk):
    """The composed form of a call (all there was before the kernels): pad
    to whole chunks, :func:`_chunked`, cut."""
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


# ------------------------------------------------------- the Pallas kernel pair

# what a kernel's blocks (twice: the pipeline's two buffers) and its scratch
# may hold of the 16 MiB of scoped VMEM a kernel gets unasked; the rest is
# the compiler's own temporaries (a chunk's (L, heads P) float32 values)
_KERNEL_VMEM_LIMIT = 10 * 2**20
# chunks a grid step, the most that fit: a step's fixed cost (~0.35 us) against
# the blocks it holds twice
_STEP_CHUNKS = (4, 2, 1)
# chunks an iteration of a kernel's loop over its step's chunks: the forward's
# second chunk has work that does not wait for the first's state (C B^T, the
# ratios), and the scheduler packs the pair into 18 % fewer bundles a chunk;
# the backward gains 6 % for twice the code (compiled for a described v5e)
_FWD_UNROLL = 2
_BWD_UNROLL = 1
_LANES = 128


class _Tiling(NamedTuple):
    """A grid step's sizes, as the kernels index with them.  The group's
    heads stand side by side along the lanes, ``heads * head_dim`` of them in
    tiles of 128: a tile holds ``128 / head_dim`` whole heads, or a head
    ``head_dim / 128`` whole tiles."""

    heads: int  # of the step's group of B and C
    head_dim: int
    chunk: int
    chunks: int  # chunks a step

    @property
    def tiles(self):
        return self.heads * self.head_dim // _LANES

    def rows(self, c):
        return _aligned(c * self.chunk, self.chunk)

    def lanes(self, tile):
        return slice(tile * _LANES, (tile + 1) * _LANES)

    def heads_of(self, tile):
        first = tile * _LANES // self.head_dim
        return range(first, max(first + 1, (tile + 1) * _LANES // self.head_dim))

    def spread(self, v, tile):
        """``v (rows, heads)``, a head a column: the columns of ``tile``'s
        heads, each over the 128 lanes."""
        return {
            e: jnp.broadcast_to(v[:, e:e + 1], (v.shape[0], _LANES))
            for e in self.heads_of(tile)
        }

    def wide(self, spread, tile):
        """Each lane of ``tile`` taking its head's of ``spread``."""
        heads = self.heads_of(tile)
        out = spread[heads[0]]
        if len(heads) > 1:
            lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
            for i, e in enumerate(heads[1:], 1):
                out = jnp.where(lane >= i * self.head_dim, spread[e], out)
        return out

    def only(self, e, tile, v):
        """``v (rows, 128)`` of ``tile``, float32, with zeros outside head
        ``e``'s lanes: a product with it is the head's alone, at the width
        of a whole tile."""
        if self.head_dim >= _LANES:
            return v
        lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        at = (e - self.heads_of(tile)[0]) * self.head_dim
        return jnp.where((lane >= at) & (lane < at + self.head_dim), v, jnp.zeros_like(v))

    def sublanes(self, e, tile):
        """Head ``e``'s channels of ``tile``, transposed."""
        if self.head_dim >= _LANES:
            return slice(None)
        at = (e - self.heads_of(tile)[0]) * self.head_dim
        return slice(at, at + self.head_dim)


def _kernel_vmem_bytes(dtype, heads, head_dim, state, chunk, step_chunks, chunks) -> int:
    """What the larger of the two kernels holds.  Backward: x, dy in and dx
    out, B, C in and dB, dC out, a token's ``dt`` and ``gamma`` (a token a
    row of 128 lanes), the saved states, gamma's and the three gradients'
    rows of the whole sequence, two buffers each, and the carried state
    gradients.  Forward: x, y, B, C, the tokens, the states and gamma's
    rows."""
    item, rows, wide = jnp.dtype(dtype).itemsize, chunk * step_chunks, heads * head_dim
    a_row = heads * -(-chunks // 8) * 8 * max(chunk, _LANES) * 4
    shared = rows * _LANES * 4 + step_chunks * state * wide * 4 + a_row
    backward = 2 * (3 * rows * wide * item + 4 * rows * state * item + shared + 3 * a_row)
    forward = 2 * (2 * rows * wide * item + 2 * rows * state * item + shared)
    return max(forward, backward) + state * wide * 4


def ssd_plan(
    backend: str, dtype, heads: int, head_dim: int, groups: int, state: int,
    seq_len: int, chunk: int,
) -> int | None:
    """The chunks a grid step of the kernel pair works through, or ``None``
    for the composed form: a pure function of what the call shows.  The
    kernels take a TPU, a state and a group's ``(H / G) P`` channels in whole
    lane tiles (a ``(rows, (H / G) P)`` block at lane offset ``g (H / G) P``
    of ``(B, S, H P)`` is then tiles, and so is a group's ``(rows, N)`` of
    ``(B, S, G N)``) with a head a whole part of a tile or whole tiles, a
    chunk of 128 tokens, a length of whole chunks — nothing is padded — and
    of whole grid steps of ``_STEP_CHUNKS`` chunks, the most whose blocks fit
    ``_KERNEL_VMEM_LIMIT`` for the group.  The model's calls are bf16 at
    chunk 128 and 8,192 tokens; float32 operands and a chunk of 16 are taken
    for the tests' sake (exactness against the recurrence; tiny interpreted
    shapes)."""
    if backend != "tpu" or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return None
    if heads % groups or state % _LANES or (heads // groups * head_dim) % _LANES:
        return None
    if _LANES % head_dim and head_dim % _LANES:
        return None
    if chunk not in (16, 128) or seq_len % chunk:
        return None
    chunks = seq_len // chunk
    for step_chunks in _STEP_CHUNKS:
        held = _kernel_vmem_bytes(
            dtype, heads // groups, head_dim, state, chunk, step_chunks, chunks
        )
        if chunks % step_chunks == 0 and held <= _KERNEL_VMEM_LIMIT:
            return step_chunks
    return None


def _ratios(gamma_i, gamma_row, chunk, *, strict=False):
    """``Gamma_ij = e^{gamma_i - gamma_j}`` for ``j <= i`` (``strict``: ``j <
    i``), else 0, from ``gamma`` over the lanes ``(L, 128)``, ``L`` <= 128, and
    as a row.  Above the diagonal the exponent is positive and may overflow:
    the mask drops that ``inf``, nothing is multiplied by it."""
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return jnp.where(
        i > j if strict else i >= j, jnp.exp(gamma_i[:, :chunk] - gamma_row), 0.0
    )


def _tile_decays(cols_ref, at, tile, tiling):
    """For the channels of ``tile`` in the chunk at rows ``at``, a lane a
    channel: ``gamma`` (the cumulative log-decay) of each of the tile's heads
    over the lanes (the ratios' column), ``dt``, ``e^gamma`` (the chunk's
    start to the token), ``e^{gamma_L - gamma}`` (the token to the chunk's
    end) and ``e^{gamma_L}`` ``(1, 128)`` — the exp after the lanes are
    filled, so that nothing folds it into the one broadcast over sublanes
    and lanes that Mosaic does not have (``tests/test_tpu_compile.py`` holds
    a head of half a lane tile, of one and of two to that)."""
    t = tiling
    cols = cols_ref[0, 0, at, :]  # (L, 2 heads): dt | gamma, a head a column
    dt, gamma = cols[:, :t.heads], cols[:, t.heads:]
    spread = t.spread(gamma, tile)
    gamma_w = t.wide(spread, tile)
    if len(t.heads_of(tile)) > 1:
        last_w = gamma_w[t.chunk - 1:t.chunk]
    else:  # one head fills the tile and nothing selects by lane: a slice of the
        # column's broadcast would fold into that broadcast, so the row is summed out
        row = jax.lax.broadcasted_iota(jnp.int32, gamma_w.shape, 0)
        last_w = jnp.sum(
            jnp.where(row == t.chunk - 1, gamma_w, 0.0), axis=0, keepdims=True
        )
    return (
        spread, t.wide(t.spread(dt, tile), tile), jnp.exp(gamma_w),
        jnp.exp(last_w - gamma_w), jnp.exp(last_w),
    )


def _fwd_kernel(
    x_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref, y_ref, *rest, tiling, save
):
    """One group of B and C with the heads it serves over ``tiling.chunks``
    chunks, a lane tile of its channels at a time; ``save`` also writes what
    the backward reads: the state at each chunk's start."""
    t = tiling
    states_ref = rest[0] if save else None
    state = rest[-1]  # (N, heads P): the heads' S^T side by side
    dtype, f32, chunk = x_ref.dtype, jnp.float32, t.chunk
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    def one_chunk(c):
        at = t.rows(c)
        n = step * t.chunks + c  # the chunk's place in the sequence
        b, cc = b_ref[0, at, :], c_ref[0, at, :]
        cb = _mm(cc, b, _NT, dtype)  # C B^T, shared by the group
        for tile in range(t.tiles):
            lanes = t.lanes(tile)
            gamma_i, dt_w, decay_w, to_end_w, end = _tile_decays(cols_ref, at, tile, t)
            s0 = state[:, lanes]
            if save:
                states_ref[0, 0, c, :, lanes] = s0
            x32 = x_ref[0, at, lanes].astype(f32)
            xt = dt_w * x32  # X~
            y = decay_w * _mm(cc, s0, _NN, dtype) + d_ref[:, lanes] * x32
            for e in t.heads_of(tile):
                ratio = _ratios(gamma_i[e], rows_ref[0, 0, e, pl.ds(n, 1), :], chunk)
                scores = (ratio * cb).astype(dtype)
                y = y + _mm(scores, t.only(e, tile, xt), _NN, dtype)
            y_ref[0, at, lanes] = y.astype(y_ref.dtype)
            state[:, lanes] = end * s0 + _mm(b, to_end_w * xt, _TN, dtype)

    _each(t.chunks, one_chunk, _FWD_UNROLL)


def _bwd_kernel(
    x_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref, states_ref, dy_ref,
    dx_ref, db_ref, dc_ref, drows_ref, dstate, *, tiling, steps,
):
    """The chunks in reverse, ``dstate`` the gradient of the state a chunk
    leaves: recomputes the chunk's ``C B^T``, ratios and scores from x, dt,
    B, C and the saved start state.  A head's per-token gradients leave as
    rows (a chunk's tokens along the lanes): of ``dt`` through ``X~``, of
    ``gamma``, and ``sum_p dy x`` (``D``'s)."""
    t = tiling
    dtype, f32, chunk = x_ref.dtype, jnp.float32, t.chunk
    step = steps - 1 - pl.program_id(2)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    is_last = jax.lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 0) == chunk - 1
    row_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    row_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)

    def one_chunk(i):
        c = t.chunks - 1 - i
        at = t.rows(c)
        n = step * t.chunks + c
        b, cc = b_ref[0, at, :], c_ref[0, at, :]
        cb = _mm(cc, b, _NT, dtype)
        # a token's own score, c_i . b_i as the forward rounded it: its ratio
        # is one whatever gamma, so it is kept out of the ratios' products —
        # dL/dgamma then holds no pair of equal terms that cancel to rounding
        own = jnp.sum(
            jnp.where(row_i == row_j, cb, 0.0), axis=1, keepdims=True
        ).astype(dtype).astype(f32)
        dcb = jnp.zeros_like(cb)
        dc = db = jnp.zeros(b.shape, f32)
        sums = {}
        for tile in range(t.tiles):
            lanes = t.lanes(tile)
            gamma_i, dt_w, decay_w, to_end_w, end = _tile_decays(cols_ref, at, tile, t)
            s0, ds = states_ref[0, 0, c, :, lanes], dstate[:, lanes]
            x32 = x_ref[0, at, lanes].astype(f32)
            dy32 = dy_ref[0, at, lanes].astype(f32)
            xt = dt_w * x32
            xt_b = xt.astype(dtype)
            w = to_end_w * xt
            dw = _mm(b, ds, _NN, dtype)  # dL/d(to_end X~), from S_next = .. + B^T (to_end X~)
            y = decay_w * _mm(cc, s0, _NN, dtype)  # the forward's y, less D x and a token's own
            dxt = jnp.zeros_like(xt)  # dL/dX~ through the earlier tokens' scores
            for e in t.heads_of(tile):
                ratio = _ratios(
                    gamma_i[e], rows_ref[0, 0, e, pl.ds(n, 1), :], chunk, strict=True
                )
                scores = (ratio * cb).astype(dtype)
                dy_e = t.only(e, tile, dy32).astype(dtype)
                dm = _mm(dy_e, xt_b, _NT, dtype)  # dy X~^T
                y = y + _mm(scores, t.only(e, tile, xt), _NN, dtype)
                dcb = dcb + jnp.where(row_i == row_j, dm, ratio * dm)  # own ratio: one
                dxt = dxt + _mm(scores, dy_e, _TN, dtype)
            # dL/dgamma_i, a channel a lane: every ratio e^{gamma_i - gamma_j}
            # pulls gamma_i up by its share of (dy . y) and gamma_j down by
            # its share of (dX~ . X~); the chunk's last token also carries
            # e^{gamma_L}'s own (through e^{gamma_L} S_prev) and every to_end's
            dw_w = dw * w
            at_end = end * jnp.sum(ds * s0, axis=0, keepdims=True) + jnp.sum(
                dw_w, axis=0, keepdims=True
            )
            d_gamma = (
                dy32 * y - xt_b.astype(f32) * dxt - dw_w + jnp.where(is_last, at_end, 0.0)
            )
            dxt = dxt + own * dy32 + to_end_w * dw
            dx_ref[0, at, lanes] = (d_ref[:, lanes] * dy32 + dt_w * dxt).astype(dx_ref.dtype)
            for which, v in enumerate((dxt * x32, d_gamma, dy32 * x32)):
                channels_first = v.T  # (128, L): a head's sum is over sublanes
                for e in t.heads_of(tile):
                    row = jnp.sum(
                        channels_first[t.sublanes(e, tile)], axis=0, keepdims=True
                    )
                    sums[which, e] = sums.get((which, e), 0.0) + row
            dz = (decay_w * dy32).astype(dtype)
            dc = dc + _mm(dz, s0, _NT, dtype)
            db = db + _mm(w, ds, _NT, dtype)
            dstate[:, lanes] = end * ds + _mm(cc, dz, _TN, dtype)
        for (which, e), row in sums.items():
            drows_ref[0, 0, which * t.heads + e, pl.ds(n, 1), :] = row
        dc_ref[0, at, :] = (dc + _mm(dcb, b, _NN, dtype)).astype(dc_ref.dtype)
        db_ref[0, at, :] = (db + _mm(dcb, cc, _TN, dtype)).astype(db_ref.dtype)

    _each(t.chunks, one_chunk, _BWD_UNROLL)


def _prepare(x, dt, A, B, C, D, chunk):
    """What the kernels read: x as ``(B, S, H P)``, B and C as ``(B, S, G
    N)`` (reshapes, no copy); a token's ``dt`` and cumulative log-decay
    ``gamma`` (``dt A``'s running sum inside a chunk, the one thing made
    outside) for a group's heads, ``(B, G, S, 2 H / G)``, and ``gamma`` also
    with a chunk's tokens along the lanes, ``(B, G, H / G, NC, L)``; ``D`` a
    lane a channel, ``(1, H P)``."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    r = h // g
    gamma = _running_sum(dt * A, chunk)
    by_group = lambda v: v.reshape(b, s, g, r)  # noqa: E731
    cols = jnp.concatenate([by_group(dt), by_group(gamma)], axis=-1)
    rows = jnp.swapaxes(gamma, 1, 2).reshape(b, g, r, s // chunk, chunk)
    return (
        x.reshape(b, s, h * p), B.reshape(b, s, g * n), C.reshape(b, s, g * n),
        jnp.swapaxes(cols, 1, 2), rows, jnp.repeat(D, p)[None],
    )


def _call(kernel, step_chunks, operands, outputs, heads, interpret, *, backward):
    """One of the two kernels on the grid ``(B, G, NC / step_chunks)``, the
    last axis sequential; the backward walks the chunk blocks from the last.
    ``operands`` and ``outputs`` are ``(array or shape, kind)``: ``channels``
    is ``(B, S, H P)`` and ``state`` ``(B, S, G N)``, a group's lanes a
    block; ``tokens`` ``(B, G, S, 2 H / G)``; ``rows`` ``(B, G, k H / G, NC,
    L)``, a group's whole sequence a block (gradients gather in it over the
    sequential axis); ``lanes`` ``(1, H P)`` and ``states`` ``(B, G, NC, N,
    (H / G) P)``."""
    x2, rows = operands[0][0], operands[4][0]
    b, g, _, nc, chunk = rows.shape
    m = step_chunks
    wide = x2.shape[-1] // g
    n = operands[1][0].shape[-1] // g
    at = (lambda j: nc // m - 1 - j) if backward else (lambda j: j)

    def spec(x, kind):
        if kind == "channels":
            return pl.BlockSpec((1, m * chunk, wide), lambda b, g, j: (b, at(j), g))
        if kind == "state":
            return pl.BlockSpec((1, m * chunk, n), lambda b, g, j: (b, at(j), g))
        if kind == "tokens":
            return pl.BlockSpec(
                (1, 1, m * chunk, x.shape[3]), lambda b, g, j: (b, g, at(j), 0)
            )
        if kind == "rows":
            return pl.BlockSpec(
                (1, 1) + x.shape[2:], lambda b, g, j: (b, g, 0, 0, 0)
            )
        if kind == "lanes":
            return pl.BlockSpec((1, wide), lambda b, g, j: (0, g))
        return pl.BlockSpec(
            (1, 1, m) + x.shape[3:], lambda b, g, j: (b, g, at(j), 0, 0)
        )

    return pl.pallas_call(
        functools.partial(kernel, tiling=_Tiling(heads, wide // heads, chunk, m)),
        grid=(b, g, nc // m),
        in_specs=[spec(x, kind) for x, kind in operands],
        out_specs=[spec(x, kind) for x, kind in outputs],
        out_shape=[x for x, _ in outputs],
        scratch_shapes=[pltpu.VMEM((n, wide), jnp.float32)],  # the carried states
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="ssd_scan_bwd" if backward else "ssd_scan_fwd",
    )(*(x for x, _ in operands))


_KINDS = ("channels", "state", "state", "tokens", "rows", "lanes")


def _kernel_forward(operands, step_chunks, interpret, *, save):
    x2, b2, _, _, rows, _ = operands
    b, g, r, nc, _ = rows.shape
    shape = jax.ShapeDtypeStruct
    outputs = [(shape(x2.shape, x2.dtype), "channels")]
    if save:
        wide, n = x2.shape[-1] // g, b2.shape[-1] // g
        outputs.append((shape((b, g, nc, n, wide), jnp.float32), "states"))
    return _call(
        functools.partial(_fwd_kernel, save=save), step_chunks,
        list(zip(operands, _KINDS)), outputs, r, interpret, backward=False,
    )


def _kernel_backward(operands, states, dy2, step_chunks, interpret):
    x2, b2, c2, _, rows, _ = operands
    b, g, r, nc, chunk = rows.shape
    shape = jax.ShapeDtypeStruct
    return _call(
        functools.partial(_bwd_kernel, steps=nc // step_chunks), step_chunks,
        list(zip(operands, _KINDS)) + [(states, "states"), (dy2, "channels")],
        [
            (shape(x2.shape, x2.dtype), "channels"),
            (shape(b2.shape, b2.dtype), "state"), (shape(c2.shape, c2.dtype), "state"),
            (shape((b, g, 3 * r, nc, chunk), jnp.float32), "rows"),
        ],
        r, interpret, backward=True,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _kernel_scan(x, dt, A, B, C, D, chunk, step_chunks, interpret):
    """``dt``, ``A`` and ``D`` in float32, B and C in ``x``'s dtype."""
    with jax.named_scope("ssd_scan"):
        (y,) = _kernel_forward(
            _prepare(x, dt, A, B, C, D, chunk), step_chunks, interpret, save=False
        )
        return y.reshape(x.shape)


def _kernel_scan_fwd(x, dt, A, B, C, D, chunk, step_chunks, interpret):
    with jax.named_scope("ssd_scan"):
        operands = _prepare(x, dt, A, B, C, D, chunk)
        y, states = _kernel_forward(operands, step_chunks, interpret, save=True)
        return y.reshape(x.shape), (operands, states, dt, A)


def _kernel_scan_bwd(chunk, step_chunks, interpret, residuals, dy):
    operands, states, dt, A = residuals
    b, s, h, p = dy.shape
    x2, b2 = operands[:2]
    g = operands[4].shape[1]
    with jax.named_scope("ssd_scan"):
        dx, db, dc, drows = _kernel_backward(
            operands, states, dy.astype(x2.dtype).reshape(x2.shape),
            step_chunks, interpret,
        )
        # (B, G, 3 H / G, NC, L): d dt through X~, d gamma, sum_p dy x
        d_dt, d_gamma, dy_x = (
            jnp.swapaxes(drows.reshape(b, g, 3, h // g, s)[:, :, i].reshape(b, h, s), 1, 2)
            for i in range(3)
        )
        # gamma is dt A's running sum inside the chunk: a_j reaches gamma_i, i >= j
        da = _running_sum(d_gamma, chunk, back=True)
        bc = (b, s, g, b2.shape[-1] // g)
        return (
            dx.reshape(dy.shape), d_dt + da * A, jnp.sum(da * dt, axis=(0, 1)),
            db.reshape(bc), dc.reshape(bc), jnp.sum(dy_x, axis=(0, 1)),
        )


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)
