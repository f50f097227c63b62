"""Multi-head attention: jnp reference + Pallas TPU flash-attention kernel.

The reference repo's only model is a CNN — it has no attention anywhere
(SURVEY.md §2.2: "no sequence dimension, no attention").  This op is the
foundation of the beyond-parity transformer family (``models/vit.py``) and
of the long-context sequence parallelism layer (``parallel/ring.py``):
ring attention needs an attention primitive that returns the online-softmax
statistics (``lse``) so partial results from different key/value shards can
be combined exactly.

Kernel design (TPU-first, not a CUDA translation):

- **FlashAttention-style online softmax** — O(S) memory, the S×S score
  matrix never exists in HBM.  The grid tiles (batch·heads, query blocks);
  each kernel instance loops over key/value blocks held in VMEM, carrying
  the running row-max ``m``, row-sum ``l`` and output accumulator in fp32.
- **MXU everywhere**: the four matmuls (qkᵀ, pv, and the backward
  contractions) use ``dot_general`` with explicit contraction dims — no
  explicit transposes, which on TPU would be relayouts — and
  ``preferred_element_type=float32``.
- **Static shapes**: sequence lengths are padded to block multiples at the
  wrapper level; masking uses ``broadcasted_iota`` against the *static*
  true lengths (pitfall: 1D iota doesn't lower on TPU).  Everything the
  kernels load or store is ≥2D (1D vectors don't tile), and the per-row
  softmax statistics (``lse``, ``delta``) are carried as (bh, S, 8) arrays
  — the row value broadcast across a stub minor dim — because TPU block
  shapes must tile to (8, 128) unless a block dim spans the whole array.
- Backward, wired via ``jax.custom_vjp`` over saved ``(out, lse)``
  residuals, in one of two forms that :func:`flash_plan` chooses from the
  call's shapes.  **Fused** (``_bwd_fused_kernel``): one kernel takes the
  scores, the probabilities and dO·vᵀ once a visited tile and writes dq, dk
  and dv — five matmuls and one ``exp`` a score element.  What it holds in
  VMEM is the query rows a key block can reach (PR 32): key blocks run in
  increasing order, a causal query tile's dq is finished when the key block
  on its diagonal is done and leaves the kernel then, and with a window q,
  dO and float32 dq are a ring of ``window + 2 tiles`` rows that the
  kernel's own DMA fills a tile a key step — a sliding layer fits at any
  length.  Without a window the ring is the sequence (8,192 tokens at head
  size 128 in bf16, 8.0 MiB).  **Two tiled kernels** (``_dq_kernel``,
  ``_dkv_kernel``; any length): a 3D grid (batch·heads, own block, streamed
  block) accumulates into fp32 scratch across the innermost grid dimension,
  so the only VMEM residents are fixed-size tiles — seven matmuls and two
  ``exp``; what is left to them is a call whose sequence does not fit and
  has no window (16,384 keys; a non-causal call, whose dq is whole, past
  4,096).  (Round 3 shipped a backward that kept whole-sequence Q/dO in
  VMEM behind a hand-written footprint formula that mis-predicted Mosaic's
  stack accounting and OOMed scoped VMEM at S=4096, D=128, bh=32; the fused
  kernel's residents are blocks and scratch Pallas itself allocates, inside
  the scoped VMEM a kernel gets unasked, and ``tests/test_tpu_compile.py``
  compiles it at its largest shapes.)
- **The causal tile plan** (PR 30).  A causal call does the lower
  triangle's work once: square tiles tight to the diagonal ((512, 512)
  visits 56 % of a 4,096-key square where 128-row query tiles under
  2,048-key blocks visited 75 %, two thirds of it through the mask), the
  mask only on tiles that straddle the diagonal or the padding, and a tile
  above the diagonal costs nothing — the resident forward and the fused
  backward loop over visited tiles inside the kernel, the tiled kernels'
  index maps name, for a skipped step, the block the neighbouring visited
  step holds, and Pallas issues no DMA when a block index repeats.
- **Head size and grouped heads as they are.**  A head size that divides
  the 128 lanes goes in unpadded (a block whose minor dim spans the array),
  and ``k`` / ``v`` may hold one head for ``group`` consecutive query
  heads: index maps read head ``h // group``, forward and backward.  The
  fused backward sums the group's dk / dv in VMEM where the two
  whole-sequence float32 accumulators fit beside q, dO and dq (4,096 keys
  at head size 64); where they do not (8,192 keys at head size 128: 8.4 MB
  of accumulators) each query head writes its own dk / dv and the group's
  sum is taken outside in float32.  Only the two tiled kernels see one
  key-value head a query head (``jnp.repeat`` before the forward).

The *forward* has two shapes: up to ~8k keys (bf16) whole-sequence K/V
live in VMEM per (batch, head) instance — 2·S·128·2 bytes, loaded once
and reused across every query block, the bandwidth-optimal layout.  Past
the ``_FWD_RESIDENT_KV_LIMIT`` footprint the wrapper switches to a fully
tiled (bh, nq, nk) grid carrying the online-softmax state (acc, running
max/sum) in fp32 VMEM scratch — K/V re-stream once per query block, and S
is bounded by HBM, not VMEM.  Beyond one chip's HBM, shard S over the
mesh with ring attention (``parallel/ring.py``).

Measured on one v5e chip (PR 30; ``PERF.md`` §6 has the table; ms a call,
forward + recomputed forward + backward under ``jax.checkpoint`` with the
layout changes around the kernels).  The token cell's layer — 4 x 32 query
heads on 8 key-value heads, 4,096 causal keys, head size 64: 46.8 -> 26.5
(forward kernel 7.35 -> 5.99 a call, backward 14.4 + 9.3 -> 10.5, and 9.9
with q, dO and dq double-buffered under a 32 MiB ``vmem_limit_bytes``,
which cost the rest of the train program more than it gave:
``_FUSED_BWD_RESIDENT_LIMIT``; upstream's splash attention with its fused
backward 26.8 at 1,024-blocks, 29.5 at 512).  Head size 128, 8 x 4 heads,
4,096 keys: non-causal 13.7 -> 11.6, causal 11.8 -> 8.1.  16,384 keys (streamed forward, two tiled backward
kernels): non-causal 27.8 -> 27.7, causal 18.2 -> 16.7.  What bounds a
512 x 512 tile at head size 64 is neither the half-filled MXU alone nor the
VPU's multiplies (folding the scale into q moved nothing): 1.3 us in the
forward and 2.2 in the fused backward for 262 k exponentials a tile.

PR 32, one v5e chip, one sequence of 8,192 keys, 32 query heads on 4
key-value heads, head size 128, recomputed forward + backward through the
dispatcher (``PERF.md`` §6).  With a window of 2,048 (70 visited tiles a
head): 13.19 -> 9.63 ms — the two tiled backward kernels 9.2, the fused one
on a ring of six tiles 5.8 with the group's sum outside, 2.6 us a tile; the
forward 4.01 -> 3.81 with k and v no longer repeated.  Without one (136
tiles): 22.78 -> 15.10 — backward 17.0 -> 9.5, 2.2 us a tile; forward 5.80
-> 5.59.  16,384 keys under that window (4 heads): 4.14 -> 3.53.  The
calls that were fused already did not move: 4 x 32 heads at 4,096 keys and
head size 64 18.32 -> 18.14, 8 x 4 heads at head size 128 5.02 -> 4.96.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.compilation import note_flash_backward, note_kernel_path

_NEG_INF = -1e30  # finite "-inf": keeps fully-masked rows NaN-free


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# --------------------------------------------------------------- reference


def mha_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: float | None = None,
    return_lse: bool = False,
    layout: str = "bhsd",
    window: int | None = None,
):
    """Plain attention; softmax in fp32.  The semantics contract the
    Pallas kernel is tested against.

    ``window`` (causal calls only) is a sliding window: key ``j`` is
    visible to query ``i`` iff ``j <= i and i - j < window``.

    ``layout`` is the q/k/v axis order: ``"bhsd"`` (B, H, S, D) or
    ``"bshd"`` (B, S, H, D).  The ``bshd`` path contracts directly via
    einsum — no transposes, which on TPU are real relayout work (measured
    17.5%% of ViT-Tiny step time before this path existed — a claim, like
    the "1.4×" below, from chip runs older than the growth PRs and from
    autodiff's backward of this function; the dispatcher now differentiates
    through ``_composed``, so the kernel issue measures them again).

    Stays plain and autodiff-differentiable: every kernel test compares
    against it.

    ``return_lse=True`` additionally returns the per-row log-sum-exp of the
    scaled scores, (B, H, S) fp32 — the statistic ring attention needs to
    combine partial results across key/value shards exactly.
    """
    d = q.shape[-1]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    if window is not None and not causal:
        raise ValueError("an attention window needs causal=True")
    if layout == "bshd":
        # q-major scores (b, q, h, k): h stays where the inputs put it, so
        # XLA emits no relayout around either matmul — measured 1.4× faster
        # fwd+bwd than the (b, h, q, k) formulation at CIFAR-ViT shapes
        score_eq, out_eq = "bqhd,bkhd->bqhk", "bqhk,bkhd->bqhd"
    else:
        score_eq, out_eq = "bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd"
    s = jnp.einsum(score_eq, q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        s = jnp.where(_causal_mask(s, layout, window), s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        out_eq, p.astype(v.dtype), v, preferred_element_type=jnp.float32
    ).astype(q.dtype)
    if return_lse:
        lse = jax.nn.logsumexp(s, axis=-1)
        if layout == "bshd":
            lse = lse.transpose(0, 2, 1)  # (b, q, h) → contract (B, H, S)
        return out, lse
    return out


# ------------------------------------------- composed path, its own VJP


def _composed_eqs(layout):
    """``(scores, out, to_kv)`` einsums: q·kᵀ and dO·vᵀ; p·v and ds·k;
    dsᵀ·q and pᵀ·dO."""
    if layout == "bshd":
        return "bqhd,bkhd->bqhk", "bqhk,bkhd->bqhd", "bqhk,bqhd->bkhd"
    return "bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd", "bhqk,bhqd->bhkd"


def _causal_mask(s, layout, window=None):
    """:func:`mha_reference`'s causal mask for scores ``s`` (``bshd``:
    broadcast over the h axis of (q, h, k)), with the band's lower edge
    where the call has a ``window``."""
    sq, skv = s.shape[-3 if layout == "bshd" else -2], s.shape[-1]
    rows, cols = jnp.arange(sq)[:, None] + (skv - sq), jnp.arange(skv)[None, :]
    mask = rows >= cols
    if window is not None:
        mask = mask & (rows - cols < window)
    return mask[:, None, :] if layout == "bshd" else mask


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _composed(q, k, v, causal, scale, layout, window=None):
    """The dispatcher's composed branch.  Not differentiated it *is*
    :func:`mha_reference`.  Differentiated, the program and not autodiff
    chooses what crosses from forward to backward: the probabilities in the
    dtype the p·v matmul consumes them in, and no float32 score-sized
    tensor (autodiff keeps float32 ``s - max`` and every consumer in the
    backward computes ``exp`` of it again)."""
    return mha_reference(
        q, k, v, causal=causal, scale=scale, layout=layout, window=window
    )


def _composed_fwd(q, k, v, causal, scale, layout, window):
    # mha_reference's arithmetic, with p·v's operand named: the residual
    score_eq, out_eq, _ = _composed_eqs(layout)

    def scores(q, k):
        s = jnp.einsum(score_eq, q, k, preferred_element_type=jnp.float32)
        s = s * scale
        if not causal:
            return s
        return jnp.where(_causal_mask(s, layout, window), s, _NEG_INF)

    s = scores(q, k)
    m = jnp.max(s, axis=-1, keepdims=True)
    if layout == "bshd" and not causal and k.shape[1] % 128 == 0:
        # The scores a second time, for exp: behind the barrier the compiler
        # keeps the two products apart and fuses each with what consumes it
        # (max; exp, sum, divide, convert), so the only score-sized tensor
        # the forward writes is p.  One product is written in float32 for
        # the softmax to read back: 8 bytes an element against 2·d FLOPs.
        # Only where the TPU compiler does fuse them (compiled for a v5e:
        # this layout, no mask, keys in whole 128-lane tiles).  On the chip
        # forward+backward take 0.68-0.72 x the one-product form's time
        # there, and 1.22-1.35 x where they are not fused (PERF.md §6,
        # PR 25).
        s = scores(*jax.lax.optimization_barrier((q, k)))
    e = jnp.exp(s - m)
    p = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(v.dtype)
    out = jnp.einsum(
        out_eq, p, v, preferred_element_type=jnp.float32
    ).astype(q.dtype)
    return out, (q, k, v, p, out)


def _composed_bwd(causal, scale, layout, window, res, do):
    q, k, v, p, out = res
    score_eq, out_eq, to_kv_eq = _composed_eqs(layout)
    f32 = jnp.float32
    # Σ_k dp∘p = Σ_d dO∘O, the flash backward's identity (``_flash_bwd``):
    # softmax's row term needs no pass over a score-sized tensor
    delta = jnp.sum(do.astype(f32) * out.astype(f32), axis=-1, keepdims=True)
    dp = jnp.einsum(score_eq, do, v, preferred_element_type=f32)
    ds = p.astype(f32) * (dp - delta) * scale
    if causal:
        # ``where``'s rule: a masked score has no cotangent (p is 0 there
        # already, but for a row that is masked whole)
        ds = jnp.where(_causal_mask(ds, layout, window), ds, 0.0)
    ds = ds.astype(q.dtype)
    dq = jnp.einsum(out_eq, ds, k, preferred_element_type=f32)
    dk = jnp.einsum(to_kv_eq, ds, q, preferred_element_type=f32)
    dv = jnp.einsum(to_kv_eq, p, do, preferred_element_type=f32)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_composed.defvjp(_composed_fwd, _composed_bwd)


# ---------------------------------------------------------- kernel helpers


def _scores(qb, kb, scale):
    """(block_q, d) × (block_k, d) → fp32 (block_q, block_k) on the MXU."""
    return jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale


def _block_mask(i, j, block_q, block_k, kv_len, causal, keys_down=False,
                window=None):
    """Validity mask for score block (i, j) from *static* true kv length:
    (block_q, block_k), or its transpose with ``keys_down`` (the fused
    backward's tiles have keys on the sublanes).  With a ``window`` the
    band's lower edge too: a key more than ``window - 1`` behind its query
    is masked."""
    shape = (block_k, block_q) if keys_down else (block_q, block_k)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 0 if keys_down else 1)
    cols = cols + j * block_k
    mask = cols < kv_len
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 1 if keys_down else 0)
        rows = rows + i * block_q
        mask = mask & (rows >= cols)
        if window is not None:
            mask = mask & (rows - cols < window)
    return mask


def _causal_nk(i, block_q, block_k, nk_total):
    """Number of key blocks at/below the diagonal of query block ``i``."""
    hi = jnp.minimum((i + 1) * block_q + block_k - 1, nk_total * block_k)
    return hi // block_k


def _causal_first_q(j, block_q, block_k):
    """First query block with a row at/below the diagonal of key block
    ``j``: tile (i, j) is visited iff ``i >= _causal_first_q(j)`` iff
    ``j < _causal_nk(i)``."""
    return (j * block_k) // block_q


def _causal_free_q(j, block_q, block_k):
    """First query block wholly at/below key block ``j``'s last column:
    from here on a causal tile needs no mask."""
    return ((j + 1) * block_k - 1 + block_q - 1) // block_q


# A window bounds a causal call's tiles from below as well: query ``r`` sees
# keys ``(r - window, r]``, so the visited tiles are a band along the
# diagonal.  Tile (i, j) is visited iff ``_band_first_k(i) <= j <
# _causal_nk(i)`` iff ``_causal_first_q(j) <= i < _band_end_q(j)``, and it
# straddles the band's lower edge (needs that mask) iff ``j <
# _band_free_k(i)`` iff ``i >= _band_free_q(j)``.


def _band_first_k(i, block_q, block_k, window):
    """First key block a row of query block ``i`` sees: the one holding
    key ``i * block_q - (window - 1)``, its first row's oldest."""
    return jnp.maximum(i * block_q - (window - 1), 0) // block_k


def _band_free_k(i, block_q, block_k, window):
    """First key block wholly inside the window of every row of query
    block ``i``: ``ceil(((i + 1) * block_q - window) / block_k)``, from 0."""
    return jnp.maximum((i + 1) * block_q - window + block_k - 1, 0) // block_k


def _band_end_q(j, block_q, block_k, window):
    """One past the last query block with a row that sees a key of block
    ``j``: key ``(j + 1) * block_k - 1`` is last seen by the row ``window -
    1`` after it."""
    return ((j + 1) * block_k + window - 2) // block_q + 1


def _band_free_q(j, block_q, block_k, window):
    """One past the last query block whose every row sees every key of
    block ``j``: its last row is less than ``window`` after key
    ``j * block_k``."""
    return (j * block_k + window) // block_q


def _band_steps(own, streamed, window, n_own, n_streamed, behind):
    """The most grid steps the band of any one ``own``-sized block takes in
    ``streamed``-sized blocks.  Its ``own + window - 1`` rows or columns
    start ``behind`` before the block's first: ``window - 1`` for a query
    block streaming keys, 0 for a key block streaming queries."""
    most = 0
    for b in range(n_own):
        start = b * own - behind
        first = max(start, 0) // streamed
        last = min((start + own + window - 2) // streamed, n_streamed - 1)
        most = max(most, last - first + 1)
    return most


def band_tiles(sq, block_q, block_k, window=None):
    """The (query block, key block) tiles a causal call of ``sq`` padded
    rows visits under these tiles, from the bounds the kernels loop by: 70
    of the square's 256 at 8,192 keys, a window of 2,048 and 512-tiles (the
    causal triangle has 136)."""
    tiles = []
    for i in range(sq // block_q):
        lo = 0 if window is None else int(
            _band_first_k(i, block_q, block_k, window)
        )
        hi = int(_causal_nk(i, block_q, block_k, sq // block_k))
        tiles += [(i, j) for j in range(lo, hi)]
    return tiles


def _mask_split(i, j, block_q, block_k, kv_len, causal, window=None):
    """``(run, needs_mask)`` predicates for a (query block i, key block j)
    tile of any tiled kernel: ``run`` gates compute (skip tiles strictly
    above the causal diagonal, and with a ``window`` those wholly behind
    it), ``needs_mask`` selects the masked path.
    The per-tile iota/compare/select of ``_block_mask`` is real VPU work
    next to the MXU matmuls, so interior tiles — almost all of them at
    streaming scale — take a mask-free path: a tile needs the mask only
    when it reaches past ``kv_len`` (padding) or straddles the causal
    diagonal (mask-free requires min row ``i·bq`` ≥ max col
    ``(j+1)·bk - 1``)."""
    run = (j * block_k < (i + 1) * block_q) if causal else (j >= 0)
    needs_mask = (j + 1) * block_k > kv_len
    if causal:
        needs_mask = needs_mask | ((j + 1) * block_k - 1 > i * block_q)
    if window is not None:
        # the tile's first row sees its last column; its last row does not
        # see its first column
        run = run & (i * block_q - ((j + 1) * block_k - 1) < window)
        needs_mask = needs_mask | ((i + 1) * block_q - 1 - j * block_k >= window)
    return run, needs_mask


# ------------------------------------------------------------ fwd kernel


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, block_k, kv_len,
    window=None,
):
    block_q, d = q_ref.shape
    i = pl.program_id(1)
    qb = q_ref[...]
    nk_total = k_ref.shape[0] // block_k
    nk = _causal_nk(i, block_q, block_k, nk_total) if causal else nk_total
    # key blocks strictly below the diagonal AND fully inside kv_len need
    # no mask at all — the iota/compare/select per block is real VPU work
    # next to the MXU matmuls.  Split the sweep: mask-free interior blocks
    # first, masked boundary blocks (diagonal and/or padding) after.
    nk_free = jnp.minimum(i * block_q, kv_len) // block_k if causal \
        else kv_len // block_k
    nk_free = jnp.minimum(nk_free, nk)

    def body(j, carry, *, masked):
        acc, m, l = carry
        kb = k_ref[pl.dslice(j * block_k, block_k), :]
        vb = v_ref[pl.dslice(j * block_k, block_k), :]
        s = _scores(qb, kb, scale)
        if masked:
            mask = _block_mask(
                i, j, block_q, block_k, kv_len, causal, window=window
            )
            s = jnp.where(mask, s, _NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        if masked and window is not None:
            # a row whose window starts in a later block meets this one
            # wholly masked, before it has a finite running max: exp(s -
            # m_new) is then 1, not 0
            p = jnp.where(mask, p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc_new, m_new, l_new

    acc = jnp.zeros((block_q, d), jnp.float32)
    m = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    carry, first = (acc, m, l), 0
    if window is not None:
        # the band's lower edge: start at the first block a row sees, and
        # mask up to the first one every row sees whole
        lo = _band_first_k(i, block_q, block_k, window)
        first = jnp.clip(_band_free_k(i, block_q, block_k, window), lo, nk)
        nk_free = jnp.maximum(nk_free, first)
        carry = jax.lax.fori_loop(
            lo, first, functools.partial(body, masked=True), carry
        )
    carry = jax.lax.fori_loop(
        first, nk_free, functools.partial(body, masked=False), carry
    )
    acc, m, l = jax.lax.fori_loop(
        nk_free, nk, functools.partial(body, masked=True), carry
    )

    l_safe = jnp.maximum(l, 1e-30)  # fully-masked (padded) rows stay finite
    o_ref[...] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to(m + jnp.log(l_safe), (block_q, 8))


# above this resident-K/V footprint (bytes, double-buffered by Mosaic) the
# forward switches to the fully-tiled kernel: S stops being VMEM-bounded
_FWD_RESIDENT_KV_LIMIT = 4 * 2**20


def _kv_resident(skv, d, dtype) -> bool:
    """Whole-sequence K/V fit the forward's VMEM budget.  In VMEM a row is
    whole 128-lane tiles whatever the head size."""
    footprint = 2 * skv * _ceil_to(d, 128) * jnp.dtype(dtype).itemsize
    return footprint <= _FWD_RESIDENT_KV_LIMIT


def _fwd_kernel_tiled(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr,
    *, scale, causal, kv_len, window=None,
):
    """One (query block, key block) tile of the forward.  Grid (bh, nq, nk):
    the innermost dim streams key/value blocks past fp32 VMEM scratch
    carrying the online-softmax state (acc, running max, running sum); the
    final key step normalizes and writes the output block.  Unlike
    ``_fwd_kernel`` nothing whole-sequence is ever VMEM-resident, so S is
    bounded by HBM, not VMEM.  With a ``window`` the innermost dim counts
    the band's key blocks only, from the first one the query block sees."""
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    i = pl.program_id(1)
    step = pl.program_id(2)
    j = step
    if window is not None:
        j = step + _band_first_k(i, block_q, block_k, window)

    @pl.when(step == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def compute(masked):
        s = _scores(q_ref[...], k_ref[...], scale)
        if masked:
            mask = _block_mask(
                i, j, block_q, block_k, kv_len, causal, window=window
            )
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        if masked:
            # masked columns stay exactly 0 whatever the running max is.
            # Without a window bare exp(s - m_new) already underflows to 0
            # (tile j=0 always sees a valid key, so m_new is finite from
            # then on); with one a row can meet its first tile wholly
            # masked, before a finite running max: exp(s - m_new) is then 1
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        else:
            p = jnp.exp(s - m_new)
        l_new = l_scr[:, 0:1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        vb = v_ref[...]
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    run, needs_mask = _mask_split(
        i, j, block_q, block_k, kv_len, causal, window
    )

    @pl.when(run & jnp.logical_not(needs_mask))
    def _():
        compute(masked=False)

    @pl.when(run & needs_mask)
    def _():
        compute(masked=True)

    # the last key step always runs (even when causal-skipped: the scratch
    # already holds this row block's complete softmax state)
    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[:, 0:1], 1e-30)  # padded rows stay finite
        o_ref[...] = (acc[...] / l_safe).astype(o_ref.dtype)
        lse_ref[...] = jnp.broadcast_to(
            m_scr[:, 0:1] + jnp.log(l_safe), lse_ref.shape
        )


def _flash_fwd_tiled(q3, k3, v3, scale, causal, plan, kv_len, group, interpret):
    bh, sq, d = q3.shape
    skv = k3.shape[1]
    # Wide query tiles amortize the streamed K/V re-read (HBM traffic
    # scales as nq · skv): measured on a v5e at S=16384/D=128, bq 256 →
    # 2048 alone lifts the streamed forward 54 → 73 TF/s.  VMEM at
    # bq=2048: q/out blocks 0.5 MiB each + fp32 acc scratch 1 MiB —
    # comfortably inside the ~4 MiB the rest of the pipeline budgets.
    # Those are bytes at head size 128: a wider head takes as many fewer
    # rows (head size 256: 1,024 — at 2,048 Mosaic refused the kernel for a
    # described v5e, 20.5 MiB of scoped VMEM against 16).
    bq = _stream_block(sq, max(plan.block_q, 2048 * 128 // _ceil_to(d, 128)))
    # bk=1024 with this bq OOMs scoped VMEM (18.6 MiB vs the 16 MiB limit
    # with Mosaic's double buffering); 512 fits and the K/V re-read
    # traffic is governed by bq, not bk
    bk = _stream_block(skv, 512)

    window, steps = plan.window, skv // bk
    if window is not None:
        steps = _band_steps(bq, bk, window, sq // bq, steps, window - 1)

    def kv_map(b, i, j):
        if window is not None:
            j = j + _band_first_k(i, bq, bk, window)
        if causal:
            # a step above the diagonal names the block the last visited
            # step held: Pallas issues no DMA when a block index repeats
            j = jnp.minimum(j, _causal_nk(i, bq, bk, skv // bk) - 1)
        return (b // group, j, 0)

    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel_tiled, scale=scale, causal=causal, kv_len=kv_len,
            window=window,
        ),
        grid=(bh, sq // bq, steps),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, d), kv_map),
            pl.BlockSpec((None, bk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bq, 8), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, sq, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 8), jnp.float32),
            pltpu.VMEM((bq, 8), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(q3, k3, v3)
    return out, lse


def _flash_fwd(q3, k3, v3, scale, causal, plan, kv_len, group, interpret):
    """``q3`` is (batch · heads, S, d); ``k3``/``v3`` hold one head for
    every ``group`` consecutive query heads, read by index map."""
    bh, sq, d = q3.shape
    skv = k3.shape[1]
    if not _kv_resident(skv, d, q3.dtype):
        # resident K/V would crowd VMEM: stream tiles instead (HBM cost:
        # K/V re-read once per query block — amortized by the q tile size)
        return _flash_fwd_tiled(
            q3, k3, v3, scale, causal, plan, kv_len, group, interpret
        )
    block_q = plan.block_q
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, block_k=plan.block_k,
            kv_len=kv_len, window=plan.window,
        ),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, skv, d), lambda b, i: (b // group, 0, 0)),
            pl.BlockSpec((None, skv, d), lambda b, i: (b // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 8), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, sq, 8), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3)
    return out, lse


# ------------------------------------------------------------ bwd kernels


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref, dq_ref, dq_acc,
    *, scale, causal, kv_len, window=None,
):
    """One (query block, key block) tile of dq.  Grid (bh, nq, nk): the
    innermost grid dim streams key/value blocks past a fp32 VMEM scratch
    accumulator; the last visited step's write to ``dq_ref`` is what Mosaic
    flushes to HBM when the (``j``-independent) output block index moves —
    one input-dtype write per element, no fp32 round trip.  With a
    ``window`` the innermost dim counts the band's key blocks only, from
    the first one the query block sees."""
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    i = pl.program_id(1)
    step = pl.program_id(2)
    j = step
    if window is not None:
        j = step + _band_first_k(i, block_q, block_k, window)

    @pl.when(step == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def compute(masked):
        qb = q_ref[...]
        kb = k_ref[...]
        lse_row = lse_ref[:, 0:1]
        # d(loss)/d(scores) = p·(dp - delta) from the out cotangent, plus
        # p·dlse from the lse cotangent (d lse / d scores = p) — fold both
        # row terms
        adj_row = dlse_ref[:, 0:1] - delta_ref[:, 0:1]
        s = _scores(qb, kb, scale)
        if masked:
            mask = _block_mask(
                i, j, block_q, block_k, kv_len, causal, window=window
            )
            p = jnp.where(mask, jnp.exp(s - lse_row), 0.0)
        else:
            p = jnp.exp(s - lse_row)
        dp = jax.lax.dot_general(
            do_ref[...], v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp + adj_row) * scale  # fold d(s)/d(q)'s scale here
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)

    # run: compute only at-or-below the causal diagonal of query block i
    # (a skipped step fetches nothing either: its index maps name the
    # block a visited step holds)
    run, needs_mask = _mask_split(
        i, j, block_q, block_k, kv_len, causal, window
    )

    @pl.when(run & jnp.logical_not(needs_mask))
    def _():
        compute(masked=False)

    @pl.when(run & needs_mask)
    def _():
        compute(masked=True)


def _dkv_kernel(
    k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dlse_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, scale, causal, kv_len, window=None, nq=None,
):
    """One (key block, query block) tile of dk/dv.  Grid (bh, nk, nq): the
    innermost grid dim streams query-side blocks past fp32 VMEM scratch
    accumulators; the last visited step's writes to ``dk_ref``/``dv_ref``
    are what Mosaic flushes to HBM.  With a ``window`` the innermost dim
    counts the band's query blocks only, from the diagonal's down."""
    block_k, d = k_ref.shape
    block_q = q_ref.shape[0]
    j = pl.program_id(1)
    step = pl.program_id(2)
    i = step
    if window is not None:
        i = step + _causal_first_q(j, block_q, block_k)

    @pl.when(step == 0)
    def _init():
        # unconditional at the first inner step — AND pre-write the output
        # blocks: under caller-chosen mismatched blocks (e.g. block_q=128,
        # block_k=2048, s=2049) a causal key block can start past the last
        # query block, so no compute step ever visits it and the
        # pre-written zeros (not stale scratch) are what flushes to HBM.
        # Such blocks are all-padding (sliced off by the pad VJP), but
        # correctness here must not hang on that caller invariant
        # (ADVICE r4).
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        dk_ref[...] = jnp.zeros_like(dk_acc).astype(dk_ref.dtype)
        dv_ref[...] = jnp.zeros_like(dv_acc).astype(dv_ref.dtype)

    def compute(masked):
        kb = k_ref[...]
        qb = q_ref[...]
        dob = do_ref[...]
        lse_row = lse_ref[:, 0:1]
        adj_row = dlse_ref[:, 0:1] - delta_ref[:, 0:1]
        s = _scores(qb, kb, scale)
        if masked:
            mask = _block_mask(
                i, j, block_q, block_k, kv_len, causal, window=window
            )
            p = jnp.where(mask, jnp.exp(s - lse_row), 0.0)
        else:
            p = jnp.exp(s - lse_row)
        # dv += pᵀ @ do — contract over the query axis, no transpose
        dv_acc[...] += jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            dob, v_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp + adj_row) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    # run ⟺ the old `i >= lo` visit gate: i >= (j·bk)//bq ⟺ j·bk < (i+1)·bq
    run, needs_mask = _mask_split(
        i, j, block_q, block_k, kv_len, causal, window
    )
    if window is not None:  # the band's steps may pass the last query block
        run = run & (i < nq)

    @pl.when(run & jnp.logical_not(needs_mask))
    def _():
        compute(masked=False)

    @pl.when(run & needs_mask)
    def _():
        compute(masked=True)


def _stream_block(n: int, target: int) -> int:
    """Largest power-of-two tile ≤ ``target`` dividing ``n``, floored at
    128 — with a gcd fallback because ``n`` is padded to a multiple of the
    *caller-chosen* forward block, which need not be a multiple of 128
    (e.g. block_q=64, sq=150 → n=192): a non-divisor tile would make the
    grid's floor division silently drop the tail block."""
    b = min(target, n)
    while b > 128 and n % b:
        b //= 2
    if n % b:
        b = math.gcd(n, b)
    return b


def _flash_bwd_split(
    q3, k3, v3, lse, do3, dlse, delta, scale, causal, plan, kv_len, interpret
):
    """Two fully-tiled backward kernels, one key-value head a query head:
    what a call gets whose fused residents do not fit VMEM
    (:func:`flash_plan`) — since PR 32 a sequence without a window past
    8,192 keys at head size 128 in bf16 (16,384 keys: 16 MiB of q, dO and
    float32 dq), a non-causal call past 4,096, a causal call whose caller
    chose unequal blocks.  They stream their own (512, 512) tiles,
    independent of the forward's blocks — per-instance VMEM is a handful
    of fixed-size blocks (~6 MiB at D=128) regardless of sequence length.
    Tile sweep on a v5e at S=4096, D=128, older than the growth PRs
    (fwd+bwd TF/s, non-causal / causal): (256,512) 62.8/35.3, (512,512)
    68.6/38.9, (256,2048) 71.4/— but ~13 MiB of temps.  PR 30 at head
    size 64, 4,096 causal keys, 128 heads (ms a call with both forwards):
    these two kernels 44.0, with skipped steps fetching nothing 38.6 —
    44 % of the streamed K/V and Q/dO traffic was for tiles above the
    diagonal — and the fused kernel in their place 29.9.  PR 32 at head size
    128, 8,192 keys, 32 heads (ms a call, backward alone): these two
    kernels 9.2 under a window of 2,048 and 17.0 without, 4.0 us a visited
    tile, the fused kernel 5.8 and 9.5."""
    bh, sq, d = q3.shape
    skv = k3.shape[1]
    # (bh, sq) → (bh, sq, 8) stub minor dim, matching lse's layout
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, 8))

    bq, bk = plan.bwd_block_q, plan.bwd_block_k
    nq, nk = sq // bq, skv // bk
    # with a window the streamed dim counts a band's blocks, not all of them
    window, k_steps, q_steps = plan.window, nk, nq
    if window is not None:
        k_steps = _band_steps(bq, bk, window, nq, nk, window - 1)
        q_steps = _band_steps(bk, bq, window, nk, nq, 0)
    # bh and the own-block grid dims are independent; only the innermost
    # (streaming, accumulating) dim must execute in order
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )

    # A step the causal gate skips names the block its neighbouring visited
    # step holds, and Pallas issues no DMA when a block index repeats: the
    # tiles above the diagonal cost a grid step and no fetch.
    def kv_of_q(b, i, j):
        if window is not None:
            j = j + _band_first_k(i, bq, bk, window)
        if causal:
            j = jnp.minimum(j, _causal_nk(i, bq, bk, nk) - 1)
        return (b, j, 0)

    def q_of_kv(b, j, i):
        if causal:
            first = jnp.minimum(_causal_first_q(j, bq, bk), nq - 1)
            if window is None:
                i = jnp.maximum(i, first)
            else:  # the band's steps count from the diagonal's block down
                last = jnp.minimum(_band_end_q(j, bq, bk, window), nq) - 1
                i = jnp.minimum(i + first, jnp.maximum(last, first))
        return (b, i, 0)

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, kv_len=kv_len,
            window=window,
        ),
        grid=(bh, nq, k_steps),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, d), kv_of_q),
            pl.BlockSpec((None, bk, d), kv_of_q),
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bq, 8), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bq, 8), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bq, 8), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        compiler_params=params,
    )(q3, k3, v3, do3, lse, delta, dlse)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, kv_len=kv_len,
            window=window, nq=nq,
        ),
        grid=(bh, nk, q_steps),
        in_specs=[
            pl.BlockSpec((None, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, bq, d), q_of_kv),
            pl.BlockSpec((None, bq, d), q_of_kv),
            pl.BlockSpec((None, bq, 8), q_of_kv),
            pl.BlockSpec((None, bq, 8), q_of_kv),
            pl.BlockSpec((None, bq, 8), q_of_kv),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skv, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, skv, d), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=params,
    )(k3, v3, q3, do3, lse, delta, dlse)
    return dq, dk, dv


def _bwd_fused_kernel(
    q_ref, do_ref, lse_ref, adj_ref, k_ref, v_ref, dq_ref, dk_ref, dv_ref,
    dq_acc, *scratch, scale, causal, block_q, nq, ring, kv_len, group, summed,
    window=None,
):
    """dq, dk and dv of one key block ``j`` against one query head, the
    scores, the probabilities and dO·vᵀ taken once a visited tile.  Grid
    (batch · key-value heads, query heads a key-value head, key blocks),
    key blocks in increasing order; the query tiles at/below key block
    ``j``'s diagonal — with a ``window``, down to the band's lower edge —
    are a loop inside the kernel, so a tile outside the band is neither a
    grid step nor a fetch.  Tiles have keys on the sublanes (``sᵀ = k qᵀ``):
    dv and dk are plain ``(bk, bq) x (bq, d)`` products and only dq
    contracts over the sublanes, where the two-kernel form has two such
    contractions.

    **What is held is the query rows a key block can reach**: ``ring``
    tiles of q, dO and float32 dq (``dq_acc``), tile ``i`` in slot ``i %
    ring``.  Where the ring is the sequence (``ring == nq``: no window)
    ``q_ref`` and ``do_ref`` are the head's whole blocks, fetched once a
    head.  Where it is shorter they are the arrays in HBM and the tiles
    arrive by the kernel's own DMA into ``q_ring`` / ``do_ring``: a head's
    first step fetches the band of key block 0; step ``j`` waits for the
    tiles that enter the band with key block ``j`` (started a step ago),
    zeroes their dq, and starts the next step's — so the ring is one tile
    more than a band holds.  A causal query tile's dq is finished when the
    key block on its diagonal is done (tiles are square): dq leaves by a
    ``(bq, d)`` output block indexed by ``j``.  A non-causal call writes
    dq whole at the last key block.

    dk and dv: ``summed`` adds the heads of a group in ``kv_acc`` (float32,
    whole sequence by the grid's order) and writes a key-value head's block
    at the group's last head; otherwise each query head writes its own
    block (the wrapper takes the group's sum in float32)."""
    block_k, d = k_ref.shape
    g, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    streamed = ring < nq
    rings, kv_acc = (scratch[:3], scratch[3:]) if streamed else ((), scratch)

    def slot_of(i):
        return jax.lax.rem(i, ring) if streamed else i

    def rows_of(i):
        return pl.ds(pl.multiple_of(slot_of(i) * block_q, block_q), block_q)

    def band_end(jj):
        """One past the last query tile key block ``jj`` reaches."""
        if window is None:
            return nq
        return jnp.minimum(_band_end_q(jj, block_q, block_k, window), nq)

    hi = band_end(j)

    if not streamed:

        @pl.when(j == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    else:
        q_ring, do_ring, sems = rings
        head = pl.program_id(0) * group + g

        def copies(i):
            src = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
            return [
                pltpu.make_async_copy(
                    hbm.at[head, src, :], held.at[rows_of(i), :],
                    sems.at[n, slot_of(i)],
                )
                for n, (hbm, held) in enumerate(
                    ((q_ref, q_ring), (do_ref, do_ring))
                )
            ]

        def each(first, last, act):
            def body(i, carry):
                act(i)
                return carry

            jax.lax.fori_loop(first, last, body, 0)

        def start(i):
            for c in copies(i):
                c.start()

        def arrive(i):
            for c in copies(i):
                c.wait()
            dq_acc[rows_of(i), :] = jnp.zeros((block_q, d), jnp.float32)

        @pl.when(j == 0)
        def _fill():
            each(0, hi, start)

        # the tiles that enter the band with this key block
        each(jnp.where(j == 0, 0, band_end(j - 1)), hi, arrive)

        @pl.when(j + 1 < nk)
        def _ahead():
            each(hi, band_end(j + 1), start)

        q_ref, do_ref = q_ring, do_ring

    kb, vb = k_ref[...], v_ref[...]

    def tile(i, carry, *, masked):
        dk, dv = carry
        rows = rows_of(i)
        qb, dob = q_ref[rows, :], do_ref[rows, :]
        pt = jnp.exp(_scores(kb, qb, scale) - lse_ref[pl.ds(i, 1), :])
        if masked:
            mask = _block_mask(
                i, j, block_q, block_k, kv_len, causal, keys_down=True,
                window=window,
            )
            pt = jnp.where(mask, pt, 0.0)
        dv = dv + jax.lax.dot_general(
            pt.astype(dob.dtype), dob, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            vb, dob, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # the row terms as in ``_dq_kernel``: -delta from the out
        # cotangent, +dlse from the lse cotangent, folded by the wrapper
        dst = (pt * (dpt + adj_ref[pl.ds(i, 1), :]) * scale).astype(qb.dtype)
        dk = dk + jax.lax.dot_general(
            dst, qb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_acc[rows, :] += jax.lax.dot_general(
            dst, kb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    # masked tiles first (they straddle the diagonal; with padded keys in
    # this block, every tile), then the mask-free ones below them, then,
    # with a window, the masked ones that straddle the band's lower edge,
    # and none behind it
    lo = _causal_first_q(j, block_q, block_k) if causal else 0
    free = _causal_free_q(j, block_q, block_k) if causal else 0
    free = jnp.where((j + 1) * block_k > kv_len, hi, free)
    free = jnp.clip(free, lo, hi)
    edge = hi
    if window is not None:
        edge = jnp.clip(_band_free_q(j, block_q, block_k, window), free, hi)
    zeros = jnp.zeros((block_k, d), jnp.float32)
    carry = jax.lax.fori_loop(
        lo, free, functools.partial(tile, masked=True), (zeros, zeros)
    )
    carry = jax.lax.fori_loop(
        free, edge, functools.partial(tile, masked=False), carry
    )
    if window is not None:
        carry = jax.lax.fori_loop(
            edge, hi, functools.partial(tile, masked=True), carry
        )
    dk, dv = carry

    if not summed:
        dk_ref[...] = dk.astype(dk_ref.dtype)
        dv_ref[...] = dv.astype(dv_ref.dtype)
    else:
        dk_acc, dv_acc = kv_acc
        keys = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)

        @pl.when(g == 0)
        def _first_head():
            dk_acc[keys, :] = dk
            dv_acc[keys, :] = dv

        @pl.when(g > 0)
        def _next_head():
            dk_acc[keys, :] += dk
            dv_acc[keys, :] += dv

        @pl.when(g == group - 1)
        def _write_kv():
            dk_ref[...] = dk_acc[keys, :].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[keys, :].astype(dv_ref.dtype)

    if causal:
        dq_ref[...] = dq_acc[rows_of(j), :].astype(dq_ref.dtype)
    else:

        @pl.when(j == nk - 1)
        def _write_q():
            dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


# What ``flash_plan`` lets the fused backward hold in VMEM
# (``_fused_bwd_resident_bytes``): the rings of q and dO and the float32
# accumulators, each single-buffered.  With ~6 MiB of score-tile temporaries
# a call at this limit compiles inside the 16 MiB of scoped VMEM a kernel
# gets unasked — and asking for more is not free: under ``vmem_limit_bytes``
# of 32 MiB the train program's bandwidth-bound fusions *outside* the kernel
# ran slower (PR 30: the selects around the expert layer's kernels, +2.8 ms
# a step in four layers that do not touch attention), the compiler having
# less VMEM left to prefetch into.
_FUSED_BWD_RESIDENT_LIMIT = 9 * 2**20


def _bwd_ring_tiles(sq, skv, d, tile, window=None) -> int:
    """Query tiles the fused backward holds for a call of padded lengths
    under ``tile``: every tile — but with a ``window``, the most a key
    block's band reaches and one more (the next step's arrives while this
    one computes).  The ring's tiles are sliced out of HBM by DMA, which
    Mosaic refuses across a minor dimension narrower than the lanes (head
    size 64 unpadded: compiled for a described v5e), so such a call holds
    the sequence."""
    nq = sq // tile
    if window is None or d % 128:
        return nq
    return min(nq, _band_steps(tile, tile, window, skv // tile, nq, 0) + 1)


def _fused_bwd_resident_bytes(
    sq, skv, d, group, dtype, window=None, *, causal=True, tile=512,
    summed=True,
) -> int:
    """Bytes ``_bwd_fused_kernel`` keeps across grid steps at padded
    lengths ``sq`` / ``skv``: q, dO and float32 dq for the rows a key
    block's band can reach (``_bwd_ring_tiles``; the sequence without a
    window), a non-causal call's dq output whole, and — ``summed`` — the
    group's dk / dv accumulators, whole by the grid's order."""
    lanes, item = _ceil_to(d, 128), jnp.dtype(dtype).itemsize
    rows = _bwd_ring_tiles(sq, skv, d, tile, window) * tile
    held = rows * lanes * (2 * item + 4)  # q, dO; dq_acc
    if not causal:
        held += sq * lanes * item
    if summed and group > 1:
        held += 2 * skv * lanes * 4
    return held


def _flash_bwd_fused(
    q3, k3, v3, lse, do3, dlse, delta, scale, causal, plan, kv_len, group,
    interpret,
):
    bh, sq, d = q3.shape
    bkv, skv = k3.shape[:2]
    bq, bk = plan.bwd_block_q, plan.bwd_block_k
    nq, nk = sq // bq, skv // bk
    ring, summed = plan.bwd_ring, plan.bwd_group_sum
    assert not causal or (bq == bk and nq == nk), "a causal call's tiles are square"
    # per-row terms with the rows on the lanes, a query tile a sublane row
    lse_rows = lse[:, :, 0].reshape(bh, nq, bq)
    adj_rows = (dlse[:, :, 0] - delta).reshape(bh, nq, bq)

    def head(b, g, j):
        return (b * group + g, 0, 0)

    def query_tile(b, g, j):
        return (b * group + g, j, 0)

    def key_block(b, g, j):
        return (b, j, 0)

    def key_block_out(b, g, j):
        if not summed:  # a query head's own block
            return (b * group + g, j, 0)
        # the block leaves VMEM when its index moves: hold block 0 until
        # the group's last head, whose steps write each block in turn
        return (b, jnp.where(g == group - 1, j, 0), 0)

    # one buffer for what moves once a head: a second would only hide a
    # head's q and dO behind the key blocks before it, at twice the VMEM
    whole = pl.BlockSpec((None, sq, d), head, pipeline_mode=pl.Buffered(1))
    held, rings = whole, []
    if ring < nq:  # q and dO stay in HBM; the kernel fetches the band's tiles
        held = pl.BlockSpec(memory_space=pl.ANY)
        rings = [
            pltpu.VMEM((ring * bq, d), q3.dtype),
            pltpu.VMEM((ring * bq, d), do3.dtype),
            pltpu.SemaphoreType.DMA((2, ring)),
        ]
    rows = pl.BlockSpec((None, nq, bq), head)
    tile = pl.BlockSpec((None, bk, d), key_block)
    tile_out = pl.BlockSpec((None, bk, d), key_block_out)
    dq_out = pl.BlockSpec((None, bq, d), query_tile) if causal else whole
    kv_heads = bkv if summed else bh
    kv_acc = [pltpu.VMEM((skv, d), jnp.float32)] * 2 if summed else []
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, scale=scale, causal=causal, block_q=bq, nq=nq,
            ring=ring, kv_len=kv_len, group=group, summed=summed,
            window=plan.window,
        ),
        grid=(bkv, group, nk),
        in_specs=[held, held, rows, rows, tile, tile],
        out_specs=[dq_out, tile_out, tile_out],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            jax.ShapeDtypeStruct((kv_heads, skv, d), k3.dtype),
            jax.ShapeDtypeStruct((kv_heads, skv, d), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((ring * bq, d), jnp.float32), *rings, *kv_acc
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
    )(q3, do3, lse_rows, adj_rows, k3, v3)
    if kv_heads != bkv:
        # the group's sum in float32, as the kernel's own accumulators take it

        def group_sum(x):
            x = x.reshape(bkv, group, skv, d).astype(jnp.float32)
            return x.sum(axis=1).astype(k3.dtype)

        dk, dv = group_sum(dk), group_sum(dv)
    return dq, dk, dv


# ----------------------------------------------------- custom_vjp plumbing


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q3, k3, v3, scale, causal, plan, kv_len, group, interpret):
    """Returns ``(out3, lse3)``; both are differentiable outputs (the lse
    cotangent folds into the backward kernels as an extra ``p·dlse`` term),
    which is what lets ring attention differentiate through its
    online-softmax combination of per-shard partials."""
    return _flash_fwd(q3, k3, v3, scale, causal, plan, kv_len, group, interpret)


def _flash_core_fwd(q3, k3, v3, scale, causal, plan, kv_len, group, interpret):
    out, lse = _flash_fwd(
        q3, k3, v3, scale, causal, plan, kv_len, group, interpret
    )
    return (out, lse), (q3, k3, v3, out, lse)


def _flash_core_bwd(scale, causal, plan, kv_len, group, interpret, res, cots):
    q3, k3, v3, out3, lse = res
    do3, dlse = cots
    # softmax's row term Σ_k dp∘p = Σ_d dO∘O, (bh, sq): no pass over scores
    delta = jnp.sum(
        do3.astype(jnp.float32) * out3.astype(jnp.float32), axis=-1
    )
    note_flash_backward("fused" if plan.fused_bwd else "tiled")
    if plan.fused_bwd:
        return tuple(_flash_bwd_fused(
            q3, k3, v3, lse, do3, dlse, delta, scale, causal, plan, kv_len,
            group, interpret,
        ))
    assert group == 1, "the two tiled kernels have one key-value head a query head"
    return _flash_bwd_split(
        q3, k3, v3, lse, do3, dlse, delta, scale, causal, plan, kv_len,
        interpret,
    )


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# the smallest head size that goes in unpadded: 64 is what the chip has run
# (32 compiles for a described v5e and has met no chip)
_MIN_UNPADDED_HEAD = 64


class FlashPlan(NamedTuple):
    """What one :func:`flash_attention` call visits, at what size, and what
    it holds in VMEM (:func:`flash_plan`)."""

    block_q: int  # forward query tile; the query length is padded to it
    block_k: int  # forward key tile; the key length is padded to it
    head: int  # head size the kernels see: the call's own, or padded to lanes
    fused_bwd: bool  # one backward kernel (``_bwd_fused_kernel``) or two tiled
    bwd_block_q: int
    bwd_block_k: int
    # a causal call's sliding window, where it leaves a key out: every
    # kernel bounds the key tiles it visits from below as well (``band_tiles``)
    window: int | None = None
    # the fused backward's residents: query tiles held (``_bwd_ring_tiles``),
    # and whether the group's dk / dv are summed in the kernel or written a
    # query head and summed outside
    bwd_ring: int = 0
    bwd_group_sum: bool = False


def flash_plan(
    sq: int, skv: int, d: int, group: int, causal: bool, dtype,
    block_q: int | None = None, block_k: int | None = None,
    *, window: int | None = None,
) -> FlashPlan:
    """The tile plan of a call, a pure function of its shapes: which score
    tiles it visits, at what size, how often, and what it keeps in VMEM.
    A ``window`` that reaches every key is no window (the plan, and so the
    program, is the plain causal call's).
    ``block_q`` / ``block_k`` are the caller's override of the forward
    tiles.  The backward is the fused kernel wherever what it holds fits
    (``_fused_bwd_resident_bytes`` against ``_FUSED_BWD_RESIDENT_LIMIT``),
    causal or not — at 4,096 keys and head size 128 it takes 0.8 x the two
    kernels' time non-causal and 0.7 x causal, at 8,192 causal keys 0.56 x
    and under a window of 2,048 0.63 x (module docstring) — and the two
    tiled kernels beyond.  What it holds follows the band: with a window
    ``bwd_ring`` query tiles of q, dO and dq, the most a key block reaches
    and one (any length fits); without one the sequence.  Where the
    group's dk / dv accumulators fit beside them the kernel sums the group
    (``bwd_group_sum``), else each query head writes its own."""
    chosen = block_q is None and block_k is None
    skv_128 = _ceil_to(skv, 128)
    if causal and chosen:
        # tiles tight to the diagonal: square, so that a query tile's sweep
        # ends on the one tile that straddles it — (512, 512) visits 56 %
        # of a 4,096-key square, one tile in 4.5 of them masked
        block_q = block_k = next(c for c in (512, 256, 128) if skv_128 % c == 0)
    if block_q is None:
        block_q = 128
    if block_k is None:
        block_k = next(
            c for c in (2048, 1024, 512, 256, 128)
            if c <= skv_128 and skv_128 % c == 0
        )
    sq_p, skv_p = _ceil_to(sq, block_q), _ceil_to(skv, block_k)
    # a head size that divides the lanes goes in as it is (the block's
    # minor dim spans the array): the zero lanes of a padded head are HBM
    # and VMEM traffic the MXU gains nothing from
    head = d if 128 % d == 0 and d >= _MIN_UNPADDED_HEAD else _ceil_to(d, 128)
    target = 512 if chosen else min(512, max(128, block_q, block_k))
    bwd_q, bwd_k = _stream_block(sq_p, target), _stream_block(skv_p, target)
    if window is not None and window >= skv:
        window = None
    if causal and sq_p != skv_p:  # the caller's unequal blocks: no square tiles
        return FlashPlan(block_q, block_k, head, False, bwd_q, bwd_k, window)
    held = functools.partial(
        _fused_bwd_resident_bytes, sq_p, skv_p, head, group, dtype, window,
        causal=causal, tile=bwd_q,
    )
    summed = group > 1 and held(summed=True) <= _FUSED_BWD_RESIDENT_LIMIT
    fused = summed or held(summed=False) <= _FUSED_BWD_RESIDENT_LIMIT
    ring = _bwd_ring_tiles(sq_p, skv_p, head, bwd_q, window)
    return FlashPlan(
        block_q, block_k, head, fused, bwd_q, bwd_k, window,
        ring if fused else 0, summed,
    )


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
    return_lse: bool = False,
    window: int | None = None,
):
    """Pallas flash attention over (B, H, S, D), differentiable.  ``k`` and
    ``v`` may hold fewer heads than ``q`` — (B, H // group, S, D), each
    serving ``group`` consecutive query heads.  ``window`` (causal calls):
    key ``j`` is visible to query ``i`` iff ``j <= i and i - j < window``;
    forward and backward visit, fetch and compute only the tiles that meet
    that band (``band_tiles``).

    Pads S to block multiples and, where :func:`flash_plan` says so, D up
    to a lane multiple (128); the true key length is masked inside the
    kernel, so padding never changes the result.  ``interpret=True`` runs
    the same kernels through the Pallas interpreter (CI on CPU).

    ``block_q`` / ``block_k`` override the plan's forward tiles (tests use
    them).  Left alone, a causal call gets square tiles tight to the
    diagonal — the largest of {512, 256, 128} that divides the padded
    length — and a non-causal call 128 query rows under the largest of
    {2048, ..., 128} keys that divides it: with K/V whole-sequence VMEM
    residents the loop over tiny key blocks is MXU-latency-bound (measured
    on a v5e at S=2048: 19 TF/s with 128-wide key blocks vs 85-105 TF/s
    with 1-2k-wide) and wide blocks cost nothing extra — unless the call is
    causal, where a 2,048-key block under a 128-row tile is mostly mask
    (PR 30: the forward at 4,096 causal keys, head size 64, 7.35 ms under
    (128, 2048), 5.99 under (512, 512) or (256, 512), 8.3 under
    (1024, 512), 11.9 under (256, 256)).  Past ``_FWD_RESIDENT_KV_LIMIT``
    the streamed forward takes over and the blocks only pin the padding —
    its tiles are chosen internally: ≤2048 query rows (wide q tiles
    amortize the K/V re-read) by ≤512 keys.
    """
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    if causal and sq != skv:
        raise ValueError("causal flash attention requires q_len == kv_len")
    if window is not None and (not causal or window < 1):
        raise ValueError("an attention window is positive and needs causal=True")
    if h % hkv or v.shape[1] != hkv:
        raise ValueError(
            f"{h} query heads over {hkv} / {v.shape[1]} key / value heads"
        )
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    group = h // hkv
    plan = flash_plan(
        sq, skv, d, group, causal, q.dtype, block_q, block_k, window=window
    )
    if group > 1 and not plan.fused_bwd:
        # the two-kernel backward has one key-value head a query head, and
        # the forward's residuals are its inputs: 67 MB a tensor at 8,192 x
        # 128 and 32 heads, which the AFMoE cell's layers paid until PR 32
        # fused their backward (their forward alone 4.01 -> 3.81 ms under
        # the window, 5.80 -> 5.59 without)
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        group = 1
    sq_p, skv_p = _ceil_to(sq, plan.block_q), _ceil_to(skv, plan.block_k)

    def pad3(x, s_p):
        x3 = x.reshape(-1, x.shape[2], d)
        if s_p == x.shape[2] and plan.head == d:
            return x3
        return jnp.pad(x3, ((0, 0), (0, s_p - x.shape[2]), (0, plan.head - d)))

    out3, lse3 = _flash_core(
        pad3(q, sq_p), pad3(k, skv_p), pad3(v, skv_p),
        scale, causal, plan, skv, group, interpret,
    )
    out = out3[:, :sq, :d].reshape(b, h, sq, d)
    if return_lse:
        return out, lse3[:, :sq, 0].reshape(b, h, sq)
    return out


def _auto_takes_kernel(q, k, causal, seq_ax) -> bool:
    """``impl="auto"``: the flash kernel on a TPU from the length at which
    it beats the composed einsums."""
    on_tpu = jax.default_backend() == "tpu"
    # the kernel only supports square causal attention; offset-causal
    # cross-attention stays on the reference path
    kernel_ok = not causal or q.shape[seq_ax] == k.shape[seq_ax]
    # Crossovers measured on a v5e against autodiff's backward of
    # ``mha_reference``, in chip runs older than the growth PRs and than
    # both sides' present backward (``_composed``'s own VJP, PR 25; the
    # causal tile plan and the fused backward here, PR 30): the kernel won
    # from S=512 at D=128 and, with half the MXU's lanes empty, from
    # S=1024 at D=64.  PR 30 timed the kernel at 4,096 and 16,384 keys
    # only, where the composed side cannot hold its scores; the thresholds
    # stand until a cell sits near them (PERF.md §7).  (The short-sequence
    # kernel in ops/attention_small.py is NOT auto-selected: standalone it
    # wins the attention sub-graph, but at the model level XLA re-lays the
    # custom-call boundaries and the end-to-end step loses — the winning
    # fused form at short S is the whole-block kernel, ops/vit_block.py,
    # which models/vit.py dispatches itself.)
    min_seq = 512 if q.shape[-1] >= 128 else 1024
    return on_tpu and kernel_ok and q.shape[seq_ax] >= min_seq


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, **options):
    """:func:`_attention` (its keyword options are this function's) under
    the scope ``attention``: whichever implementation runs — composed
    einsums, a Pallas kernel, a sequence-parallel ring — its device ops
    carry one name, so a trace times attention the same before and after a
    kernel replaces the einsums."""
    window = options.pop("window", None)
    if window is not None:
        if not options.get("causal") or window < 1:
            raise ValueError(
                "an attention window is positive and needs causal=True"
            )
        seq_ax = 1 if options.get("layout") == "bshd" else 2
        if window >= k.shape[seq_ax]:  # it reaches every key: plain causal
            window = None
    # a call with a window carries a second name, so that a trace times the
    # sliding layers apart from the full ones
    inner = (
        contextlib.nullcontext() if window is None
        else jax.named_scope("attention_window")
    )
    with jax.named_scope("attention"), inner:
        return _attention(q, k, v, window=window, **options)


def _attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: float | None = None,
    impl: str = "auto",
    return_lse: bool = False,
    layout: str = "bhsd",
    interpret: bool = False,
    window: int | None = None,
):
    """Dispatch: Pallas kernel on TPU for non-trivial sequences, composed
    einsums elsewhere (CPU CI, tiny sequences where one fused XLA softmax
    beats a kernel launch per (batch, head)): ``mha_reference``'s
    arithmetic with a backward of its own (``_composed``).

    ``impl="ring[:axis]"`` / ``"ulysses[:axis]"`` dispatch to the
    sequence-parallel implementations (``parallel/ring.py``) over the named
    mesh axis (default ``"model"``) — for callers already inside
    ``shard_map`` with the sequence sharded, e.g. a sequence-parallel model
    trunk.

    ``layout="bshd"`` accepts (B, S, H, D) inputs: the reference path then
    runs transpose-free (the fast choice for short sequences, where
    relayouts dominate); the kernel / sequence-parallel paths transpose at
    this boundary (amortized at the long lengths that select them).

    ``window`` (causal calls; the flash kernels and the composed branch):
    key ``j`` is visible to query ``i`` iff ``j <= i and i - j < window``
    (:func:`attention` has checked it, and dropped one that reaches every
    key)."""
    if layout not in ("bhsd", "bshd"):
        raise ValueError(f"unknown attention layout {layout!r}")
    seq_ax, head_ax = (1, 2) if layout == "bshd" else (2, 1)
    group, rest = divmod(q.shape[head_ax], k.shape[head_ax])
    if rest or v.shape[head_ax] != k.shape[head_ax]:
        raise ValueError(
            f"{q.shape[head_ax]} query heads over {k.shape[head_ax]} / "
            f"{v.shape[head_ax]} key / value heads"
        )

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3) if layout == "bshd" else x

    if impl == "auto":
        impl = (
            "pallas" if _auto_takes_kernel(q, k, causal, seq_ax)
            else "reference"
        )
    if group > 1 and impl != "pallas":
        # only the flash kernels read key-value head ``h // group`` by
        # index; every other implementation sees one a query head
        k, v = jnp.repeat(k, group, head_ax), jnp.repeat(v, group, head_ax)

    kind, _, axis = impl.partition(":")
    if window is not None and (kind in ("ring", "ulysses") or impl == "fused_small"):
        raise ValueError(f"attention impl {impl!r} takes no window")
    if kind in ("ring", "ulysses"):
        if return_lse:
            raise ValueError("return_lse is not supported through the "
                             "sequence-parallel dispatch")
        from ..parallel.ring import ring_attention, ulysses_attention

        fn = ring_attention if kind == "ring" else ulysses_attention
        out = fn(
            to_bhsd(q), to_bhsd(k), to_bhsd(v),
            axis_name=axis or "model", causal=causal, scale=scale,
        )
        return to_bhsd(out)  # transpose is its own inverse for these axes
    if impl in ("pallas", "fused_small"):
        note_kernel_path(
            "attention", "pallas-interpret" if interpret else "pallas"
        )
    elif impl == "reference":
        note_kernel_path("attention", "composed")
    if impl == "fused_small":
        from .attention_small import small_mha

        if return_lse:
            raise ValueError("impl='fused_small' does not return lse")
        if layout != "bshd":
            raise ValueError("impl='fused_small' requires layout='bshd'")
        if not interpret and jax.default_backend() != "tpu":
            raise ValueError(
                "attention(impl='fused_small') requires a TPU backend "
                f"(current: {jax.default_backend()!r}). Pass interpret=True "
                "to run the kernel through the Pallas interpreter off-TPU."
            )
        return small_mha(
            q, k, v, causal=causal, scale=scale, interpret=interpret
        )
    if impl == "pallas":
        if not interpret and jax.default_backend() != "tpu":
            raise ValueError(
                "attention(impl='pallas') requires a TPU backend (current: "
                f"{jax.default_backend()!r}). Pass interpret=True to run the "
                "kernel through the Pallas interpreter off-TPU, or use "
                "impl='reference'/'auto'."
            )
        out = flash_attention(
            to_bhsd(q), to_bhsd(k), to_bhsd(v),
            causal=causal, scale=scale, return_lse=return_lse,
            interpret=interpret, window=window,
        )
        if return_lse:
            return to_bhsd(out[0]), out[1]
        return to_bhsd(out)
    if impl == "reference":
        if return_lse:
            # lse is an output with a cotangent of its own (ring attention
            # differentiates through it): plain autodiff keeps this one
            return mha_reference(
                q, k, v, causal=causal, scale=scale, return_lse=True,
                layout=layout, window=window,
            )
        scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
        return _composed(q, k, v, causal, scale, layout, window)
    raise ValueError(f"unknown attention impl {impl!r}")
