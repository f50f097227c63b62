"""Grouped expert FFN — a Pallas fused kernel over expert-sorted tokens.

Beyond parity (the reference has no MoE at all; ``models/moe.py`` situates
the layer against SURVEY.md §2.2).  This kernel is the TPU answer to the
dispatch cost of the XLA formulations: at CIFAR dims (n=16384 tokens,
d=192, E=8) a chip profile older than this round's benchmark showed the
sort/gather dispatch spending most of its device time in gather/scatter
fusions and a small part in the expert matmuls themselves — the
capacity-buffer scatter ``(E·cap, d)``, the gather back, and the
``(E, cap, hidden)`` activation round-trips through HBM.

The megablocks-style fix (Gale et al., MegaBlocks; the jax ``gmm`` kernels
in maxtext follow the same shape): keep tokens in *sorted order* and run a
grouped matmul directly on the ragged groups, so

- the only data movement left outside the kernel is the sort-order
  permutation gather and its inverse (both O(n·d), unavoidable), and
- the whole expert MLP — up-projection, bias, gelu, down-projection,
  bias — runs **fused in VMEM**: the ``(rows, hidden)`` activation never
  exists in HBM, in forward or backward.

Kernel design (one v5e core, ~16 MiB VMEM):

- Grid over row tiles of the sorted token array (``block_rows`` × d).
  All E experts' weights stay VMEM-resident across the whole grid
  (E=8, d=192, hidden=768, bf16 → 4.7 MiB; constant index maps mean
  Mosaic fetches them once).
- Each tile statically unrolls over experts: a ``pl.when`` guard skips
  experts whose row range [starts[e], starts[e]+kept_e) does not overlap
  the tile, so compute per tile ≈ (1 + boundary crossings) full-tile
  MLPs — with E=8 and 32 tiles, ≈18% duplicate-tile overhead, paid in
  the cheapest currency (MXU FLOPs) to avoid the expensive one (HBM
  gathers).
- Rows past an expert's capacity, and padding rows past ``starts[-1]``,
  match no expert's mask and come out exactly zero — the caller's
  gate-weighted combine then reproduces Switch drop semantics
  bit-for-bit with the other two dispatch implementations.
- Backward = two kernels: ``dx`` (same tile grid, recomputes the
  pre-gelu activation) and ``dW`` (grid ``(E, tiles)`` with the weight
  gradients VMEM-resident across each expert's inner sweep; a
  scalar-prefetched index map clamps the x/dy tile DMA to the tiles that
  actually overlap the expert, so skipped grid steps move no data).

Numerics mirror the XLA einsum path exactly: matmuls accumulate fp32
(``preferred_element_type``), results cast to the compute dtype *before*
the bias add, gelu in compute dtype — so ``dispatch="gmm"`` and
``dispatch="gather"`` agree to float roundoff, which the equivalence
tests in ``tests/test_moe.py`` pin down in fp32 interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


# ------------------------------------------------------------- fwd kernel


def _ffn_kernel(
    starts_ref, x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref,
    *, cap, ne, block_rows,
):
    row0 = pl.program_id(0) * block_rows
    gid = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_rows, 1), 0)
    o_ref[...] = jnp.zeros_like(o_ref)
    x = x_ref[...]
    for e in range(ne):
        s = starts_ref[e]
        kept_end = s + jnp.minimum(starts_ref[e + 1] - s, cap)

        @pl.when((kept_end > row0) & (s < row0 + block_rows))
        def _(e=e, s=s, kept_end=kept_end):
            h = jnp.dot(x, w1_ref[e], preferred_element_type=jnp.float32)
            h = jax.nn.gelu(h.astype(x.dtype) + b1_ref[e])
            o = jnp.dot(h, w2_ref[e], preferred_element_type=jnp.float32)
            o = o.astype(x.dtype) + b2_ref[e]
            mask = (gid >= s) & (gid < kept_end)
            o_ref[...] += jnp.where(mask, o, jnp.zeros_like(o))


# -------------------------------------------------------------- dx kernel


def _dh_chain(x, dy, w1_e, b1_e, w2_e):
    """Shared backward recompute: masked dy → (pre-gelu cotangent, gelu(h)).

    Mirrors autodiff of the forward chain ``o = dot(gelu(dot(x,w1)↓+b1),
    w2)↓+b2`` where ↓ is the fp32→compute-dtype cast: cotangents re-cast
    to the compute dtype at each cast boundary, exactly as XLA's VJP of
    the einsum formulation does."""
    h1 = jnp.dot(x, w1_e, preferred_element_type=jnp.float32)
    h1 = h1.astype(x.dtype) + b1_e
    g, gelu_vjp = jax.vjp(jax.nn.gelu, h1)
    dg = jax.lax.dot_general(
        dy, w2_e, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    (dh1,) = gelu_vjp(dg)
    return dh1, g


def _dx_kernel(
    starts_ref, x_ref, dy_ref, w1_ref, b1_ref, w2_ref, dx_ref,
    *, cap, ne, block_rows,
):
    row0 = pl.program_id(0) * block_rows
    gid = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_rows, 1), 0)
    dx_ref[...] = jnp.zeros_like(dx_ref)
    x = x_ref[...]
    dy = dy_ref[...]
    for e in range(ne):
        s = starts_ref[e]
        kept_end = s + jnp.minimum(starts_ref[e + 1] - s, cap)

        @pl.when((kept_end > row0) & (s < row0 + block_rows))
        def _(e=e, s=s, kept_end=kept_end):
            mask = (gid >= s) & (gid < kept_end)
            dym = jnp.where(mask, dy, jnp.zeros_like(dy))
            dh1, _ = _dh_chain(x, dym, w1_ref[e], b1_ref[e], w2_ref[e])
            dx = jax.lax.dot_general(
                dh1, w1_ref[e], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(x.dtype)
            # every row belongs to exactly one expert, so the masked-dy
            # chain is already row-disjoint; += assembles, never mixes
            dx_ref[...] += dx


# -------------------------------------------------------------- dW kernel


def _dw_kernel(
    starts_ref, x_ref, dy_ref, w1_ref, b1_ref, w2_ref,
    dw1_ref, db1_ref, dw2_ref, db2_ref,
    *, cap, ne, block_rows,
):
    e, i = pl.program_id(0), pl.program_id(1)
    row0 = i * block_rows
    gid = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_rows, 1), 0)
    s = starts_ref[e]
    kept_end = s + jnp.minimum(starts_ref[e + 1] - s, cap)

    @pl.when(i == 0)
    def _():
        dw1_ref[...] = jnp.zeros_like(dw1_ref)
        db1_ref[...] = jnp.zeros_like(db1_ref)
        dw2_ref[...] = jnp.zeros_like(dw2_ref)
        db2_ref[...] = jnp.zeros_like(db2_ref)

    @pl.when((kept_end > row0) & (s < row0 + block_rows))
    def _():
        x = x_ref[...]
        mask = (gid >= s) & (gid < kept_end)
        dym = jnp.where(mask, dy_ref[...], jnp.zeros_like(dy_ref))
        dh1, g = _dh_chain(x, dym, w1_ref[0], b1_ref[0, 0], w2_ref[0])
        xm = jnp.where(mask, x, jnp.zeros_like(x))
        dw1_ref[...] += jax.lax.dot_general(
            xm, dh1, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dw1_ref.dtype)[None]
        db1_ref[...] += jnp.sum(dh1, axis=0).astype(db1_ref.dtype)[None, None]
        dw2_ref[...] += jax.lax.dot_general(
            g, dym, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dw2_ref.dtype)[None]
        db2_ref[...] += jnp.sum(dym, axis=0).astype(db2_ref.dtype)[None, None]


# ------------------------------------------------------------ pallas_call


def _whole_spec(w):
    """Whole-array weight block with a constant index map: fetched once."""
    return pl.BlockSpec(w.shape, lambda i, _nd=w.ndim: (0,) * _nd)


def _row_grid_call(kernel, n_out, out_dtype, xs, dy, weights, starts,
                   cap, block_rows, interpret):
    n_p, d = xs.shape
    ne = weights[0].shape[0]
    tensor_in = [xs] + ([dy] if dy is not None else []) + list(weights)
    row_spec = pl.BlockSpec((block_rows, d), lambda i: (i, 0))
    in_specs = (
        [pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [row_spec] * (2 if dy is not None else 1)
        + [_whole_spec(w) for w in weights]
    )
    return pl.pallas_call(
        functools.partial(kernel, cap=cap, ne=ne, block_rows=block_rows),
        grid=(n_p // block_rows,),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, d), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
    )(starts, *tensor_in)


def _dw_call(xs, dy, w1, b1, w2, starts, cap, block_rows, interpret):
    n_p, d = xs.shape
    ne, _, hidden = w1.shape
    nb = n_p // block_rows

    def clamp(i, e, starts_ref):
        # only DMA x/dy tiles that overlap expert e; repeats of the same
        # block index on consecutive grid steps skip the copy entirely.
        # Whenever the kernel's overlap guard fires, clamp(i) == i, so the
        # loaded block always matches the mask arithmetic; for empty
        # groups (s == n, possible under router collapse) the raw s//bm
        # would be one past the last block — pin everything to [0, nb).
        s = starts_ref[e]
        kept_end = s + jnp.minimum(starts_ref[e + 1] - s, cap)
        lo = jnp.minimum(s // block_rows, nb - 1)
        hi = jnp.clip((kept_end - 1) // block_rows, lo, nb - 1)
        return jnp.clip(i, lo, hi)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ne, nb),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda e, i, st: (clamp(i, e, st), 0)),
            pl.BlockSpec((block_rows, d), lambda e, i, st: (clamp(i, e, st), 0)),
            pl.BlockSpec((1, d, hidden), lambda e, i, st: (e, 0, 0)),
            # biases carry a singleton middle axis so every block's last
            # two dims span the full array (the Mosaic block-shape rule)
            pl.BlockSpec((1, 1, hidden), lambda e, i, st: (e, 0, 0)),
            pl.BlockSpec((1, hidden, d), lambda e, i, st: (e, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, d, hidden), lambda e, i, st: (e, 0, 0)),
            pl.BlockSpec((1, 1, hidden), lambda e, i, st: (e, 0, 0)),
            pl.BlockSpec((1, hidden, d), lambda e, i, st: (e, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda e, i, st: (e, 0, 0)),
        ],
    )
    dw1, db1, dw2, db2 = pl.pallas_call(
        functools.partial(
            _dw_kernel, cap=cap, ne=ne, block_rows=block_rows
        ),
        grid_spec=grid_spec,
        # fp32 accumulators regardless of compute dtype: the per-tile
        # partials add up across ~n/block_rows sequential grid steps, and
        # bf16 '+=' chains lose digits the XLA einsum VJP (one fp32
        # reduction, one cast) never does; cast once on return instead
        out_shape=[
            jax.ShapeDtypeStruct((ne, d, hidden), jnp.float32),
            jax.ShapeDtypeStruct((ne, 1, hidden), jnp.float32),
            jax.ShapeDtypeStruct((ne, hidden, d), jnp.float32),
            jax.ShapeDtypeStruct((ne, 1, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
    )(starts, xs, dy, w1, b1[:, None, :], w2)
    return dw1, db1[:, 0], dw2, db2[:, 0]


# ------------------------------------------------------------- custom VJP


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _gmm_core(xs, w1, b1, w2, b2, starts, cap, block_rows, interpret):
    return _row_grid_call(
        _ffn_kernel, xs.shape[0], xs.dtype, xs, None,
        (w1, b1, w2, b2), starts, cap, block_rows, interpret,
    )


def _gmm_core_fwd(xs, w1, b1, w2, b2, starts, cap, block_rows, interpret):
    ys = _gmm_core(xs, w1, b1, w2, b2, starts, cap, block_rows, interpret)
    return ys, (xs, w1, b1, w2, b2[:0], starts)


def _gmm_core_bwd(cap, block_rows, interpret, res, dy):
    xs, w1, b1, w2, b2_empty, starts = res
    dxs = _row_grid_call(
        _dx_kernel, xs.shape[0], xs.dtype, xs, dy,
        (w1, b1, w2), starts, cap, block_rows, interpret,
    )
    dw1, db1, dw2, db2 = _dw_call(
        xs, dy, w1, b1, w2, starts, cap, block_rows, interpret
    )
    dstarts = np.zeros(starts.shape, dtype=jax.dtypes.float0)
    return (
        dxs,
        dw1.astype(w1.dtype), db1.astype(b1.dtype),
        dw2.astype(w2.dtype), db2.astype(b2_empty.dtype),
        dstarts,
    )


_gmm_core.defvjp(_gmm_core_fwd, _gmm_core_bwd)


# ------------------------------------------------------------- public API


def grouped_ffn(
    xs: jnp.ndarray,
    w1: jnp.ndarray,
    b1: jnp.ndarray,
    w2: jnp.ndarray,
    b2: jnp.ndarray,
    starts: jnp.ndarray,
    cap: int,
    *,
    block_rows: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused grouped MLP ``gelu(xs @ w1[e] + b1[e]) @ w2[e] + b2[e]``
    over ragged expert groups of expert-sorted tokens.

    Args:
      xs: ``(n, d)`` tokens sorted by expert (compute dtype).
      w1/b1/w2/b2: expert-stacked MLP parameters ``(E, d, h)`` / ``(E, h)``
        / ``(E, h, d)`` / ``(E, d)``, already cast to the compute dtype.
      starts: ``(E+1,)`` int32 group boundaries — expert ``e`` owns rows
        ``[starts[e], starts[e+1])``; ``starts[E]`` is the total token
        count.
      cap: static per-expert capacity; rows past ``starts[e] + cap``
        within a group are dropped (output exactly zero, Switch
        semantics).

    Returns ``(n, d)`` outputs in the same sorted order; dropped rows are
    zero.  Differentiable in ``xs`` and all four parameters.
    """
    n, d = xs.shape
    block_rows = min(block_rows, _ceil_to(max(n, 8), 8))
    n_p = _ceil_to(n, block_rows)
    xs_p = jnp.pad(xs, ((0, n_p - n), (0, 0)))
    ys = _gmm_core(
        xs_p, w1, b1, w2, b2, starts.astype(jnp.int32),
        int(cap), block_rows, bool(interpret),
    )
    return ys[:n]


# ------------------------------------------------- streamed grouped matmul

# The kernel above keeps every expert's weights in VMEM (8 MiB for all of
# them, ops/vmem.py).  An expert of a language model does not fit — one of
# LFM2-24B-A2B's is 3 x 2048 x 1536, 18.9 MB in bf16 — so its weights stream
# from HBM tile by tile.  Two implementations of the same mathematics, both
# JAX's own; which one ``auto`` takes on a TPU was decided by timing them
# inside the train step on the chip (PERF.md, Findings, PR 27).
GMM_IMPLS = ("ragged_dot", "megablox")


def _tile(dim: int, target: int) -> int:
    """The largest multiple of 128 up to ``target`` that divides ``dim``;
    ``dim`` itself where there is none: a test's width under one lane tile,
    which a block may span whole.  A wider one (1,856 = 14.5 x 128) comes
    here at its ``lane_width``."""
    for t in range(min(target, dim) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def lane_width(width: int, impl: str) -> int:
    """The width ``impl`` takes an expert's hidden axis at: ``width``
    itself, or for ``megablox`` at a width past one lane tile that no
    multiple of 128 divides, the next multiple of 128.  A block of the
    whole width is no way out there: the backward's kernels take the
    transposed tiling, whose block is neither whole lanes nor the array's
    width (Mosaic refuses it; compiled for a described v5e at 1,856), and
    at 1,856 a forward block would be 14 MB of the 16 MiB a kernel gets
    unasked.  The caller pads its cast weights with zeros (``models/moe.py
    TopKMoE``): exact for an activation that sends zero to zero."""
    if impl != "megablox" or width <= 128 or _tile(width, 1024) % 128 == 0:
        return width
    return _ceil_to(width, 128)


def resolve_gmm_impl(impl: str = "auto") -> str:
    if impl == "auto":
        return "megablox" if jax.default_backend() == "tpu" else "ragged_dot"
    if impl not in GMM_IMPLS:
        raise ValueError(f"unknown grouped matmul {impl!r}; one of {GMM_IMPLS}")
    return impl


def grouped_matmul(
    xs: jnp.ndarray,
    w: jnp.ndarray,
    group_sizes: jnp.ndarray,
    *,
    impl: str = "ragged_dot",
    interpret: bool = False,
) -> jnp.ndarray:
    """``ys[r] = xs[r] @ w[e]`` for the rows ``r`` of group ``e``.

    ``xs`` is ``(m, k)`` with its rows sorted by expert, ``w`` is ``(E, k,
    n)`` and ``group_sizes`` ``(E,)`` int32: expert ``e`` owns the
    ``group_sizes[e]`` rows after those of the experts before it.  Rows
    past ``sum(group_sizes)`` belong to no expert and come out zero; the
    work done is in proportion to the rows that belong to one (``megablox``
    runs a grid over the row tiles that hold such rows and no others).
    Differentiable in ``xs`` and ``w``.  Returns ``(m, n)`` in ``xs``'s dtype.
    """
    group_sizes = group_sizes.astype(jnp.int32)
    m, k = xs.shape
    # What lies behind the groups is not the implementations' to define:
    # megablox visits only row tiles that hold a group's rows and stores
    # only those rows (the rest is uninitialised memory, in the output and,
    # through its VJP, in the gradient with respect to ``xs``), and the
    # chip's ``ragged_dot`` gave non-finite steps on such rows (my chip run,
    # PR 27).  Selecting on both sides makes both exact zeros.
    valid = (jnp.arange(m) < jnp.sum(group_sizes))[:, None]
    xs = jnp.where(valid, xs, 0)
    if impl == "ragged_dot":
        out = jax.lax.ragged_dot(
            xs, w, group_sizes, preferred_element_type=jnp.float32
        ).astype(xs.dtype)
    elif impl == "megablox":
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        tiling = (_tile(m, 512), _tile(k, 1024), _tile(w.shape[2], 1024))
        out = megablox.gmm(
            xs, w, group_sizes, xs.dtype, tiling, interpret=interpret
        )
    else:
        raise ValueError(f"unknown grouped matmul {impl!r}; one of {GMM_IMPLS}")
    return jnp.where(valid, out, 0)


def grouped_matmul_t(
    lhs: jnp.ndarray,
    rhs: jnp.ndarray,
    group_sizes: jnp.ndarray,
    *,
    impl: str = "ragged_dot",
    interpret: bool = False,
) -> jnp.ndarray:
    """``out[g] = lhs[rows of g].T @ rhs[rows of g]``: the grouped matmul
    with the rows contracted away (the form a grouped matmul's weight
    gradient has).  ``lhs`` is ``(m, k)``, ``rhs`` ``(m, n)``, both with
    their rows sorted by group; group ``g`` owns the ``group_sizes[g]`` rows
    after those of the groups before it, rows past ``sum(group_sizes)`` are
    not read, and a group without rows comes out zero.  Products accumulate
    in float32.  Returns ``(G, k, n)`` in ``rhs``'s dtype; not
    differentiable (its callers have VJPs of their own)."""
    group_sizes = group_sizes.astype(jnp.int32)
    if impl == "ragged_dot":
        contract_rows = jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(([0], [0]), ([], [])),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[],
        )
        return jax.lax.ragged_dot_general(
            lhs, rhs, group_sizes, contract_rows,
            preferred_element_type=jnp.float32,
        ).astype(rhs.dtype)
    if impl != "megablox":
        raise ValueError(f"unknown grouped matmul {impl!r}; one of {GMM_IMPLS}")
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    m, k = lhs.shape
    tiling = (_tile(m, 512), _tile(k, 1024), _tile(rhs.shape[1], 1024))
    return tgmm(lhs.T, rhs, group_sizes, rhs.dtype, tiling, interpret=interpret)
