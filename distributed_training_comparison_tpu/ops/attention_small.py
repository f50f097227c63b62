"""Fused multi-head attention for short sequences — the CIFAR-ViT regime.

The flash kernel (``ops/attention.py``) owns long sequences; below its
crossover the framework used the batched-einsum reference path.  Profiling
that path at ViT-Tiny shapes (B=256, S=64, H=3, D=64 on a v5e) showed the
matmuls were never the problem: **29% of step time was pure data
formatting** — XLA relayouts of the ``(B, S, 3, 64)`` q/k/v/score
tensors between the layouts its batched dots and softmax prefer — plus
more behind the fusion boundaries.  No einsum phrasing removes them (the
``bshd`` form was already the best of five measured formulations), because
the 4-D head-split tensors themselves are what force layout choices.

This kernel deletes the head-split tensors instead.  It takes q/k/v in
the packed ``(B, S, H·D)`` layout the Dense projections already produce
(a free reshape from ``(B, S, H, D)`` — adjacent row-major dims), keeps
everything in VMEM in that one layout, and slices each head's lanes
in-register.

The second trick makes the matmuls MXU-shaped.  Per-item scores at S=64
are (64, 64, 64) dots — latency-bound at ≈1.4 TF/s no matter who issues
them (measured: a per-item Pallas loop and XLA's batched dot are within
25%).  Instead the kernel stacks ``tb`` batch items into one
``(tb·S, D) @ (D, tb·S)`` matmul and masks the score matrix
**block-diagonally**: cross-item blocks get -inf before the softmax, so
they exp to exactly zero and contribute nothing to ``P @ V`` — the
outputs are bit-identical to per-item attention, no extraction step.
The waste is ``tb×`` score FLOPs, paid in the currency the chip has in
surplus (MXU throughput on big tiles) to avoid the two it doesn't
(per-dot latency, relayout bandwidth).  At S=64/tb=8 the fused forward
measures ~20 µs vs ~520 µs for the reference path's attention block.

Backward is one kernel with the same grid and the same stacked algebra
(dP, softmax VJP, dQ/dK/dV are all ``(tb·S)``-row matmuls); q/k/v are
block inputs anyway, so it recomputes P from them rather than saving a
``(rows, rows)`` tensor per (tile, head).

Status — opt-in (``attention(impl="fused_small")``), not auto-selected:
standalone the fused forward wins by an order of magnitude, but wired
into the ViT the step got *slower* (23.8k → 21.5k img/s on vit_tiny):
XLA's projection/MLP gemms prefer batch-minor layouts, so every
custom-call boundary grew a ``(B·S, dim)`` relayout copy (~19% of step
time) that ate the win.  The lesson is structural — a kernel whose
neighbors are XLA gemms pays the boundary — and the winning form of
this design is ``ops/vit_block.py``, which swallows the gemms too and
reuses this module's stacked-attention helpers; models/vit.py
dispatches it for the regimes where it measures faster.

Scope: self-attention (``sq == skv``), ``bshd`` layout, ``S % 8 == 0``,
``D % 8 == 0``.  Causal is supported (the block mask additionally keeps
``row ≥ col`` within each item's block).  Numerics match
``mha_reference`` — fp32 scores/softmax, P cast to the compute dtype
before the output matmul.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _head_slices(h, d):
    return [slice(hh * d, (hh + 1) * d) for hh in range(h)]


def _row_block(rows, s):
    return jax.lax.broadcasted_iota(jnp.int32, (rows, s), 0) // s


def _extract_diag(big, rows, tb, s):
    """(rows, rows) → (rows, s): each row keeps its own item's columns.

    The stacked score matrix is only valid on its block diagonal; rather
    than softmax over all ``rows`` columns (8× wasted VPU exp at tb=8 —
    measured as the kernel's bottleneck), rows extract their own
    ``s``-wide block, softmax small, and re-expand.  Static lane slices
    + sublane row masks only — Mosaic has no lane-splitting shape cast."""
    rblk = _row_block(rows, s)
    acc = jnp.zeros((rows, s), jnp.float32)
    for g in range(tb):
        acc += jnp.where(rblk == g, big[:, g * s:(g + 1) * s], 0.0)
    return acc


def _expand_diag(small, rows, tb, s, dtype):
    """(rows, s) → block-diagonal (rows, rows): inverse of _extract_diag."""
    rblk = _row_block(rows, s)
    parts = [jnp.where(rblk == g, small, 0.0) for g in range(tb)]
    return jnp.concatenate(parts, axis=1).astype(dtype)


def _softmax_small(scd, s, causal, dtype):
    if causal:
        r = jax.lax.broadcasted_iota(jnp.int32, scd.shape, 0) % s
        c = jax.lax.broadcasted_iota(jnp.int32, scd.shape, 1)
        scd = jnp.where(r >= c, scd, _NEG_INF)
    m = jnp.max(scd, axis=-1, keepdims=True)
    e = jnp.exp(scd - m)
    return (e / jnp.sum(e, axis=-1, keepdims=True)).astype(dtype)


def _head_probs(qh, kh, tb, s, scale, causal):
    """One head's stacked block-diagonal softmax probabilities: the
    (rows, rows) score matmul, diagonal extraction, fp32 softmax.
    Shared by this module's kernels and the fused block kernel
    (ops/vit_block.py) so the numerics live in exactly one place."""
    rows = tb * s
    sc = jax.lax.dot_general(
        qh, kh, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    return _softmax_small(_extract_diag(sc, rows, tb, s), s, causal, jnp.float32)


def head_fwd(qh, kh, vh, tb, s, scale, causal):
    """(o, p_small) for one head of stacked block-diagonal attention."""
    rows = tb * s
    pf = _head_probs(qh, kh, tb, s, scale, causal)
    p = _expand_diag(pf, rows, tb, s, qh.dtype)
    o = jnp.dot(p, vh, preferred_element_type=jnp.float32).astype(qh.dtype)
    return o, pf


def head_bwd(qh, kh, vh, doh, pf, tb, s, scale):
    """(dq, dk, dv) for one head given its saved/recomputed p_small.

    The softmax VJP ``ds = p∘(dp − Σ(dp∘p))`` runs on the extracted
    (rows, s) diagonal; ds and p re-expand for the MXU matmuls."""
    rows = tb * s
    dp = _extract_diag(
        jax.lax.dot_general(
            doh, vh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ),
        rows, tb, s,
    )
    ds = pf * (dp - jnp.sum(dp * pf, axis=-1, keepdims=True))
    ds = _expand_diag(ds * scale, rows, tb, s, qh.dtype)
    p = _expand_diag(pf, rows, tb, s, qh.dtype)
    dq = jnp.dot(ds, kh, preferred_element_type=jnp.float32).astype(qh.dtype)
    dk = jax.lax.dot_general(
        ds, qh, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(qh.dtype)
    dv = jax.lax.dot_general(
        p, doh, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(qh.dtype)
    return dq, dk, dv


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, tb, s, h, d, scale, causal):
    for sl in _head_slices(h, d):
        o, _ = head_fwd(
            q_ref[:, sl], k_ref[:, sl], v_ref[:, sl], tb, s, scale, causal
        )
        o_ref[:, sl] = o.astype(o_ref.dtype)


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref,
    *, tb, s, h, d, scale, causal,
):
    for sl in _head_slices(h, d):
        qh, kh, vh = q_ref[:, sl], k_ref[:, sl], v_ref[:, sl]
        pf = _head_probs(qh, kh, tb, s, scale, causal)
        dq, dk, dv = head_bwd(qh, kh, vh, do_ref[:, sl], pf, tb, s, scale)
        dq_ref[:, sl] = dq.astype(dq_ref.dtype)
        dk_ref[:, sl] = dk.astype(dk_ref.dtype)
        dv_ref[:, sl] = dv.astype(dv_ref.dtype)


def _call(kernel, n_out, q2, *rest, tb, s, h, d, scale, causal, interpret):
    n = q2.shape[0]  # b*s rows, 2-D view: contiguous row blocks, so the
    dim = h * d      # boundary with XLA is a plain {1,0} layout
    spec = pl.BlockSpec((tb * s, dim), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct((n, dim), q2.dtype)
    out = pl.pallas_call(
        functools.partial(
            kernel, tb=tb, s=s, h=h, d=d, scale=scale, causal=causal
        ),
        grid=(n // (tb * s),),
        in_specs=[spec] * (1 + len(rest)),
        out_specs=spec if n_out == 1 else [spec] * n_out,
        out_shape=shape if n_out == 1 else [shape] * n_out,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
    )(q2, *rest)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _small_core(q3, k3, v3, tb, s, h, d, scale, causal, interpret):
    return _call(
        _fwd_kernel, 1, q3, k3, v3,
        tb=tb, s=s, h=h, d=d, scale=scale, causal=causal, interpret=interpret,
    )


def _small_core_fwd(q3, k3, v3, tb, s, h, d, scale, causal, interpret):
    out = _small_core(q3, k3, v3, tb, s, h, d, scale, causal, interpret)
    return out, (q3, k3, v3)


def _small_core_bwd(tb, s, h, d, scale, causal, interpret, res, do3):
    q3, k3, v3 = res
    dq, dk, dv = _call(
        _bwd_kernel, 3, q3, k3, v3, do3,
        tb=tb, s=s, h=h, d=d, scale=scale, causal=causal, interpret=interpret,
    )
    return dq, dk, dv


_small_core.defvjp(_small_core_fwd, _small_core_bwd)


def pick_block_items(b: int, s: int, target_rows: int = 512) -> int:
    """Largest ``tb`` dividing ``b`` with ``tb·s ≤ target_rows`` (≥ 1).

    512 stacked rows keeps the score tile ≈1 MiB fp32 in VMEM and the
    matmuls MXU-wide; measured flat between 256 and 512 rows at S=64."""
    tb = max(1, target_rows // s)
    while b % tb:
        tb -= 1
    return tb


def small_mha(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: float | None = None,
    block_items: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused short-sequence self-attention over ``(B, S, H, D)`` (bshd).

    Differentiable (custom VJP, one backward kernel).  Requires
    ``S % 8 == 0`` and ``D % 8 == 0``; q, k, v must share shapes
    (self-attention).  See the module docstring for the design.
    """
    b, s, h, d = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(
            f"small_mha is self-attention only: q {q.shape} vs k {k.shape} "
            f"/ v {v.shape}"
        )
    if s % 8 or d % 8:
        raise ValueError(f"small_mha needs S, D multiples of 8; got {s}, {d}")
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    tb = pick_block_items(b, s) if block_items is None else block_items
    pack = lambda x: x.reshape(b * s, h * d)  # adjacent dims: free reshape
    out = _small_core(
        pack(q), pack(k), pack(v), tb, s, h, d, scale, causal, interpret
    )
    return out.reshape(b, s, h, d)
