"""One Pallas kernel per transformer block — the CIFAR-ViT fast path.

Why fuse the *whole* block and not just attention: the fused
short-sequence attention kernel (``ops/attention_small.py``) deletes the
head-split relayouts, but measured end-to-end it LOST throughput — XLA's
surrounding projection/MLP gemms prefer exotic batch-minor layouts
(``{0,2,1}``-style), so every custom-call boundary grew a
``(B·S, dim)`` transpose copy (~19% of vit_tiny step time), eating the
win.  The boundary problem is structural: any kernel whose neighbors are
XLA gemms pays it.

So the kernel swallows the gemms.  One ``pallas_call`` computes the
entire pre-LN block

    x ── LN₁ ── qkv gemm ── MHA ── out-proj ──(+x)── LN₂ ── MLP ──(+)── out

and the backward is one kernel producing dx *and all twelve parameter
gradients* (fp32 VMEM accumulators with constant-index output blocks,
flushed once).  Consecutive blocks then feed each other custom-call to
custom-call with identical row-major ``(B·S, dim)`` layouts — there is
no XLA gemm left between them to impose a layout, so the boundary copies
vanish by construction; only the patch embed (entry) and head (exit)
touch XLA gemms, once per step instead of 4× per layer.

In-kernel design notes:

- **Gemm shapes**: per 512-row tile the projections run as
  ``(512, D) @ (D, 3D)`` (one packed qkv gemm), the MLP as
  ``(512, D) @ (D, 4D)`` — proper MXU tiles, vs the composed path's
  per-head ``(64, 64, 64)`` score dots that run latency-bound at
  ≈1.4 TF/s.
- **Attention** uses the stacked block-diagonal trick from
  ``ops/attention_small.py``: ``tb`` items' scores in one
  ``(tb·S, tb·S)`` matmul, cross-item blocks masked; softmax runs on the
  extracted ``(tb·S, S)`` diagonal (the full-width softmax's wasted exp
  was the VPU bottleneck), then P re-expands for the ``P @ V`` matmul.
- **LayerNorm** follows ``models/norms.py``: stat reductions in fp32 by
  default (``norm_f32=False`` reproduces ``norm_dtype=None``), params
  fp32, output cast to the compute dtype — same chain as the composed
  ``norm_policy`` path, eps 1e-6.
- **Backward recomputes** every intermediate from ``x`` (the only saved
  residual) — at these sizes recompute is ~1 extra fwd of MXU work,
  cheaper than round-tripping ``(B·S, 4D)`` activations through HBM.

Measured regime (v5e, vit_tiny dims, bf16, bs256): the fused block wins
from S≈256 (**6,479 vs 5,037 img/s on the 256-token patch-2 leg, +29%**
— committed capture ``vit_tiny_p2_bf16_bs256`` vs the r4 composed run)
where the stacked-score waste is only 2×.  At S=64 it loses (18.8–20.4k
vs 23.8k): tb=8 stacking wastes 8× score FLOPs, and the backward's
full-chain recompute (~21 GFLOP/layer) exceeds what the deleted
relayouts buy back — so ``models/vit.py`` gates the fused path to
``128 ≤ S ≤ 512`` and the composed XLA path keeps the 64-token CIFAR
default.  (Profile evidence the fusion does what it claims: with the
kernel active, the step is 98.2% custom-call and data formatting drops
to 0.4% — the copies are gone; at S=64 the composed path's better
FLOP economy simply matters more.)

Parity: the flax param tree is *identical* to the composed ViTBlock
(``models/vit.py`` creates the same ``{q_proj,k_proj,v_proj,proj,
mlp_up,mlp_down}/{kernel,bias}`` and ``{ln_attn,ln_mlp}/{scale,bias}``
leaves), so checkpoints, the torch-parity tooling, and the tensor/
pipeline-parallel composed path all interoperate; fused-vs-composed
equivalence is pinned by tests in interpret mode and on-chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention_small import head_bwd, head_fwd, pick_block_items

_LN_EPS = 1e-6


# ----------------------------------------------------------- layer pieces


def _ln_fwd(x, gamma, beta, f32):
    """Returns (y, xhat, inv_sigma); y in x.dtype, stats per norm policy."""
    xs = x.astype(jnp.float32) if f32 else x
    mu = jnp.mean(xs, axis=-1, keepdims=True)
    var = jnp.mean(xs * xs, axis=-1, keepdims=True) - mu * mu
    inv = jax.lax.rsqrt(var + _LN_EPS)
    xhat = (xs - mu) * inv
    y = xhat * gamma + beta
    return y.astype(x.dtype), xhat, inv


def _ln_bwd(dy, xhat, inv, gamma):
    """dx for y = xhat*gamma + beta; dy fp32, returns fp32 (rows, d)."""
    dxhat = dy * gamma
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    return (dxhat - m1 - xhat * m2) * inv


def _gemm(x, w, b):
    """x @ w + b with fp32 accumulation, result in x.dtype (the Dense
    chain: MXU-accumulated matmul cast to compute dtype, bias added in
    compute dtype)."""
    o = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    return o + b.astype(x.dtype)


def _gemm_T(g, w):
    """g @ w^T in fp32 → caller casts; contraction over w's output dim."""
    return jax.lax.dot_general(
        g, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _acc_T(a, g):
    """a^T @ g in fp32: weight-gradient contraction over rows."""
    return jax.lax.dot_general(
        a, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _qkv_head(qkv, hh, d, dim):
    return (
        qkv[:, hh * d:(hh + 1) * d],
        qkv[:, dim + hh * d:dim + (hh + 1) * d],
        qkv[:, 2 * dim + hh * d:2 * dim + (hh + 1) * d],
    )


def _attn_fwd(qkv, tb, s, h, d, scale):
    """Stacked block-diagonal MHA (shared per-head algebra:
    ``attention_small.head_fwd``); returns (o, [p_small per head])."""
    dim = h * d
    outs, ps = [], []
    for hh in range(h):
        o, pf = head_fwd(*_qkv_head(qkv, hh, d, dim), tb, s, scale, False)
        outs.append(o)
        ps.append(pf)
    return jnp.concatenate(outs, axis=1), ps


def _attn_bwd(qkv, ps, do, tb, s, h, d, scale):
    """do (rows, dim) → dqkv (rows, 3*dim) in qkv.dtype (shared per-head
    algebra: ``attention_small.head_bwd``)."""
    dim = h * d
    dqs, dks, dvs = [], [], []
    for hh in range(h):
        qh, kh, vh = _qkv_head(qkv, hh, d, dim)
        dq, dk, dv = head_bwd(
            qh, kh, vh, do[:, hh * d:(hh + 1) * d], ps[hh], tb, s, scale
        )
        dqs.append(dq)
        dks.append(dk)
        dvs.append(dv)
    return jnp.concatenate(dqs + dks + dvs, axis=1)


# --------------------------------------------------------------- kernels


def _block_fwd_kernel(
    x_ref, g1_ref, bt1_ref, wqkv_ref, bqkv_ref, wo_ref, bo_ref,
    g2_ref, bt2_ref, wup_ref, bup_ref, wdn_ref, bdn_ref, o_ref,
    *, tb, s, h, d, scale, norm_f32,
):
    x = x_ref[...]
    ln1, _, _ = _ln_fwd(x, g1_ref[0], bt1_ref[0], norm_f32)
    qkv = _gemm(ln1, wqkv_ref[...], bqkv_ref[0])
    o, _ = _attn_fwd(qkv, tb, s, h, d, scale)
    r1 = x + _gemm(o, wo_ref[...], bo_ref[0])
    ln2, _, _ = _ln_fwd(r1, g2_ref[0], bt2_ref[0], norm_f32)
    hmid = jax.nn.gelu(_gemm(ln2, wup_ref[...], bup_ref[0]))
    o_ref[...] = r1 + _gemm(hmid, wdn_ref[...], bdn_ref[0])


def _block_bwd_kernel(
    x_ref, dy_ref, g1_ref, bt1_ref, wqkv_ref, bqkv_ref, wo_ref, bo_ref,
    g2_ref, bt2_ref, wup_ref, bup_ref, wdn_ref, bdn_ref,
    dx_ref, dg1_ref, dbt1_ref, dwqkv_ref, dbqkv_ref, dwo_ref, dbo_ref,
    dg2_ref, dbt2_ref, dwup_ref, dbup_ref, dwdn_ref, dbdn_ref,
    *, tb, s, h, d, scale, norm_f32,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        for ref in (
            dg1_ref, dbt1_ref, dwqkv_ref, dbqkv_ref, dwo_ref, dbo_ref,
            dg2_ref, dbt2_ref, dwup_ref, dbup_ref, dwdn_ref, dbdn_ref,
        ):
            ref[...] = jnp.zeros_like(ref)

    x = x_ref[...]
    dy = dy_ref[...]
    g1, bt1 = g1_ref[0], bt1_ref[0]
    g2, bt2 = g2_ref[0], bt2_ref[0]

    # ---- forward recompute (x is the only saved residual)
    ln1, xhat1, inv1 = _ln_fwd(x, g1, bt1, norm_f32)
    qkv = _gemm(ln1, wqkv_ref[...], bqkv_ref[0])
    o, ps = _attn_fwd(qkv, tb, s, h, d, scale)
    r1 = x + _gemm(o, wo_ref[...], bo_ref[0])
    ln2, xhat2, inv2 = _ln_fwd(r1, g2, bt2, norm_f32)
    up = _gemm(ln2, wup_ref[...], bup_ref[0])
    hmid, gelu_vjp = jax.vjp(jax.nn.gelu, up)

    # ---- backward
    dyf = dy.astype(jnp.float32)
    # MLP branch: out = r1 + (hmid @ wdn + bdn)
    dwdn_ref[...] += _acc_T(hmid, dy)
    dbdn_ref[...] += jnp.sum(dyf, axis=0)[None]
    dh = _gemm_T(dy, wdn_ref[...]).astype(x.dtype)
    (dup,) = gelu_vjp(dh)
    dwup_ref[...] += _acc_T(ln2, dup)
    dupf = dup.astype(jnp.float32)
    dbup_ref[...] += jnp.sum(dupf, axis=0)[None]
    dln2 = _gemm_T(dup, wup_ref[...])  # fp32 (rows, d)
    dg2_ref[...] += jnp.sum(dln2 * xhat2, axis=0)[None]
    dbt2_ref[...] += jnp.sum(dln2, axis=0)[None]
    dr1 = dyf + _ln_bwd(dln2, xhat2, inv2, g2)

    # attention branch: r1 = x + (o @ wo + bo)
    dr1c = dr1.astype(x.dtype)
    dwo_ref[...] += _acc_T(o, dr1c)
    dbo_ref[...] += jnp.sum(dr1, axis=0)[None]
    do = _gemm_T(dr1c, wo_ref[...]).astype(x.dtype)
    dqkv = _attn_bwd(qkv, ps, do, tb, s, h, d, scale)
    dwqkv_ref[...] += _acc_T(ln1, dqkv)
    dbqkv_ref[...] += jnp.sum(dqkv.astype(jnp.float32), axis=0)[None]
    dln1 = _gemm_T(dqkv, wqkv_ref[...])  # fp32 (rows, d)
    dg1_ref[...] += jnp.sum(dln1 * xhat1, axis=0)[None]
    dbt1_ref[...] += jnp.sum(dln1, axis=0)[None]
    dx = dr1 + _ln_bwd(dln1, xhat1, inv1, g1)
    dx_ref[...] = dx.astype(dx_ref.dtype)


# ------------------------------------------------------------ pallas_call


def _specs(arrs, row_spec, n_rows_args):
    out = [row_spec] * n_rows_args
    for a in arrs:
        out.append(pl.BlockSpec(a.shape, lambda i, _nd=a.ndim: (0,) * _nd))
    return out


def _params_2d(params):
    """Lift 1-D params to (1, n) so every block's last-two dims span the
    array (the Mosaic block-shape rule)."""
    return [p[None] if p.ndim == 1 else p for p in params]


def _block_call(x2, dy2, params, tb, s, h, d, scale, norm_f32, interpret):
    n, dim = x2.shape
    rows = tb * s
    row_spec = pl.BlockSpec((rows, dim), lambda i: (i, 0))
    p2 = _params_2d(params)
    static = dict(tb=tb, s=s, h=h, d=d, scale=scale, norm_f32=norm_f32)
    if dy2 is None:
        return pl.pallas_call(
            functools.partial(_block_fwd_kernel, **static),
            grid=(n // rows,),
            in_specs=_specs(p2, row_spec, 1),
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct((n, dim), x2.dtype),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
        )(x2, *p2)
    f32 = jnp.float32
    grad_shapes = [jax.ShapeDtypeStruct(p.shape, f32) for p in p2]
    out = pl.pallas_call(
        functools.partial(_block_bwd_kernel, **static),
        grid=(n // rows,),
        in_specs=_specs(p2, row_spec, 2),
        out_specs=[row_spec] + [
            pl.BlockSpec(sh.shape, lambda i, _nd=sh.ndim: (0,) * _nd)
            for sh in grad_shapes
        ],
        out_shape=[jax.ShapeDtypeStruct((n, dim), x2.dtype)] + grad_shapes,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
    )(x2, dy2, *p2)
    dx, *dparams = out
    # un-lift the (1, n) bias/LN gradients back to their param shapes
    dparams = [
        dp[0] if p.ndim == 1 else dp for dp, p in zip(dparams, params)
    ]
    return dx, dparams


# ------------------------------------------------------------- custom VJP


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(13, 20)))
def _block_core(
    x2, g1, bt1, wqkv, bqkv, wo, bo, g2, bt2, wup, bup, wdn, bdn,
    tb, s, h, d, scale, norm_f32, interpret,
):
    return _block_call(
        x2, None, (g1, bt1, wqkv, bqkv, wo, bo, g2, bt2, wup, bup, wdn, bdn),
        tb, s, h, d, scale, norm_f32, interpret,
    )


def _block_core_fwd(
    x2, g1, bt1, wqkv, bqkv, wo, bo, g2, bt2, wup, bup, wdn, bdn,
    tb, s, h, d, scale, norm_f32, interpret,
):
    out = _block_core(
        x2, g1, bt1, wqkv, bqkv, wo, bo, g2, bt2, wup, bup, wdn, bdn,
        tb, s, h, d, scale, norm_f32, interpret,
    )
    return out, (x2, g1, bt1, wqkv, bqkv, wo, bo, g2, bt2, wup, bup, wdn, bdn)


def _block_core_bwd(tb, s, h, d, scale, norm_f32, interpret, res, dy2):
    x2, *params = res
    dx, dparams = _block_call(
        x2, dy2, tuple(params), tb, s, h, d, scale, norm_f32, interpret
    )
    # parameter cotangents must match primal dtypes (fp32 here: the caller
    # passes the flax fp32 params for LN and compute-dtype casts happen
    # inside the kernel chain, mirroring the composed path's autodiff
    # through the .astype boundaries)
    dparams = [
        dp.astype(p.dtype) for dp, p in zip(dparams, params)
    ]
    return (dx, *dparams)


_block_core.defvjp(_block_core_fwd, _block_core_bwd)


# ------------------------------------------------------------- public API


def fused_vit_block(
    x: jnp.ndarray,
    params: dict,
    *,
    heads: int,
    norm_f32: bool = True,
    block_items: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Run one pre-LN transformer block as a single fused kernel.

    ``x``: (B, S, dim) activations in the compute dtype.  ``params``: the
    composed ViTBlock's param subtree (``ln_attn``, ``q_proj``,
    ``k_proj``, ``v_proj``, ``proj``, ``ln_mlp``, ``mlp_up``,
    ``mlp_down``) — fp32 leaves, cast to the compute dtype here exactly
    where the composed path's ``.astype`` boundaries sit, so gradients
    flow back to fp32 through the same casts.
    """
    b, s, dim = x.shape
    if dim % heads:
        raise ValueError(f"dim {dim} not divisible by heads {heads}")
    d = dim // heads
    if s % 8 or d % 8:
        raise ValueError(
            f"fused_vit_block needs S and head dim multiples of 8; got "
            f"S={s}, head_dim={d}"
        )
    cd = x.dtype
    scale = 1.0 / math.sqrt(d)
    tb = pick_block_items(b, s) if block_items is None else block_items
    wqkv = jnp.concatenate(
        [params[k]["kernel"].astype(cd) for k in ("q_proj", "k_proj", "v_proj")],
        axis=1,
    )
    bqkv = jnp.concatenate(
        [params[k]["bias"].astype(cd) for k in ("q_proj", "k_proj", "v_proj")]
    )
    ln1, ln2 = params["ln_attn"], params["ln_mlp"]
    ln_dt = jnp.float32 if norm_f32 else cd
    out = _block_core(
        x.reshape(b * s, dim),
        ln1["scale"].astype(ln_dt), ln1["bias"].astype(ln_dt),
        wqkv, bqkv,
        params["proj"]["kernel"].astype(cd), params["proj"]["bias"].astype(cd),
        ln2["scale"].astype(ln_dt), ln2["bias"].astype(ln_dt),
        params["mlp_up"]["kernel"].astype(cd), params["mlp_up"]["bias"].astype(cd),
        params["mlp_down"]["kernel"].astype(cd), params["mlp_down"]["bias"].astype(cd),
        tb, s, heads, d, scale, norm_f32, interpret,
    )
    return out.reshape(b, s, dim)
