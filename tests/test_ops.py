"""Pallas flash-attention kernel vs the jnp reference (interpret mode).

The kernels are exercised through the Pallas interpreter so the exact
production code paths (fwd + both backward kernels, masking, padding,
causal block-skipping) run in CI on the CPU mesh.  Comparisons run under
``default_matmul_precision("highest")`` — this CPU backend's default
matmul precision is bf16-like, which would drown the parity signal.

On real TPU hardware the same checks hold at bf16 tolerance and run at
their design points in ``tests_tpu/``; the kernel's chip time is read in
the cell ``lfm2_ep8_seq4k_job`` (``attention_roofline_pct``, ``PERF.md``).
"""

import functools
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from distributed_training_comparison_tpu.ops import (
    attention,
    flash_attention,
    mha_reference,
)


def _rand_qkv(seed, sq, skv, d, dtype=jnp.float32, b=2, h=3):
    kq, kk, kv, kdo = jax.random.split(jax.random.key(seed), 4)
    return (
        jax.random.normal(kq, (b, h, sq, d), dtype),
        jax.random.normal(kk, (b, h, skv, d), dtype),
        jax.random.normal(kv, (b, h, skv, d), dtype),
        jax.random.normal(kdo, (b, h, sq, d), dtype),
    )


# fast gate keeps one non-causal + one causal representative; the padded /
# cross-attention variants run in the full suite
@pytest.mark.parametrize(
    "causal,sq,skv,d",
    [
        (False, 256, 256, 64),   # aligned
        pytest.param(False, 200, 200, 48, marks=pytest.mark.slow),   # seq and head-dim padding
        pytest.param(False, 128, 384, 64, marks=pytest.mark.slow),   # cross-attention (kv longer)
        pytest.param(False, 64, 500, 128, marks=pytest.mark.slow),   # both lengths padded, full-width head
        (True, 256, 256, 64),
        pytest.param(True, 200, 200, 48, marks=pytest.mark.slow),
        # multi-tile backward: padded 1024 > the 512 streamed tile, so the
        # causal diagonal gate, lo-based accumulator init, and cross-step
        # scratch accumulation actually execute (single-tile cases leave
        # them dead)
        pytest.param(True, 1024, 1024, 64, marks=pytest.mark.slow),
        pytest.param(True, 1000, 1000, 64, marks=pytest.mark.slow),
        pytest.param(False, 640, 1152, 64, marks=pytest.mark.slow),
    ],
)
def test_flash_matches_reference(causal, sq, skv, d):
    q, k, v, do = _rand_qkv(sq * 7 + d + causal, sq, skv, d)
    with jax.default_matmul_precision("highest"):
        out_f, vjp_f = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=causal, interpret=True),
            q, k, v,
        )
        out_r, vjp_r = jax.vjp(
            lambda q, k, v: mha_reference(q, k, v, causal=causal), q, k, v
        )
        grads_f, grads_r = vjp_f(do), vjp_r(do)

    assert out_f.shape == (q.shape[0], q.shape[1], sq, d)
    assert float(jnp.max(jnp.abs(out_f - out_r))) < 2e-5
    for gf, gr, name in zip(grads_f, grads_r, "qkv"):
        assert float(jnp.max(jnp.abs(gf - gr))) < 5e-4, f"d{name} mismatch"


@pytest.mark.parametrize(
    "causal,sq,skv,d",
    [
        (False, 256, 256, 64),
        # multi-tile causal: diagonal gate + scratch carry across key steps
        pytest.param(True, 1024, 1024, 64, marks=pytest.mark.slow),
        # padded seq + head dim
        pytest.param(False, 200, 200, 48, marks=pytest.mark.slow),
        # cross-attention with kv padding
        pytest.param(False, 640, 1152, 64, marks=pytest.mark.slow),
    ],
)
def test_flash_tiled_forward_matches_reference(monkeypatch, causal, sq, skv, d):
    """The streamed-K/V forward (selected above _FWD_RESIDENT_KV_LIMIT) is
    numerically the same kernel contract as the resident-K/V one; force it
    by zeroing the limit and check outputs + grads against the reference."""
    import importlib

    A = importlib.import_module(
        "distributed_training_comparison_tpu.ops.attention"
    )
    monkeypatch.setattr(A, "_FWD_RESIDENT_KV_LIMIT", 0)
    q, k, v, do = _rand_qkv(sq * 3 + d + causal, sq, skv, d)
    with jax.default_matmul_precision("highest"):
        out_f, vjp_f = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=causal, interpret=True),
            q, k, v,
        )
        out_r, vjp_r = jax.vjp(
            lambda q, k, v: mha_reference(q, k, v, causal=causal), q, k, v
        )
        grads_f, grads_r = vjp_f(do), vjp_r(do)
    assert float(jnp.max(jnp.abs(out_f - out_r))) < 2e-5
    for gf, gr, name in zip(grads_f, grads_r, "qkv"):
        assert float(jnp.max(jnp.abs(gf - gr))) < 5e-4, f"d{name} mismatch"


def test_flash_tiled_forward_fully_masked_tile(monkeypatch):
    """Explicit block_k much larger than the true key length pads past a
    whole 512-wide streamed tile, so a fully-masked stream tile is
    visited: its contribution must be exactly zero and the online-softmax
    scratch must carry through it unchanged."""
    import importlib

    A = importlib.import_module(
        "distributed_training_comparison_tpu.ops.attention"
    )
    monkeypatch.setattr(A, "_FWD_RESIDENT_KV_LIMIT", 0)
    q, k, v, _ = _rand_qkv(7, 256, 300, 64)
    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, block_k=1024, interpret=True)
        base = mha_reference(q, k, v)
    assert float(jnp.max(jnp.abs(out - base))) < 2e-5


def test_flash_non_pow2_padded_length(monkeypatch):
    """Caller-chosen blocks can pad the sequence to a non-multiple of 128
    (block_q=64, sq=150 → padded 192).  The streamed tiles must still
    cover the whole padded length — a non-divisor tile makes the grid's
    floor division silently drop the tail block (rows beyond it would be
    garbage in the fwd output and dq, and tail keys would never
    contribute to dk/dv)."""
    import importlib

    A = importlib.import_module(
        "distributed_training_comparison_tpu.ops.attention"
    )
    monkeypatch.setattr(A, "_FWD_RESIDENT_KV_LIMIT", 0)  # tiled fwd too
    q, k, v, do = _rand_qkv(13, 150, 150, 64)
    with jax.default_matmul_precision("highest"):
        out_f, vjp_f = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, block_q=64, block_k=64, interpret=True
            ),
            q, k, v,
        )
        out_r, vjp_r = jax.vjp(lambda q, k, v: mha_reference(q, k, v), q, k, v)
        grads_f, grads_r = vjp_f(do), vjp_r(do)
    assert float(jnp.max(jnp.abs(out_f - out_r))) < 2e-5
    for gf, gr, name in zip(grads_f, grads_r, "qkv"):
        assert float(jnp.max(jnp.abs(gf - gr))) < 5e-4, f"d{name} mismatch"


def test_flash_streamed_causal_mask_free_interior(monkeypatch):
    """Streamed causal forward at S=4096 (forced via the resident limit):
    with the 2048-row query tile the grid has interior tiles fully below
    the diagonal — the causal mask-free branch of the streamed forward
    (``_mask_split``) — plus straddling and skipped tiles.  All three
    classes must agree with the reference."""
    import importlib

    A = importlib.import_module(
        "distributed_training_comparison_tpu.ops.attention"
    )
    monkeypatch.setattr(A, "_FWD_RESIDENT_KV_LIMIT", 0)
    q, k, v, _ = _rand_qkv(19, 4096, 4096, 64, b=1, h=1)
    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, causal=True, interpret=True)
        base = mha_reference(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - base))) < 2e-5


def test_flash_causal_backward_mask_free_interior():
    """Causal fwd+bwd at S=1024: the backward's (512, 512) stream tiles
    give both dq and dk/dv grids tiles fully below the diagonal — the
    causal mask-free branch of both backward kernels — which smaller
    causal tests (S<=512, single-tile grids) never reach."""
    q, k, v, do = _rand_qkv(23, 1024, 1024, 64, b=1, h=2)
    with jax.default_matmul_precision("highest"):
        out_f, vjp_f = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True),
            q, k, v,
        )
        out_r, vjp_r = jax.vjp(
            lambda q, k, v: mha_reference(q, k, v, causal=True), q, k, v
        )
        grads_f, grads_r = vjp_f(do), vjp_r(do)
    assert float(jnp.max(jnp.abs(out_f - out_r))) < 2e-5
    for gf, gr, name in zip(grads_f, grads_r, "qkv"):
        assert float(jnp.max(jnp.abs(gf - gr))) < 5e-4, f"d{name} mismatch"


def test_flash_causal_key_blocks_past_query_padding():
    """Causal with caller blocks padding K/V far past the padded query
    length (s=129, block_q=64, block_k=1024): the dkv backward grid gets
    key blocks whose first intersecting query block lies beyond the grid
    (lo >= nq), so no compute step visits them — the kernel's i==0
    pre-write of zero output blocks (not stale scratch) is what flushes
    (ADVICE r4).  Gradients on the real rows must match the reference."""
    q, k, v, do = _rand_qkv(17, 129, 129, 64)
    with jax.default_matmul_precision("highest"):
        out_f, vjp_f = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=64, block_k=1024, interpret=True
            ),
            q, k, v,
        )
        out_r, vjp_r = jax.vjp(
            lambda q, k, v: mha_reference(q, k, v, causal=True), q, k, v
        )
        grads_f, grads_r = vjp_f(do), vjp_r(do)
    assert float(jnp.max(jnp.abs(out_f - out_r))) < 2e-5
    for gf, gr, name in zip(grads_f, grads_r, "qkv"):
        assert float(jnp.max(jnp.abs(gf - gr))) < 5e-4, f"d{name} mismatch"


def test_flash_explicit_blocks():
    """Non-default block shapes (incl. block_k spanning the whole padded
    sequence, the measured-fastest TPU config) agree with the default."""
    q, k, v, _ = _rand_qkv(11, 256, 512, 64)
    with jax.default_matmul_precision("highest"):
        base = mha_reference(q, k, v)
        for bq, bk in [(128, 512), (256, 256), (128, 128)]:
            out = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
            assert float(jnp.max(jnp.abs(out - base))) < 2e-5, (bq, bk)


def test_flash_causal_masks_future():
    """Perturbing future keys/values never changes causal output."""
    q, k, v, _ = _rand_qkv(3, 256, 256, 64)
    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, causal=True, interpret=True)
        k2 = k.at[:, :, 200:, :].add(5.0)
        v2 = v.at[:, :, 200:, :].add(-3.0)
        out2 = flash_attention(q, k2, v2, causal=True, interpret=True)
    # rows < 200 attend only to keys ≤ row index < 200 → identical
    assert float(jnp.max(jnp.abs(out[:, :, :200] - out2[:, :, :200]))) == 0.0
    # last rows do see the perturbation
    assert float(jnp.max(jnp.abs(out[:, :, 200:] - out2[:, :, 200:]))) > 1e-3


def test_flash_causal_requires_square():
    q, k, v, _ = _rand_qkv(0, 128, 256, 64)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=True, interpret=True)


def test_attention_dispatcher():
    q, k, v, _ = _rand_qkv(5, 64, 64, 32)
    with jax.default_matmul_precision("highest"):
        # CPU backend → auto resolves to the reference implementation
        out_auto = attention(q, k, v)
        out_ref = attention(q, k, v, impl="reference")
    assert float(jnp.max(jnp.abs(out_auto - out_ref))) == 0.0
    with pytest.raises(ValueError, match="unknown attention impl"):
        attention(q, k, v, impl="nope")
    # near-miss sequence-parallel names must fail fast, not silently route
    for typo in ("ring_attn", "rings", "ulysses2"):
        with pytest.raises(ValueError, match="unknown attention impl"):
            attention(q, k, v, impl=typo)


def test_attention_pallas_off_tpu():
    """Explicit impl='pallas' off-TPU must fail with a clear message, not an
    opaque Mosaic lowering error — unless interpret=True is plumbed through
    (advisor r2)."""
    q, k, v, _ = _rand_qkv(6, 128, 128, 32)
    with pytest.raises(ValueError, match="requires a TPU backend"):
        attention(q, k, v, impl="pallas")
    with jax.default_matmul_precision("highest"):
        out = attention(q, k, v, impl="pallas", interpret=True)
        ref = attention(q, k, v, impl="reference")
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


@pytest.mark.parametrize("causal", [False, True])
def test_flash_lse_and_its_cotangent(causal):
    """return_lse parity AND the dlse backward path through the Pallas
    kernels: a loss that uses BOTH outputs must match reference autodiff —
    this is the path ring attention differentiates through."""
    q, k, v, do = _rand_qkv(21 + causal, 200, 200, 64)

    def loss(attn):
        def f(q, k, v):
            o, lse = attn(q, k, v)
            return (o * do).sum() + (jnp.sin(lse)).sum()  # nonzero dlse
        return f

    flash = loss(
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, interpret=True, return_lse=True
        )
    )
    ref = loss(
        lambda q, k, v: mha_reference(q, k, v, causal=causal, return_lse=True)
    )
    with jax.default_matmul_precision("highest"):
        of, lf = flash_attention(
            q, k, v, causal=causal, interpret=True, return_lse=True
        )
        orr, lr = mha_reference(q, k, v, causal=causal, return_lse=True)
        gf = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    assert lf.shape == (2, 3, 200) and lf.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(lf - lr))) < 1e-5
    assert float(jnp.max(jnp.abs(of - orr))) < 2e-5
    for a, b, name in zip(gf, gr, "qkv"):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-3, f"d{name}"


def _attention_module():
    import importlib

    return importlib.import_module(
        "distributed_training_comparison_tpu.ops.attention"
    )


# The token cell's call at a reduced length: head size 64, four query heads
# a key-value head, causal.  640 is five of the plan's 128-key tiles (512
# and 256 do not divide it), 600 pads to them; 384 / 300 under the caller's
# 128-blocks; ``split`` forces the two-kernel backward (what a call past
# the fused backward's VMEM budget gets), whose skipped tiles name the
# block a visited step holds.
@pytest.mark.parametrize(
    "s,blocks,lse,split",
    [
        (640, None, False, False),
        (600, None, False, False),
        (384, 128, True, False),
        (300, 128, True, False),
        (384, 128, False, True),
        (300, 128, True, True),
    ],
    ids=["tiles5", "tiles5_padded", "lse", "lse_padded", "split", "split_lse_padded"],
)
def test_flash_grouped_heads_causal(monkeypatch, s, blocks, lse, split):
    """Forward, all three gradients and the ``lse`` output with a non-zero
    ``dlse`` cotangent against ``mha_reference`` on repeated heads."""
    A = _attention_module()
    if split:
        monkeypatch.setattr(A, "_FUSED_BWD_RESIDENT_LIMIT", 0)
    b, h, hkv, d = 2, 4, 1, 64
    kq, kk, kv, kdo = jax.random.split(jax.random.key(s + lse), 4)
    q = jax.random.normal(kq, (b, h, s, d))
    k = jax.random.normal(kk, (b, hkv, s, d))
    v = jax.random.normal(kv, (b, hkv, s, d))
    do = jax.random.normal(kdo, (b, h, s, d))
    plan = A.flash_plan(s, s, d, h // hkv, True, q.dtype, blocks, blocks)
    assert plan.head == d, "a head size that divides the lanes is not padded"
    assert plan.fused_bwd != split
    assert -(-s // plan.bwd_block_k) >= 3, "the backward must cross tiles"

    def loss(attn):
        def f(q, k, v):
            o, l = attn(q, k, v)
            return (o * do).sum() + (jnp.sin(l).sum() if lse else 0.0)
        return f

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=blocks, block_k=blocks,
        interpret=True, return_lse=True,
    )
    ref = lambda q, k, v: mha_reference(  # noqa: E731
        q, jnp.repeat(k, h // hkv, 1), jnp.repeat(v, h // hkv, 1),
        causal=True, return_lse=True,
    )
    with jax.default_matmul_precision("highest"):
        (of, lf), (orr, lr) = flash(q, k, v), ref(q, k, v)
        gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    assert float(jnp.max(jnp.abs(of - orr))) < 2e-5
    assert float(jnp.max(jnp.abs(lf - lr))) < 1e-5
    for a, b_, name in zip(gf, gr, "qkv"):
        assert a.shape == b_.shape
        assert float(jnp.max(jnp.abs(a - b_))) < 1e-3, f"d{name}"


def _causal_visits(A, plan, s):
    """Score tiles a causal call of length ``s`` visits under ``plan``:
    ``(forward, backward)``, each a list of (first row, last row, first
    column, tile area), from the bounds the kernels themselves loop by."""
    s_p = -(-s // plan.block_q) * plan.block_q
    fwd, bwd = [], []
    bq, bk = plan.block_q, plan.block_k
    for i in range(s_p // bq):
        for j in range(int(A._causal_nk(i, bq, bk, s_p // bk))):
            fwd.append((i * bq, (i + 1) * bq - 1, j * bk, bq * bk))
    bq, bk = plan.bwd_block_q, plan.bwd_block_k
    for j in range(s_p // bk):
        for i in range(A._causal_first_q(j, bq, bk), s_p // bq):
            bwd.append((i * bq, (i + 1) * bq - 1, j * bk, bq * bk))
    return s_p, fwd, bwd


@pytest.mark.parametrize("s", [4096, 2048, 1536, 1000])
def test_flash_plan_causal_visits_the_lower_triangle_once(s):
    """The plan as a pure function of the call's shapes: at the token
    cell's call no tile strictly above the diagonal is visited, and at
    4,096 keys the visited share of the square is at most 0.57 (it was
    0.75 under 128-row query tiles and 2,048-key blocks)."""
    A = _attention_module()
    plan = A.flash_plan(s, s, 64, 4, True, jnp.bfloat16)
    assert plan.head == 64 and plan.fused_bwd
    s_p, fwd, bwd = _causal_visits(A, plan, s)
    for visits in (fwd, bwd):
        assert all(col <= last for _, last, col, _ in visits), "above the diagonal"
        share = sum(area for *_, area in visits) / s_p**2
        # every tile on or below the diagonal is there
        assert share >= 0.5
        if s == 4096:
            assert share <= 0.57
    # the free loop starts where the mask is not needed any more
    bq, bk = plan.bwd_block_q, plan.bwd_block_k
    for j in range(s_p // bk):
        free = A._causal_free_q(j, bq, bk)
        assert free * bq >= (j + 1) * bk - 1 > (free - 1) * bq


@pytest.mark.parametrize(
    "call,expected",
    [
        # non-causal at head size 128 (vit_long): the tiles it had before
        # the causal plan, under the fused backward
        ((4096, 4096, 128, 1, False, jnp.bfloat16),
         dict(block_q=128, block_k=2048, head=128, fused_bwd=True,
              bwd_block_q=512, bwd_block_k=512)),
        # the streamed forward's length: no whole-sequence residents
        ((16384, 16384, 64, 4, True, jnp.bfloat16), dict(fused_bwd=False)),
        # float32 operands at 4,096 keys: q, dO and dq fit (6 MiB) once the
        # group's accumulators are not beside them — each query head writes
        # its dk / dv (PR 32; split before, when the kernel had no such form)
        ((4096, 4096, 64, 4, True, jnp.float32),
         dict(fused_bwd=True, bwd_group_sum=False, bwd_ring=8)),
        # ... and at 8,192 keys they do not
        ((8192, 8192, 64, 4, True, jnp.float32), dict(fused_bwd=False)),
        # a head size that does not divide the lanes is padded to them
        ((200, 200, 48, 1, True, jnp.float32), dict(head=128, block_q=256)),
        # the token cell's call (LFM2): the plan PR 30 gave it, field for
        # field — the sequence held whole, the group summed in the kernel
        ((4096, 4096, 64, 4, True, jnp.bfloat16),
         dict(block_q=512, block_k=512, head=64, fused_bwd=True,
              bwd_block_q=512, bwd_block_k=512, window=None,
              bwd_ring=8, bwd_group_sum=True)),
        # the AFMoE cell's full layer: 8,192 keys at head size 128 whole
        # (8.0 MiB of q, dO and float32 dq), each query head its own dk / dv
        ((8192, 8192, 128, 8, True, jnp.bfloat16),
         dict(fused_bwd=True, bwd_ring=16, bwd_group_sum=False)),
        # 16,384 keys without a window stay on the two tiled kernels, and
        # so does a non-causal call past the budget (its dq is whole)
        ((16384, 16384, 128, 8, True, jnp.bfloat16), dict(fused_bwd=False)),
        ((8192, 8192, 128, 1, False, jnp.bfloat16), dict(fused_bwd=False)),
    ],
    ids=["vit_long", "s16384", "float32", "float32_s8192", "head48", "lfm2",
         "trinity_full", "s16384_head128", "s8192_non_causal"],
)
def test_flash_plan_other_calls(call, expected):
    A = _attention_module()
    plan = A.flash_plan(*call)
    assert {k: getattr(plan, k) for k in expected} == expected
    # the caller's blocks win over the plan's
    assert A.flash_plan(*call, block_q=64, block_k=64)[:2] == (64, 64)


@pytest.mark.parametrize(
    "s,d,group,window,ring",
    [
        # the AFMoE cell's sliding layer: six of sixteen tiles held
        (8192, 128, 8, 2048, 6),
        # any length fits with a window: the ring follows the band
        (65536, 128, 8, 2048, 6), (16384, 128, 1, 300, 3),
        # head size 64 goes in unpadded, and Mosaic slices no tile out of
        # HBM across 64 lanes: the sequence whole, as without a window
        (4096, 64, 4, 2048, 8),
    ],
    ids=["trinity_sliding", "s65536", "s16384_narrow", "lfm2_with_a_window"],
)
def test_flash_plan_holds_the_rows_a_key_block_reaches(s, d, group, window, ring):
    """With a window the fused backward's residents follow the band, not the
    sequence: ``fused_bwd`` at any length, a ring of the band's tiles and
    one, and the bytes ``_fused_bwd_resident_bytes`` counts inside the one
    limit."""
    A = _attention_module()
    plan = A.flash_plan(s, s, d, group, True, jnp.bfloat16, window=window)
    assert plan.fused_bwd and plan.window == window and plan.bwd_ring == ring
    tile = plan.bwd_block_q
    reached = max(
        min(int(A._band_end_q(j, tile, tile, window)), s // tile) - j
        for j in range(s // tile)
    )
    assert ring == (reached + 1 if d % 128 == 0 else s // tile)
    held = A._fused_bwd_resident_bytes(
        s, s, plan.head, group, jnp.bfloat16, window, tile=tile,
        summed=plan.bwd_group_sum,
    )
    lanes = 128
    assert held == ring * tile * lanes * (2 + 2 + 4) + (
        2 * s * lanes * 4 if plan.bwd_group_sum else 0
    )
    assert held <= A._FUSED_BWD_RESIDENT_LIMIT


def test_flash_plan_fused_residents_fit_the_limit():
    """``flash_plan`` as a property over the calls a model can make: wherever
    it says ``fused_bwd`` the bytes the kernel holds are inside the limit,
    the group is summed in the kernel only where the accumulators fit beside
    them, and a call it leaves to the tiled kernels would not have fitted."""
    A = _attention_module()
    limit = A._FUSED_BWD_RESIDENT_LIMIT
    seen = {True: 0, False: 0}
    for s in (512, 1000, 2048, 4096, 8192, 16384, 32768):
        for d in (64, 128):
            for group in (1, 4, 8):
                for causal, window in ((False, None), (True, None), (True, 2048)):
                    for dtype in (jnp.bfloat16, jnp.float32):
                        plan = A.flash_plan(
                            s, s, d, group, causal, dtype, window=window
                        )
                        s_p = -(-s // plan.block_q) * plan.block_q
                        held = functools.partial(
                            A._fused_bwd_resident_bytes, s_p, s_p, plan.head,
                            group, dtype, plan.window, causal=causal,
                            tile=plan.bwd_block_q,
                        )
                        seen[plan.fused_bwd] += 1
                        if not plan.fused_bwd:
                            assert held(summed=False) > limit
                            assert plan.bwd_ring == 0 and not plan.bwd_group_sum
                            continue
                        assert held(summed=plan.bwd_group_sum) <= limit
                        if group > 1 and not plan.bwd_group_sum:
                            assert held(summed=True) > limit
                        assert 1 <= plan.bwd_ring <= s_p // plan.bwd_block_q
    assert min(seen.values()) > 20, seen


# ------------------------------------------------------- sliding window


def _dense_window_attention(q, k, v, window, layout="bhsd"):
    """Attention under a dense boolean mask ``j <= i and i - j < window``,
    written apart from every mask helper of the op."""
    if layout == "bshd":
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    scores = jnp.where((j <= i) & (i - j < window), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    return out.transpose(0, 2, 1, 3) if layout == "bshd" else out


@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
@pytest.mark.parametrize("window", [1, 7, 40])
def test_window_in_reference_and_composed_matches_a_dense_mask(layout, window):
    """``mha_reference`` and the dispatcher's composed branch (its own VJP)
    against a dense mask: output and all three gradients."""
    full, _ = _composed_case(layout, jnp.float32, sq=48, skv=48, b=2)
    q, k, v, do = full
    dense = lambda q, k, v: _dense_window_attention(q, k, v, window, layout)  # noqa: E731
    options = dict(causal=True, window=window, layout=layout)
    with jax.default_matmul_precision("highest"):
        want, want_g = dense(q, k, v), _grads(dense, q, k, v, do)
        for fn in (mha_reference, attention):
            assert float(jnp.abs(fn(q, k, v, **options) - want).max()) < 2e-6
            for g, w, name in zip(_grads(fn, q, k, v, do, **options), want_g, "qkv"):
                assert float(jnp.abs(g - w).max()) < 1e-5, (fn.__name__, name)


def test_a_window_that_reaches_every_key_is_the_causal_call():
    """``window >= S``: the same program as the plain causal call (the
    dispatcher and ``flash_plan`` drop it), so the same bits."""
    A = _attention_module()
    (q, k, v, do), _ = _composed_case("bshd", jnp.float32, sq=64, skv=64, b=2)
    causal = dict(causal=True, layout="bshd")
    want = attention(q, k, v, **causal)
    for window in (64, 65, 1000):
        assert bool(jnp.all(attention(q, k, v, window=window, **causal) == want))
        assert A.flash_plan(64, 64, 64, 1, True, q.dtype, window=window).window is None
        text = jax.jit(
            lambda q, k, v: attention(q, k, v, window=window, **causal)
        ).lower(q, k, v).compile().as_text()
        assert "/attention/" in text and "attention_window" not in text
    text = jax.jit(
        lambda q, k, v: attention(q, k, v, window=63, **causal)
    ).lower(q, k, v).compile().as_text()
    assert "/attention/attention_window/" in text
    assert A.flash_plan(64, 64, 64, 1, True, q.dtype, window=63).window == 63
    with pytest.raises(ValueError, match="causal"):
        attention(q, k, v, window=8, layout="bshd")
    with pytest.raises(ValueError, match="takes no window"):
        attention(q, k, v, causal=True, window=8, layout="bshd", impl="ring")
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=8, interpret=True)


# The flash kernels with a window, in interpret mode, against the composed
# branch.  640 keys are five 128-tiles: windows of one tile and a half
# (192: not a tile multiple), of exactly two tiles (256), narrower than a
# tile (50), padded keys (600); ``split`` forces the two tiled backward
# kernels (what a call past the fused backward's VMEM budget gets),
# ``streamed`` the tiled forward.  At head size 128 the fused backward holds
# q, dO and dq as a ring of ``ring`` query tiles it fetches itself — the
# most a key block's band reaches and one more — and the cases below make it
# wrap: the cell's own window of 2,048 under 512-tiles at 8,192 keys (six of
# sixteen tiles held), a window narrower than a tile, a length that is no
# tile multiple, and ``lse`` with a non-zero cotangent (ring attention's
# form) against ``mha_reference``.  ``held`` is the VMEM budget in bytes:
# 1.5 MB holds a ring and not the group's dk / dv accumulators beside it, so
# each query head writes its own and the sum is taken outside (the cell's
# form); left alone the group is summed in the kernel.
def _window_case(s, window, d, h, hkv, *, split=False, streamed=False,
                 lse=False, held=None, ring=None, summed=None):
    return dict(s=s, window=window, d=d, h=h, hkv=hkv, split=split,
                streamed=streamed, lse=lse, held=held, ring=ring, summed=summed)


@pytest.mark.parametrize(
    "case",
    [
        pytest.param(_window_case(640, 192, 64, 2, 1), id="w192_fused"),
        pytest.param(_window_case(640, 256, 64, 2, 1, split=True), id="w256_split"),
        pytest.param(
            _window_case(640, 50, 128, 8, 1, split=True, streamed=True),
            id="w50_head128_group8_split_streamed",
        ),
        pytest.param(
            _window_case(600, 130, 64, 4, 2, streamed=True),
            id="w130_padded_fused_streamed",
        ),
        pytest.param(
            _window_case(640, 300, 128, 8, 1, summed=True),
            id="w300_head128_group8_fused",
        ),
        pytest.param(
            _window_case(1024, 300, 64, 1, 1, split=True), id="w300_tile512_split"
        ),
        pytest.param(
            _window_case(8192, 2048, 128, 1, 1, ring=6), id="w2048_tile512_ring_wraps"
        ),
        pytest.param(
            _window_case(1280, 50, 128, 2, 1, ring=3, summed=True),
            id="w50_narrower_than_a_tile_ring",
        ),
        pytest.param(
            _window_case(1100, 300, 128, 8, 1, held=1_500_000, ring=5, summed=False),
            id="w300_padded_head128_group8_ring_per_head_sum",
        ),
        pytest.param(
            _window_case(1100, 200, 128, 2, 1, lse=True, ring=4, summed=True),
            id="w200_padded_ring_lse_cotangent",
        ),
        pytest.param(
            _window_case(640, 192, 64, 4, 1, lse=True, ring=5, summed=True),
            id="w192_head64_group4_summed_lse_cotangent",
        ),
        pytest.param(
            _window_case(640, 192, 64, 4, 1, held=1_000_000, ring=5, summed=False),
            id="w192_head64_group4_per_head_sum",
        ),
    ],
)
def test_flash_window_matches_composed(monkeypatch, case):
    A = _attention_module()
    s, window, d, h, hkv = (case[n] for n in ("s", "window", "d", "h", "hkv"))
    if case["split"]:
        monkeypatch.setattr(A, "_FUSED_BWD_RESIDENT_LIMIT", 0)
    if case["held"] is not None:
        monkeypatch.setattr(A, "_FUSED_BWD_RESIDENT_LIMIT", case["held"])
    if case["streamed"]:
        monkeypatch.setattr(A, "_FWD_RESIDENT_KV_LIMIT", 0)
    kq, kk, kv, kdo = jax.random.split(jax.random.key(s + window), 4)
    q = jax.random.normal(kq, (1, h, s, d))
    k = jax.random.normal(kk, (1, hkv, s, d))
    v = jax.random.normal(kv, (1, hkv, s, d))
    do = jax.random.normal(kdo, (1, h, s, d))
    plan = A.flash_plan(s, s, d, h // hkv, True, q.dtype, window=window)
    assert plan.window == window and plan.fused_bwd != case["split"]
    if case["ring"] is not None:
        tiles = -(-s // plan.bwd_block_q)
        assert plan.bwd_ring == case["ring"] <= tiles
        # at head size 128 the ring is shorter than the sequence and wraps
        assert (plan.bwd_ring < tiles) == (d == 128)
    if case["summed"] is not None:
        assert plan.bwd_group_sum == case["summed"]

    def loss(attn):
        def f(q, k, v):
            o, l = attn(q, k, v)
            return (o * do).sum() + (jnp.sin(l).sum() if case["lse"] else 0.0)
        return f

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=window, interpret=True, return_lse=True
    )
    if case["lse"]:  # the composed branch's own VJP carries no lse cotangent
        want_of = lambda q, k, v: mha_reference(  # noqa: E731
            q, jnp.repeat(k, h // hkv, 1), jnp.repeat(v, h // hkv, 1),
            causal=True, window=window, return_lse=True,
        )
    else:
        want_of = lambda q, k, v: (  # noqa: E731
            attention(q, k, v, causal=True, window=window, impl="reference"), 0.0
        )
    with jax.default_matmul_precision("highest"):
        (got, got_lse), (want, want_lse) = flash(q, k, v), want_of(q, k, v)
        got_g = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        want_g = jax.grad(loss(want_of), argnums=(0, 1, 2))(q, k, v)
    assert float(jnp.abs(got - want).max()) < 2e-5
    if case["lse"]:
        assert float(jnp.abs(got_lse - want_lse).max()) < 1e-5
    for g, w, name in zip(got_g, want_g, "qkv"):
        assert g.shape == w.shape
        assert float(jnp.abs(g - w).max()) < 1e-3, f"d{name}"


@pytest.mark.parametrize(
    "s,window,tile,tiles",
    [(8192, 2048, 512, 70), (8192, None, 512, 136), (8192, 512, 512, 31),
     (8192, 514, 512, 45), (4096, 2048, 512, 30), (640, 50, 128, 9)],
)
def test_flash_plan_visits_only_the_tiles_that_meet_the_band(s, window, tile, tiles):
    """The visited-tile count as a pure function of the call: 70 of the
    causal triangle's 136 tiles at the AFMoE cell's sliding layer; every
    visited tile holds a visible (query, key) pair and every visible pair
    is in a visited tile; the backward's bounds name the same tiles, its
    grids step no further than a band's blocks, and the mask-free range
    holds only tiles that need no mask."""
    A = _attention_module()
    plan = A.flash_plan(s, s, 128, 8, True, jnp.bfloat16, window=window)
    assert (plan.block_q, plan.block_k, plan.bwd_block_q) == (tile,) * 3
    visited = A.band_tiles(s, tile, tile, plan.window)
    assert len(visited) == tiles
    w = s if window is None else window
    meets = {
        (i, j) for i in range(s // tile) for j in range(i + 1)
        # the tile's last column is seen by its first row, or later ones
        if i * tile - ((j + 1) * tile - 1) < w
    }
    assert set(visited) == meets
    if window is None:
        return
    n = s // tile
    by_key = {
        (i, j) for j in range(n)
        for i in range(A._causal_first_q(j, tile, tile),
                       min(int(A._band_end_q(j, tile, tile, w)), n))
    }
    assert by_key == meets
    assert A._band_steps(tile, tile, w, n, n, w - 1) == max(
        sum(1 for (i, j) in meets if i == row) for row in range(n)
    )
    assert A._band_steps(tile, tile, w, n, n, 0) == max(
        sum(1 for (i, j) in meets if j == col) for col in range(n)
    )
    for i in range(n):
        for j in range(int(A._band_free_k(i, tile, tile, w)), i):
            assert (i + 1) * tile - 1 - j * tile < w  # no lower-edge mask
    for j in range(n):
        for i in range(j + 1, min(int(A._band_free_q(j, tile, tile, w)), n)):
            assert (i + 1) * tile - 1 - j * tile < w


def test_attention_dispatcher_grouped_heads():
    """Fewer key-value heads than query heads: every implementation but
    the flash kernels sees them repeated, in both layouts."""
    kq, kk, kv = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(kq, (2, 96, 4, 32))
    k = jax.random.normal(kk, (2, 96, 2, 32))
    v = jax.random.normal(kv, (2, 96, 2, 32))
    rep = lambda x: jnp.repeat(x, 2, axis=2)  # noqa: E731
    t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = attention(q, rep(k), rep(v), causal=True, layout="bshd")
        got = attention(q, k, v, causal=True, layout="bshd")
        got_bhsd = attention(t(q), t(k), t(v), causal=True)
        got_flash = attention(
            q, k, v, causal=True, layout="bshd", impl="pallas", interpret=True
        )
    assert float(jnp.max(jnp.abs(got - want))) == 0.0
    assert float(jnp.max(jnp.abs(t(got_bhsd) - want))) < 2e-5
    assert float(jnp.max(jnp.abs(got_flash - want))) < 2e-5
    with pytest.raises(ValueError, match="query heads over"):
        attention(q, k[:, :, :1].repeat(3, axis=2), v, layout="bshd")


def test_flash_jit_and_grad_compile():
    """The custom_vjp plumbing stays jittable (static meta args hash)."""
    q, k, v, do = _rand_qkv(9, 128, 128, 64)

    @jax.jit
    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, interpret=True)
        return (o * do).sum()

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert all(x.shape == y.shape for x, y in zip(g, (q, k, v)))
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in g)


# ------------------------------------------- composed branch, its own VJP
#
# ``attention(impl="reference")`` (what ``auto`` picks off the TPU and below
# the kernels' windows) differentiates through a custom VJP that keeps the
# probabilities in the compute dtype and no float32 score-sized tensor.
# ``mha_reference`` stays on plain autodiff and is the contract.  One case
# of the branch stays on plain autodiff too: ``return_lse=True`` (ring
# attention's blocks), because ``lse`` is an output with a cotangent of its
# own and the blocks it is asked for are a ring step's, not a model's.


def _composed_case(layout, dtype, sq=256, skv=256, b=4, h=6, d=64, seed=11):
    """Unit-scale float32 q, k, v and an output cotangent, their ``dtype``
    casts, in ``layout``."""
    shape = lambda s: (b, s, h, d) if layout == "bshd" else (b, h, s, d)  # noqa: E731
    keys = jax.random.split(jax.random.key(seed), 4)
    full = [
        jax.random.normal(key, shape(s), jnp.float32)
        for key, s in zip(keys, (sq, skv, skv, sq))
    ]
    return full, [x.astype(dtype) for x in full]


def _grads(fn, q, k, v, do, **options):
    def loss(q, k, v):
        out = fn(q, k, v, **options)
        return jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _composed(q, k, v, **options):
    return attention(q, k, v, impl="reference", **options)


# beyond the square self-attention a ViT block asks for: an explicit scale,
# offset-causal cross-attention, and more queries than keys, where causal
# masks the first rows whole (their scores get no cotangent, as ``where``'s
# rule gives).  ``square`` (256 keys) in ``bshd`` without a mask is where the
# forward takes the score product twice; every other case takes it once
COMPOSED_SHAPES = {
    "square": dict(),
    "scaled": dict(sq=64, skv=64, scale=0.3),
    "more_keys": dict(sq=64, skv=96),
    "more_queries": dict(sq=96, skv=64),
}


@pytest.mark.parametrize("shape", sorted(COMPOSED_SHAPES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
def test_composed_gradients_match_reference(layout, causal, dtype, shape):
    """Against ``jax.grad`` of ``mha_reference`` in float32 at ``highest``:
    float32 to 1e-5 relative, bf16 no further off than 1.25 x what plain
    bf16 autodiff of ``mha_reference`` is."""
    sizes = dict(COMPOSED_SHAPES[shape])
    options = dict(causal=causal, layout=layout)
    if "scale" in sizes:
        options["scale"] = sizes.pop("scale")
    full, cast = _composed_case(layout, jnp.dtype(dtype), **sizes)
    with jax.default_matmul_precision("highest"):
        want = _grads(mha_reference, *full, **options)
        got = _grads(_composed, *cast, **options)
        plain = _grads(mha_reference, *cast, **options)
    for g, p, w, c, name in zip(got, plain, want, cast, "qkv"):
        assert g.dtype == c.dtype and g.shape == c.shape
        if dtype == "float32":
            assert _rel(g, w) < 1e-5, f"d{name}"
        else:
            assert _rel(g, w) <= 1.25 * _rel(p, w), f"d{name}"


def _score_sized(avals, dtype, s=256):
    return [
        a for a in avals
        if a.dtype == dtype and sum(n == s for n in a.shape) >= 2
    ]


@pytest.mark.parametrize(
    "fn,float32,bfloat16",
    [(_composed, 0, 1), (mha_reference, 1, 1)],
    ids=["composed", "plain_autodiff"],
)
def test_composed_residuals_hold_no_float32_scores(fn, float32, bfloat16):
    """What crosses from forward to backward at 2 x 256 x 6 x 64 in bf16:
    the probabilities once, in bf16, and no float32 tensor with two
    sequence axes.  Plain autodiff of ``mha_reference`` keeps both, which
    is what the custom VJP is for (and shows the reading can tell)."""
    from jax._src.ad_checkpoint import saved_residuals

    _, (q, k, v, _) = _composed_case("bshd", jnp.bfloat16, b=2)
    avals = [
        aval for aval, _ in saved_residuals(
            lambda q, k, v: fn(q, k, v, layout="bshd"), q, k, v
        )
    ]
    assert len(_score_sized(avals, jnp.float32)) == float32, avals
    assert len(_score_sized(avals, jnp.bfloat16)) == bfloat16, avals


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
def test_composed_primal_is_the_reference(layout, causal):
    """Not differentiated (validation, serving), the branch is
    ``mha_reference`` bit for bit, jitted or not; differentiated, its
    forward is the same arithmetic."""
    _, (q, k, v, do) = _composed_case(layout, jnp.bfloat16, b=2)
    options = dict(causal=causal, layout=layout)
    want = mha_reference(q, k, v, **options)
    assert bool(jnp.all(_composed(q, k, v, **options) == want))
    jitted = jax.jit(lambda q, k, v: _composed(q, k, v, **options))
    assert bool(jnp.all(
        jitted(q, k, v) == jax.jit(
            lambda q, k, v: mha_reference(q, k, v, **options)
        )(q, k, v)
    ))
    out, _ = jax.vjp(lambda q, k, v: _composed(q, k, v, **options), q, k, v)
    assert bool(jnp.all(out == want))


def test_composed_return_lse_stays_on_plain_autodiff():
    """The one case the custom VJP leaves out: with ``return_lse=True`` the
    branch is ``mha_reference`` itself, and both outputs differentiate."""
    full, _ = _composed_case("bhsd", jnp.float32, sq=64, skv=64, b=2)
    q, k, v, do = full

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v, return_lse=True)
            return jnp.sum(out * do) + jnp.sum(lse)
        return f

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(_composed), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert bool(jnp.all(g == w))


def test_composed_backward_keeps_the_attention_scope():
    """Under ``jax.grad`` the backward's four einsums carry
    ``transpose(jvp(...))`` and the scope ``attention`` in their
    ``op_name``, so the benchmark's ``attention_ms_per_step`` and
    ``bwd_ms_per_step`` keep reading them (its own reader decides)."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmark"))
    from harness import scopes

    _, (q, k, v, do) = _composed_case("bshd", jnp.bfloat16, sq=64, skv=64, b=2)
    grad = jax.jit(lambda q, k, v: _grads(attention, q, k, v, do, layout="bshd"))
    text = grad.lower(q, k, v).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*dot_general)"', text))
    backward = {
        n.rsplit("/", 2)[-2] for n in names
        if scopes.phase_of(n) == "backward" and scopes.under(n, "attention")
    }
    # dO·vᵀ, ds·k, and dsᵀ·q with pᵀ·dO (one equation, two einsums)
    assert backward == {"bqhd,bkhd->bqhk", "bqhk,bkhd->bqhd", "bqhk,bqhd->bkhd"}
    assert all("transpose(jvp(" in n for n in names
               if scopes.phase_of(n) == "backward")
    forward = [n for n in names if scopes.phase_of(n) == "forward"]
    assert forward and all(scopes.under(n, "attention") for n in forward)


def test_vit_train_step_still_reports_composed():
    """The label does not move: a ViT train step's ``compile`` event says
    ``{"attention": "composed"}`` and no other key (the benchmark's cell
    compares that dict by equality)."""
    from distributed_training_comparison_tpu import obs
    from distributed_training_comparison_tpu.models.vit import ViT
    from distributed_training_comparison_tpu.parallel import (
        make_mesh,
        replicated_sharding,
    )
    from distributed_training_comparison_tpu.train import (
        configure_optimizers,
        create_train_state,
        make_train_step,
    )
    from test_train import HP

    mesh = make_mesh(1, backend="ddp")
    model = ViT(depth=2, dim=64, heads=2, num_classes=10, patch=8)
    tx, _ = configure_optimizers(HP, steps_per_epoch=4)
    state = jax.device_put(
        create_train_state(model, jax.random.key(0), tx),
        replicated_sharding(mesh),
    )
    bus = obs.configure(run_id=obs.new_run_id(), persist=True)
    try:
        monitor = obs.CompileMonitor(bus=bus, registry=obs.MetricRegistry())
        step = make_train_step(mesh, monitor=monitor)
        x = jnp.zeros((4, 32, 32, 3), jnp.uint8)
        step(state, x, jnp.zeros((4,), jnp.int32), jax.random.key(1))
        (event,) = [e for e in bus.ring_events() if e["kind"] == "compile"]
    finally:
        obs.reset()
    assert event["payload"]["kernel_paths"] == {"attention": "composed"}
    assert event["payload"].get("tpu_custom_calls", 0) == 0


def test_flash_backward_form_is_on_the_compile_event(monkeypatch):
    """Which backward a flash call site took is a fact of the trace: an
    observed compile of a gradient through two call sites — one inside the
    fused kernel's budget, one (a non-causal call held past it) on the two
    tiled kernels — carries ``flash_backward: {"fused": 1, "tiled": 1}``
    under a key of its own, ``kernel_paths`` keeps its one entry, and a
    forward-only program notes nothing."""
    from distributed_training_comparison_tpu import obs

    A = _attention_module()
    whole = A._fused_bwd_resident_bytes(
        256, 256, 64, 1, jnp.float32, causal=False, tile=128, summed=False
    )
    monkeypatch.setattr(A, "_FUSED_BWD_RESIDENT_LIMIT", whole - 1)
    (q, k, v, _), _ = _composed_case("bhsd", jnp.float32, sq=256, skv=256, b=1, h=2)

    def both(q, k, v, **options):
        sliding = attention(
            q, k, v, causal=True, window=100, impl="pallas", interpret=True
        )
        full = attention(q, k, v, impl="pallas", interpret=True)
        return (sliding + full).sum()

    assert A.flash_plan(256, 256, 64, 1, True, q.dtype, window=100).fused_bwd
    assert not A.flash_plan(256, 256, 64, 1, False, q.dtype).fused_bwd
    bus = obs.configure(run_id=obs.new_run_id(), persist=True)
    try:
        monitor = obs.CompileMonitor(bus=bus, registry=obs.MetricRegistry())
        monitor.instrument(jax.jit(both), "forward_only")(q, k, v)
        monitor.instrument(jax.jit(jax.grad(both, argnums=(0, 1, 2))), "both")(q, k, v)
        forward, grad = [
            e["payload"] for e in bus.ring_events() if e["kind"] == "compile"
        ]
    finally:
        obs.reset()
    assert grad["flash_backward"] == {"fused": 1, "tiled": 1}
    assert grad["kernel_paths"] == {"attention": "pallas-interpret"}
    assert "flash_backward" not in forward
    assert forward["kernel_paths"] == grad["kernel_paths"]


# ------------------------------------------------------- grouped MoE FFN


def _grouped_ffn_reference(xs, w1, b1, w2, b2, starts, cap):
    """Per-group dense reference for ops/moe_gmm.py: rows
    [starts[e], starts[e] + min(count_e, cap)) go through expert e's MLP
    with the kernel's exact cast discipline; everything else is zero."""
    n, d = xs.shape
    ys = jnp.zeros_like(xs)
    for e in range(w1.shape[0]):
        s, nxt = int(starts[e]), int(starts[e + 1])
        end = s + min(nxt - s, cap)
        if end <= s:
            continue
        h = jnp.dot(xs[s:end], w1[e], preferred_element_type=jnp.float32)
        h = jax.nn.gelu(h.astype(xs.dtype) + b1[e])
        o = jnp.dot(h, w2[e], preferred_element_type=jnp.float32)
        ys = ys.at[s:end].set(o.astype(xs.dtype) + b2[e])
    return ys


def test_grouped_ffn_matches_reference():
    """Ragged groups with an empty group at each end, a group spanning a
    tile boundary, and one past capacity: outputs and all gradients match
    the per-group dense reference (fp32, interpret mode)."""
    from distributed_training_comparison_tpu.ops.moe_gmm import grouped_ffn

    ne, d, hidden, n, cap = 4, 16, 64, 100, 40
    k = jax.random.key
    xs = jax.random.normal(k(0), (n, d))
    w1 = jax.random.normal(k(1), (ne, d, hidden)) * 0.1
    b1 = jax.random.normal(k(2), (ne, hidden)) * 0.1
    w2 = jax.random.normal(k(3), (ne, hidden, d)) * 0.1
    b2 = jax.random.normal(k(4), (ne, d)) * 0.1
    # group 0 empty; group 1 spans the 64-row tile boundary; group 2
    # overflows cap=40 by 10 rows; group 3 empty (starts[3] == n)
    starts = jnp.asarray([0, 0, 50, 100, 100], jnp.int32)

    run = lambda f: f(xs, w1, b1, w2, b2, starts, cap)
    ref = run(_grouped_ffn_reference)
    got = run(
        lambda *a: grouped_ffn(*a[:5], a[5], a[6], block_rows=64, interpret=True)
    )
    assert float(jnp.max(jnp.abs(ref - got))) < 1e-6
    # dropped rows (past capacity) and empty groups produce exactly zero
    assert float(jnp.abs(got[90:]).max()) == 0.0

    def loss(f, *diff):
        return jnp.sum(f(*diff, starts, cap) ** 2)

    g_ref = jax.grad(
        lambda *a: loss(_grouped_ffn_reference, *a), argnums=(0, 1, 2, 3, 4)
    )(xs, w1, b1, w2, b2)
    g_got = jax.grad(
        lambda *a: loss(
            lambda *b: grouped_ffn(*b[:5], b[5], b[6], block_rows=64, interpret=True),
            *a,
        ),
        argnums=(0, 1, 2, 3, 4),
    )(xs, w1, b1, w2, b2)
    for a, b, name in zip(g_ref, g_got, ("xs", "w1", "b1", "w2", "b2")):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-5, f"d{name}"


def test_grouped_ffn_jit_single_tile():
    """n smaller than one tile (the padding path) under jit."""
    from distributed_training_comparison_tpu.ops.moe_gmm import grouped_ffn

    ne, d, hidden, n = 2, 8, 32, 20
    k = jax.random.key
    xs = jax.random.normal(k(0), (n, d))
    w1 = jax.random.normal(k(1), (ne, d, hidden)) * 0.1
    b1 = jnp.zeros((ne, hidden))
    w2 = jax.random.normal(k(2), (ne, hidden, d)) * 0.1
    b2 = jnp.zeros((ne, d))
    starts = jnp.asarray([0, 12, 20], jnp.int32)

    @jax.jit
    def f(xs):
        return grouped_ffn(xs, w1, b1, w2, b2, starts, 16, interpret=True)

    ys = f(xs)
    ref = _grouped_ffn_reference(xs, w1, b1, w2, b2, starts, 16)
    assert float(jnp.max(jnp.abs(ys - ref))) < 1e-6


# ------------------------------------------------- the gated delta rule's scan

import numpy as np  # noqa: E402

from distributed_training_comparison_tpu.ops import gated_delta  # noqa: E402
from distributed_training_comparison_tpu.ops.gated_delta import (  # noqa: E402
    _unit_lower_inverse,
    gated_delta_plan,
    gated_delta_rule,
    gated_delta_rule_sequential,
)

# log-decay a token by how fast a head forgets: as the model starts (``A`` up
# to 16: a head keeps e^-20 of its state), near 0 everywhere, near 1 everywhere
DECAYS = {"as_initialised": None, "near_0": 20.0, "near_1": 1e-3}
# the two paths of ``gated_delta_rule``: the composed form at the tiny widths
# (80 tokens: five chunks of 16, or one and a quarter of 64, padded), and the
# Pallas kernel pair through the interpreter at the smallest sizes it takes,
# a head size of one lane tile and a length of whole chunks (256 tokens: two
# grid steps of eight chunks of 16, or one of four chunks of 64)
PATHS = {
    "composed": dict(sizes=dict(), options={}),
    "pallas": dict(
        sizes=dict(b=1, s=256, dk=128, dv=128), options=dict(interpret=True)
    ),
}


def _delta_inputs(decay, b=2, s=80, hk=2, hv=4, dk=16, dv=24, alike=False):
    keys = jax.random.split(jax.random.key(35), 6)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)  # noqa: E731
    k = jax.random.normal(keys[1], (b, s, hk, dk))
    if alike:  # every token the same key but for a little noise
        k = k[:, :1] + 0.05 * k
    q = unit(jax.random.normal(keys[0], (b, s, hk, dk))) * dk ** -0.5
    v = jax.random.normal(keys[2], (b, s, hv, dv))
    softplus = jax.nn.softplus(jax.random.normal(keys[3], (b, s, hv)) + 1.0)
    rate = jnp.linspace(0.05, 16.0, hv) if DECAYS[decay] is None else DECAYS[decay]
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, hv)))
    cot = jax.random.normal(keys[5], (b, s, hv, dv))
    return (q, unit(k), v, -rate * softplus, beta), cot


def _delta_grads(f, x, cot):
    return jax.grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) * cot), argnums=(0, 1, 2, 3, 4)
    )(*x)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_gated_delta_rule_is_the_token_by_token_recurrence(chunk, decay, path):
    """Output and all five gradients, in float32; two value heads a key
    head.  Both paths (``PATHS``) against the one recurrence."""
    x, cot = _delta_inputs(decay, **PATHS[path]["sizes"])
    rule = functools.partial(gated_delta_rule, chunk=chunk, **PATHS[path]["options"])
    o = rule(*x)
    want = gated_delta_rule_sequential(*x)
    assert o.shape == want.shape == x[2].shape
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(o, want, rtol=1e-4, atol=2e-6 * scale)
    got = _delta_grads(rule, x, cot)
    refs = _delta_grads(gated_delta_rule_sequential, x, cot)
    for g, r, name in zip(got, refs, ("q", "k", "v", "g", "beta")):
        top = float(jnp.abs(r).max())
        assert top > 0 or decay == "near_0", name
        np.testing.assert_allclose(g, r, rtol=2e-3, atol=2e-5 * top, err_msg=name)
    if decay == "near_0":  # where nothing survives a token, o_t = beta (k.q) v
        q, k, v, g, beta = x
        alone = beta[..., None] * jnp.sum(
            jnp.repeat(q * k, 2, axis=2), -1, keepdims=True
        ) * v
        gone = rule(q, k, v, jnp.full_like(g, -100.0), beta)
        np.testing.assert_allclose(gone, alone, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gated_delta_rule_with_keys_alike_and_no_decay(path, dtype):
    """The triangular system at its worst: every key of a chunk nearly the
    same and nothing forgotten, where a series in powers of ``L`` cancels
    binomially; substitution does not.  In bf16 (the cell's operands: the
    kernels' solve is then float32 tiles made of bf16 keys) against the
    recurrence on the same rounded operands, output and five gradients in
    relative l2: what rounds is ``T`` on use and the products' operands
    (measured 0.7-1.9 % in the output, up to 4.7 % in dg, either path)."""
    x, cot = _delta_inputs("near_1", alike=True, **PATHS[path]["sizes"])
    options = PATHS[path]["options"]
    if dtype == "bfloat16":
        low = tuple(a.astype(jnp.bfloat16) for a in x[:3]) + x[3:]
        x = tuple(a.astype(jnp.float32) for a in low)
        want = gated_delta_rule_sequential(*x)
        refs = _delta_grads(gated_delta_rule_sequential, x, cot)
        rel = lambda a, b: float(  # noqa: E731
            jnp.linalg.norm((a.astype(jnp.float32) - b).ravel()) / jnp.linalg.norm(b.ravel())
        )
        for chunk in (16, 64):
            rule = functools.partial(gated_delta_rule, chunk=chunk, **options)
            assert rel(rule(*low), want) < 0.03, chunk
            for a, r, name in zip(_delta_grads(rule, low, cot), refs, "qkvgb"):
                assert rel(a, r) < 0.08, (chunk, name)
        return
    want = gated_delta_rule_sequential(*x)
    for chunk in (16, 64):
        np.testing.assert_allclose(
            gated_delta_rule(*x, chunk=chunk, **options), want,
            rtol=1e-3, atol=1e-5 * float(jnp.abs(want).max()),
        )
    m = jnp.tril(jnp.ones((64, 64)), -1)  # L of identical keys, beta = 1
    inverse = _unit_lower_inverse(m)
    np.testing.assert_allclose(
        inverse @ (jnp.eye(64) + m), jnp.eye(64), atol=1e-5
    )
    # (I + L)^-1 is then I minus the first subdiagonal: entries of size one
    assert float(jnp.abs(inverse).max()) == pytest.approx(1.0, abs=1e-5)


@pytest.mark.parametrize("path", PATHS)
def test_gated_delta_rule_in_bf16_and_its_bad_calls(path):
    """bf16 operands, float32 decays and state: output and the five
    gradients close to the float32 recurrence's (relative l2, the limits of
    ``tests_tpu``'s run at the cell's shape), in the operands' dtype; the
    recurrence in blocks is the recurrence; a value head count the key heads
    do not divide is refused."""
    options = PATHS[path]["options"]
    x, cot = _delta_inputs("as_initialised", **PATHS[path]["sizes"])
    q, k, v, g, beta = x
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v)) + (g, beta)
    rule = functools.partial(gated_delta_rule, chunk=16, **options)
    o = rule(*low)
    want = gated_delta_rule_sequential(*x)
    assert o.dtype == jnp.bfloat16
    rel = lambda a, b: float(  # noqa: E731
        jnp.linalg.norm((a.astype(jnp.float32) - b).ravel()) / jnp.linalg.norm(b.ravel())
    )
    assert rel(o, want) < 0.02
    got = _delta_grads(rule, low, cot)
    refs = _delta_grads(gated_delta_rule_sequential, x, cot)
    for a, r, name in zip(got, refs, ("q", "k", "v", "g", "beta")):
        assert a.dtype == (g.dtype if name in ("g", "beta") else jnp.bfloat16), name
        assert rel(a, r) < 0.05, name
    np.testing.assert_array_equal(gated_delta_rule_sequential(*x, block=16), want)
    with pytest.raises(ValueError, match="value heads"):
        gated_delta_rule(q, k, v[:, :, :3], g[..., :3], beta[..., :3], **options)
    with pytest.raises(ValueError, match="whole blocks"):
        gated_delta_rule_sequential(*x, block=48)


def _noted_path(fn, *x, key="gated_delta"):
    """``fn(*x)`` under an observed compile, and the ``key`` path its trace
    noted on the ``compile`` event."""
    from distributed_training_comparison_tpu import obs

    bus = obs.configure(run_id=obs.new_run_id(), persist=True)
    try:
        monitor = obs.CompileMonitor(bus=bus, registry=obs.MetricRegistry())
        out = monitor.instrument(jax.jit(fn), "rule")(*x)
        (event,) = [e["payload"] for e in bus.ring_events() if e["kind"] == "compile"]
    finally:
        obs.reset()
    return out, event["kernel_paths"][key]


# calls the kernel pair cannot take: (sizes, chunk, interpret)
GDN_LEFT_TO_THE_COMPOSED_FORM = {
    "cpu_without_interpret": (dict(b=1, s=128, dk=128, dv=128), 64, False),
    "head_size_64": (dict(b=1, s=128, dk=64, dv=128), 64, True),
    "value_head_size_24": (dict(b=1, s=128, dk=128, dv=24), 64, True),
    "length_of_no_whole_chunks": (dict(b=1, s=80, dk=128, dv=128), 64, True),
    "length_of_no_whole_steps": (dict(b=1, s=144, dk=128, dv=128), 16, True),
    "chunk_48": (dict(b=1, s=96, dk=128, dv=128), 48, True),
}


@pytest.mark.parametrize("case", GDN_LEFT_TO_THE_COMPOSED_FORM)
def test_a_call_the_gated_delta_kernels_cannot_take_is_the_composed_form(case):
    """Such a call notes ``composed`` on the compile event and is, bit for
    bit, what ``_chunked`` gives (the only path before the kernels)."""
    sizes, chunk, interpret = GDN_LEFT_TO_THE_COMPOSED_FORM[case]
    x, _ = _delta_inputs("as_initialised", **sizes)
    rule = functools.partial(gated_delta_rule, chunk=chunk, interpret=interpret)
    o, noted = _noted_path(rule, *x)
    assert noted == "composed"
    composed = jax.jit(lambda *a: gated_delta._chunked(*a, chunk))(*x)
    np.testing.assert_array_equal(o, composed)


GDN_PLANS = {
    # the cell's call: 128 chunks, eight a grid step; float32 operands too
    "cell": (("tpu", jnp.bfloat16, 128, 128, 2, 8192, 64), 8),
    "float32": (("tpu", jnp.float32, 128, 128, 2, 8192, 64), 8),
    # up to eight chunks are one grid step
    "one_chunk": (("tpu", jnp.bfloat16, 128, 128, 1, 64, 64), 1),
    "five_chunks": (("tpu", jnp.bfloat16, 128, 256, 2, 80, 16), 5),
    # what the composed form keeps: no TPU, a head size or chunk that is no
    # whole tile, another dtype, a length that would need padding (to whole
    # chunks, or past one grid step to whole steps), a group whose states do
    # not fit
    "cpu": (("cpu", jnp.bfloat16, 128, 128, 2, 8192, 64), None),
    "head_64": (("tpu", jnp.bfloat16, 64, 128, 2, 8192, 64), None),
    "value_head_24": (("tpu", jnp.float32, 128, 24, 2, 80, 16), None),
    "chunk_48": (("tpu", jnp.bfloat16, 128, 128, 2, 8192, 48), None),
    "float16": (("tpu", jnp.float16, 128, 128, 2, 8192, 64), None),
    "padded_chunk": (("tpu", jnp.bfloat16, 128, 128, 2, 8200, 64), None),
    "padded_step": (("tpu", jnp.bfloat16, 128, 128, 2, 9 * 64, 64), None),
    "group_64": (("tpu", jnp.bfloat16, 128, 128, 64, 8192, 64), None),
}


@pytest.mark.parametrize("case", GDN_PLANS)
def test_gated_delta_plan_takes_the_kernel_where_it_can(case):
    """Which path a call takes is a pure function of what it shows: backend,
    dtype, head sizes, group, length, chunk."""
    call, step_chunks = GDN_PLANS[case]
    assert gated_delta_plan(*call) == step_chunks


def test_the_gated_delta_kernels_note_their_path():
    """Through the interpreter the kernel pair notes ``pallas-interpret``
    (on a TPU ``pallas``, which the cell's ``expect`` lists), at the
    smallest call it takes: one chunk, one key head."""
    x, _ = _delta_inputs("as_initialised", b=1, s=64, hk=1, hv=2, dk=128, dv=128)
    rule = functools.partial(gated_delta_rule, chunk=64, interpret=True)
    o, noted = _noted_path(rule, *x)
    assert noted == "pallas-interpret"
    np.testing.assert_allclose(
        o, gated_delta_rule_sequential(*x), rtol=1e-4, atol=1e-5
    )


# ------------------------------------- the DeltaNet mixer's pointwise stages

from distributed_training_comparison_tpu.ops import gdn_pointwise  # noqa: E402
from distributed_training_comparison_tpu.ops.gdn_pointwise import (  # noqa: E402
    gated_rms_norm,
    gdn_pointwise_plan,
    short_conv_l2norm,
)

# the smallest mixer the fused passes take whole: one key head and two value
# heads of one lane tile, two token tiles of 512
MIXER = dict(key_heads=1, value_heads=2, key_dim=128, value_dim=128)
MIXER_TOKENS = 1024


def _mixer_inputs(dtype, s=MIXER_TOKENS):
    """``qkvz``, the taps, ``norm_scale``, a scan's output and a cotangent
    for each result, the activations in ``dtype``."""
    hk, hv, d = MIXER["key_heads"], MIXER["value_heads"], MIXER["key_dim"]
    keys = jax.random.split(jax.random.key(39), 8)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)  # noqa: E731
    return dict(
        qkvz=normal(keys[0], 1, s, 2 * (hk + hv) * d).astype(dtype),
        taps=jax.random.uniform(keys[1], ((2 * hk + hv) * d, 4), jnp.float32, -0.5, 0.5),
        scale=1.0 + 0.1 * normal(keys[2], d),
        o=normal(keys[3], 1, s, hv, d).astype(dtype),
        d_qkv=(normal(keys[4], 1, s, hk, d), normal(keys[5], 1, s, hk, d),
               normal(keys[6], 1, s, hv, d)),
        d_y=normal(keys[7], 1, s, hv * d),
    )


def _conv_stage(x, qkvz, taps, **options):
    """q, k, v and the gradients of ``sum(result * cotangent)``."""
    def loss(qkvz, taps):
        out = short_conv_l2norm(qkvz, taps, **MIXER, **options)
        return sum(
            jnp.sum(o.astype(jnp.float32) * c) for o, c in zip(out, x["d_qkv"])
        ), out

    (_, out), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(qkvz, taps)
    return dict(zip(("q", "k", "v", "d_qkvz", "d_conv_kernel"), (*out, *grads)))


def _gate_stage(x, o, qkvz, scale, **options):
    def loss(o, qkvz, scale):
        y = gated_rms_norm(
            o, qkvz, scale, key_dim=MIXER["key_dim"], eps=1e-6, **options
        )
        return jnp.sum(y.astype(jnp.float32) * x["d_y"]), y

    (_, y), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(o, qkvz, scale)
    return dict(zip(("y", "d_o", "d_qkvz", "d_norm_scale"), (y, *grads)))


def _error(got, want):
    """Relative l2 of every result against ``want``'s."""
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    return {
        n: float(np.linalg.norm(f32(got[n]) - f32(want[n])) / np.linalg.norm(f32(want[n])))
        for n in want
    }


def _stages(x, dtype, **options):
    qkvz, o = x["qkvz"].astype(dtype), x["o"].astype(dtype)
    return {
        **{f"conv/{n}": v for n, v in _conv_stage(x, qkvz, x["taps"], **options).items()},
        **{f"gate/{n}": v for n, v in _gate_stage(x, o, qkvz, x["scale"], **options).items()},
    }


@pytest.fixture(scope="module")
def mixer_stages():
    """Both kernel pairs (through the interpreter) and the composed stages on
    the same bf16-representable inputs, in float32 and in bf16."""
    x = _mixer_inputs(jnp.bfloat16)
    return {
        (form, dtype): _stages(x, dtype, **options)
        for form, options in (("fused", dict(interpret=True)), ("composed", {}))
        for dtype in (jnp.float32, jnp.bfloat16)
    }


MIXER_RESULTS = [
    "conv/q", "conv/k", "conv/v", "conv/d_qkvz", "conv/d_conv_kernel",
    "gate/y", "gate/d_o", "gate/d_qkvz", "gate/d_norm_scale",
]


@pytest.mark.parametrize("result", MIXER_RESULTS)
def test_fused_pointwise_stages_are_the_composed_stages(mixer_stages, result):
    """Outputs and every gradient (``qkvz``, ``conv_kernel``, ``norm_scale``,
    the scan's ``o``) of both kernel pairs against the composed stages: in
    float32 to 1e-5, in bf16 no further from the float32 result than the
    composed form's own bf16 is (it rounds the convolution before the norms;
    the kernels round once)."""
    exact = mixer_stages["composed", jnp.float32]
    same = _error(mixer_stages["fused", jnp.float32], exact)[result]
    assert same < 1e-5, same
    fused = _error(mixer_stages["fused", jnp.bfloat16], exact)[result]
    composed = _error(mixer_stages["composed", jnp.bfloat16], exact)[result]
    assert fused <= 1.05 * composed + 1e-6, (fused, composed)
    assert mixer_stages["fused", jnp.bfloat16][result].dtype == (
        jnp.float32 if result.endswith(("conv_kernel", "norm_scale")) else jnp.bfloat16
    )


@pytest.mark.parametrize("token", [0, 509, 511, 512, 1023])
def test_an_impulse_meets_the_halo_and_the_zero_history(token):
    """One token set, every other zero: its three successors see it across
    the token tiles' edge (511 | 512) through the halo block, and nothing
    stands before token 0; one token's cotangent reaches its three
    predecessors the other way (the rows a tile owes the one before)."""
    x = _mixer_inputs(jnp.float32)
    at = jnp.arange(MIXER_TOKENS)[None, :, None] == token
    qkvz = jnp.where(at, x["qkvz"], 0.0)
    x["d_qkv"] = tuple(jnp.where(at[..., None], c, 0.0) for c in x["d_qkv"])
    fused = _conv_stage(x, qkvz, x["taps"], interpret=True)
    composed = _conv_stage(x, qkvz, x["taps"])
    for name in composed:
        np.testing.assert_allclose(
            fused[name], composed[name], rtol=1e-5, atol=1e-6, err_msg=name
        )
    reached = np.flatnonzero(np.abs(np.asarray(fused["v"])).sum(axis=(0, 2, 3)))
    assert reached.tolist() == list(range(token, min(token + 4, MIXER_TOKENS)))
    owed = np.flatnonzero(np.abs(np.asarray(fused["d_qkvz"])).sum(axis=(0, 2)))
    assert owed.tolist() == list(range(max(token - 3, 0), token + 1))


POINTWISE_PLANS = {
    # the cell's call, eight-thousand tokens in tiles of 512; float32 too
    "cell": (("tpu", jnp.bfloat16, 128, 128, 8192), 512),
    "float32": (("tpu", jnp.float32, 128, 128, 8192), 512),
    "two_tiles": (("tpu", jnp.bfloat16, 128, 128, 1024), 512),
    # up to 512 tokens are one tile of whole loop steps
    "one_short_tile": (("tpu", jnp.bfloat16, 128, 128, 128), 128),
    "head_size_256": (("tpu", jnp.bfloat16, 256, 256, 8192), 512),
    # what the composed form keeps: no TPU, head sizes of no whole lane tile
    # (qwen3_next_tiny's 16 and 24) or apart, another dtype, a length that
    # would need padding
    "cpu": (("cpu", jnp.bfloat16, 128, 128, 8192), None),
    "tiny_heads": (("tpu", jnp.bfloat16, 16, 24, 64), None),
    "head_size_64": (("tpu", jnp.bfloat16, 64, 64, 8192), None),
    "head_sizes_apart": (("tpu", jnp.bfloat16, 128, 256, 8192), None),
    "float16": (("tpu", jnp.float16, 128, 128, 8192), None),
    "no_whole_tiles": (("tpu", jnp.bfloat16, 128, 128, 8200), None),
    "a_tile_and_a_half": (("tpu", jnp.bfloat16, 128, 128, 768), None),
    "no_whole_loop_steps": (("tpu", jnp.bfloat16, 128, 128, 96), None),
}


@pytest.mark.parametrize("case", POINTWISE_PLANS)
def test_gdn_pointwise_plan_takes_the_kernels_where_it_can(case):
    """Which form a mixer's pointwise stages take is a pure function of
    what the call shows: backend, dtype, head sizes, length."""
    call, tile = POINTWISE_PLANS[case]
    assert gdn_pointwise_plan(*call) == tile


def test_sections_no_one_grid_serves_take_a_call_each():
    """Three value heads on one key head: v's range starts at column 256,
    no multiple of its 384 lanes, so q, k and v are a kernel call each (a
    head a grid step) — and still the composed stage."""
    sections = gdn_pointwise._sections(1, 3, 128)
    assert gdn_pointwise._calls(sections) == tuple((sec,) for sec in sections)
    assert gdn_pointwise._calls(gdn_pointwise._sections(1, 2, 128)) == (
        gdn_pointwise._sections(1, 2, 128),
    )
    assert gdn_pointwise._channel_tiles(gdn_pointwise._sections(16, 32, 128)) == 16
    heads = dict(key_heads=1, value_heads=3, key_dim=128, value_dim=128)
    keys = jax.random.split(jax.random.key(40), 5)
    qkvz = jax.random.normal(keys[0], (1, 128, 8 * 128), jnp.float32)
    taps = jax.random.uniform(keys[1], (5 * 128, 4), jnp.float32, -0.5, 0.5)
    cots = [
        jax.random.normal(k, (1, 128, h, 128), jnp.float32)
        for k, h in zip(keys[2:], (1, 1, 3))
    ]

    def stage(**options):
        def loss(qkvz, taps):
            out = short_conv_l2norm(qkvz, taps, **heads, **options)
            return sum(jnp.sum(o * c) for o, c in zip(out, cots)), out

        (_, out), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(qkvz, taps)
        return (*out, *grads)

    for got, want in zip(stage(interpret=True), stage()):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_a_cpu_call_without_interpret_is_the_composed_form_bit_for_bit():
    x = _mixer_inputs(jnp.bfloat16, s=128)
    q, k, v = jax.jit(functools.partial(short_conv_l2norm, **MIXER))(x["qkvz"], x["taps"])
    want = jax.jit(lambda a, w: gdn_pointwise._composed_conv(a, w, 1, 2, 128, 128))(
        x["qkvz"], x["taps"]
    )
    for got, ref in zip((q, k, v), want):
        np.testing.assert_array_equal(got, ref)
    y = jax.jit(functools.partial(gated_rms_norm, key_dim=128, eps=1e-6))(
        x["o"], x["qkvz"], x["scale"]
    )
    np.testing.assert_array_equal(
        y, jax.jit(functools.partial(gdn_pointwise._composed_gate_norm, eps=1e-6))(
            x["o"], x["qkvz"], x["scale"]
        ),
    )


def test_the_mixers_form_is_on_the_compile_event_under_its_own_key():
    """An observed compile of two mixer call sites — one the kernels take
    (through the interpreter), one left to the composed form (a CPU call
    without it) — carries ``gdn_pointwise: {"fused": 1, "composed": 1}``
    under a key of its own: ``kernel_paths``, which a cell's ``expect`` pins
    key for key, gains nothing.  The gated norm counts no second site."""
    from distributed_training_comparison_tpu import obs

    x = _mixer_inputs(jnp.bfloat16, s=128)

    def both(qkvz, taps, o, scale):
        fused = short_conv_l2norm(qkvz, taps, **MIXER, interpret=True)
        composed = short_conv_l2norm(qkvz, taps, **MIXER)
        y = gated_rms_norm(o, qkvz, scale, key_dim=128, eps=1e-6, interpret=True)
        return fused, composed, y

    bus = obs.configure(run_id=obs.new_run_id(), persist=True)
    try:
        monitor = obs.CompileMonitor(bus=bus, registry=obs.MetricRegistry())
        monitor.instrument(jax.jit(both), "mixers")(
            x["qkvz"], x["taps"], x["o"], x["scale"]
        )
        monitor.instrument(jax.jit(lambda a: a + 1), "no_mixer")(x["scale"])
        mixers, other = [
            e["payload"] for e in bus.ring_events() if e["kind"] == "compile"
        ]
    finally:
        obs.reset()
    assert mixers["gdn_pointwise"] == {"fused": 1, "composed": 1}
    assert "kernel_paths" not in mixers and "flash_backward" not in mixers
    assert "gdn_pointwise" not in other


# ------------------------------------------------- the state-space (SSD) scan

from distributed_training_comparison_tpu.ops import ssd  # noqa: E402
from distributed_training_comparison_tpu.ops.ssd import (  # noqa: E402
    ssd_plan,
    ssd_scan,
    ssd_scan_sequential,
)

# the two paths of ``ssd_scan``, as (sizes, chunk, options): the composed
# form at the tiny widths (80 tokens: five chunks of 16, or one and a quarter
# of 64, padded), and the Pallas kernel pair through the interpreter at the
# smallest sizes it takes — a state of one lane tile, a group's two heads of
# 64 one lane tile, two groups, two batch rows — at two grid steps (128
# tokens: eight chunks of 16, four a step) and at one (two chunks of 128; one
# batch row, one head of 128 a group)
_SSD_KERNEL_SIZES = dict(b=2, s=128, h=4, p=64, g=2, n=128)
SSD_PATHS = {
    "composed-chunk_16": (dict(), 16, {}),
    "composed-chunk_64": (dict(), 64, {}),
    "pallas-two_steps": (_SSD_KERNEL_SIZES, 16, dict(interpret=True)),
    "pallas-one_step": (
        dict(b=1, s=256, h=2, p=128, g=2, n=128), 128, dict(interpret=True)
    ),
}


def _ssd_inputs(b=2, s=80, h=6, p=8, g=2, n=16, rate=None, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(40), 7)
    x = jax.random.normal(keys[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h)) - 1.0)
    A = -(jnp.linspace(0.05, 16.0, h) if rate is None else jnp.full((h,), rate))
    B = jax.random.normal(keys[2], (b, s, g, n), dtype)
    C = jax.random.normal(keys[3], (b, s, g, n), dtype)
    D = 1.0 + 0.3 * jax.random.normal(keys[4], (h,))
    return (x, dt, A, B, C, D), jax.random.normal(keys[5], (b, s, h, p))


def _ssd_grads(f, x, cot):
    return jax.grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) * cot), argnums=tuple(range(6))
    )(*x)


@pytest.mark.parametrize("rate", [None, 60.0, 1e-3], ids=["as_initialised", "near_0", "near_1"])
@pytest.mark.parametrize("path", SSD_PATHS)
def test_chunked_ssd_scan_is_the_token_by_token_recurrence(path, rate):
    """Output and all six gradients, in float32, both paths (``SSD_PATHS``)
    against the one recurrence; more than one head a group of B and C.
    Decays from the model's start (``A`` up to 16) to a state that forgets
    within a token (every ratio underflows to zero, none to inf)."""
    sizes, chunk, options = SSD_PATHS[path]
    x, cot = _ssd_inputs(rate=rate, **sizes)
    scan = functools.partial(ssd_scan, chunk=chunk, **options)
    with jax.default_matmul_precision("highest"):
        y = scan(*x)
        want = ssd_scan_sequential(*x, block=16)
        assert y.shape == want.shape == x[0].shape
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(y, want, rtol=1e-4, atol=2e-6 * scale)
        got = _ssd_grads(scan, x, cot)
        refs = _ssd_grads(ssd_scan_sequential, x, cot)
    for g, r, name in zip(got, refs, ("x", "dt", "A", "B", "C", "D")):
        top = float(jnp.abs(r).max())
        assert top > 0, name
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(g, r, rtol=2e-3, atol=2e-5 * top, err_msg=name)
    if rate == 60.0:  # where a token's decay leaves nothing: y = dt (C.B) x + D x
        xv, dt, A, B, C, D = x
        cb = jnp.repeat(jnp.sum(B * C, -1), xv.shape[2] // B.shape[2], axis=2)
        alone = (dt * cb + D)[..., None] * xv
        wiped = (dt * rate > 14.0)[..., None]
        assert float(wiped.mean()) > 0.3
        np.testing.assert_allclose(
            jnp.where(wiped, y, 0), jnp.where(wiped, alone, 0), rtol=1e-3, atol=1e-3 * scale
        )


@pytest.mark.parametrize("path", ["composed-chunk_16", "pallas-two_steps", "pallas-one_step"])
def test_ssd_scan_in_bf16_and_its_bad_calls(path):
    """bf16 operands, float32 decays and state: output and the six gradients
    within bf16's rounding of the float32 recurrence's (relative l2, the
    limits of ``tests_tpu``'s run at the cell's shape), in the inputs'
    dtypes; heads that the groups do not divide are refused."""
    sizes, chunk, options = SSD_PATHS[path]
    x, cot = _ssd_inputs(dtype=jnp.bfloat16, **sizes)
    scan = functools.partial(ssd_scan, chunk=chunk, **options)
    y = scan(*x)
    assert y.dtype == jnp.bfloat16
    full = tuple(v.astype(jnp.float32) for v in x)
    rel = lambda a, b: float(  # noqa: E731
        jnp.linalg.norm((a.astype(jnp.float32) - b).ravel()) / jnp.linalg.norm(b.ravel())
    )
    assert 1e-4 < rel(y, ssd_scan_sequential(*full)) < 2e-2
    refs = _ssd_grads(ssd_scan_sequential, full, cot)
    for a, v, r, name in zip(_ssd_grads(scan, x, cot), x, refs, ("x", "dt", "A", "B", "C", "D")):
        assert a.dtype == v.dtype, name
        assert rel(a, r) < 0.05, name
    h = x[0].shape[2] - 1
    with pytest.raises(ValueError, match="groups"):
        scan(x[0][:, :, :h], x[1][:, :, :h], x[2][:h], x[3], x[4], x[5][:h])
    with pytest.raises(ValueError, match="whole blocks"):
        ssd_scan_sequential(*x, block=48)


# calls the kernel pair cannot take: (sizes over the smallest it takes, chunk, interpret)
SSD_LEFT_TO_THE_COMPOSED_FORM = {
    "cpu_without_interpret": (dict(), 16, False),
    "padded_length": (dict(s=120), 16, True),
    "state_64": (dict(n=64), 16, True),
    "group_channels_96": (dict(h=6, p=32), 16, True),
    "head_size_8": (dict(p=8), 16, True),
    "chunk_64": (dict(), 64, True),
}


@pytest.mark.parametrize("case", SSD_LEFT_TO_THE_COMPOSED_FORM)
def test_a_call_the_ssd_kernels_cannot_take_is_the_composed_form(case):
    """Such a call notes ``composed`` on the compile event and is, bit for
    bit, what ``_composed`` gives (pad, ``_chunked``, cut: the only path
    before the kernels)."""
    sizes, chunk, interpret = SSD_LEFT_TO_THE_COMPOSED_FORM[case]
    x, _ = _ssd_inputs(**{**_SSD_KERNEL_SIZES, "b": 1, **sizes})
    scan = functools.partial(ssd_scan, chunk=chunk, interpret=interpret)
    y, noted = _noted_path(scan, *x, key="ssd")
    assert noted == "composed"
    composed = jax.jit(functools.partial(ssd._composed, chunk=chunk))(*x)
    np.testing.assert_array_equal(y, composed)


SSD_PLANS = {
    # (backend, dtype, heads, head size, groups, state, tokens, chunk): the
    # cell's call, 64 chunks, four a grid step; float32 operands two a step
    "cell": (("tpu", jnp.bfloat16, 64, 64, 8, 128, 8192, 128), 4),
    "float32": (("tpu", jnp.float32, 64, 64, 8, 128, 8192, 128), 2),
    # a length of whole chunks is whole grid steps of four, two or one
    "one_chunk": (("tpu", jnp.bfloat16, 4, 64, 2, 128, 128, 128), 1),
    "six_chunks": (("tpu", jnp.bfloat16, 4, 64, 2, 128, 96, 16), 2),
    "seven_chunks": (("tpu", jnp.bfloat16, 4, 64, 2, 128, 112, 16), 1),
    # a head of whole lane tiles, a wider state
    "head_256": (("tpu", jnp.bfloat16, 2, 256, 1, 256, 1024, 128), 2),
    # what the composed form keeps: no TPU, a state or a group's channels
    # that are no whole lane tiles, a head that is neither a whole part of a
    # tile nor whole tiles, another chunk or dtype, a length that would need
    # padding, a group whose blocks do not fit
    "cpu": (("cpu", jnp.bfloat16, 64, 64, 8, 128, 8192, 128), None),
    "state_64": (("tpu", jnp.bfloat16, 64, 64, 8, 64, 8192, 128), None),
    "group_channels_96": (("tpu", jnp.bfloat16, 6, 32, 2, 128, 8192, 128), None),
    "head_48": (("tpu", jnp.bfloat16, 16, 48, 2, 128, 8192, 128), None),
    "chunk_64": (("tpu", jnp.bfloat16, 64, 64, 8, 128, 8192, 64), None),
    "float16": (("tpu", jnp.float16, 64, 64, 8, 128, 8192, 128), None),
    "padded_chunk": (("tpu", jnp.bfloat16, 64, 64, 8, 128, 8200, 128), None),
    "one_group_of_64_heads": (("tpu", jnp.bfloat16, 64, 64, 1, 128, 8192, 128), None),
}


@pytest.mark.parametrize("case", SSD_PLANS)
def test_ssd_plan_takes_the_kernel_where_it_can(case):
    """Which path a call takes is a pure function of what it shows: backend,
    dtype, heads, head size, groups, state, length, chunk."""
    call, step_chunks = SSD_PLANS[case]
    assert ssd_plan(*call) == step_chunks


def test_the_ssd_kernels_note_their_path():
    """Through the interpreter the kernel pair notes ``pallas-interpret``
    (on a TPU ``pallas``, which the cell's ``expect`` lists), at the
    smallest call it takes: one chunk, one group of two heads."""
    x, _ = _ssd_inputs(b=1, s=16, h=2, p=64, g=1, n=128)
    scan = functools.partial(ssd_scan, chunk=16, interpret=True)
    y, noted = _noted_path(scan, *x, key="ssd")
    assert noted == "pallas-interpret"
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            y, ssd_scan_sequential(*x), rtol=1e-4, atol=1e-4
        )
