"""Ask the TPU's compiler, without a TPU, whether the kernels compile.

The rest of ``tests/`` runs the Pallas kernels through the interpreter on
the CPU — semantics only.  The interpreter cannot see what Mosaic refuses:
a slice not aligned to the tiling, more scoped VMEM than a kernel may use, a
kernel that cannot be lowered at all.  libtpu is installed here and compiles
for a chip that is *described*, not attached
(``jax.experimental.topologies``), so each kernel family of the main path is
compiled for a v5e at the real shapes the zoo trains, from shapes alone,
with ``interpret=False`` passed to the kernel directly (code that asks
``jax.default_backend()`` still sees the CPU here).

A compile that passes is not a chip run: it says nothing about results or
times (``chip_smoke.py`` and ``tests_tpu/`` are the chip's side).  Skipped,
not failed, where the topology cannot be described.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_training_comparison_tpu.models.moe import TopKMoE
from distributed_training_comparison_tpu.ops import attention, flash_attention
from distributed_training_comparison_tpu.ops.attention import flash_plan
from distributed_training_comparison_tpu.ops.attention_small import small_mha
from distributed_training_comparison_tpu.ops.moe_gmm import (
    grouped_ffn,
    grouped_matmul,
)
from distributed_training_comparison_tpu.ops.vit_block import fused_vit_block

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip.  The persistent compile cache is off around
    these compiles: an executable compiled for a described device is
    written to the cache but cannot be read back without a chip, and the
    next run would warn and compile again."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or one that cannot describe a v5e
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compiled_text(fn, chip, *shapes) -> str:
    args = [
        jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), a
        )
        for a in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _s(*shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _grad_of(fn, n_args):
    """d(sum of outputs)/d(every argument) — the program a train step's
    backward runs through the kernel's custom VJP."""
    return jax.grad(
        lambda *a: fn(*a).astype(jnp.float32).sum(), argnums=tuple(range(n_args))
    )


# (batch, query heads, key-value heads, tokens, head size).  vit_long as
# chip_smoke trains it, and S=16384: past _FWD_RESIDENT_KV_LIMIT, the
# streamed forward.  lfm2: the token cell's attention layer — 4 sequences,
# 32 query heads on 8 key-value heads read by index, 4,096 tokens, head size
# 64 as it is (a block whose minor dim spans the array: no pad to 128 lanes)
# qwen3next: that cell's attention layer — 16 query heads on 2 key-value
# heads, 8,192 tokens, head size 256: neither K/V nor the fused backward's q,
# dO and dq (16 MiB) stay resident, so the streamed forward (1,024-row query
# tiles: at 2,048 Mosaic refused it, 20.5 MiB of scoped VMEM) and the two
# tiled backward kernels on repeated heads
FLASH_SHAPES = {
    "vit_long": (8, 4, 4, 4096, 128), "s16384": (1, 4, 4, 16384, 128),
    "lfm2": (4, 32, 8, 4096, 64), "qwen3next": (1, 16, 2, 8192, 256),
}


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape", FLASH_SHAPES.values(), ids=FLASH_SHAPES)
def test_flash_attention_compiles_for_v5e(chip, shape, backward, causal):
    b, h, hkv, s, d = shape
    attn = lambda q, k, v: flash_attention(q, k, v, causal=causal)  # noqa: E731
    fn = _grad_of(attn, 3) if backward else attn
    text = _compiled_text(
        fn, chip, _s(b, h, s, d), _s(b, hkv, s, d), _s(b, hkv, s, d)
    )
    assert "tpu_custom_call" in text
    if d < 128:
        assert " pad(" not in text, "the head dimension went in unpadded"
    if causal and h > hkv:
        # the plan's fused backward: one kernel, the group's dk / dv summed
        # inside it, no repeated heads around it — or, where its residents
        # do not fit, the two tiled kernels
        fused = flash_plan(s, s, d, h // hkv, True, BF16).fused_bwd
        assert fused == (d <= 128)
        assert text.count('custom_call_target="tpu_custom_call"') == (
            1 + (1 if fused else 2) * backward
        )


# (batch, query heads, key-value heads, tokens, head size, window).  The
# AFMoE cell's sliding layer — the resident forward at its VMEM limit and the
# fused backward with q, dO and dq as a ring of six 512-row tiles the kernel
# fetches itself, each query head's dk / dv summed outside — and its full
# layer (``None``: 8,192 keys whole, 8.0 MiB held); a window under the
# whole-sequence residents of head size 64 (4,096 keys, the group summed in
# the kernel); under the streamed forward, where only the ring lets the
# backward fuse; narrower than a tile
WINDOW_SHAPES = {
    "trinity": (1, 32, 4, 8192, 128, 2048),
    "trinity_full": (1, 32, 4, 8192, 128, None),
    "fused": (4, 32, 8, 4096, 64, 2048),
    "s16384": (1, 4, 4, 16384, 128, 2048), "narrow": (1, 8, 1, 8192, 128, 300),
}


@pytest.mark.parametrize("shape", WINDOW_SHAPES.values(), ids=WINDOW_SHAPES)
def test_flash_attention_with_a_window_compiles_for_v5e(chip, shape):
    b, h, hkv, s, d, window = shape
    attn = lambda q, k, v: flash_attention(q, k, v, causal=True, window=window)  # noqa: E731
    text = _compiled_text(
        _grad_of(attn, 3), chip, _s(b, h, s, d), _s(b, hkv, s, d), _s(b, hkv, s, d)
    )
    # one forward and one backward kernel, inside the scoped VMEM a kernel
    # gets unasked, key-value heads read by index: no head repeated
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "vmem_limit_bytes" not in text
    repeated = rf"\[{hkv},{h // hkv},{s},{d}\]\S* broadcast\("
    assert not re.search(repeated, text), "k / v repeated a query head"


def _gated_delta_text(chip, tokens):
    """``gated_delta_rule``'s forward and backward at the cell's heads (32
    value heads of 128 on 16 key heads, chunk 64), compiled for the chip."""
    from distributed_training_comparison_tpu.ops.gated_delta import gated_delta_rule

    f32 = jnp.float32
    return _compiled_text(
        _grad_of(lambda *a: gated_delta_rule(*a, chunk=64), 5), chip,
        _s(1, tokens, 16, 128), _s(1, tokens, 16, 128), _s(1, tokens, 32, 128),
        _s(1, tokens, 32, dtype=f32), _s(1, tokens, 32, dtype=f32),
    )


def test_gated_delta_scan_compiles_for_v5e(chip, monkeypatch):
    """The gated delta rule at ``qwen3next_ep32_seq8k_job``'s sizes (one
    sequence of 8,192 tokens), through the dispatcher as a TPU shows it the
    call (``jax.default_backend()``, which only code of this repo reads
    under that name, says ``tpu`` for the trace): one forward and one
    backward kernel, both under the scope ``gdn_scan`` that the cell's
    readers match, no loop over chunks or tokens outside them, q, k, v read
    as ``(B, S, H d)`` — no key head repeated, no float32 copy of a ``(S,
    H, d)`` operand."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _gated_delta_text(chip, 8192)
    kernels = re.findall(r'tpu_custom_call.*op_name="([^"]*)"', text)
    assert len(kernels) == 2 and all("gdn_scan" in n for n in kernels), kernels
    assert "transpose(" in kernels[1] and "transpose(" not in kernels[0]
    assert " while(" not in text
    assert "vmem_limit_bytes" not in text
    assert not re.search(r"f32\[1,8192,(16|32),128\]\S* (copy|convert)\(", text)
    assert not re.search(r"\[1,8192,16,2,128\]\S* broadcast\(", text)


def test_composed_gated_delta_scan_compiles_for_v5e(chip, monkeypatch):
    """A length the kernels leave to the composed form on a TPU too (8,200
    tokens: no whole chunks, padded to 129): composed XLA, one loop over the
    chunks each way and no kernel — and no loop per token."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _gated_delta_text(chip, 8200)
    assert "tpu_custom_call" not in text
    loops = re.findall(r" while\(.*op_name=\"([^\"]*)\"", text)
    assert len(loops) == 2 and all("gdn_scan" in n for n in loops), loops
    assert "transpose(" in loops[1] and "transpose(" not in loops[0]


# the scan call of ``nemotron3nano_ep16_seq8k_job``: one sequence, 64 heads of
# 64 on a 64 x 128 state, B and C shared by 8 groups, chunk 128, bf16
_SSD_CELL = dict(
    batch=1, heads=64, head_dim=64, groups=8, state=128, chunk=128, dtype=BF16
)


def _ssd_scan_text(chip, tokens, *, backward=True, **sizes):
    """``ssd_scan`` compiled for the chip, its backward with it, at the
    cell's sizes (``_SSD_CELL``) but for ``sizes``."""
    from distributed_training_comparison_tpu.ops.ssd import ssd_scan

    z = {**_SSD_CELL, **sizes}
    f32, heads = jnp.float32, z["heads"]
    scan = lambda *a: ssd_scan(*a, chunk=z["chunk"])  # noqa: E731
    bc = _s(z["batch"], tokens, z["groups"], z["state"], dtype=z["dtype"])
    return _compiled_text(
        _grad_of(scan, 6) if backward else scan, chip,
        _s(z["batch"], tokens, heads, z["head_dim"], dtype=z["dtype"]),
        _s(z["batch"], tokens, heads, dtype=f32), _s(heads, dtype=f32),
        bc, bc, _s(heads, dtype=f32),
    )


def test_ssd_scan_compiles_for_v5e(chip, monkeypatch):
    """The state-space scan at ``nemotron3nano_ep16_seq8k_job``'s sizes (one
    sequence of 8,192 tokens), through ``ssd_scan`` as a TPU shows it the
    call: one forward and one backward kernel, both under the scope
    ``ssd_scan`` that the cell's readers match, no loop over chunks or
    tokens outside them, no ``vmem_limit_bytes``; x, B and C read as ``(B,
    S, H P)`` and ``(B, S, G N)`` — no float32 copy of an ``(S, H P)``
    operand, no B or C repeated by head — and nothing ``L x L`` a head and
    chunk in HBM."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _ssd_scan_text(chip, 8192)
    kernels = re.findall(r'tpu_custom_call.*op_name="([^"]*)"', text)
    assert len(kernels) == 2 and all("ssd_scan" in n for n in kernels), kernels
    assert "transpose(" in kernels[1] and "transpose(" not in kernels[0]
    assert " while(" not in text
    assert "vmem_limit_bytes" not in text
    assert not re.search(r"f32\[[\d,]*128,128\]", text), "an (L, L) float32 tensor"
    assert not re.search(
        r"f32\[1,(8192,4096|8192,64,64|64,128,8,8,64)\]\S* (copy|convert)\(", text
    )
    assert not re.search(r"\[1,8192,8,8,128\]\S* broadcast\(", text)
    # what the backward keeps: the 64 chunks' start states, float32
    assert "f32[1,8,64,128,512]" in text


def test_composed_ssd_scan_compiles_for_v5e(chip, monkeypatch):
    """A length the kernels leave to the composed form on a TPU too (8,200
    tokens: no whole chunks, padded to 65): composed XLA, one loop over the
    chunks each way and no kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _ssd_scan_text(chip, 8200)
    assert "tpu_custom_call" not in text
    loops = re.findall(r" while\(.*op_name=\"([^\"]*)\"", text)
    assert len(loops) == 2 and all("ssd_scan" in n for n in loops), loops


def test_ssd_scan_forward_alone_compiles_for_v5e(chip, monkeypatch):
    """The cell's call where nothing is differentiated (``eval_runner``): the
    forward kernel alone, and it writes no chunk's start state."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _ssd_scan_text(chip, 8192, backward=False)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert " while(" not in text and "vmem_limit_bytes" not in text
    assert "f32[1,8,64,128,512]" not in text


# calls at the corners of what ``ssd_plan`` takes, as (tokens, sizes): Mosaic
# has to take each (a head of whole lane tiles it refused in PR 41's tree,
# "Broadcast in both sublanes and lanes", which no interpreted test could see)
SSD_PLAN_CORNERS = {
    "head_128": (256, dict(heads=2, head_dim=128, groups=2)),
    "head_256_state_256": (1024, dict(heads=2, head_dim=256, groups=1, state=256)),
    "head_16": (1024, dict(heads=8, head_dim=16, groups=1)),
    "float32": (8192, dict(dtype=jnp.float32)),
    "group_of_1024_channels": (8192, dict(heads=32, groups=2)),
    "state_512": (4096, dict(heads=8, groups=1, state=512)),
    "four_sequences": (4096, dict(batch=4)),
    "three_chunks": (384, dict(batch=2, heads=2, groups=1)),
    "chunk_16": (128, dict(batch=2, heads=4, groups=2, chunk=16)),
}


@pytest.mark.parametrize("case", SSD_PLAN_CORNERS)
def test_what_ssd_plan_takes_compiles_for_v5e(chip, monkeypatch, case):
    """A call the plan takes must be one the kernels run: the pair compiles
    for the chip at each corner of the plan's conditions."""
    from distributed_training_comparison_tpu.ops.ssd import ssd_plan

    tokens, sizes = SSD_PLAN_CORNERS[case]
    z = {**_SSD_CELL, **sizes}
    assert ssd_plan(
        "tpu", z["dtype"], z["heads"], z["head_dim"], z["groups"], z["state"],
        tokens, z["chunk"],
    ) is not None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _ssd_scan_text(chip, tokens, **sizes)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "vmem_limit_bytes" not in text


def _mixer_pointwise_text(chip, backward):
    """The mixer's two pointwise stages at ``qwen3next_ep32_seq8k_job``'s
    sizes (16 key and 32 value heads of 128, one sequence of 8,192 tokens,
    ``qkvz`` 12,288 wide), forward or the program a train step's backward
    runs, compiled for the chip."""
    from distributed_training_comparison_tpu.ops.gdn_pointwise import (
        gated_rms_norm, short_conv_l2norm,
    )

    def stages(qkvz, taps, o, scale):
        # flat at the program's edge, as the projections and the scan's
        # kernels hold them: a (B, S, H, d) argument or result of the
        # program itself would be tiled over (H, d) and copied
        q, k, v = short_conv_l2norm(
            qkvz, taps, key_heads=16, value_heads=32, key_dim=128, value_dim=128
        )
        y = gated_rms_norm(
            o.reshape(1, 8192, 32, 128), qkvz, scale, key_dim=128, eps=1e-6
        )
        return tuple(x.reshape(1, 8192, -1) for x in (q, k, v)) + (y,)

    def summed(*a):
        return sum(x.astype(jnp.float32).sum() for x in stages(*a))

    f32 = jnp.float32
    return _compiled_text(
        jax.grad(summed, argnums=(0, 1, 2, 3)) if backward else stages, chip,
        _s(1, 8192, 12288), _s(8192, 4, dtype=f32), _s(1, 8192, 4096),
        _s(128, dtype=f32),
    )


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
def test_gdn_pointwise_kernels_compile_for_v5e(chip, monkeypatch, backward):
    """All four kernels of ``ops/gdn_pointwise.py`` through Mosaic at the
    cell's shape, as a TPU shows the dispatcher the call: the convolution's
    one call (q, k and v of a key head's group a grid step: column ranges of
    one slab) and the gated norm's, each under the scope its stage has, and
    around them no float32 copy of an 8,192-token activation — the ``(S, H,
    d)`` relayouts the composed l2-norms and gated norm cost — and beside
    the forward's no op at all."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _mixer_pointwise_text(chip, backward)
    kernels = re.findall(r'tpu_custom_call.*op_name="([^"]*)"', text)
    side = "bwd" if backward else "fwd"
    assert sorted(n.split("/")[-2] for n in kernels) == (
        [f"gdn_conv_{side}", f"gdn_gate_norm_{side}"]
    ), kernels
    for name in kernels:
        scope = "gdn_conv" if "gdn_conv_" in name else "gdn_gate_norm"
        assert f"/{scope}/" in name or f"({scope})" in name, name
        assert ("transpose(" in name) == backward, name
    assert "vmem_limit_bytes" not in text
    assert not re.search(r"f32\[(1,8192|1024,8),[\d,]+\]\S* copy\(", text)
    if not backward:
        assert not re.search(r" (fusion|copy|concatenate)\(", text), "an op beside the kernels"


def test_flash_attention_with_lse_compiles_for_v5e(chip):
    """Ring attention's call at the token cell's shape: the ``lse`` output
    and its cotangent through the fused backward."""
    b, h, hkv, s, d = FLASH_SHAPES["lfm2"]

    def both(q, k, v):
        out, lse = flash_attention(q, k, v, causal=True, return_lse=True)
        return out.astype(jnp.float32).sum() + jnp.sin(lse).sum()

    text = _compiled_text(
        jax.grad(both, argnums=(0, 1, 2)), chip,
        _s(b, h, s, d), _s(b, hkv, s, d), _s(b, hkv, s, d),
    )
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_fused_vit_block_compiles_for_v5e(chip):
    """vit_tiny at --patch-size 2: batch 256, 256 tokens, dim 192, 3 heads."""
    dim, hidden = 192, 4 * 192
    dense = lambda i, o: {"kernel": _s(i, o, dtype=jnp.float32),  # noqa: E731
                          "bias": _s(o, dtype=jnp.float32)}
    ln = {"scale": _s(dim, dtype=jnp.float32), "bias": _s(dim, dtype=jnp.float32)}
    params = {
        "ln_attn": ln, "q_proj": dense(dim, dim), "k_proj": dense(dim, dim),
        "v_proj": dense(dim, dim), "proj": dense(dim, dim), "ln_mlp": ln,
        "mlp_up": dense(dim, hidden), "mlp_down": dense(hidden, dim),
    }
    block = lambda x, p: fused_vit_block(x, p, heads=3)  # noqa: E731
    text = _compiled_text(_grad_of(block, 2), chip, _s(256, 256, dim), params)
    assert "tpu_custom_call" in text


def test_grouped_ffn_compiles_for_v5e(chip):
    """vit_moe's own expert layer: 256 x 64 tokens over 8 experts, d=192,
    h=768, capacity 1.25 x n/E padded to the bf16 sublane tile."""
    n, e, d, h = 256 * 64, 8, 192, 768
    cap = -(-int(n * 1.25) // e)
    cap = -(-cap // 16) * 16
    ffn = lambda xs, w1, b1, w2, b2, starts: grouped_ffn(  # noqa: E731
        xs, w1, b1, w2, b2, starts, cap
    )
    text = _compiled_text(
        _grad_of(ffn, 5), chip, _s(n, d), _s(e, d, h), _s(e, h), _s(e, h, d),
        _s(e, d), _s(e + 1, dtype=jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_streamed_grouped_matmul_compiles_for_v5e(chip):
    """LFM2-24B-A2B's expert layer on one chip's share: the 65,536-row
    dispatch buffer (16,384 tokens x top-4) over 8 held experts of 2048 x
    1536 whose weights stream from HBM (JAX's megablox kernels at this
    repo's tiling), the SwiGLU's three products, forward and backward."""
    m, e, d, f = 4 * 4096 * 4, 8, 2048, 1536

    def experts(xs, w1, w3, w2, sizes):
        gmm = lambda a, w: grouped_matmul(a, w, sizes, impl="megablox")  # noqa: E731
        return gmm(jax.nn.silu(gmm(xs, w1)) * gmm(xs, w3), w2)

    grad = jax.grad(
        lambda *a: experts(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3)
    )
    text = _compiled_text(
        grad, chip, _s(m, d), _s(e, d, f), _s(e, d, f), _s(e, f, d),
        _s(e, dtype=jnp.int32),
    )
    # 2 forward (the last one feeds only the sum, which grad drops), 3 dx, 3 dW
    assert text.count("tpu_custom_call") >= 8


def test_topk_moe_moves_no_worst_case_buffer_for_v5e(chip, monkeypatch):
    """The token cell's expert layer as its chip sees it — 16,384 tokens of
    2,048, experts of 1,536, 8 of 64 held, top-4, bf16, megablox — compiles
    under ``jax.grad``, and no tensor in the program has the ``n * k`` =
    65,536 rows of the worst-case dispatch buffer: the common path works on
    the 16,384-row held prefix and the fallback on the 16,384 tokens.
    ``auto`` asks ``jax.default_backend()``, which sees the CPU here: the
    test answers for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = TopKMoE(2048, 1536, 64, 4, 8, 0, dtype=BF16)
    x = _s(4, 4096, 2048)
    variables = jax.eval_shape(
        lambda: layer.init(jax.random.key(0), jnp.zeros(x.shape, x.dtype))
    )

    def loss(params, stats, x):
        y = layer.apply({"params": params, "batch_stats": stats}, x)
        return y.astype(jnp.float32).sum()

    text = _compiled_text(
        jax.grad(loss, (0, 2)), chip,
        variables["params"], variables["batch_stats"], x,
    )
    rows = {
        int(r) for r in re.findall(r"\b(?:bf16|f32)\[(\d+),(?:2048|1536)\]", text)
    }
    assert 16384 in rows and max(rows) == 16384, sorted(rows)
    # 3 products forward (the forward's token sum feeds only the loss's sum,
    # which grad drops, and its fallback with it); 3 dx, 3 dW and the token
    # sum for dx backward.  The fallback is a loop and holds no kernel
    assert text.count("tpu_custom_call") == 10
    assert " while(" in text and " conditional(" not in text


def test_relu2_expert_layer_at_1856_compiles_for_v5e(chip, monkeypatch):
    """Nemotron-3-Nano's expert layer as its chip sees it — 8,192 tokens of
    2,688, squared-ReLU experts of 1,856 (14.5 lane tiles), 8 of 128 held,
    top-6, a shared expert of 3,712, bf16, megablox — compiles under
    ``jax.grad`` with the cast weights at 1,920 columns and the parameters'
    gradients at 1,856; at the whole width Mosaic refuses the backward's
    transposed block, which is why ``lane_width`` pads."""
    from distributed_training_comparison_tpu.ops import moe_gmm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = TopKMoE(
        2688, 1856, 128, 6, 8, 0, 2.5, dtype=BF16, bias_update_rate=1e-3,
        shared_hidden=3712, mlp="relu2",
    )
    x = _s(1, 8192, 2688)
    variables = jax.eval_shape(
        lambda: layer.init(jax.random.key(0), jnp.zeros(x.shape, x.dtype))
    )
    assert variables["params"]["w1"].shape == (8, 1856, 2688)

    def loss(params, stats, x):
        y = layer.apply({"params": params, "batch_stats": stats}, x)
        return jnp.square(y.astype(jnp.float32)).sum()

    grad = jax.grad(loss, (0, 2))
    text = _compiled_text(grad, chip, variables["params"], variables["batch_stats"], x)
    assert "bf16[8,2688,1920]" in text and "bf16[8,1920,2688]" in text
    assert "f32[8,1856,2688]" in text
    # 2 products forward, 2 dx, 2 dW, the token sums forward and backward
    assert text.count("tpu_custom_call") == 8
    monkeypatch.setattr(moe_gmm, "lane_width", lambda width, impl: width)
    monkeypatch.setattr(
        "distributed_training_comparison_tpu.models.moe.lane_width",
        lambda width, impl: width,
    )
    whole = jax.grad(lambda *a: loss(*a), (0, 2))  # a new function: traced anew
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        _compiled_text(whole, chip, variables["params"], variables["batch_stats"], x)


def test_small_mha_compiles_for_v5e(chip):
    """The short-sequence kernel at vit_tiny's S=64 (its head_fwd/head_bwd
    helpers are what the fused block kernel is built from)."""
    shape = (256, 64, 3, 64)
    text = _compiled_text(
        _grad_of(small_mha, 3), chip, _s(*shape), _s(*shape), _s(*shape)
    )
    assert "tpu_custom_call" in text


def _written(text: str, dtype: str) -> list:
    """Element counts of the ``dtype`` arrays that the entry computation's
    instructions write (a fusion's outputs; what stays inside a fused
    computation never reaches HBM)."""
    entry = text[text.index("\nENTRY "):]
    entry = entry[: entry.index("\n}")]
    counts = []
    for line in entry.splitlines()[1:]:
        outputs = line.partition(" = ")[2]
        outputs = outputs[: re.search(r"\s[\w\-]+\(", outputs + " x(").start()]
        for dims in re.findall(rf"\b{dtype}\[([\d,]+)\]", outputs):
            counts.append(math.prod(int(n) for n in dims.split(",")))
    return counts


def test_composed_attention_writes_no_float32_scores_for_v5e(chip):
    """DeiT-S's cell (batch 256, 256 tokens, 6 heads of 64, ``bshd``): in
    the composed branch's forward and backward the compiler writes no
    float32 score-sized tensor — the forward's two score products each
    fuse with what consumes them — and exactly two bf16 ones, ``p`` and
    ``ds``.  A reading of the compiler's fusion choices, which the
    phrasing in ``ops/attention.py _composed_fwd`` leans on: if a new
    compiler splits them, this says so before a chip does."""
    shape = (256, 256, 6, 64)
    scores = 256 * 256 * 6 * 256
    attn = lambda q, k, v: attention(  # noqa: E731
        q, k, v, impl="reference", layout="bshd"
    )
    text = _compiled_text(
        _grad_of(attn, 3), chip, _s(*shape), _s(*shape), _s(*shape)
    )
    assert "tpu_custom_call" not in text
    assert scores not in _written(text, "f32")
    assert _written(text, "bf16").count(scores) == 2
