"""Vision Transformer family (beyond parity: the reference is CNN-only).

The reference's model zoo is the CIFAR ResNet family and nothing else
(``src/single/net.py``; SURVEY.md §2.2: "no sequence dimension, no
attention").  This transformer family gives the framework a sequence axis,
which is what makes the long-context machinery real: attention runs
through ``ops.attention`` (the Pallas flash kernel on TPU), and the
sequence dimension is what ring attention (``parallel/ring.py``) and
pipeline parallelism (``parallel/pipeline.py``) shard.

TPU-native choices:

- **Scanned trunk**: the ``depth`` identical pre-LN blocks are one
  ``nn.scan`` over stacked parameters ``(depth, ...)`` — one block trace
  instead of ``depth`` unrolled copies (faster compiles), and the stacked
  leading axis is exactly what stage-sharded pipeline parallelism
  partitions.
- **Separable forward**: ``embed`` / ``trunk`` / ``head`` are standalone
  methods (``__call__`` chains them), so the pipeline-parallel path can
  run the identical embed/head computations on the identical parameters
  and replace only the trunk with its staged schedule.
- **bf16 policy** like the ResNet zoo: activations/matmuls in ``dtype``,
  parameters fp32, LayerNorm statistics under the shared ``norm_dtype``
  contract (``models/norms.py``), fp32 logits.
- **Global-average-pool head** (no class token): keeps the sequence
  homogeneous — every token flows through the same scanned/sharded path.

Shapes: ``image_size=32`` with ``patch=4`` → 64 tokens.  ``stem`` is
accepted for ``get_model`` interface compatibility and ignored (the patch
embed is the stem).
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..obs.compilation import note_kernel_path
from ..ops.vmem import fits_weight_budget, fused_block_weight_bytes
from .norms import norm_policy

# reasons already warned about when --block-fusion force silently composed
# (one warning per distinct reason per process; tests may clear this)
_FUSION_FORCE_WARNED: set[str] = set()


def _warn_force_composed(reason: str) -> None:
    """One-time warning when ``block_fusion='force'`` is declined.

    'force' silently composing was documented in help text only — a user
    benchmarking 'force' could measure the composed path believing the
    kernel ran (ADVICE r5 #3).  Emitted at trace time, once per distinct
    reason, naming the condition that failed.
    """
    if reason in _FUSION_FORCE_WARNED:
        return
    _FUSION_FORCE_WARNED.add(reason)
    warnings.warn(
        "--block-fusion force: the fused Pallas block kernel was declined "
        f"({reason}); this block runs the composed XLA path",
        UserWarning,
        stacklevel=2,
    )


class _DenseParams(nn.Module):
    """Parameter mirror of ``nn.Dense(features, kernel_init=xavier)`` —
    creates the identical ``{kernel, bias}`` leaves (same names, shapes,
    dtypes, initializers, and path-derived RNG) without running the
    matmul, so the fused-block kernel path shares one param tree with the
    composed path (checkpoints and parallel styles interoperate)."""

    features: int

    @nn.compact
    def __call__(self, in_features: int) -> dict:
        xavier = nn.initializers.xavier_uniform()
        return {
            "kernel": self.param(
                "kernel", xavier, (in_features, self.features), jnp.float32
            ),
            "bias": self.param(
                "bias", nn.initializers.zeros, (self.features,), jnp.float32
            ),
        }


class _LNParams(nn.Module):
    """Parameter mirror of ``nn.LayerNorm`` (``{scale, bias}``)."""

    @nn.compact
    def __call__(self, features: int) -> dict:
        return {
            "scale": self.param(
                "scale", nn.initializers.ones, (features,), jnp.float32
            ),
            "bias": self.param(
                "bias", nn.initializers.zeros, (features,), jnp.float32
            ),
        }


class ViTBlock(nn.Module):
    """Pre-LN transformer block, scan-compatible: ``(x, None) -> (x, None)``.

    ``num_experts > 0`` replaces the dense MLP with a Switch-style
    mixture-of-experts FFN (``models/moe.py``) — the expert axis is what
    expert parallelism shards (``parallel/tp.py``).

    ``block_fusion`` gates the fully-fused Pallas block kernel
    (``ops/vit_block.py``, one kernel for LN→qkv→MHA→proj→LN→MLP):
    ``"auto"`` uses it on TPU for short-sequence dense blocks (the CIFAR
    regime), ``"force"`` also off-TPU through the interpreter (CI),
    ``"off"`` always composes — required whenever the block's
    *parameters* are sharded (tensor parallelism), since GSPMD cannot
    partition a pallas_call; the trainer makes that call."""

    dim: int
    heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.float32
    norm_dtype: Any = jnp.float32
    attn_impl: str = "auto"
    num_experts: int = 0
    capacity_factor: float = 1.25
    moe_dispatch: str = "auto"
    block_fusion: str = "auto"

    @nn.compact
    def __call__(self, x: jnp.ndarray, _carry_in=None):
        from ..ops import attention

        b, s, dim = x.shape
        # Structural gate conditions, checked in order; the first failure
        # is what the force-decline warning names.
        declined = []
        if self.num_experts != 0:
            declined.append("MoE block (the kernel has no expert FFN form)")
        if self.attn_impl != "auto":
            declined.append(f"attn_impl={self.attn_impl!r} pins attention")
        if s % 8 or (dim // self.heads) % 8:
            declined.append(
                f"tokens ({s}) and head dim ({dim // self.heads}) must be "
                "multiples of 8"
            )
        # Measured crossover on a v5e (vit_tiny dims, bf16, bs256):
        # at S=64 the composed XLA path still wins (18.8-20.4k vs
        # 23.8k img/s — the kernel's stacked-score waste and backward
        # recompute outweigh the relayouts it deletes), at S=256 the
        # fused block wins 6.48k vs 5.04k (+29%).  Above 512 the
        # flash path owns attention and scores would blow VMEM.
        if not 128 <= s <= 512:
            declined.append(f"{s} tokens outside the measured 128-512 window")
        # The kernel keeps every block weight VMEM-resident (backward adds
        # an fp32 accumulator per parameter); a config whose static
        # footprint exceeds the budget would die in Mosaic compilation —
        # compose instead (ADVICE r5 #2).
        wbytes = fused_block_weight_bytes(dim, self.mlp_ratio, self.dtype)
        if not fits_weight_budget(wbytes):
            declined.append(
                f"static VMEM weight footprint {wbytes / 2**20:.1f} MiB "
                "exceeds the kernel budget"
            )
        use_fused = (
            self.block_fusion in ("auto", "force")
            and not declined
            and (
                jax.default_backend() == "tpu"
                or self.block_fusion == "force"
            )
        )
        if self.block_fusion == "force" and not use_fused:
            _warn_force_composed(declined[0])
        # off-TPU the kernel only runs through the Pallas interpreter (CPU
        # tests); which of the three ran is on the compile event
        interpret = jax.default_backend() != "tpu"
        if not declined:
            note_kernel_path(
                "vit_block",
                "composed" if not use_fused
                else "pallas-interpret" if interpret else "pallas",
            )
        if use_fused:
            from ..ops.vit_block import fused_vit_block

            params = {
                "ln_attn": _LNParams(name="ln_attn")(dim),
                "q_proj": _DenseParams(dim, name="q_proj")(dim),
                "k_proj": _DenseParams(dim, name="k_proj")(dim),
                "v_proj": _DenseParams(dim, name="v_proj")(dim),
                "proj": _DenseParams(dim, name="proj")(dim),
                "ln_mlp": _LNParams(name="ln_mlp")(dim),
                "mlp_up": _DenseParams(self.mlp_ratio * dim, name="mlp_up")(dim),
                "mlp_down": _DenseParams(dim, name="mlp_down")(
                    self.mlp_ratio * dim
                ),
            }
            out = fused_vit_block(
                x.astype(self.dtype),
                params,
                heads=self.heads,
                norm_f32=self.norm_dtype is not None,
                interpret=interpret,
            )
            return out, None

        norm = norm_policy(nn.LayerNorm, self.norm_dtype, self.dtype)
        xavier = nn.initializers.xavier_uniform()
        hd = dim // self.heads

        # the block's two halves, named for the device trace (flax names the
        # modules inside them; the products of attention sit in no module)
        with jax.named_scope("attn"):
            h = norm(name="ln_attn")(x).astype(self.dtype)
            # q/k/v as three separate projections, not one packed 3*dim
            # Dense: unpacking a packed qkv (reshape+slice, or transpose) is
            # a real relayout on TPU — measured 21% of per-block fwd+bwd
            # time at CIFAR shapes. Separate projections also make tensor
            # parallelism head-aligned for free (each output axis shards on
            # whole heads when heads % model_parallel == 0, parallel/tp.py).
            proj_qkv = partial(
                nn.Dense, dim, dtype=self.dtype, kernel_init=xavier
            )
            q = proj_qkv(name="q_proj")(h).reshape(b, s, self.heads, hd)
            k = proj_qkv(name="k_proj")(h).reshape(b, s, self.heads, hd)
            v = proj_qkv(name="v_proj")(h).reshape(b, s, self.heads, hd)
            o = attention(
                q, k, v,
                impl=self.attn_impl,
                # (B, S, H, D): the short-sequence path runs transpose-free
                layout="bshd",
            )
            o = o.reshape(b, s, dim)
            x = x + nn.Dense(
                dim, dtype=self.dtype, kernel_init=xavier, name="proj"
            )(o)

        with jax.named_scope("mlp"):
            h = norm(name="ln_mlp")(x).astype(self.dtype)
            if self.num_experts:
                from .moe import SwitchFFN

                x = x + SwitchFFN(
                    dim=dim,
                    num_experts=self.num_experts,
                    mlp_ratio=self.mlp_ratio,
                    capacity_factor=self.capacity_factor,
                    dtype=self.dtype,
                    dispatch=self.moe_dispatch,
                    name="moe",
                )(h)
                return x, None
            h = nn.Dense(
                self.mlp_ratio * dim, dtype=self.dtype, kernel_init=xavier,
                name="mlp_up",
            )(h)
            h = nn.gelu(h)
            x = x + nn.Dense(
                dim, dtype=self.dtype, kernel_init=xavier, name="mlp_down"
            )(h)
        return x, None


class ViT(nn.Module):
    """Patch embed → ``depth`` scanned blocks → LN → mean pool → linear head."""

    depth: int
    dim: int
    heads: int
    patch: int = 4
    mlp_ratio: int = 4
    num_classes: int = 100
    image_size: int = 32
    dtype: Any = jnp.float32
    norm_dtype: Any = jnp.float32
    attn_impl: str = "auto"
    num_experts: int = 0  # > 0: Switch-MoE FFN in every block (models/moe.py)
    capacity_factor: float = 1.25
    # "auto" | "gmm" | "gather" | "onehot" — models/moe.py cost model;
    # auto = the fused Pallas grouped matmul on TPU, sort/gather elsewhere
    moe_dispatch: str = "auto"
    # "auto" | "force" | "off" — the fully-fused Pallas block kernel
    # (ops/vit_block.py); the trainer turns it off under tensor/pipeline
    # parallelism, where block params shard (ViTBlock docstring)
    block_fusion: str = "auto"
    remat: bool = False
    stem: str = "cifar"  # accepted for get_model compat; patch embed IS the stem
    # lax.scan unroll factor for the trunk (params stay stacked either way,
    # so pipeline-parallel stage sharding is unaffected).  At CIFAR scale
    # the scanned loop's per-layer residual stacking (dynamic-update-slice
    # writes of every block's saved activations) is a measured ~15% of
    # step time; unrolling lets XLA keep residuals as separate buffers
    # (vit_tiny/bs256/bf16 on a v5e: 12.0k → 23.0k img/s).  Non-positive
    # means full unroll (= depth).
    scan_unroll: int = 1

    def setup(self):
        if self.dim % self.heads:
            raise ValueError(
                f"ViT dim ({self.dim}) must be divisible by heads "
                f"({self.heads}); per-head dim would not be integral"
            )
        xavier = nn.initializers.xavier_uniform()
        self.patch_embed = nn.Conv(
            self.dim,
            kernel_size=(self.patch, self.patch),
            strides=self.patch,
            padding=0,
            dtype=self.dtype,
            kernel_init=xavier,
        )
        tokens = (self.image_size // self.patch) ** 2
        self.pos_emb = self.param(
            "pos_emb", nn.initializers.normal(stddev=0.02),
            (1, tokens, self.dim), jnp.float32,
        )
        block = ViTBlock
        if self.remat:
            block = nn.remat(block, prevent_cse=False)
        self.blocks = nn.scan(
            block,
            # "losses": the MoE aux loss sown per block stacks on the depth
            # axis; "moe_metrics": per-block routing health (dropped-token
            # fraction, expert load) stacks the same way (both are no-op
            # collections for dense blocks)
            variable_axes={"params": 0, "losses": 0, "moe_metrics": 0},
            split_rngs={"params": True},
            length=self.depth,
            unroll=self.depth if self.scan_unroll <= 0 else self.scan_unroll,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(
            dim=self.dim,
            heads=self.heads,
            mlp_ratio=self.mlp_ratio,
            dtype=self.dtype,
            norm_dtype=self.norm_dtype,
            attn_impl=self.attn_impl,
            num_experts=self.num_experts,
            capacity_factor=self.capacity_factor,
            moe_dispatch=self.moe_dispatch,
            block_fusion=self.block_fusion,
        )
        self.ln_head = norm_policy(nn.LayerNorm, self.norm_dtype, self.dtype)()
        self.head = nn.Dense(
            self.num_classes, dtype=self.dtype, kernel_init=xavier
        )

    def embed(self, x: jnp.ndarray) -> jnp.ndarray:
        """Images (B, H, W, 3) → tokens (B, S, dim) with position added."""
        b, h, w, _ = x.shape
        if h != self.image_size or w != self.image_size:
            raise ValueError(
                f"ViT(image_size={self.image_size}) got {h}x{w} input"
            )
        x = self.patch_embed(x.astype(self.dtype))
        x = x.reshape(b, -1, self.dim)
        return x + self.pos_emb.astype(self.dtype)

    def trunk(self, x: jnp.ndarray) -> jnp.ndarray:
        x, _ = self.blocks(x, None)
        return x

    def head_out(self, x: jnp.ndarray) -> jnp.ndarray:
        x = self.ln_head(x).astype(self.dtype)
        x = jnp.mean(x, axis=1)
        return self.head(x).astype(jnp.float32)

    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        return self.head_out(self.trunk(self.embed(x)))


def ViTTiny(**kw) -> ViT:
    return ViT(depth=12, dim=192, heads=3, **kw)


def ViTSmall(**kw) -> ViT:
    return ViT(depth=12, dim=384, heads=6, **kw)


def ViTMoE(**kw) -> ViT:
    """Switch-MoE config: ViT-Tiny-scale trunk where every block's FFN is
    8 experts behind a top-1 router — ~4.6× the dense FFN parameters at
    roughly the dense FLOPs/token (one expert per token + router).  The
    expert axis shards over ``"model"`` (``--model-parallel N``,
    expert parallelism); 8 % N == 0 keeps experts whole per shard."""
    kw.setdefault("num_experts", 8)
    kw.setdefault("depth", 8)
    kw.setdefault("dim", 192)
    kw.setdefault("heads", 3)
    return ViT(**kw)


def ViTLong(**kw) -> ViT:
    """Long-context config, TPU-native head sizing: head dim 512/4 = 128
    fills the MXU's 128 lanes exactly — the flash kernel's design point
    (at head dim 64 the kernel runs half-filled and the XLA reference path
    wins until S~2048; see ops/attention.py dispatch).  Defaults target
    256px inputs → 4096 tokens at patch 4."""
    kw.setdefault("image_size", 256)
    return ViT(depth=8, dim=512, heads=4, **kw)
