"""What the token decoders share (``models/lfm2.py``, ``models/afmoe.py``,
``models/qwen3_next.py``, ``models/nemotron_h.py``): the ``--model-cut`` that says what one chip holds
of a published model, and the plain pieces every such decoder is made of —
RMSNorm with float32 statistics (scaled by ``w`` or, zero-centred, by ``1 +
w``), a bias-free projection, rotate-half RoPE on the whole head or its
first elements, the SwiGLU and the ungated squared-ReLU MLP."""

from __future__ import annotations

from typing import Any

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp

CUT_KEYS = ("layers", "dense", "experts", "first_expert", "vocab")


def parse_cut(text: str | None) -> dict:
    """``--model-cut layers=5,dense=1,experts=8,first_expert=0,vocab=8192``
    as a dict; keys left out keep the published value."""
    cut = {}
    for part in filter(None, (text or "").split(",")):
        key, _, value = part.partition("=")
        if key not in CUT_KEYS or not value.isdigit():
            raise ValueError(
                f"--model-cut takes {'=N,'.join(CUT_KEYS)}=N; got {part!r}"
            )
        cut[key] = int(value)
    return cut


def cut_config(config: dict, cut: dict) -> dict:
    """The share of ``config`` one chip holds: the first ``dense`` of the
    leading dense layers, then the layers that follow the published dense
    ones, ``layers`` in all; experts ``first_expert`` .. ``+ experts``; the
    first ``vocab`` rows of the vocabulary.  ``config`` carries
    ``num_dense_layers`` and ``layer_types``: a model whose published config
    has neither derives them in its own module (``models/qwen3_next.py``:
    no leading dense layer, the kinds from ``full_attention_interval``)."""
    published_dense = config["num_dense_layers"]
    dense = cut.get("dense", published_dense)
    layers = cut.get("layers", config["num_hidden_layers"] - published_dense + dense)
    types = config["layer_types"]
    kept = list(types[:dense]) + list(
        types[published_dense:published_dense + layers - dense]
    )
    held = cut.get("experts", config["num_experts"])
    first = cut.get("first_expert", 0)
    vocab = cut.get("vocab", config["vocab_size"])
    if (
        not 0 <= dense <= published_dense or len(kept) != layers
        or not 0 < held <= config["num_experts"] - first
        or not 0 < vocab <= config["vocab_size"]
    ):
        raise ValueError(f"--model-cut {cut} does not fit the published model")
    return {
        **config, "layer_types": kept, "num_hidden_layers": layers,
        "num_dense_layers": dense, "num_experts_held": held,
        "first_expert": first, "vocab_size": vocab,
    }


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * w``, ``w`` from one; ``zero_centred``:
    ``* (1 + w)``, ``w`` from zero (the same function at the start)."""

    eps: float
    dtype: Any = jnp.float32
    zero_centred: bool = False

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.zero_centred else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],), jnp.float32)
        if self.zero_centred:
            scale = 1.0 + scale
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


def _dense(features, dtype, name, std=0.02):
    return nn.Dense(
        features, use_bias=False, dtype=dtype, param_dtype=jnp.float32,
        kernel_init=nn.initializers.normal(stddev=std), name=name,
    )


def rope(x, theta: float, rotary: int | None = None):
    """Rotary position embedding in the rotate-half form on ``(B, S, H,
    D)``, positions ``0 .. S-1``, angles in float32.  ``rotary`` < ``D``
    turns the first ``rotary`` elements of each head (pairs ``(i, i +
    rotary / 2)``, frequencies over ``rotary``) and passes the rest."""
    if rotary is not None and rotary < x.shape[-1]:
        return jnp.concatenate(
            [rope(x[..., :rotary], theta), x[..., rotary:]], axis=-1
        )
    s, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    return (x32 * cos + jnp.concatenate([-x2, x1], axis=-1) * sin).astype(x.dtype)


class SwiGLU(nn.Module):
    dim: int
    hidden: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        gate = nn.silu(_dense(self.hidden, self.dtype, "w1")(x))
        return _dense(self.dim, self.dtype, "w2")(gate * _dense(self.hidden, self.dtype, "w3")(x))


class ReLU2(nn.Module):
    """``W_2 relu(W_1 x)^2``: the MLP without a gate (Nemotron-H)."""

    dim: int
    hidden: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        up = nn.relu(_dense(self.hidden, self.dtype, "w1")(x))
        return _dense(self.dim, self.dtype, "w2")(up * up)


def frozen_config(config: dict):
    """The config as a hashable module attribute (lists become tuples)."""
    return flax.core.freeze({
        k: tuple(v) if isinstance(v, list) else v for k, v in config.items()
    })


def zoo_entry(model_cls, config: dict):
    """A zoo constructor for a token decoder ``model_cls(config, dtype=,
    remat=, moe_gmm=)`` at a published ``config``: takes the Trainer's model
    keywords and its own ``cli_options`` (the cut); the image families'
    (``stem``, ``norm_dtype``) do not apply to a token decoder."""

    def build(*, dtype=jnp.float32, remat=False, model_cut=None,
              moe_gmm="auto", **_image_options):
        cut = cut_config(config, parse_cut(model_cut))
        return model_cls(
            frozen_config(cut), dtype=dtype, remat=remat, moe_gmm=moe_gmm
        )

    build.cli_options = ("model_cut",)
    return build
