"""Sequence / context parallelism: ring attention and Ulysses all-to-all.

The reference repo caps out at data parallelism over a CNN — it has no
sequence axis at all (SURVEY.md §2.2).  This module is the long-context
layer of the TPU framework: when a sequence is too long for one chip's HBM
(or one attention call's VMEM working set), shard the **sequence axis**
over a mesh axis and keep attention exact:

- ``ring_attention``: K/V shards rotate around the mesh axis with
  ``lax.ppermute`` (ICI neighbor hops — the rotation is bandwidth-optimal
  on a TPU torus) while each device's Q shard stays put.  Per-hop partial
  results combine with the online-softmax rule, using the ``lse`` each
  attention call returns; the result is *exact* full attention, never
  materialized.  Causal runs skip fully-masked (future) blocks via
  ``lax.switch``: block-causal on the diagonal hop, full attention on
  strictly-past hops, nothing on future hops.
- ``ulysses_attention`` (all-to-all): redistributes (heads ↔ sequence) so
  every device holds *all* tokens for ``H/P`` heads, runs ordinary
  (flash) attention locally, and redistributes back.  Two
  ``lax.all_to_all``s per call; heads must divide by the axis size.

Both are plain differentiable functions of local shards, designed to be
called **inside** ``shard_map`` (``make_ring_attention`` /
``make_ulysses_attention`` wrap the ``shard_map`` plumbing for global
arrays).  Gradients flow through ``ppermute`` / ``all_to_all`` transposes
and the attention kernel's ``(out, lse)`` custom VJP — no hand-written
backward pass, yet the per-hop compute still runs the Pallas kernel on
TPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import _NEG_INF as _NEG_BIG, attention
from .mesh import DATA_AXIS, MODEL_AXIS


def _combine(out_a, lse_a, out_b, lse_b):
    """Merge two attention partials over disjoint key sets (online softmax).

    ``out_x`` are normalized partial outputs, ``lse_x`` the log-sum-exp of
    their (scaled) scores; the merged pair is the exact attention over the
    union of the key sets.
    """
    lse = jnp.logaddexp(lse_a, lse_b)
    w_a = jnp.exp(lse_a - lse)[..., None]
    w_b = jnp.exp(lse_b - lse)[..., None]
    return out_a * w_a + out_b * w_b, lse


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = False,
    scale: float | None = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """Exact attention over a sequence sharded on ``axis_name``.

    Call inside ``shard_map``; ``q``/``k``/``v`` are the local
    ``(B, H, S/P, D)`` shards of a global length-S sequence laid out in
    contiguous chunks along the axis.  ``scale`` defaults to the global
    head-dim rule ``1/sqrt(D)`` (identical local/global — D is unsharded).
    """
    axis = jax.lax.axis_index(axis_name)
    p_size = axis_size(axis_name)
    b, h, s_local, d = q.shape
    acc_dtype = jnp.float32

    def full_fn(q, k, v):
        return attention(q, k, v, causal=False, scale=scale, impl=impl,
                         return_lse=True)

    def diag_fn(q, k, v):
        return attention(q, k, v, causal=causal, scale=scale, impl=impl,
                         return_lse=True)

    def masked_fn(q, k, v):
        return (
            jnp.zeros(q.shape, q.dtype),
            jnp.full((b, h, s_local), _NEG_BIG, jnp.float32),
        )

    out = jnp.zeros((b, h, s_local, d), acc_dtype)
    lse = jnp.full((b, h, s_local), _NEG_BIG, jnp.float32)
    perm = [(j, (j + 1) % p_size) for j in range(p_size)]

    for step in range(p_size):
        kv_idx = (axis - step) % p_size  # which global shard (k, v) hold now
        if causal:
            # 0: strictly past → full; 1: diagonal → block-causal; 2: future
            branch = (kv_idx == axis).astype(jnp.int32) + 2 * (kv_idx > axis)
            out_t, lse_t = jax.lax.switch(
                branch, (full_fn, diag_fn, masked_fn), q, k, v
            )
        else:
            out_t, lse_t = full_fn(q, k, v)
        out, lse = _combine(out, lse, out_t.astype(acc_dtype), lse_t)
        if step + 1 < p_size:
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)
    return out.astype(q.dtype)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = False,
    scale: float | None = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style).

    Inside ``shard_map`` with the sequence sharded on ``axis_name``:
    redistribute so each device holds all S tokens of ``H/P`` heads, run
    ordinary attention (the Pallas kernel on TPU — at full sequence
    length, where it shines), then redistribute back to sequence shards.
    """
    p_size = axis_size(axis_name)
    if q.shape[1] % p_size:
        raise ValueError(
            f"ulysses needs heads ({q.shape[1]}) divisible by the axis size "
            f"({p_size})"
        )
    # (B, H, S/P, D) → (B, H/P, S, D): split heads, gather sequence
    gather = partial(
        jax.lax.all_to_all, axis_name=axis_name, split_axis=1, concat_axis=2,
        tiled=True,
    )
    scatter = partial(
        jax.lax.all_to_all, axis_name=axis_name, split_axis=2, concat_axis=1,
        tiled=True,
    )
    out = attention(
        gather(q), gather(k), gather(v), causal=causal, scale=scale, impl=impl
    )
    return scatter(out)


def _sharded_attention_call(fn, mesh: Mesh, seq_axis: str, batch_axis: str | None):
    spec = P(batch_axis, None, seq_axis, None)
    return shard_map(
        partial(fn, axis_name=seq_axis),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )


def make_ring_attention(
    mesh: Mesh,
    *,
    seq_axis: str = MODEL_AXIS,
    batch_axis: str | None = DATA_AXIS,
    causal: bool = False,
    scale: float | None = None,
    impl: str = "auto",
):
    """Global-array convenience wrapper: (B, H, S, D) with S sharded on
    ``seq_axis`` (and B on ``batch_axis``) → exact attention output, same
    sharding."""
    fn = partial(ring_attention, causal=causal, scale=scale, impl=impl)
    return _sharded_attention_call(fn, mesh, seq_axis, batch_axis)


def make_ulysses_attention(
    mesh: Mesh,
    *,
    seq_axis: str = MODEL_AXIS,
    batch_axis: str | None = DATA_AXIS,
    causal: bool = False,
    scale: float | None = None,
    impl: str = "auto",
):
    """Global-array convenience wrapper for ``ulysses_attention``."""
    fn = partial(ulysses_attention, causal=causal, scale=scale, impl=impl)
    return _sharded_attention_call(fn, mesh, seq_axis, batch_axis)


# ------------------------------------------------- sequence-parallel ViT


def sequence_vit_apply(
    model,
    variables,
    images: jnp.ndarray,
    mesh: Mesh,
    *,
    seq_impl: str = "ring",
    seq_axis: str = MODEL_AXIS,
    batch_axis: str | None = DATA_AXIS,
) -> jnp.ndarray:
    """Forward a zoo ViT with its trunk sequence-parallel over ``seq_axis``.

    The token axis is sharded across the mesh axis for the whole trunk:
    LayerNorms and MLPs are per-token (no communication), and attention
    runs as ring attention (``seq_impl="ring"``) or Ulysses all-to-all
    (``"ulysses"``) via the block's ``attn_impl`` dispatch.  Embed and
    head run as ordinary data-parallel computations via the model's own
    methods — semantically identical to ``model.apply(variables, images)``
    for any shard count.
    """
    from .pipeline import vit_stage_fn

    p_size = mesh.shape[seq_axis]
    tokens = model.apply(variables, images, method="embed")
    s = tokens.shape[1]
    if s % p_size:
        raise ValueError(
            f"sequence length {s} not divisible by the {seq_axis} axis "
            f"({p_size})"
        )
    if seq_impl == "ulysses" and model.heads % p_size:
        raise ValueError(
            f"ulysses needs heads ({model.heads}) divisible by the "
            f"{seq_axis} axis ({p_size})"
        )

    local_trunk = vit_stage_fn(model, attn_impl=f"{seq_impl}:{seq_axis}")
    stacked = variables["params"]["blocks"]
    x_spec = P(batch_axis, seq_axis, None)
    staged = shard_map(
        local_trunk,
        mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(), stacked), x_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    y = staged(stacked, tokens)
    return model.apply(variables, y, method="head_out")


def make_sequence_apply_fn(model, mesh: Mesh, *, seq_impl: str = "ring"):
    """An ``apply_fn`` drop-in for ``TrainState`` running the
    sequence-parallel forward with the train step's calling conventions."""

    def apply_fn(variables, x, train=False, mutable=()):
        logits = sequence_vit_apply(model, variables, x, mesh, seq_impl=seq_impl)
        return (logits, {}) if mutable else logits

    return apply_fn
