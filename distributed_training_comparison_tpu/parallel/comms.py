"""Communications layer: ZeRO-style sharded weight updates + compressed
gradient sync.

Two redundancies survive in plain data parallelism, and this module
removes both:

- **Every replica applies the full weight update.**  Params are replicated
  over the ``data`` axis, so each device redundantly holds the whole
  optimizer state and redundantly computes the whole update — the exact
  waste "Automatic Cross-Replica Sharding of Weight Update in
  Data-Parallel Training" (arxiv 2004.13336) eliminates in this same
  TPU/XLA setting.  ``--shard-optim`` expresses the ZeRO decomposition as
  sharding constraints: gradients are pinned to a data-axis layout at the
  update boundary (the all-reduce the backward already owes fuses with the
  slice into a **reduce-scatter**), the optimizer step runs on each
  device's 1/N shard (the momentum ``trace`` is *carried* data-sharded
  between dispatches, so per-device optimizer-state HBM shrinks ~1/N —
  visible in the compile-event memory ledger as smaller argument bytes),
  and the updated params are constrained back to their own layout (an
  **all-gather**).  Everything is ``with_sharding_constraint``, so the
  decomposition composes with the existing DP×TP meshes: a leaf already
  sharded over ``model`` gains the ``data`` axis on a *free* dimension.
- **Gradient sync moves fp32.**  ``--grad-comms {fp32,fp16,int8}``
  quantizes the gradient at the sync boundary with an error-feedback
  residual carried in the train state (the DynamiQ recipe, arxiv
  2602.08923): ``g_eff = g + r``; quantize; the dequantization error
  becomes the next step's residual, so compression noise accumulates into
  later updates instead of being lost — int8 tracks the fp32 loss
  trajectory instead of stalling.  Under ``--shard-optim`` the quantized
  payload (int8 tensor / fp16 tensor; the per-leaf scale is one replicated
  fp32 scalar) is what crosses the reduce-scatter boundary, so the
  resharded bytes are genuinely 1/4 (int8) or 1/2 (fp16) of fp32.

Honesty note for the GSPMD formulation: the backward's cross-replica
all-reduce is inserted by XLA *inside* the compiled step, upstream of any
code this module can run, and it reduces in the gradient dtype (fp32).
What the quantization provably bounds is (a) the numerics — pinned by the
bit-equivalence tests — and (b) the bytes of the reduce-scatter/all-gather
legs the ZeRO decomposition introduces.  A formulation that compresses the
*whole* sync wire needs to own its backward; ``make_compressed_allreduce``
below provides that primitive (a ``shard_map`` all-reduce whose wire dtype
really is fp16/int8, with int8 accumulating in int32 under a shared
``pmax`` scale) for runners that do (the ``fwd_bwd`` hook, pipeline
schedules); ``tests/test_comms.py`` prices both against the compile ledger.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS

GRAD_COMMS_MODES = ("fp32", "fp16", "int8")

# int8 wire format: symmetric, per-leaf scale = amax/127 (the full int8
# range minus the asymmetric -128, so quantization is sign-symmetric and
# dequantization needs one multiply)
_INT8_LEVELS = 127.0
# fp16 wire saturates at the format's max finite value: a finite fp32
# gradient past 65504 must clip, not overflow to inf — an inf on the wire
# would dequantize into the update and poison params PAST the numerics
# guard (which checks the RAW pre-compression grads); with error feedback
# the clipped excess lands in the residual and re-injects next step
_FP16_MAX = 65504.0
# amax floor: an all-zero gradient leaf must not divide by zero; anything
# at this magnitude quantizes to zero either way
_SCALE_FLOOR = 1e-30


def _is_float(leaf) -> bool:
    dtype = getattr(leaf, "dtype", None)
    if dtype is None:
        dtype = jnp.result_type(leaf)
    return jnp.issubdtype(dtype, jnp.floating)


class _NoBase:
    """Sentinel leaf for "no base sharding known" in opt-state trees —
    ``None`` itself is an empty pytree node, so it cannot ride a
    ``tree_map`` over a tree that has a real leaf in that position."""

    spec = None


_NO_BASE = _NoBase()


def zero_partition_spec(shape, base_spec, data_size: int) -> P:
    """The ZeRO shard rule for one leaf: add ``DATA_AXIS`` to the largest
    *free* dimension the data axis tiles evenly, leaving any existing
    assignment (tensor-parallel ``model`` shards, pipeline ``stage``
    layouts) untouched.  Leaves with no such dimension (scalars, odd
    shapes) stay on their base spec — sharding must never change a
    value, only a layout.
    """
    base = tuple(base_spec) if base_spec is not None else ()
    base = base + (None,) * (len(shape) - len(base))
    axes_in_use = set()
    for entry in base:
        if isinstance(entry, (tuple, list)):
            axes_in_use.update(entry)
        elif entry is not None:
            axes_in_use.add(entry)
    if data_size <= 1 or DATA_AXIS in axes_in_use:
        return P(*base)
    best = None
    for i, dim in enumerate(shape):
        if base[i] is not None or not dim or dim % data_size:
            continue
        if best is None or dim > shape[best]:
            best = i
    if best is None:
        return P(*base)
    parts = list(base)
    parts[best] = DATA_AXIS
    return P(*parts)


def zero_opt_shardings(mesh: Mesh, opt_state, base_shardings=None):
    """``NamedSharding``s carrying the optimizer state data-sharded: the
    momentum ``trace`` (param-shaped) shards per :func:`zero_partition_spec`;
    scalar leaves (schedule counts) stay replicated.  ``base_shardings`` —
    an opt-state-shaped tree of the current layout (tensor-parallel runs
    pass it so the ``model`` assignment survives); ``None`` = replicated
    base.  The Trainer swaps this tree into ``state_sharding.opt_state``
    under ``--shard-optim``, which is ALL the re-layout takes: the jitted
    runners carry the state between dispatches with these in/out
    shardings, and checkpoints stay bit-compatible because save/restore
    already round-trips host pytrees (``place_tree`` re-lays them out
    under whatever the restoring run's shardings are — the reshard step).
    """
    data_size = int(mesh.shape.get(DATA_AXIS, 1))

    def one(leaf, base) -> NamedSharding:
        spec = getattr(base, "spec", None)
        shape = getattr(leaf, "shape", ())
        return NamedSharding(mesh, zero_partition_spec(shape, spec, data_size))

    if base_shardings is None:
        return jax.tree_util.tree_map(lambda l: one(l, _NO_BASE), opt_state)
    return jax.tree_util.tree_map(one, opt_state, base_shardings)


def opt_state_bytes(opt_state, shardings=None) -> tuple[int, int]:
    """``(total_bytes, per_device_bytes)`` of an optimizer-state pytree —
    the host-side arithmetic behind the ``comms/opt_state_bytes*`` gauges
    and their expected saving under ``--shard-optim``.  ``shardings`` must be a
    matching tree of ``NamedSharding``s (the mesh on each one supplies
    the axis sizes the division needs — a bare ``PartitionSpec`` carries
    no mesh and would silently count as replicated); ``None`` =
    replicated (per-device == total)."""
    total = per_device = 0
    leaves = jax.tree_util.tree_leaves(opt_state)
    shard_leaves = (
        jax.tree_util.tree_leaves(shardings)
        if shardings is not None
        else [None] * len(leaves)
    )
    for leaf, sh in zip(leaves, shard_leaves):
        size = int(np.prod(getattr(leaf, "shape", ()) or (1,)))
        nbytes = size * jnp.dtype(getattr(leaf, "dtype", jnp.float32)).itemsize
        total += nbytes
        factor = 1
        spec = getattr(sh, "spec", sh) if sh is not None else None
        mesh = getattr(sh, "mesh", None)
        if spec is not None and mesh is not None:
            for entry in spec:
                names = entry if isinstance(entry, (tuple, list)) else (entry,)
                for name in names:
                    if name is not None:
                        factor *= int(dict(mesh.shape).get(name, 1))
        per_device += nbytes // max(1, factor)
    return total, per_device


def quantize_tree(tree, mode: str):
    """Quantize a float pytree to the ``mode`` wire format.

    Returns ``(wire, dequant)``: ``wire`` holds the compressed payload
    (fp16 tensors, or int8 tensors whose per-leaf fp32 scale the closure
    retains), ``dequant(wire_like)`` maps a tree of the same structure —
    at ANY sharding — back to fp32.  Non-float leaves pass through
    untouched.  The error-feedback identity the tests pin:
    ``residual = tree - dequant(wire)`` is exactly the information the
    wire dropped.
    """
    if mode not in GRAD_COMMS_MODES:
        raise ValueError(
            f"grad-comms mode must be one of {GRAD_COMMS_MODES}, got {mode!r}"
        )
    if mode == "fp32":
        return tree, lambda w: w
    isf = jax.tree_util.tree_map(_is_float, tree)
    if mode == "fp16":
        wire = jax.tree_util.tree_map(
            lambda g, f: (
                jnp.clip(g, -_FP16_MAX, _FP16_MAX).astype(jnp.float16)
                if f
                else g
            ),
            tree,
            isf,
        )
        dequant = lambda w: jax.tree_util.tree_map(  # noqa: E731
            lambda q, f: q.astype(jnp.float32) if f else q, w, isf
        )
        return wire, dequant
    # int8: symmetric per-leaf scale; the scale is a replicated fp32
    # scalar (4 bytes), the payload the int8 tensor
    scales = jax.tree_util.tree_map(
        lambda g, f: (
            jnp.maximum(jnp.max(jnp.abs(g), initial=0.0), _SCALE_FLOOR)
            / _INT8_LEVELS
            if f
            else jnp.float32(1.0)
        ),
        tree,
        isf,
    )
    wire = jax.tree_util.tree_map(
        lambda g, s, f: (
            jnp.clip(jnp.round(g / s), -_INT8_LEVELS, _INT8_LEVELS).astype(
                jnp.int8
            )
            if f
            else g
        ),
        tree,
        scales,
        isf,
    )
    dequant = lambda w: jax.tree_util.tree_map(  # noqa: E731
        lambda q, s, f: q.astype(jnp.float32) * s if f else q, w, scales, isf
    )
    return wire, dequant


class Comms:
    """The per-run communications plan, built once by the Trainer from
    ``(mesh, param shardings, --shard-optim, --grad-comms)`` and threaded
    into every step maker (``train/step.py`` ``comms=``).

    ``active == False`` (both flags off) makes the makers treat it as
    absent — the benign path's traced update is byte-identical to a run
    without this module, which the executable-fingerprint test pins.
    """

    def __init__(
        self,
        mesh: Mesh,
        param_shardings=None,
        *,
        shard_optim: bool = False,
        grad_comms: str = "fp32",
        wire_inline: bool = False,
    ) -> None:
        if grad_comms not in GRAD_COMMS_MODES:
            raise ValueError(
                f"grad-comms mode must be one of {GRAD_COMMS_MODES}, "
                f"got {grad_comms!r}"
            )
        self.mesh = mesh
        self.shard_optim = bool(shard_optim)
        self.grad_comms = grad_comms
        # wire_inline: a runner that OWNS its backward (the pipeline
        # fwd_bwd) already carried the gradients over the compressed wire
        # inside its schedule (``wire_psum``, error feedback included) —
        # apply_gradients must then NOT re-quantize the already-synced
        # grads (double compression) and leaves the residual to the step
        # core, which installs the schedule's own
        self.wire_inline = bool(wire_inline)
        # params-shaped tree of NamedShardings (None = fully replicated):
        # the base layout the ZeRO rule extends and the all-gather restores
        self.param_shardings = param_shardings

    @property
    def active(self) -> bool:
        return self.shard_optim or self.grad_comms != "fp32"

    @property
    def compressing(self) -> bool:
        return self.grad_comms != "fp32"

    @property
    def wire_bits(self) -> int:
        return {"fp32": 32, "fp16": 16, "int8": 8}[self.grad_comms]

    # ------------------------------------------------------------- layout

    def _param_spec_tree(self, like):
        if self.param_shardings is None:
            return jax.tree_util.tree_map(lambda _: P(), like)
        return jax.tree_util.tree_map(
            lambda s: getattr(s, "spec", P()), self.param_shardings
        )

    def _constrain_zero(self, tree):
        """Pin a params-shaped tree to the ZeRO data-sharded layout — the
        reduce-scatter boundary.  The payload dtype at this point is the
        wire dtype (int8/fp16 under compression), so the resharded bytes
        are the compressed ones."""
        data_size = int(self.mesh.shape.get(DATA_AXIS, 1))
        specs = self._param_spec_tree(tree)

        def one(x, base_spec):
            if not hasattr(x, "shape"):
                return x
            spec = zero_partition_spec(x.shape, base_spec, data_size)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, spec)
            )

        return jax.tree_util.tree_map(one, tree, specs)

    def _constrain_params(self, tree):
        """Pin updated params back to their own layout — the all-gather."""
        specs = self._param_spec_tree(tree)
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, s if s is not None else P())
            ),
            tree,
            specs,
        )

    # ------------------------------------------------------------- update

    def apply_gradients(self, state, *, grads, batch_stats):
        """The comms-aware replacement for ``TrainState.apply_gradients``:
        (compress with error feedback) → (reduce-scatter) → per-shard
        optimizer step → (all-gather).  Traced inside the scanned runners,
        so XLA schedules the quantization against the rest of the step —
        the overlap is the compiler's, not a host thread's."""
        residual = state.comms_residual
        new_residual = residual
        if self.compressing and not self.wire_inline:
            if residual is not None:
                # error feedback: re-inject what earlier wires dropped
                grads = jax.tree_util.tree_map(jnp.add, grads, residual)
            wire, dequant = quantize_tree(grads, self.grad_comms)
            if residual is not None:
                new_residual = jax.tree_util.tree_map(
                    jnp.subtract, grads, dequant(wire)
                )
            if self.shard_optim:
                wire = self._constrain_zero(wire)
            grads = dequant(wire)
        elif self.shard_optim:
            grads = self._constrain_zero(grads)
        updates, new_opt_state = state.tx.update(
            grads, state.opt_state, state.params
        )
        new_params = optax.apply_updates(state.params, updates)
        if self.shard_optim:
            new_params = self._constrain_params(new_params)
        return state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=batch_stats,
            opt_state=new_opt_state,
            comms_residual=new_residual,
        )

    def residual_init(self, params):
        """Zero error-feedback residual, params-shaped (the Trainer
        attaches it to the state when compression is on; it is NOT
        checkpointed — a resumed run restarts with a clean residual,
        which costs at most one step's quantization error)."""
        return jax.tree_util.tree_map(jnp.zeros_like, params)

    # ------------------------------------------------------------- gauges

    def summary(self, params, opt_state, opt_shardings=None) -> dict:
        """Host-side static accounting for the ``comms/*`` gauges: the
        wire width, the bytes one gradient sync moves at that width, and
        the optimizer-state footprint total vs per-device under the ZeRO
        layout (equal when ``--shard-optim`` is off).

        ``opt_shardings`` — the opt-state sharding tree the run ACTUALLY
        carries (the Trainer passes the tree it installed into
        ``state_sharding.opt_state``), so the gauges price the real
        layout; when absent (standalone use) the tree is re-derived via
        the same suffix-matching rule."""
        sync_bytes = 0
        wire_itemsize = self.wire_bits // 8
        for leaf in jax.tree_util.tree_leaves(params):
            size = int(np.prod(getattr(leaf, "shape", ()) or (1,)))
            if _is_float(leaf):
                sync_bytes += size * wire_itemsize
                if self.grad_comms == "int8":
                    sync_bytes += 4  # the per-leaf fp32 scale
            else:
                sync_bytes += size * jnp.dtype(leaf.dtype).itemsize
        shardings = None
        if self.shard_optim:
            shardings = opt_shardings
            if shardings is None:
                shardings = zero_opt_shardings(
                    self.mesh,
                    opt_state,
                    (
                        None
                        if self.param_shardings is None
                        else _opt_base_shardings(
                            opt_state, self.param_shardings
                        )
                    ),
                )
        total, per_device = opt_state_bytes(opt_state, shardings)
        return {
            "wire_bits": self.wire_bits,
            "grad_sync_bytes": sync_bytes,
            "opt_state_bytes": total,
            "opt_state_bytes_per_device": per_device,
        }


def _opt_base_shardings(opt_state, param_shardings):
    """Project the param layout onto the opt-state tree by key-path
    suffix (the momentum ``trace`` mirrors the param tree) — the same
    matching rule ``parallel.tp.build_state_shardings`` uses.  Leaves
    without a param suffix match (schedule counts) get ``None``."""
    from .tp import _key_names

    suffix_map = {}
    for kp, sh in jax.tree_util.tree_flatten_with_path(param_shardings)[0]:
        suffix_map[_key_names(kp)] = sh

    def lookup(key_path, _leaf):
        names = _key_names(key_path)
        for start in range(len(names)):
            hit = suffix_map.get(names[start:])
            if hit is not None:
                return hit
        return _NO_BASE

    return jax.tree_util.tree_map_with_path(lookup, opt_state)


# ----------------------------------------------------- wire-true collectives


def wire_psum(tree, axis: str, mode: str = "fp32", *, residual=None):
    """The in-``shard_map`` form of :func:`make_compressed_allreduce` — a
    quantized gradient SUM over ``axis`` for schedule bodies that already
    run inside a manual mesh (the pipeline fwd_bwd, ``parallel/pipeline
    .py``), with optional per-device error feedback.

    Same wire formats (fp16 saturating cast; int8 with a shared
    ``pmax``-agreed scale accumulating in int32), same DynamiQ recipe as
    ``Comms.apply_gradients``: ``eff = g + residual``, the wire carries
    ``quantize(eff)``, and ``eff - dequant(wire)`` — exactly the
    information the wire dropped — becomes the next step's residual.
    Returns ``(summed, new_residual)``; ``residual=None`` skips the
    feedback (``new_residual`` comes back ``None``), and ``mode="fp32"``
    is a plain ``psum`` with the residual passed through untouched.
    Non-float leaves always cross uncompressed."""
    if mode not in GRAD_COMMS_MODES:
        raise ValueError(
            f"grad-comms mode must be one of {GRAD_COMMS_MODES}, got {mode!r}"
        )
    if mode == "fp32":
        return (
            jax.tree_util.tree_map(lambda g: jax.lax.psum(g, axis), tree),
            residual,
        )
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    r_leaves = (
        [None] * len(leaves)
        if residual is None
        else jax.tree_util.tree_leaves(residual)
    )
    summed, new_r = [], []
    for g, r in zip(leaves, r_leaves):
        if not _is_float(g):
            summed.append(jax.lax.psum(g, axis))
            new_r.append(r)
            continue
        eff = g.astype(jnp.float32) + (0.0 if r is None else r)
        if mode == "fp16":
            wire = jnp.clip(eff, -_FP16_MAX, _FP16_MAX).astype(jnp.float16)
            new_r.append(eff - wire.astype(jnp.float32))
            summed.append(jax.lax.psum(wire, axis).astype(jnp.float32))
        else:
            amax = jax.lax.pmax(jnp.max(jnp.abs(eff), initial=0.0), axis)
            scale = jnp.maximum(amax, _SCALE_FLOOR) / _INT8_LEVELS
            q = jnp.clip(
                jnp.round(eff / scale), -_INT8_LEVELS, _INT8_LEVELS
            ).astype(jnp.int8)
            new_r.append(eff - q.astype(jnp.float32) * scale)
            summed.append(
                jax.lax.psum(q.astype(jnp.int32), axis).astype(jnp.float32)
                * scale
            )
    out = jax.tree_util.tree_unflatten(treedef, summed)
    if residual is None:
        return out, None
    return out, jax.tree_util.tree_unflatten(treedef, new_r)


def make_compressed_allreduce(
    mesh: Mesh, mode: str = "fp16", *, axis: str = DATA_AXIS, mean: bool = True
):
    """A quantized all-reduce whose WIRE really carries the low-bit
    payload — the ``shard_map`` primitive for runners that own their
    backward (the ``fwd_bwd`` hook, pipeline schedules) and therefore
    hold per-shard partial gradients GSPMD has not already reduced.

    Input: a pytree whose leaves carry a leading per-shard axis of size
    ``mesh.shape[axis]`` (shard ``i``'s partial at index ``i``), laid out
    over ``axis``.  Output: the replicated reduction (mean by default).
    Wire semantics per mode:

    - ``fp32`` — plain ``psum`` (the uncompressed baseline);
    - ``fp16`` — cast, ``psum`` accumulating in fp16 (the honest low-bit
      wire: both payload AND accumulator are half precision);
    - ``int8`` — shared scale via ``pmax`` of the per-shard amax (one
      scalar collective), symmetric int8 quantization, ``psum``
      accumulating in int32 (no overflow up to 2^24 shards), one
      dequantizing multiply.
    """
    if mode not in GRAD_COMMS_MODES:
        raise ValueError(
            f"grad-comms mode must be one of {GRAD_COMMS_MODES}, got {mode!r}"
        )
    n = int(mesh.shape[axis])

    def body(tree):
        def one(x):
            local = x.reshape(x.shape[1:])  # (1, ...) local block
            if mode == "fp32" or not jnp.issubdtype(local.dtype, jnp.floating):
                total = jax.lax.psum(local, axis)
            elif mode == "fp16":
                # saturate the cast; ACCUMULATION overflow across shards
                # remains a property of an honest fp16-wire all-reduce
                total = jax.lax.psum(
                    jnp.clip(local, -_FP16_MAX, _FP16_MAX).astype(
                        jnp.float16
                    ),
                    axis,
                ).astype(jnp.float32)
            else:
                amax = jax.lax.pmax(
                    jnp.max(jnp.abs(local), initial=0.0), axis
                )
                scale = jnp.maximum(amax, _SCALE_FLOOR) / _INT8_LEVELS
                q = jnp.clip(
                    jnp.round(local / scale), -_INT8_LEVELS, _INT8_LEVELS
                ).astype(jnp.int8)
                total = (
                    jax.lax.psum(q.astype(jnp.int32), axis).astype(jnp.float32)
                    * scale
                )
            return total / n if mean else total

        return jax.tree_util.tree_map(one, tree)

    return jax.jit(
        shard_map(body, mesh=mesh, in_specs=P(axis), out_specs=P())
    )
