"""The eager execution rail: any planned layout's train step, no ``jit``.

The compiled stack proves its transforms — GSPMD layouts, ZeRO re-layout,
compressed wire, pipeline schedules — against each other, but every one of
those proofs runs through XLA.  veScale (arxiv 2509.07003) argues the
reference semantics for a distributed program is the EAGER one: the same
math executed op by op, no whole-program fusion, no GSPMD partitioner in
the loop.  This module is that rail.

It is deliberately not a second implementation.  The eager step *is*
``train/step.py``'s ``_make_step_core`` — the exact augment → normalize →
fwd/bwd → guards → update pipeline every compiled runner traces — simply
called without ``jax.jit``, so jax dispatches one op at a time on the
default device.  The comms transforms are likewise the real ones:

- **wire tiers** — ``EagerComms`` inherits ``Comms.apply_gradients``
  verbatim, so the fp16/int8 quantize → error-feedback → dequant recipe
  (``comms.quantize_tree``) is shared code, not a port;
- **ZeRO partition** — sharding never changes a value, only a layout
  (``parallel/comms.py`` docstring), so the eager reference drops the
  reduce-scatter/all-gather constraints and keeps the elementwise update:
  the parity diff against the compiled ZeRO run is then precisely the
  test that the layout claim holds on real hardware;
- **ring/sequence styles** — the eager reference is the plain
  ``model.apply`` that ``parallel/ring.py`` pins itself against: the ring
  ``ppermute`` schedule and the Ulysses ``all_to_all`` are layout-moves
  around the same attention math.

Seeding is the existing ``fold_in`` key-table (``host_step_key`` /
``device_step_keys`` mirror the chunk runners' derivations exactly), so
batch ``k`` of step ``s`` is bit-identical input on both rails.

What the rail does NOT cover: the wire-true compressed pipeline
(``--pipeline-schedule 1f1b/interleaved`` + ``--grad-comms fp16/int8``),
whose per-device error-feedback residual lives in the schedule layout —
``eager_comms_like`` returns ``NotImplemented``-style ``None`` with
``wire_inline`` set and ``parity/diff.py`` records the reference gate as
``unsupported`` (the bitwise replay gate still runs for those layouts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..data.cifar100 import CIFAR100_MEAN, CIFAR100_STD
from ..data.sampler import epoch_permutation
from ..parallel import comms as comms_mod
from ..train.step import _make_step_core


class EagerComms(comms_mod.Comms):
    """``Comms`` with the layout constraints stripped: ``apply_gradients``
    (quantize → error feedback → dequant → elementwise update) is inherited
    UNCHANGED — same code object, one implementation — while the ZeRO
    reduce-scatter/all-gather pins become identity.  Values are unchanged
    by construction (sharding is layout, not math); what remains is exactly
    the value-relevant part of the comms plan, runnable on one device with
    no mesh in the loop."""

    def _constrain_zero(self, tree):
        return tree

    def _constrain_params(self, tree):
        return tree


def eager_comms_like(comms) -> EagerComms | None:
    """The eager twin of a trainer's comms plan, or ``None`` when no plan
    is active (the plain ``TrainState.apply_gradients`` path) — and also
    ``None`` for ``wire_inline`` plans (the wire-true compressed pipeline),
    which the eager rail does not model; callers must check
    ``comms.wire_inline`` to tell the two Nones apart."""
    if comms is None or not comms.active or comms.wire_inline:
        return None
    return EagerComms(
        comms.mesh,
        param_shardings=None,
        shard_optim=comms.shard_optim,
        grad_comms=comms.grad_comms,
        wire_inline=False,
    )


def make_eager_step(
    *,
    precision: str = "fp32",
    augment: bool = True,
    mean=CIFAR100_MEAN,
    std=CIFAR100_STD,
    grad_accum: int = 1,
    comms: EagerComms | None = None,
):
    """Build the eager ``(state, images_u8, labels, key, fault_scale) ->
    (state, metrics)`` step.

    This is ``_make_step_core`` with every sharding hint absent
    (``accum_sharding=None``, ``repl_sharding=None`` — both are layout
    pins, not math) and NO ``jax.jit`` around it: calling the result
    executes the pipeline op by op.  ``fault_scale`` is the same trailing
    seam the compiled runners trace (multiply by exactly 1.0 is
    IEEE-exact, so a benign scale leaves the trajectory untouched).

    For pipeline/sequence layouts pass a state whose ``apply_fn`` is the
    PLAIN ``model.apply`` (``eager_state_like``): the schedule/ring
    rewrites are layout transforms around that same forward, which is what
    makes the diff against them meaningful.
    """
    core = _make_step_core(
        precision, augment, mean, std, grad_accum, None, None, comms, None
    )

    def step(state, images, labels, key, fault_scale=None):
        images = jnp.asarray(images)
        labels = jnp.asarray(labels)
        if fault_scale is not None:
            fault_scale = jnp.asarray(fault_scale, jnp.float32)
        return core(state, images, labels, key, fault_scale)

    return step


def eager_state_like(state_host, apply_fn):
    """A host-side state ready for the eager rail: same leaves (the
    capture's initial snapshot), but ``apply_fn`` swapped to the plain
    un-scheduled forward so pipeline/sequence layouts replay through
    their reference semantics."""
    return state_host.replace(apply_fn=apply_fn)


# --------------------------------------------------------------- key table
#
# The two data modes derive their per-step keys differently; these helpers
# ARE those derivations (same fold graph, same constants), so the eager
# rail feeds bit-identical keys/batches without touching the runners.


def host_step_key(data_key, epoch: int, step: int):
    """Host/streaming mode: ``fold_in(fold_in(data_key, epoch), step)`` —
    the chunk runner's in-scan fold with the GLOBAL step index
    (``make_chunk_runner``)."""
    return jax.random.fold_in(
        jax.random.fold_in(data_key, epoch), step
    )


def device_step_keys(data_key, epoch: int, steps: int):
    """Device mode: ``split(fold_in(fold_in(data_key, epoch), 1), steps)``
    — the epoch's key table, one key a step, out of which every
    ``make_device_chunk_runner`` dispatch slices its rows."""
    epoch_key = jax.random.fold_in(data_key, epoch)
    return jax.random.split(jax.random.fold_in(epoch_key, 1), steps)


def device_epoch_rows(data_key, epoch: int, n: int, batch_size: int):
    """Device mode's per-step sample rows: the epoch permutation truncated
    to whole batches and reshaped ``(steps, batch)`` — exactly the gather
    index table the scanned runners slice."""
    steps = n // batch_size
    perm = epoch_permutation(data_key, epoch, n)[: steps * batch_size]
    return perm.reshape(steps, batch_size)
