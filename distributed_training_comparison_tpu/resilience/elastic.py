"""Elastic restore: the device topology is a run-time variable.

A preempted run often comes back on a different slice — fewer hosts, a
different local device count, a resized mesh.  The checkpoint format was
chosen to make this cheap: ``last.ckpt`` holds *host* numpy pytrees (no
device-layout coupling, unlike sharded per-device checkpoint formats), so
restore-on-a-new-mesh is ``load_resume_state`` + ``place_tree`` with the
new mesh's shardings — the exact path the Trainer already runs, on whatever
mesh ``make_mesh`` built from the devices the relaunched process has.

What stays consistent across a topology change, and why:

- **step/epoch/best-acc** — scalars in the payload, topology-free;
- **optimizer state** — host pytrees re-placed like params;
- **PRNG** — all device-side randomness derives from
  ``fold_in(root_key, epoch/step)`` (utils/seed.py); keys are *functions of
  the trajectory*, never of a device index, so no per-device key state
  needs re-folding — a resumed epoch draws the same augmentations on 4
  devices as it would have on 8;
- **the loss trajectory** — identical up to float reduction order (batches
  are split across a different number of devices, so cross-device sums
  reassociate; ``tests/test_resilience.py`` pins allclose, not bitwise).

What legitimately changes: the global batch must still divide the new data
axis (the Trainer validates and raises with the actual numbers), and
host-streaming loaders re-shard by the new process count.

This module provides the *observability* half: record the saving topology
in the checkpoint manifest, and describe the delta at restore time.
"""

from __future__ import annotations

import os

import jax


def forced_host_device_env(n: int, base: dict | None = None) -> dict:
    """Subprocess environment forcing ``n`` virtual CPU devices — the one
    recipe behind every elastic-on-CPU child (tests,
    ``tools/chaos_matrix.py``): replace any existing
    ``--xla_force_host_platform_device_count`` in ``XLA_FLAGS`` and pin
    the CPU backend (``JAX_PLATFORMS=cpu``).  Returns a COPY of
    ``base`` (default ``os.environ``) — never mutates the caller's env,
    so nothing leaks between children or into this process."""
    env = dict(os.environ if base is None else base)
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={n}"]
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    return env


def topology() -> dict:
    """The current process's device topology, for manifests and goodput
    records."""
    return {
        "devices": jax.device_count(),
        "local_devices": jax.local_device_count(),
        "processes": jax.process_count(),
        "platform": jax.devices()[0].platform,
    }


def mesh_meta(mesh) -> dict:
    """Manifest fragment recording the mesh a checkpoint was saved under."""
    return {"mesh": dict(mesh.shape), **topology()}


class ReshardError(ValueError):
    """No legal mesh/batch split exists for the re-rendered topology.  The
    message always carries the actual numbers plus the nearest legal
    alternatives — an operator resizing a fleet at 3am acts on "use batch
    96 or run 2 hosts", not on a bare divisibility traceback."""


def _divisors(n: int, cap: int) -> list[int]:
    return [d for d in range(1, cap + 1) if n % d == 0]


def divisibility_help(
    batch_size: int, data_axis: int, grad_accum: int = 1
) -> str:
    """The actionable tail of every batch-divisibility refusal: which
    data-parallel widths THIS batch supports, and the nearest batch sizes
    that would support THIS width."""
    unit = max(1, grad_accum)
    legal_axes = (
        _divisors(batch_size // unit, max(data_axis, 1))
        if batch_size and batch_size % unit == 0
        else []
    )
    lower = (batch_size // (data_axis * unit)) * data_axis * unit
    upper = lower + data_axis * unit
    parts = [
        f"global batch {batch_size} is not divisible by "
        f"data-parallel size {data_axis}"
        + (f" x grad_accum {grad_accum}" if grad_accum > 1 else "")
    ]
    if legal_axes:
        parts.append(
            f"legal data-parallel sizes for this batch: "
            f"{legal_axes[-8:]}"
        )
    parts.append(
        f"nearest legal batch sizes at width {data_axis}: "
        f"{[b for b in (lower, upper) if b > 0]}"
    )
    return "; ".join(parts)


def microbatch_help(
    batch_size: int,
    microbatches: int,
    data_axis: int = 1,
    pipe: int | None = None,
) -> str:
    """The actionable tail of every pipeline-microbatch refusal, matching
    the batch-split error style (:func:`divisibility_help`): which
    microbatch counts THIS batch supports over THIS data axis, and — for
    the interleaved schedule — the multiple-of-P constraint with the
    counts that satisfy both."""
    d = max(1, data_axis)
    parts = []
    legal: list[int] = []
    batch_splits = bool(batch_size) and batch_size % (microbatches * d) == 0
    if batch_size:
        legal = [
            mm
            for mm in range(1, batch_size + 1)
            if batch_size % (mm * d) == 0
        ]
        # only claim a batch-split failure when the batch actually fails
        # to split — an interleaved run refused purely for micro % P must
        # not send the operator off tuning --batch-size
        if not batch_splits:
            parts.append(
                f"batch {batch_size} with --pipeline-microbatches "
                f"{microbatches} does not split into microbatch shards "
                f"over data-parallel size {d}"
            )
            if legal:
                parts.append(
                    f"legal microbatch counts for this batch: {legal[-8:]}"
                )
    if not parts:
        parts.append(
            f"--pipeline-microbatches {microbatches} is not a multiple of "
            f"the pipeline-stage count"
        )
    if pipe and pipe > 1:
        interleaved = [mm for mm in (legal or []) if mm % pipe == 0]
        parts.append(
            f"the interleaved schedule additionally needs a multiple of "
            f"the stage count {pipe}"
            + (f": {interleaved[-8:]}" if interleaved else "")
        )
    return "; ".join(parts)


def pipeline_help(depth: int, pipe: int, virtual: int = 1) -> str:
    """The actionable tail of a pipe-axis refusal: which pipeline degrees
    THIS model depth supports (at the requested virtual-stage count)."""
    v = max(1, virtual)
    legal = [p for p in range(1, depth + 1) if depth % (p * v) == 0]
    return (
        f"model depth {depth} does not split into {pipe} pipeline "
        f"stage(s) x {v} virtual stage(s); legal --pipeline-parallel "
        f"values at virtual={v}: {legal[-8:]}"
    )


def validate_reshard(
    manifest: dict | None,
    mesh,
    *,
    batch_size: int,
    grad_accum: int = 1,
    shard_optim: bool = False,
    pipeline: dict | None = None,
    state_layout: str | None = None,
) -> dict:
    """The explicit reshard step of an elastic restore: validate the saved
    mesh against the re-rendered one and the global batch against the new
    data axis, and return the reshard plan — what changed and how state
    will be re-placed.  Raises :class:`ReshardError` (with the numbers and
    the nearest legal alternatives) only when no legal split exists; a
    topology change by itself is fine, that is the whole point of the
    host-pytree checkpoint format.

    The Trainer runs this after reading the resume manifest; the fleet
    supervisor runs the same arithmetic (``parallel.mesh
    .elastic_mesh_shape`` + the divisibility rule) BEFORE launching a
    shrunk attempt, so a doomed world size is refused at the launch
    boundary, not after a full process start + compile.
    """
    now_shape = dict(mesh.shape)
    data_axis = int(now_shape.get("data", 1))
    unit = data_axis * max(1, grad_accum)
    if batch_size % unit:
        raise ReshardError(
            "elastic reshard refused: "
            + divisibility_help(batch_size, data_axis, grad_accum)
            + f" (restoring onto mesh {now_shape})"
        )
    # the pipe-axis half of the reshard step: restoring onto a CHANGED
    # pipeline degree is legal exactly when the stacked trunk re-slices
    # (depth % (pipe x virtual) == 0) and the microbatch count still
    # splits the batch over the new data axis — refuse with the numbers
    # otherwise, BEFORE tracing into a doomed staged jit
    pipe_size = int(now_shape.get("pipe", 1))
    if pipeline:
        depth = int(pipeline.get("depth", 0))
        virtual = int(pipeline.get("virtual", 1)) or 1
        micro = int(pipeline.get("microbatches", 0))
        eff_pipe = int(pipeline.get("pipe", pipe_size)) or pipe_size
        if depth and eff_pipe > 1 and depth % (eff_pipe * virtual):
            raise ReshardError(
                "pipe-axis reshard refused: "
                + pipeline_help(depth, eff_pipe, virtual)
                + f" (restoring onto mesh {now_shape})"
            )
        # the PER-UPDATE batch is what splits into microbatch shards —
        # same unit as the Trainer's own check, matching the data-axis
        # rule above (a grad_accum>1 restore refused here, at the launch
        # boundary, instead of after a full process start + compile)
        per_update = batch_size // max(1, grad_accum)
        if micro and per_update and per_update % (micro * data_axis):
            raise ReshardError(
                "pipe-axis reshard refused: "
                + microbatch_help(
                    per_update, micro, data_axis,
                    pipe=eff_pipe if virtual > 1 else None,
                )
                + f" (restoring onto mesh {now_shape})"
            )
        if virtual > 1 and micro and micro % eff_pipe:
            raise ReshardError(
                "pipe-axis reshard refused: "
                + microbatch_help(per_update, micro, data_axis, pipe=eff_pipe)
                + f" (restoring onto mesh {now_shape})"
            )
    saved_mesh = (manifest or {}).get("mesh")
    saved_devices = (manifest or {}).get("devices")
    changed = bool(manifest) and (
        saved_mesh != now_shape
        or saved_devices not in (None, jax.device_count())
    )
    # the comms-layout half of the reshard step: a checkpoint saved under
    # --shard-optim restores onto a replicated layout (and vice versa) by
    # plain re-placement — the host-pytree format carries no layout — but
    # the delta is recorded so the restore log can say so.  Manifests from
    # before the comms layer carry no key; treated as "unchanged".
    saved_shard_optim = (manifest or {}).get("shard_optim")
    saved_pipe = (saved_mesh or {}).get("pipe") if saved_mesh else None
    # the state-layout half: checkpoints are CANONICAL on disk whatever
    # resident layout the saving schedule carried (parallel/layouts.py),
    # so restoring across a layout change (v change, pp resize,
    # chunked<->contiguous) is always legal — the restoring run
    # re-residents through its own layout seam.  Recorded here so the
    # restore log and run_report can say a re-layout happened.  Old
    # manifests carry no key; treated as "unchanged".
    saved_state_layout = (manifest or {}).get("state_layout")
    now_state_layout = str(state_layout) if state_layout is not None else "contiguous"
    return {
        "changed": changed,
        "saved_mesh": saved_mesh,
        "saved_devices": saved_devices,
        "saved_processes": (manifest or {}).get("processes"),
        "mesh": now_shape,
        "devices": jax.device_count(),
        "processes": jax.process_count(),
        "per_device_batch": batch_size // data_axis,
        "saved_pipe": saved_pipe,
        "pipe": pipe_size,
        "pipe_changed": (
            saved_pipe is not None and int(saved_pipe) != pipe_size
        ),
        "saved_shard_optim": saved_shard_optim,
        "shard_optim": bool(shard_optim),
        "shard_optim_changed": (
            saved_shard_optim is not None
            and bool(saved_shard_optim) != bool(shard_optim)
        ),
        "saved_state_layout": saved_state_layout,
        "state_layout": now_state_layout,
        "state_layout_changed": (
            saved_state_layout is not None
            and str(saved_state_layout) != now_state_layout
        ),
    }


def describe_restore(manifest: dict | None, mesh) -> str | None:
    """A human-readable elastic-restore notice, or None when the topology is
    unchanged (or the checkpoint predates manifests)."""
    if not manifest:
        return None
    saved_mesh = manifest.get("mesh")
    saved_devices = manifest.get("devices")
    now = dict(mesh.shape)
    now_devices = jax.device_count()
    if saved_mesh == now and saved_devices in (None, now_devices):
        return None
    return (
        "elastic restore: checkpoint saved under mesh "
        f"{saved_mesh} ({saved_devices} devices, "
        f"{manifest.get('processes', '?')} processes) → restoring onto mesh "
        f"{now} ({now_devices} devices, {jax.process_count()} processes); "
        "host-pytree state re-sharded, PRNG trajectory unchanged"
    )
