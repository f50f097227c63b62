"""Device mesh construction.

The mesh is the single source of truth for topology.  Axes:

- ``"data"``  — batch-parallel axis (the reference's DP/DDP world),
- ``"model"`` — tensor-parallel axis (reference has none; size 1 for parity
  configs),
- ``"pipe"``  — pipeline-parallel axis (``--pipeline-parallel``; size 1
  unless a run stages the transformer trunk).  A dedicated axis, NOT the
  ``model`` axis doing double duty, so DP×TP×PP meshes exist and model
  size is no longer capped by one tensor-parallel group's HBM.

``jax.experimental.mesh_utils.create_device_mesh`` orders devices so that
neighboring mesh coordinates are ICI neighbors — collectives ride ICI rings
rather than hopping arbitrary links.  The ``pipe`` axis is last so that
consecutive pipeline stages are ICI neighbors and the per-tick ``ppermute``
activation handoff is one hop.
"""

from __future__ import annotations

import logging
from typing import Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"

# a child of the run logger (utils/logging.py): lines reach experiment.log
# once a Trainer has set that up; the reshape fallback warns either way
_log = logging.getLogger("dtc_tpu.mesh")


def mesh_shape_for_backend(
    backend: str,
    num_devices: int,
    model_parallel: int = 1,
    pipeline_parallel: int = 1,
) -> tuple[int, int, int]:
    """(data, model, pipe) mesh shape for a named backend variant.

    ``single`` pins a 1×1×1 mesh (reference ``src/single/``); ``dp``/
    ``ddp``/``tpu`` use every available device on the data axis, divided by
    any tensor-parallel × pipeline-parallel degree.
    """
    if backend == "single":
        return (1, 1, 1)
    cells = model_parallel * pipeline_parallel
    if num_devices % cells != 0:
        raise ValueError(
            f"num_devices={num_devices} not divisible by model_parallel="
            f"{model_parallel} x pipeline_parallel={pipeline_parallel}"
        )
    return (num_devices // cells, model_parallel, pipeline_parallel)


def elastic_mesh_shape(
    num_devices: int, model_parallel: int = 1, pipeline_parallel: int = 1
) -> tuple[int, int, int] | None:
    """Re-derive the ``(data, model, pipe)`` axes for a RE-RENDERED device
    count (elastic shrink/expand), or ``None`` when no legal mesh exists at
    that count — the model/pipe axes cannot shrink below the tensor-/
    pipeline-parallel degrees, and the devices must tile them evenly.  The
    elastic supervisor uses this to pick the widest legal world size before
    launching an attempt, and ``resilience/elastic.py::validate_reshard``
    to refuse (with numbers) instead of tracing into a doomed jit."""
    if num_devices < 1 or model_parallel < 1 or pipeline_parallel < 1:
        return None
    cells = model_parallel * pipeline_parallel
    if num_devices < cells or num_devices % cells:
        return None
    # one source of truth for the axis arithmetic: the same function every
    # mesh construction goes through (this wrapper only adds None-on-illegal)
    return mesh_shape_for_backend(
        "tpu", num_devices, model_parallel, pipeline_parallel
    )


def make_mesh(
    num_devices: int = 0,
    model_parallel: int = 1,
    pipeline_parallel: int = 1,
    *,
    backend: str = "tpu",
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build the global ``("data", "model", "pipe")`` mesh.

    ``num_devices=0`` means all addressable devices (across every host when
    running under ``jax.distributed``).  ``pipeline_parallel=1`` (the
    default) leaves the pipe axis trivial, so every pre-pipeline config
    sees exactly the layouts it always did — ``PartitionSpec``s name axes,
    and an unnamed size-1 axis shards nothing.
    """
    if devices is None:
        devices = jax.devices()
    if num_devices:
        if num_devices > len(devices):
            raise ValueError(f"requested {num_devices} devices, have {len(devices)}")
        devices = devices[:num_devices]
    shape = mesh_shape_for_backend(
        backend, len(devices), model_parallel, pipeline_parallel
    )
    n_used = shape[0] * shape[1] * shape[2]
    if n_used != len(devices):
        devices = devices[:n_used]
    kind = f"{len(devices)} {devices[0].device_kind} device(s)"
    try:
        dev_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
        _log.info(
            "make_mesh: %s over %s, device array built by "
            "mesh_utils.create_device_mesh", shape, kind,
        )
    except (ValueError, AssertionError) as e:
        # create_device_mesh can reject shapes that don't tile the physical
        # topology; a plain reshape is always valid, but its axis
        # neighbours need not be interconnect neighbours — say so.
        dev_array = np.asarray(list(devices)).reshape(shape)
        _log.warning(
            "make_mesh: %s over %s, create_device_mesh refused (%s): "
            "device array built by plain reshape", shape, kind, e,
        )
    return Mesh(dev_array, (DATA_AXIS, MODEL_AXIS, PIPE_AXIS))
