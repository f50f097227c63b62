"""The Trainer: fit / validate / test over a device mesh.

Parity: reference ``Trainer`` (``src/single/trainer.py:18-228``,
``src/ddp/trainer.py:20-252``) — constructor wires model/optimizer/data/
logging/checkpointing; ``fit`` runs the epoch loop with per-``eval_step``
train-loss logging, per-epoch validation, best-checkpoint saving and LR
stepping; ``test`` reports loss/top-1/top-5.

One Trainer serves every variant (the reference maintains three ~95%%
identical copies): the mesh shape — (1,1) single, (n,1) data-parallel,
multi-host after ``jax.distributed.initialize`` — is the only difference.

TPU-native structure of ``fit``:

- the epoch runs as chunked ``lax.scan`` dispatches over the HBM-resident
  dataset (``make_device_chunk_runner``; ``--device-chunk-steps`` defaults
  to the whole epoch — ONE device program, the original design); the host
  fetches the stacked per-step losses once per epoch — the reference's
  per-step ``loss.item()`` sync (``src/single/trainer.py:147``) and
  per-step H2D copies disappear.  Runners donate the input state (no
  per-dispatch state copy), and the streaming path stages chunks to the
  device from a background thread (``DevicePrefetcher``) so H2D transfer
  hides behind compute;
- the reference's every-``eval_step``-global-steps log lines are
  reconstructed exactly from the stacked loss array after the fact;
- validation/test use a padded fixed-shape batch + weight mask so every
  example counts once on any mesh (fixes SURVEY.md §5 quirk 1);
- process-0 gating covers logging/TB/checkpoints (``src/ddp/trainer.py``
  rank-0 gates), but metrics are already global — no local-loss-only
  logging quirk.
"""

from __future__ import annotations

import bisect
import os
import time
from collections import deque
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..config import (
    DEVICE_PREFETCH_DEFAULT,
    HOST_CHUNK_STEPS_DEFAULT,
    WORKERS_DEFAULT,
)
from ..data import (
    DevicePrefetcher,
    HostLoader,
    PrefetchLoader,
    chunked_batches,
)
from ..data.cifar100 import CIFAR100_MEAN, CIFAR100_STD, IMAGENET_MEAN, IMAGENET_STD
from ..health import HealthConfig, Watchdog, check_desync, param_fingerprint, write_health
from ..models import get_model, model_cli_options
from ..parallel import is_main_process, make_mesh, state_shardings
from ..parallel import comms as comms_mod
from ..parallel import layouts as layouts_mod
from ..parallel.sharding import (
    fetch_to_host,
    host_local_batch_slice,
    needs_collective_fetch,
    place_tree,
    put_replicated,
    shard_batch,
)
from ..resilience import (
    FaultPlan,
    GoodputMeter,
    MidEpochRollback,
    Preempted,
    PreemptionHandler,
    read_and_hash,
    read_manifest,
    verify_checkpoint,
)
from ..resilience import elastic, goodput as goodput_mod
from ..utils import AverageMeter, StepTimeMeter, fix_seed, setup_logger
from ..utils.tensorboard import SummaryWriter
from . import checkpoint as ckpt
from .async_ckpt import AsyncCheckpointer
from .optim import configure_optimizers
from .state import create_train_state
from .task import task_of
from .step import (
    DECAY_GAUGES,
    LAYER_GAUGES,
    make_chunk_runner,
    make_device_chunk_runner,
    make_device_replay_step,
    make_eval_runner,
    make_replay_step,
    observed_jit,
)


def _pad_batches(images: np.ndarray, labels: np.ndarray, batch_size: int):
    """Pad a split to a whole number of fixed-shape batches + weight mask."""
    n = len(images)
    nb = -(-n // batch_size)
    pad = nb * batch_size - n
    if pad:
        images = np.concatenate([images, np.repeat(images[:1], pad, axis=0)])
        labels = np.concatenate([labels, np.repeat(labels[:1], pad, axis=0)])
    weights = np.ones(nb * batch_size, np.float32)
    if pad:
        weights[-pad:] = 0.0
    return images, labels, weights


class _WriterSnapshot:
    """What the epoch boundary hands the checkpoint writer: a device
    snapshot that the writer fetches once.  The first job that runs moves
    it to the host and drops the device copy — serialising and writing a
    multi-gigabyte state takes the writer half a minute, and a device copy
    held that long meets the next save's; the jobs that share it (best and
    last of one epoch) read the host copy.  A job superseded before it ran
    drops its reference with it.  (Fetching at once on a thread of its own
    was tried: the transfer then runs beside the next epoch's dispatch, and
    the boundary grew from 241 to 291 ms; PERF.md, Findings, PR 27.  The
    fetch beside the rest of the boundary stalls it too, so ``fit()`` holds
    the writer until the next train dispatch: ``AsyncCheckpointer.hold``.)"""

    def __init__(self, state):
        self._state, self._fetched = state, False

    def on_host(self):  # the one writer thread runs its jobs in turn
        if not self._fetched:
            self._state, self._fetched = fetch_to_host(self._state), True
        return self._state


class Trainer:
    """Drives training of a model over a mesh; one instance per run."""

    def __init__(self, hparams, model=None, mesh=None):
        self._t_construct = time.monotonic()
        self.hparams = hparams
        # --- resilience: fault plan + preemption latch + goodput meter.
        # The goodput meter always runs (host-side timers, ~free); the
        # signal handler installs only for resilient runs so tests and
        # library embedders keep their own SIGTERM semantics.
        self.goodput = GoodputMeter()
        self.fault_plan = FaultPlan.parse(
            getattr(hparams, "fault_plan", None),
            seed=getattr(hparams, "fault_seed", 0),
        )
        self.preempt_handler = None
        if getattr(hparams, "resilience", False) or self.fault_plan is not None:
            self.preempt_handler = PreemptionHandler().install()
        # --- observability (obs/): the run-event bus + span recorder for
        # this attempt.  run_id comes from the environment (the supervisor
        # hands every attempt the same one) or is generated here; under
        # multi-host every process takes process 0's — a COLLECTIVE, like
        # the save-throttle broadcast, so it runs before any other one.
        self._setup_obs(hparams)
        # step faults (nan_grad/bad_batch/loss_spike) trace an extra fault
        # argument into the compiled runners; built only when the plan
        # carries them so the normal executables are unchanged
        self._step_faults = (
            self.fault_plan is not None and self.fault_plan.has_step_faults()
        )
        # --- auto-parallel planner (parallel/planner.py): with
        # --parallel-plan auto the layout flags below (model_parallel /
        # pipeline_parallel / shard_optim / grad_comms / the pipeline
        # schedule knobs) are the PLANNER's output, installed here BEFORE
        # the mesh/model/comms constructions read them.  The decision is
        # one registered `plan` event (chosen layout, every candidate's
        # predicted step-s/HBM, fit provenance) — run_report --plan fails
        # the stream if run_start's layout disagrees with an installed
        # plan.  'dump' scores and logs but keeps the hand-picked flags.
        # An explicitly passed mesh wins (tests/embedders own the layout).
        self.plan = None
        self._plan_installed = False
        self._plan_refusal = None
        plan_mode = str(getattr(hparams, "parallel_plan", "off") or "off")
        if plan_mode != "off" and mesh is None:
            from ..parallel import planner as planner_mod

            try:
                self.plan = planner_mod.plan_layout(
                    hparams,
                    events=planner_mod.load_ledger_events(
                        getattr(hparams, "ckpt_path", None)
                    ),
                    model=model,
                )
            except planner_mod.PlanError as e:
                # dump's contract is "score and log, never gate": a
                # refusal with legal hand flags must not kill the run —
                # the refusal (with its numbers) is logged below instead.
                # auto has nothing to install, so the refusal stands.
                if plan_mode == "auto":
                    raise
                self._plan_refusal = str(e)
            else:
                self._plan_installed = plan_mode == "auto"
                if self._plan_installed:
                    planner_mod.install_plan(self.plan, hparams)
                self.bus.emit(
                    planner_mod.PLAN_KIND,
                    **self.plan.payload(installed=self._plan_installed),
                )
        self.mesh = mesh if mesh is not None else make_mesh(
            hparams.num_devices,
            hparams.model_parallel,
            getattr(hparams, "pipeline_parallel", 1) or 1,
            backend=hparams.backend,
        )
        n_data = self.mesh.shape["data"]
        ga = getattr(hparams, "grad_accum", 1)
        self.grad_accum = 1 if ga is None else ga
        if self.grad_accum < 1:
            raise ValueError(f"--grad-accum must be >= 1, got {self.grad_accum}")
        if hparams.batch_size % (self.grad_accum * n_data):
            # actionable numbers, not a bare divisibility traceback: the
            # elastic supervisor's operator acts on "legal widths for this
            # batch" / "nearest legal batches at this width"
            raise ValueError(
                "global batch does not split over this mesh: "
                + elastic.divisibility_help(
                    hparams.batch_size, n_data, self.grad_accum
                )
            )

        self.root_key = fix_seed(hparams.seed)
        self.precision = hparams.precision
        compute_dtype = jnp.bfloat16 if self.precision == "bf16" else jnp.float32
        norm_dtype = (
            compute_dtype
            if getattr(hparams, "bn_dtype", "fp32") == "compute"
            else jnp.float32
        )
        model_kw = dict(
            dtype=compute_dtype,
            norm_dtype=norm_dtype,
            stem=getattr(hparams, "stem", "cifar"),
            remat=getattr(hparams, "remat", False),
        )
        expert_parallel = False
        if hparams.model.startswith("vit"):
            # the ViT sizes its position embedding in setup(); the ResNet
            # family is resolution-agnostic and takes no such field
            model_kw["image_size"] = getattr(hparams, "image_size", 32)
            if getattr(hparams, "patch_size", 0):
                model_kw["patch"] = hparams.patch_size
            # trunk unroll: 0 = auto (full unroll on TPU — measured 1.9x
            # on vit_tiny by eliminating the scanned loop's per-layer
            # residual stacking; scan elsewhere for compile-time economy).
            # -1 = full unroll (ViT maps non-positive to its depth).
            unroll = getattr(hparams, "scan_unroll", 0)
            if unroll == 0:
                unroll = -1 if jax.default_backend() == "tpu" else 1
            model_kw["scan_unroll"] = unroll
            # Sharding-aware dispatch resolution is shared with every
            # other get_model caller (models/moe.py resolve_dispatch):
            # under expert parallelism GSPMD must shard the expert
            # computation, and only the XLA sort/gather formulation
            # partitions — an explicit 'gmm' is a config error there.
            model_kw["moe_dispatch"] = getattr(hparams, "moe_dispatch", "auto")
            expert_parallel = (
                hparams.model == "vit_moe"
                and getattr(hparams, "model_parallel", 1) > 1
            )
            # the fused block kernel requires unsharded block params:
            # tensor parallelism shards the projection/MLP kernels and
            # pipeline stages re-drive blocks under shard_map — compose
            # there (models/vit.py ViTBlock docstring)
            fusion = getattr(hparams, "block_fusion", "auto")
            if (
                getattr(hparams, "model_parallel", 1) > 1
                and getattr(hparams, "parallel_style", "tensor")
                in ("tensor", "pipeline")
            ) or getattr(hparams, "pipeline_parallel", 1) > 1:
                if fusion == "force":
                    raise ValueError(
                        "--block-fusion force requires unsharded block "
                        "params: tensor/pipeline model parallelism shards "
                        "them and GSPMD cannot partition the fused Pallas "
                        "block kernel — use 'auto' (composes there) or "
                        "'off' with --model-parallel > 1"
                    )
                fusion = "off"
            model_kw["block_fusion"] = fusion
        # the launcher flags a zoo entry registers for itself (a token
        # model's cut): taken by field, not by name
        for opt in model_cli_options(hparams.model):
            model_kw[opt] = getattr(hparams, opt)
        self.model = model if model is not None else get_model(
            hparams.model, expert_parallel=expert_parallel, **model_kw
        )
        # what the family trains on (train/task.py): its splits, the array
        # it is initialised on; the step and eval programs find it on the
        # train state
        self.task = task_of(self.model)

        # --- data.  'device' mode: split is HBM-resident and replicated;
        # per-batch sharding happens inside the compiled epoch.  'host'
        # mode: train batches stream from a per-host-sharded numpy loader
        # (val/test stay device-resident — they are small either way).
        trn, val, tst = self.task.datasets(hparams, self.model)
        # labels an example carries: 1 an image, the sequence length a
        # token sequence (the hit counts are per label)
        self._labels_per_example = int(np.prod(trn.labels.shape[1:]))
        if len(trn) < hparams.batch_size or len(val) == 0:
            raise ValueError(
                f"dataset too small after split: {len(trn)} train / {len(val)} "
                f"val examples for batch size {hparams.batch_size} "
                "(raise --limit-examples or lower --batch-size)"
            )
        self.data_mode = getattr(hparams, "data_mode", "device")
        if self.data_mode == "device":
            self.trn_images, self.trn_labels = put_replicated(
                (trn.images, trn.labels), self.mesh
            )
            self.train_loader = None
        else:
            local_batch = host_local_batch_slice(hparams.batch_size)
            base_loader = HostLoader(
                trn,
                local_batch,
                shuffle=True,
                drop_last=True,
                seed=hparams.seed,
                num_shards=jax.process_count(),
                shard=jax.process_index(),
            )
            # --workers (reference DataLoader num_workers) sets the prefetch
            # depth; 0 means synchronous batch assembly, like the
            # reference's num_workers=0
            workers = getattr(hparams, "workers", WORKERS_DEFAULT)
            self.train_loader = (
                PrefetchLoader(base_loader, depth=workers)
                if workers > 0
                else base_loader
            )
        self.steps_per_epoch = trn.steps_per_epoch(hparams.batch_size, drop_last=True)
        self._val = put_replicated(
            _pad_batches(val.images, val.labels, hparams.batch_size), self.mesh
        )
        self._tst = put_replicated(
            _pad_batches(tst.images, tst.labels, hparams.batch_size), self.mesh
        )

        # --- optimizer + state
        self.tx, self.lr_schedule = configure_optimizers(hparams, self.steps_per_epoch)
        init_key, self.data_key = jax.random.split(self.root_key)
        size = getattr(hparams, "image_size", 32) or 32
        with jax.default_device(jax.local_devices()[0]):
            state = create_train_state(
                self.model, init_key, self.tx, input_shape=(1, size, size, 3)
            )
        # The "model" axis's meaning is the --parallel-style: 'tensor'
        # (Megatron param sharding, the default), 'pipeline' (the LEGACY
        # single-axis pipeline spelling: the schedule runs on the model
        # axis itself), or 'sequence'/'sequence-ulysses' (token axis
        # sharded across the trunk; params stay fully replicated —
        # sequence parallelism shards activations, not parameters).  The
        # DEDICATED "pipe" axis (--pipeline-parallel, parallel/mesh.py)
        # composes with the tensor style: the trunk shards (pipe on the
        # depth axis, model on the feature dims) — DP×TP×PP.  At
        # model_parallel == pipeline_parallel == 1 every style
        # degenerates to the replicated tensor path.
        style = getattr(hparams, "parallel_style", "tensor")
        mp_size = self.mesh.shape["model"]
        pp_size = self.mesh.shape.get("pipe", 1)
        # comms flags are read early: the pipeline schedules OWN their
        # gradient-sync wire, so the fwd_bwd build below needs the mode
        self.shard_optim = bool(getattr(hparams, "shard_optim", False))
        self.grad_comms = getattr(hparams, "grad_comms", "fp32") or "fp32"
        # --ckpt-comms-residual: serialize the error-feedback residual in
        # last.ckpt (manifest records presence) so resume keeps the
        # compression error the wire already dropped.  Rollback always
        # resets it regardless — a rolled-back residual belonged to the
        # discarded trajectory.
        self._ckpt_residual = bool(
            getattr(hparams, "ckpt_comms_residual", False)
        ) and self.grad_comms != "fp32"
        legacy_pipe = style == "pipeline" and mp_size > 1
        pipe_axis = "pipe" if pp_size > 1 else "model"
        pipe_size = pp_size if pp_size > 1 else (mp_size if legacy_pipe else 1)
        tp_axis = "model" if (pp_size > 1 and mp_size > 1) else None
        pipeline_active = pipe_size > 1
        self._pipe_meta = None
        self._local_stages: list[int] = []
        self._residual_spec_fn = None  # pipeline wire: params -> (zeros, sh)
        # the resident trunk layout the installed schedule declares
        # (parallel/layouts.py): contiguous everywhere except resident
        # interleaved v>1, where the TrainState carries the (v, P, K)
        # chunk view so the per-step relayout disappears from the hot path
        self._state_layout = layouts_mod.CONTIGUOUS
        if (style != "tensor" and mp_size > 1) or pipeline_active:
            from ..models.vit import ViT

            what = (
                f"--pipeline-parallel {pp_size}"
                if pp_size > 1
                else f"--parallel-style {style}"
            )
            if not isinstance(self.model, ViT):
                raise ValueError(
                    f"{what} needs a stacked transformer "
                    f"trunk (vit_* models); got --model {hparams.model}"
                )
            if getattr(self.model, "num_experts", 0):
                # the staged/sequence apply paths neither thread the sown
                # MoE aux loss nor define per-shard routing semantics;
                # experts shard over "model" under the tensor style (EP)
                raise ValueError(
                    f"{what} does not support MoE models; "
                    "use the default tensor style, where --model-parallel "
                    "shards the expert axis (expert parallelism)"
                )
            if style.startswith("sequence") and pp_size > 1:
                raise ValueError(
                    "--pipeline-parallel does not compose with the "
                    "sequence styles (the trunk cannot be both staged and "
                    "token-sharded); use --parallel-style tensor"
                )
        self.train_fwd_bwd = None  # 1F1B replaces value_and_grad when set
        if pipeline_active:
            from ..parallel.pipeline import (
                make_interleaved_fwd_bwd,
                make_pipelined_apply_fn,
                pipeline_residual_spec,
                pp_state_shardings,
                schedule_meta,
            )
            from ..resilience.elastic import microbatch_help, pipeline_help

            schedule = getattr(hparams, "pipeline_schedule", "gpipe")
            virtual = getattr(hparams, "pipeline_virtual_stages", 0) or (
                2 if schedule == "interleaved" else 1
            )
            if schedule != "interleaved":
                virtual = 1
            if self.model.depth % (pipe_size * virtual):
                # fail at the CLI, not from inside jit tracing of the
                # staged trunk (advisor r2)
                raise ValueError(
                    "pipeline stages refused: "
                    + pipeline_help(self.model.depth, pipe_size, virtual)
                )
            if tp_axis is not None:
                if self.model.heads % mp_size:
                    raise ValueError(
                        f"DP×TP×PP needs attention heads "
                        f"({self.model.heads}) divisible by "
                        f"--model-parallel ({mp_size}) for head-local "
                        "tensor-parallel attention"
                    )
                if (self.model.mlp_ratio * self.model.dim) % mp_size:
                    raise ValueError(
                        f"DP×TP×PP needs the MLP hidden width "
                        f"({self.model.mlp_ratio * self.model.dim}) "
                        f"divisible by --model-parallel ({mp_size})"
                    )
            micro = getattr(hparams, "pipeline_microbatches", 0) or (
                4 * pipe_size
            )
            if virtual > 1 and micro % pipe_size:
                raise ValueError(
                    "pipeline microbatch split impossible: "
                    + microbatch_help(
                        hparams.batch_size, micro, n_data, pipe=pipe_size
                    )
                )
            per_micro = hparams.batch_size // self.grad_accum
            if per_micro % (micro * n_data):
                raise ValueError(
                    f"per-update batch {per_micro}: "
                    + microbatch_help(
                        per_micro, micro, n_data,
                        pipe=pipe_size if virtual > 1 else None,
                    )
                )
            # the schedule's resident trunk layout: chunked (v, P, K) for
            # resident interleaved v>1, contiguous otherwise.  The state
            # is re-laid ONCE below (state_from_canonical) and every
            # reader — eval, checkpoints, parity, the planner — goes
            # through this one seam.  --no-pipeline-resident-layout keeps
            # the legacy per-step relayout (ROADMAP.md D3).
            self._state_layout = layouts_mod.layout_for(
                schedule, virtual=virtual, pipe=pipe_size,
                pipe_axis=pipe_axis, tp_axis=tp_axis,
                resident=bool(
                    getattr(hparams, "pipeline_resident_layout", True)
                ),
            )
            # eval always runs the (forward-only) GPipe schedule; the
            # train-time backward is picked by --pipeline-schedule
            state = state.replace(
                apply_fn=make_pipelined_apply_fn(
                    self.model, self.mesh, num_microbatches=micro,
                    pipe_axis=pipe_axis, tp_axis=tp_axis,
                    state_layout=self._state_layout,
                )
            )
            if schedule in ("1f1b", "interleaved"):
                # the 1F1B family owns its backward — and therefore its
                # gradient-sync wire: --grad-comms here is the WIRE-TRUE
                # compressed all-reduce (fp16/int8 payload really crosses
                # the data axis, per-device error feedback), the path the
                # GSPMD runners cannot take (parallel/comms.py)
                self.train_fwd_bwd = make_interleaved_fwd_bwd(
                    self.model, self.mesh, num_microbatches=micro,
                    virtual=virtual, pipe_axis=pipe_axis, tp_axis=tp_axis,
                    grad_comms=self.grad_comms,
                    state_layout=self._state_layout,
                )
                if self.train_fwd_bwd.carries_residual:
                    self._residual_spec_fn = (
                        lambda params, _v=virtual, _pa=pipe_axis,
                        _ta=tp_axis, _sl=self._state_layout: (
                            pipeline_residual_spec(
                                params, self.mesh, virtual=_v,
                                pipe_axis=_pa, tp_axis=_ta,
                                state_layout=_sl,
                            )
                        )
                    )
            # the ONE construction-time relayout that replaced the
            # per-step one: params + mirrored momentum go resident here,
            # and pp_state_shardings below shards the resident shapes
            state = layouts_mod.state_from_canonical(state, self._state_layout)
            self.state_sharding = pp_state_shardings(
                self.mesh, state, pipe_axis=pipe_axis, tp_axis=tp_axis,
                state_layout=self._state_layout,
            )
            self._pipe_meta = {
                **schedule_meta(schedule, pipe_size, micro, virtual),
                "pipe_axis": pipe_axis,
                "tp": mp_size if tp_axis is not None else 1,
                "data": n_data,
                "depth": self.model.depth,
                "state_layout": self._state_layout.tag,
            }
            # the pipe coordinates this process's devices own — the
            # (host, stage) span lanes and per-stage straggler sketches
            # are recorded for exactly these
            ax = list(self.mesh.axis_names).index(pipe_axis)
            self._local_stages = sorted(
                {
                    pos[ax]
                    for pos, dev in np.ndenumerate(self.mesh.devices)
                    if dev.process_index == jax.process_index()
                }
            )
        elif style.startswith("sequence") and mp_size > 1:
            from ..parallel.ring import make_sequence_apply_fn
            from ..parallel.sharding import replicated_sharding

            seq_impl = "ulysses" if style == "sequence-ulysses" else "ring"
            state = state.replace(
                apply_fn=make_sequence_apply_fn(
                    self.model, self.mesh, seq_impl=seq_impl
                )
            )
            # sequence parallelism shards activations, not parameters
            repl = replicated_sharding(self.mesh)
            self.state_sharding = jax.tree_util.tree_map(
                lambda _: repl, state
            )
        else:
            self.state_sharding = state_shardings(self.mesh, state)
        # --- comms layer (parallel/comms.py): ZeRO-style sharded weight
        # update (--shard-optim) + compressed gradient sync (--grad-comms).
        # Both off (the default) leaves self.comms inactive and the traced
        # update — and therefore every executable fingerprint — unchanged.
        # (shard_optim/grad_comms were read above, before the pipeline
        # block: the 1F1B schedules carry the wire themselves.)
        self.comms = None
        if self.shard_optim or self.grad_comms != "fp32":
            self.comms = comms_mod.Comms(
                self.mesh,
                param_shardings=self.state_sharding.params,
                shard_optim=self.shard_optim,
                grad_comms=self.grad_comms,
                # the pipeline schedule already moved the gradients over
                # the compressed wire (error feedback included) inside its
                # own backward — apply_gradients must not re-quantize
                wire_inline=self._residual_spec_fn is not None,
            )
            if self.grad_comms != "fp32":
                if self._residual_spec_fn is not None:
                    # wire-true pipeline sync: the error-feedback residual
                    # is PER-DEVICE state in the schedule layout (leading
                    # data axis + chunk view), not params-shaped — each
                    # data replica carries the error its own wire dropped
                    host_res, res_sh = self._residual_spec_fn(state.params)
                    state = state.replace(comms_residual=host_res)
                    self.state_sharding = self.state_sharding.replace(
                        comms_residual=res_sh
                    )
                else:
                    # GSPMD runners: params-shaped fp32 residual, carried
                    # in the train state (laid out like the params), NOT
                    # checkpointed — a resume restarts it at zero
                    state = state.replace(
                        comms_residual=self.comms.residual_init(state.params)
                    )
                    self.state_sharding = self.state_sharding.replace(
                        comms_residual=self.state_sharding.params
                    )
            if self.shard_optim:
                # the whole re-layout: the optimizer state is CARRIED
                # data-sharded between dispatches (per-device opt-state HBM
                # ~1/N — the compile-event ledger shows it as smaller
                # argument bytes); the update's reduce-scatter/all-gather
                # constraints live in Comms.apply_gradients
                self.state_sharding = self.state_sharding.replace(
                    opt_state=comms_mod.zero_opt_shardings(
                        self.mesh, state.opt_state,
                        self.state_sharding.opt_state,
                    )
                )
            # static comms gauges (wire width, sync bytes, opt-state
            # footprint total vs per-device) ride the registry like every
            # other plane — flushes, exporter, alert rules.  The per-device
            # arithmetic prices the sharding tree the run ACTUALLY carries
            # (installed just above), not a re-derivation.
            for k, v in self.comms.summary(
                state.params, state.opt_state,
                opt_shardings=(
                    self.state_sharding.opt_state if self.shard_optim else None
                ),
            ).items():
                self.metrics.gauge(f"comms/{k}").set(v)
        self.state = place_tree(state, self.state_sharding)

        # --- compiled programs
        test_stats = (
            (IMAGENET_MEAN, IMAGENET_STD)
            if getattr(hparams, "legacy_test_stats", False)
            else (CIFAR100_MEAN, CIFAR100_STD)
        )
        # Both data modes run CHUNKED scanned dispatches (device mode
        # defaults to one whole-epoch chunk, preserving the monolithic
        # behavior exactly); the runners DONATE the input state, so the
        # output state reuses its buffers — no per-dispatch state copy in
        # HBM.  The async checkpoint writer gets an explicit device-side
        # snapshot instead of a live reference (see fit()).
        dcs = getattr(hparams, "device_chunk_steps", 0) or 0
        self._device_chunk = (
            min(dcs, self.steps_per_epoch) if dcs > 0 else self.steps_per_epoch
        )
        self._device_runners: dict[int, callable] = {}
        self._device_prefetch = getattr(
            hparams, "device_prefetch", DEVICE_PREFETCH_DEFAULT
        )
        self._prefetch_note = None
        if self._device_prefetch == "auto":
            # per-host staging depth from THIS host's free HBM headroom
            # (parallel/planner.py): a straggler host with less headroom
            # stages shallower locally instead of stalling the collective
            # dispatch at a fleet-global constant.  One staged chunk is
            # K stacked uint8 image batches + int labels.
            from ..parallel import planner as planner_mod

            size = getattr(hparams, "image_size", 32) or 32
            local_batch = host_local_batch_slice(hparams.batch_size)
            chunk_bytes = (
                max(1, getattr(hparams, "host_chunk_steps",
                               HOST_CHUNK_STEPS_DEFAULT))
                * local_batch * (size * size * 3 + 8)
            )
            free = planner_mod.hbm_free_bytes()
            self._device_prefetch = planner_mod.auto_staging_depth(
                chunk_bytes, free, default=DEVICE_PREFETCH_DEFAULT
            )
            self._prefetch_note = (
                f"--device-prefetch auto: staging depth "
                f"{self._device_prefetch} on this host "
                + (
                    f"({free / 2**20:.0f} MB free HBM, "
                    f"{chunk_bytes / 2**20:.1f} MB/chunk)"
                    if free is not None
                    else "(no device memory stats; default kept)"
                )
            )
        self._device_prefetch = int(self._device_prefetch)
        if self.data_mode == "device":
            self.chunk_runner = None
        else:
            self.chunk_runner = make_chunk_runner(
                self.mesh,
                precision=self.precision,
                state_sharding=self.state_sharding,
                grad_accum=self.grad_accum,
                fwd_bwd=self.train_fwd_bwd,
                comms=self.comms,
                fault_injection=self._step_faults,
                monitor=self.compile_monitor,
                state_layout=self._state_layout,
            )
        # whole-split scanned eval: one dispatch per validate()/test() call
        # (one executable per split shape), matching the train path's
        # one-dispatch-per-epoch design
        self.eval_runner = make_eval_runner(
            self.mesh, hparams.batch_size, precision=self.precision,
            monitor=self.compile_monitor,
        )
        if test_stats == (CIFAR100_MEAN, CIFAR100_STD):
            self.test_eval_runner = self.eval_runner  # same constants
        else:
            self.test_eval_runner = make_eval_runner(
                self.mesh,
                hparams.batch_size,
                precision=self.precision,
                mean=test_stats[0],
                std=test_stats[1],
                monitor=self.compile_monitor,
                name="test_eval_runner",
            )

        # --- eager-parity debug rail (--parity-check N)
        self.parity = None
        parity_n = int(getattr(hparams, "parity_check", 0) or 0)
        if parity_n > 0:
            from .. import parity as parity_mod

            if jax.process_count() > 1:
                raise ValueError(
                    "--parity-check is a single-process debug rail: it "
                    "snapshots the full state host-side, which a "
                    "multi-process run cannot device_get"
                )
            self.parity = parity_mod.ParityCapture(
                min(parity_n, self.steps_per_epoch),
                parity_mod.Tolerance.parse(
                    getattr(hparams, "parity_tol", f"ulp={1 << 26}")
                    or f"ulp={1 << 26}"
                ),
                getattr(hparams, "parity_corrupt", None),
            )

        # --- run dir, logging, provenance (process-0 only)
        self.is_main = is_main_process()
        self.ckpt_writer = (
            AsyncCheckpointer(metrics=self.metrics) if self.is_main else None
        )
        self._last_resume_save = float("-inf")
        # -1 so the first validation always produces a best checkpoint, even
        # at 0.0% val accuracy (with 100 classes and a small val split that
        # is a reachable score; the reference's 0-init would then never save)
        self.best_acc = -1.0
        self.start_epoch = 0
        self.version_dir: Path | None = None
        self.writer = None
        # --auto-resume: continue the newest interrupted run in place (its
        # version dir, its last.ckpt) — the crash-restart story the
        # reference lacks entirely (torchelastic is quoted in its README but
        # never implemented, SURVEY.md §5).  Explicit --resume wins.
        auto_resumed = False
        resume_bytes = None  # one read serves verify + restore (states can be GBs)
        if getattr(hparams, "auto_resume", False) and not getattr(
            hparams, "resume", None
        ):
            # verify-on-restore: a torn newest checkpoint falls back to the
            # rotated previous good one instead of crashing the relaunch
            hit = ckpt.find_valid_resume_bytes(hparams.ckpt_path)
            if hit is not None:
                hparams.resume = str(hit[0])
                resume_bytes = hit[1]
                auto_resumed = True
        if jax.process_count() > 1:
            # The branch below is collective-bearing, so every process must
            # take the SAME one.  --ckpt-path is contractually a shared
            # filesystem under multi-host (every process scans the same
            # checkpoint dirs); broadcast process 0's discovery and fail
            # loudly on disagreement — a local-FS misconfiguration must not
            # become a silent collective mismatch/deadlock.
            from jax.experimental import multihost_utils

            agreed = bool(
                multihost_utils.broadcast_one_to_all(np.asarray(auto_resumed))
            )
            if agreed != auto_resumed:
                raise RuntimeError(
                    "--auto-resume discovery disagrees across hosts "
                    f"(process 0: {agreed}, this process: {auto_resumed}); "
                    "--ckpt-path must be a filesystem shared by every host"
                )
        # Fresh version dirs are claimed race-safely (mkdir is the claim);
        # under multi-host, process 0 claims and the rest follow its
        # broadcast pick — a COLLECTIVE, so it runs on every process.
        agreed_dir = None
        if not auto_resumed and jax.process_count() > 1:
            agreed_dir = ckpt.agreed_version_dir(hparams.ckpt_path)
        if self.is_main:
            # Only an auto-DISCOVERED checkpoint continues in its own
            # version dir; an explicit --resume (even with --auto-resume
            # set) starts a fresh version under --ckpt-path so it can never
            # clobber the source run's artifacts.
            self.version_dir = (
                Path(hparams.resume).parent
                if auto_resumed
                else (agreed_dir or ckpt.find_version_dir(hparams.ckpt_path))
            )
            self.writer = SummaryWriter(self.version_dir / "tb")
            self._dump_hparams()
        self.logger = setup_logger(
            self.version_dir, is_main_process=self.is_main, to_stdout=True
        )
        if self.plan is not None:
            from ..parallel import planner as planner_mod

            self.logger.info(
                ("installed " if self._plan_installed else
                 "dump only (hand flags kept) — ")
                + planner_mod.format_plan(self.plan)
            )
        elif self._plan_refusal:
            self.logger.warning(
                "--parallel-plan dump: no feasible planned layout (hand "
                f"flags kept): {self._plan_refusal}"
            )
        if self._prefetch_note:
            self.logger.info(self._prefetch_note)
        self.version = (
            int(self.version_dir.name.split("-")[1]) if self.version_dir else -1
        )
        # Every process can name this attempt's event dir — the resumed
        # run's version dir, the multi-host agreed fresh dir, or (single
        # process) the claimed one — so per-process event files land next
        # to the checkpoints, where run_report merges them.  Events emitted
        # before this point (construction) flush from the bus's buffer now.
        self._obs_dir = (
            Path(hparams.resume).parent
            if auto_resumed
            else (self.version_dir if self.is_main else agreed_dir)
        )
        if self._obs_enabled and self._obs_dir is not None:
            self.bus.bind_dir(self._obs_dir)
            if getattr(hparams, "flight_ring", True):
                # durable twin of the flight recorder: an mmap'd fixed-slot
                # file whose dirty pages the OS keeps even through SIGKILL —
                # the supervisor (or run_report --blackbox) decodes every
                # host's ring into one cross-host blackbox.json
                self.bus.attach_ring(
                    self._obs_dir
                    / obs.ring_filename(self.bus.attempt, self.bus.process_index)
                )

        # mid-epoch resume (host data mode): a checkpoint drained at a chunk
        # boundary records how many steps of the in-progress epoch it holds;
        # the first epoch after restore fast-forwards past them (exact: the
        # loader order and the per-step keys are functions of the global
        # step index, not of where the attempt started)
        self._resume_step_offset = 0
        # watchdog rollback target of last resort: an explicit --resume runs
        # in a FRESH version dir, so until its first save a bad early epoch
        # would otherwise have nothing to roll back to — the (read-only)
        # source checkpoint is exactly the state the run started from
        self._rollback_source = getattr(hparams, "resume", None)
        self._reshard = None  # the elastic reshard plan, set on resume
        if getattr(hparams, "resume", None):
            if resume_bytes is None:
                # explicit --resume: one read-and-hash pass (the checksum
                # pipelines against large reads), verify that buffer (a torn
                # file fails loudly at the CLI, not mid-restore), restore
                # from it.  Auto-discovered paths arrive with their already-
                # verified bytes from find_valid_resume_bytes.
                resume_bytes, resume_digest = read_and_hash(hparams.resume)
                ok, reason = verify_checkpoint(
                    hparams.resume, data=resume_bytes, digest=resume_digest
                )
                if not ok:
                    raise ValueError(
                        f"refusing to resume from {hparams.resume}: {reason}"
                    )
            resume_info: dict = {}
            state, self.start_epoch, self.best_acc = ckpt.load_resume_state(
                hparams.resume, self.state, raw_bytes=resume_bytes,
                info=resume_info, state_layout=self._state_layout,
            )
            resume_bytes = None  # drop the (possibly GB-sized) buffer now
            res_note = resume_info.get("comms_residual", "absent")
            if res_note == "restored" and not self._ckpt_residual:
                # the documented cross-flag contract: a run that did not
                # pass --ckpt-comms-residual gets flag-off behavior even
                # when the checkpoint carries the residual — drop and
                # warn, never silently restore off an absent flag
                res_note = "dropped:ckpt-comms-residual off on this run"
            if res_note == "restored":
                # --ckpt-comms-residual round trip: the error-feedback
                # carry continues instead of restarting at zero
                self.logger.info(
                    "comms: error-feedback residual restored from the "
                    "checkpoint (--ckpt-comms-residual)"
                )
            else:
                if res_note.startswith("dropped"):
                    # the documented cross-flag path: saved with a
                    # residual this run cannot carry — drop and warn
                    self.logger.warning(
                        "comms: checkpointed error-feedback residual "
                        f"dropped ({res_note.split(':', 1)[1]}); "
                        "restarting it at zero"
                    )
                state = self._reset_comms_residual(state)
            # from_state_dict returns host numpy leaves; re-place them as
            # global mesh arrays with the run's layout (jit on a multi-host
            # mesh requires global jax.Arrays, not host buffers).  The
            # layout is THIS run's mesh, whatever its device count — the
            # host-pytree checkpoint format is what makes restoring onto a
            # resized slice a plain re-placement (resilience/elastic.py).
            self.state = place_tree(state, self.state_sharding)
            self.logger.info(
                f"Resumed from {hparams.resume} at epoch {self.start_epoch} "
                f"(best acc {self.best_acc:.4f})"
            )
            manifest = read_manifest(hparams.resume)
            # the explicit reshard step of an elastic restore: validate the
            # saved mesh against THIS run's re-rendered one and the batch
            # against the new data axis (raises ReshardError with the
            # numbers when no legal split exists — the construction-time
            # divisibility check above already caught the batch half, so
            # this mostly records the topology delta for the restore log
            # and the run_start payload)
            self._reshard = elastic.validate_reshard(
                manifest, self.mesh,
                batch_size=hparams.batch_size, grad_accum=self.grad_accum,
                shard_optim=self.shard_optim,
                pipeline=(
                    {
                        k: self._pipe_meta[k]
                        for k in ("pipe", "virtual", "microbatches", "depth")
                    }
                    if self._pipe_meta is not None
                    else None
                ),
                state_layout=self._state_layout.tag,
            )
            if self._reshard.get("shard_optim_changed"):
                # checkpoints are host pytrees, so crossing --shard-optim
                # on↔off is just a different place_tree layout — noted so
                # the restore log explains the relaid optimizer state
                self.logger.info(
                    "comms reshard: checkpoint saved with shard_optim="
                    f"{self._reshard['saved_shard_optim']} → restoring "
                    f"with shard_optim={self.shard_optim} (optimizer "
                    "state re-laid out; values unchanged)"
                )
            if self._reshard.get("state_layout_changed"):
                # the state-layout half: the canonical-on-disk format makes
                # crossing a schedule/layout change (v change, pp resize,
                # chunked↔contiguous) a restore-time re-layout through the
                # seam — bitwise-neutral reshapes, values unchanged
                self.logger.info(
                    "state-layout reshard: checkpoint saved resident as "
                    f"{self._reshard['saved_state_layout']} → restoring "
                    f"resident as {self._reshard['state_layout']} (trunk "
                    "stack re-laid through the canonical view; values "
                    "unchanged)"
                )
            elastic_msg = elastic.describe_restore(manifest, self.mesh)
            if elastic_msg:
                self.logger.info(elastic_msg)
            if manifest is not None and hasattr(self.train_loader, "quarantine"):
                # corrupt-shard quarantine survives the relaunch: the
                # manifest carries rank 0's excluded example ids, the
                # per-rank quarantine-p*.json sidecars next to the
                # checkpoint carry every OTHER rank's — union them all, so
                # a multi-host relaunch (possibly onto a different world
                # size) re-applies the whole fleet's set, not one shard's
                from ..resilience.ckpt_io import union_quarantine

                merged = union_quarantine(
                    Path(hparams.resume).parent,
                    manifest.get("quarantined"),
                )
                if merged:
                    try:
                        n = self.train_loader.quarantine(merged)
                    except ValueError as e:
                        self.logger.error(
                            f"health: persisted quarantine not re-applied: {e}"
                        )
                    else:
                        self.logger.info(
                            f"health: re-applied persisted quarantine "
                            f"({n} example(s) excluded, "
                            f"{len(merged)} fleet-wide)"
                        )
            if manifest and manifest.get("epoch_in_progress") == self.start_epoch:
                # both data modes fast-forward exactly: the loader order and
                # the per-step keys (host mode) / the epoch permutation and
                # key split (device mode) are functions of the global step
                # index, not of where the attempt started
                steps_done = int(manifest.get("epoch_steps_done", 0))
                self._resume_step_offset = steps_done
                if steps_done:
                    self.logger.info(
                        f"mid-epoch resume: epoch {self.start_epoch} "
                        f"fast-forwards past its first {steps_done} steps"
                    )
        # --- training-health watchdog (health/): the compiled guards run
        # unconditionally (a skipped NaN update is strictly better than an
        # applied one); the watchdog adds spike/desync detection and the
        # rollback policy.  --no-health keeps the bare abort-on-divergence.
        self.watchdog = None
        if getattr(hparams, "health", True):
            self.watchdog = Watchdog(
                HealthConfig.from_hparams(hparams), logger=self.logger,
                bus=self.bus,
            )
        self._fingerprint_fn = None  # jitted lazily on first desync check
        # per-device partial-reduce desync path (model_parallel > 1):
        # compiled lazily; False = permanently degraded to the host fetch
        self._partial_fp_fn = None
        self._epoch_health: dict = {}
        self._epoch_step_base = 0  # first global-within-epoch step trained
        # step-time breakdown (h2d-wait / dispatch / compute): per-epoch
        # meter + run totals for the goodput record; the snapshot program
        # (device-side state copy for the async writer) compiles lazily
        self._step_meter = StepTimeMeter(tracer=self.tracer, metrics=self.metrics)
        self._overlap_totals = StepTimeMeter()
        self._snapshot_fn = None

        # init/recovery cost: construction through restore + program builds
        # — the price every restart pays again, charged against goodput
        self._init_secs = time.monotonic() - self._t_construct
        self.bus.emit(
            "run_start",
            epoch=self.start_epoch,
            model=hparams.model,
            backend=hparams.backend,
            version=self.version,
            epochs=hparams.epoch,
            steps_per_epoch=self.steps_per_epoch,
            batch_size=hparams.batch_size,
            mesh=dict(self.mesh.shape),
            world_size=jax.process_count(),
            data_mode=self.data_mode,
            precision=self.precision,
            resumed=bool(getattr(hparams, "resume", None)),
            resharded=bool(self._reshard and self._reshard["changed"]),
            shard_optim=self.shard_optim,
            grad_comms=self.grad_comms,
            state_layout=self._state_layout.tag,
            resume_step_offset=self._resume_step_offset,
            init_s=round(self._init_secs, 4),
        )
        if self._pipe_meta is not None:
            # one `pipeline` event per attempt: the schedule's static tick
            # arithmetic (run_report joins it with the measured dispatch
            # sketches into the per-executable bubble table) + the static
            # bubble gauge on the registry
            self.bus.emit("pipeline", **self._pipe_meta)
            self.metrics.gauge("pipeline/bubble_frac_schedule").set(
                self._pipe_meta["bubble_frac"]
            )

    # ------------------------------------------------------------------ utils

    def _setup_obs(self, hparams) -> None:
        """Install this attempt's event bus + span recorder as the
        process-current ones (obs/).

        The run identity: ``run_id`` names the whole supervised run — the
        supervisor exports it (and the attempt index) into every child's
        environment, so records written by different attempts join on it;
        an unsupervised run generates a fresh one.  Under multi-host every
        process takes process 0's id/attempt (one tiny broadcast — the
        collective runs identically on every process, BEFORE the
        auto-resume agreement broadcast below).
        """
        self._obs_enabled = getattr(hparams, "obs", True)
        run_id = os.environ.get(obs.RUN_ID_ENV) or obs.new_run_id()
        attempt = int(os.environ.get(obs.ATTEMPT_ENV, "0") or 0)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            token = np.frombuffer(
                run_id.encode("ascii", "replace")[:32].ljust(32), np.uint8
            ).copy()
            token = multihost_utils.broadcast_one_to_all(token)
            run_id = token.tobytes().decode("ascii", "ignore").strip()
            attempt = int(
                multihost_utils.broadcast_one_to_all(np.asarray(attempt))
            )
        self.bus = obs.configure(
            run_id=run_id,
            attempt=attempt,
            process_index=jax.process_index(),
            ring_size=getattr(hparams, "flight_recorder_size", 256),
            # --no-obs: ring-only, no pre-bind buffering (the bus will
            # never be bound, so a pending list would grow for the run)
            persist=self._obs_enabled,
        )
        self.tracer = obs.SpanRecorder(process_index=jax.process_index())
        self._prev_recorder = obs.set_recorder(self.tracer)
        self.goodput.tracer = self.tracer  # phase(..., span=) draws spans too
        self._obs_dir: Path | None = None
        # per-step metrics (obs/metrics.py): grad_norm/loss/step-phase
        # samples accumulate in typed sketches EVERY step; the bus sees one
        # bounded `metrics` event per --metrics-flush-steps trained steps
        # (checked at chunk boundaries) plus one per epoch end
        self.metrics = obs.MetricRegistry(
            flush_steps=getattr(hparams, "metrics_flush_steps", 50)
        )
        # compiler observability (obs/compilation.py): every jit
        # lowering/compile of this attempt's runners emits a `compile`
        # event (fingerprint, wall time, persistent-cache outcome, HLO
        # cost/memory analysis) and per-executable dispatch sketches —
        # the substrate of run_report --compute's measured-MFU table.
        # Disabled with --no-obs: the runners then dispatch exactly as
        # before and the event stream carries nothing new.
        self.compile_monitor = obs.CompileMonitor(
            bus=self.bus, registry=self.metrics, enabled=self._obs_enabled
        )
        # --- live fleet operations (obs/): bounded-cadence heartbeats
        # (liveness the supervisor's watcher classifies slow vs dead),
        # resource gauges sampled once per flush, an optional per-process
        # OpenMetrics endpoint, and — for UNSUPERVISED runs — the in-process
        # alert engine (a supervised attempt's rules are evaluated by the
        # supervisor, which sees every host's stream and survives a wedged
        # collective; running them here too would double-fire every alert).
        self.heartbeat = obs.HeartbeatEmitter(
            self.bus, every_s=getattr(hparams, "heartbeat_secs", 10.0)
        )
        self.resources = obs.ResourceSampler(
            ckpt_root=getattr(hparams, "ckpt_path", None)
        )
        self.alert_engine = None
        specs = getattr(hparams, "alert", None)
        if specs and os.environ.get(obs.RUN_ID_ENV) is None:
            self.alert_engine = obs.AlertEngine(
                obs.parse_alert_specs(specs),
                bus=self.bus,
                heartbeats=self.heartbeat,
            )
            self.bus.subscribe(self.alert_engine.observe_event)
            # heartbeat-age rules evaluate from their own daemon thread:
            # a tick that only runs on the trainer thread stops exactly
            # when the hang it watches for begins
            self.alert_engine.start_ticker()
        self.exporter = obs.start_exporter(
            getattr(hparams, "metrics_port", 0),
            jax.process_index(),
            registry=self.metrics,
            heartbeats=self.heartbeat,
            alerts=self.alert_engine,
        )
        # --- closed-loop autopilot (ops/policy.py).  Two shapes:
        # unsupervised runs own a full in-process engine (fed by the same
        # bus tap as the in-process alert engine) whose rollback/abort
        # executors DEFER to the epoch boundary — the one point where the
        # whole fleet is aligned and the rollback collectives can run;
        # supervised runs instead poll the supervisor's request channel
        # (<ckpt>/fleet/policy-*.req) there, because the supervisor is the
        # one evaluating the alerts.  drain_host/rewarm_serve have no
        # trainer-side executor (the fleet and the serve session own them).
        from ..resilience import control as control_mod

        self.policy_engine = None
        self._policy_poller = None
        self._control_poller = None
        self._policy_requests: list[dict] = []
        # mid-epoch control plane (resilience/control.py): where policy
        # actions apply.  "chunk" (default) is the tentpole path — the
        # barrier below the preempt poll consumes decisions at every
        # chunk boundary; "epoch" is the legacy baseline.
        self._control_boundary = getattr(
            hparams, "control_boundary", control_mod.DEFAULT_BOUNDARY
        )
        self._attempt_index = control_mod.current_attempt()
        self._drain_requested = False
        self._drain_reqs: list[dict] = []
        # (t_wall, global_step) marks, one per chunk boundary: dating a
        # supervisor decision on the step axis for steps_since_decide
        self._ttm_marks: deque = deque(maxlen=4096)
        if getattr(hparams, "policy", None):
            from ..ops import policy as policy_mod

            if os.environ.get(obs.RUN_ID_ENV) is None:
                self.policy_engine = policy_mod.engine_from_hparams(
                    hparams,
                    bus=self.bus,
                    # late-bound: _setup_obs runs before the logger exists,
                    # and decisions only ever fire once training does
                    log=lambda msg: self.logger.warning(msg),
                )
                if self.policy_engine is not None:
                    self.policy_engine.bind_actions(
                        {
                            "rollback": self._policy_defer,
                            "abort_with_evidence": self._policy_defer,
                        }
                    )
                    self.bus.subscribe(self.policy_engine.observe_event)
            elif getattr(hparams, "ckpt_path", None) and (
                getattr(hparams, "policy_mode", "dry-run") != "off"
            ):
                self._policy_poller = policy_mod.PolicyRequestPoller(
                    hparams.ckpt_path
                )
                # the chunk-boundary control channel rides beside the
                # legacy epoch-boundary one: the supervisor writes
                # whichever --control-boundary selects, and the trainer
                # keeps both polls live (one stat per action each) so a
                # mixed-version root still drains
                self._control_poller = control_mod.ControlPoller(
                    hparams.ckpt_path
                )

    def _policy_defer(self, decision: dict) -> dict:
        """In-process executor for rollback/abort: queue the decision for
        the next control boundary (the rollback path runs collectives
        every process must enter together; acting mid-tap would not be
        safe).  Stamped with the decide-time wall clock so the applying
        boundary's ``control`` event can carry the measured
        time-to-mitigation."""
        self._policy_requests.append(
            dict(decision, t_decide=time.time())
        )
        return {"deferred": True}

    def _obs_tick(self, *, epoch: int, step: int) -> None:
        """The per-chunk-boundary observability work: one heartbeat (rate-
        limited to ``--heartbeat-secs``), the resource gauges when a flush
        is due (the sampler additionally rate-limits its own ~1 ms
        ``/proc`` pass; stale gauges persist in the registry so every
        flush still carries values), and the metric flush itself.  The
        in-process alert engine needs nothing here: window rules ride the
        bus tap and age rules tick on their own daemon thread (a tick on
        THIS thread would double the window rate and stop exactly when
        the hang it watches for begins).  Cost when nothing is due: two
        clock reads and a lock."""
        # date this boundary on the step axis BEFORE the flush: a policy
        # decision the flush triggers (in-process tap) then lands after
        # its boundary's mark, so steps_since_decide starts at 0 here
        self._ttm_marks.append((time.time(), step))
        self.heartbeat.beat(
            epoch=epoch, step=step, flush_seq=self.metrics.flushes
        )
        if self.metrics.flush_due():
            self.resources.sample(self.metrics)
            self.metrics.maybe_flush(self.bus, epoch=epoch, step=step)

    def _ckpt_view(self, state):
        """The state as every checkpoint path consumes it.  By default
        the comms error-feedback residual is dropped before the fetch —
        ``_state_dict`` serializes it only when present, so carrying it
        would pay a params-sized device→host gather (or HBM copy) per
        save for bytes that are discarded.  ``--ckpt-comms-residual``
        keeps it: the save then serializes the residual and the manifest
        records its presence, so resume no longer restarts the
        quantization error at zero."""
        if state.comms_residual is None or self._ckpt_residual:
            return state
        return state.replace(comms_residual=None)

    def _reset_comms_residual(self, state):
        """Restart the compressed-sync error-feedback residual at zero.
        Rollback ALWAYS lands here (a rolled-back residual belonged to
        the discarded trajectory); resume lands here unless
        ``--ckpt-comms-residual`` restored a matching checkpointed
        residual (the only path that skips the reset — see the resume
        branch above).  HOST zeros, deliberately — both callers
        feed ``place_tree``, whose multi-host branch cannot re-place a
        live partitioned device leaf.  The zeros' SHAPE follows the wire
        owner: params-shaped for the GSPMD comms path, the per-device
        schedule layout for the wire-true pipeline sync."""
        if state.comms_residual is None:
            return state
        if self._residual_spec_fn is not None:
            host_res, _ = self._residual_spec_fn(state.params)
            return state.replace(comms_residual=host_res)
        return state.replace(
            comms_residual=jax.tree_util.tree_map(
                lambda l: np.zeros(l.shape, l.dtype), state.params
            )
        )

    def _ckpt_meta(self) -> dict:
        """Manifest metadata every resumable save carries: the saving mesh
        topology (elastic-restore accounting) plus the run identity, so a
        checkpoint names the run/attempt that wrote it.  A non-empty
        corrupt-shard quarantine rides along too — a supervisor relaunch
        must re-apply it, or the quarantined examples re-enter the stream
        and re-fire the very rollback the quarantine exists to stop.
        (Multi-host: the manifest still carries process 0's set — the
        back-compat field — while every rank, 0 included, persists its
        own in a quarantine-p{i}.json sidecar; restore unions them.)"""
        meta = {
            **elastic.mesh_meta(self.mesh),
            "run_id": self.bus.run_id,
            "attempt": self.bus.attempt,
        }
        # the comms layout the checkpoint was saved under — recorded
        # UNCONDITIONALLY (a comms-off manifest must be distinguishable
        # from a pre-comms-layer one, or the off→on restore would never
        # report its re-layout); restore is a plain host-pytree
        # re-placement either way (the reshard step), validate_reshard
        # records the delta for the log
        meta["shard_optim"] = self.shard_optim
        meta["grad_comms"] = self.grad_comms
        # the resident trunk layout the SAVING run carried — the payload
        # itself is always canonical on disk (parallel/layouts.py), so
        # this is identity metadata: validate_reshard compares it against
        # the restoring run's layout and reports state_layout_changed
        meta["state_layout"] = self._state_layout.tag
        # does this checkpoint carry the error-feedback residual?  A
        # restore that cannot use it (flag off, fp32 wire, or a changed
        # wire layout) reads this to say WHY it dropped it.
        meta["comms_residual"] = self._ckpt_residual
        if self._pipe_meta is not None:
            # the pipeline layout the checkpoint was trained under:
            # restore across a schedule / pipe-degree change is a plain
            # host-pytree re-placement (validate_reshard checks the new
            # degree still slices the trunk), and the delta is logged
            meta["pipeline"] = {
                k: self._pipe_meta[k]
                for k in ("schedule", "pipe", "virtual", "microbatches")
            }
        quarantined = getattr(self.train_loader, "quarantined", None)
        if quarantined:
            meta["quarantined"] = sorted(quarantined)
        return meta

    def _dump_hparams(self) -> None:
        """hparams.yaml provenance dump (reference ``src/single/trainer.py:70-73``)."""
        items = sorted(vars(self.hparams).items())
        try:
            import yaml

            text = yaml.safe_dump({k: v for k, v in items})
        except ImportError:
            text = "".join(f"{k}: {v}\n" for k, v in items)
        (self.version_dir / "hparams.yaml").write_text(text)

    def _log_tb(self, tag: str, value: float, step: int) -> None:
        if self.writer is not None:
            self.writer.add_scalar(tag, value, step)

    def _progress_bar(self, iterable, desc: str):
        """tqdm wrapper, process-0 only (the reference shows bars on every
        variant, ``src/single/trainer.py:126-130`` — with rank-gating quirks
        under ddp, SURVEY.md §5 quirk 2, fixed here: bars on process 0
        everywhere).  Returns None when disabled/unavailable."""
        if not getattr(self.hparams, "progress", False) or not self.is_main:
            return None
        try:
            from tqdm import tqdm
        except ImportError:
            return None
        return tqdm(iterable, desc=desc, leave=False)

    def _snapshot_state(self, state, whole: bool = True):
        """Device-side copy of ``state`` (same shardings, async dispatch).

        The write-behind checkpointer fetches from this snapshot while the
        next epoch's donated dispatch reuses the live state's buffers.  Cost:
        one HBM→HBM state copy on epochs that actually save — versus the
        pre-donation design's copy on EVERY dispatch.

        The copy runs as two executions of one program: what a best-only
        save writes (params, statistics), then the rest.  ``whole=False``
        stops after the first and hands back a state without optimizer
        state.  Both compile at the first whole save (epoch 0), so a later
        best-only epoch compiles nothing.
        """
        if self._snapshot_fn is None:
            # sentinel=False: the snapshot program compiles whenever the
            # FIRST throttled save happens — legitimately after warmup
            self._snapshot_fn = observed_jit(
                lambda s: jax.tree_util.tree_map(jnp.copy, s),
                self.compile_monitor, "state_snapshot", sentinel=False,
            )
        params, batch_stats = self._snapshot_fn(
            (state.params, state.batch_stats)
        )
        rest = state.replace(params=None, batch_stats=None)
        rest = self._snapshot_fn(rest) if whole else rest.replace(
            step=None, opt_state=None, comms_residual=None
        )
        return rest.replace(params=params, batch_stats=batch_stats)

    def _note_pipeline_obs(self, t0: float, t1: float) -> None:
        """Per-dispatch pipeline observability (pipeline runs only): one
        synthetic span-lane triple per LOCAL stage — the fill/busy/drain
        trapezoid of the schedule scaled onto the measured dispatch
        interval, so the Perfetto timeline renders the bubble structure a
        device trace would show — plus a per-stage busy-seconds histogram
        (``step/stage{s}/busy_s``) the straggler attribution scores
        cross-host, giving findings a STAGE name, not just a host.  The
        proportions are the schedule's static tick arithmetic
        (``schedule_meta``); the interval is the measured one."""
        meta = self._pipe_meta
        if meta is None or t1 <= t0:
            return
        if self._step_meter.last_compiled:
            # mirror the host phase sketches' compile-taint split: a
            # dispatch that compiled would dominate every stage's busy
            # sketch and star the host as a straggler for the attempt
            return
        span = t1 - t0
        ticks = meta["ticks"]
        for s in self._local_stages:
            fill = meta["fill_ticks"][s] / ticks * span
            drain = meta["drain_ticks"][s] / ticks * span
            lane = f"stage{s}"
            if fill > 0:
                self.tracer.record(
                    "pp_fill_bubble", t0, t0 + fill, lane=lane, stage=s
                )
            self.tracer.record(
                "pp_busy", t0 + fill, t1 - drain, lane=lane, stage=s,
                schedule=meta["schedule"], virtual=meta["virtual"],
                bubble_frac=meta["bubble_frac"],
            )
            if drain > 0:
                self.tracer.record(
                    "pp_drain_bubble", t1 - drain, t1, lane=lane, stage=s
                )
            self.metrics.histogram(f"step/stage{s}/busy_s").record(
                max(0.0, span - fill - drain)
            )

    def _device_runner_for(self, take: int):
        """The compiled device-mode chunk runner for a ``take``-step chunk
        (cached; at most two live per run — the full chunk and the epoch's
        remainder)."""
        runner = self._device_runners.get(take)
        if runner is None:
            runner = make_device_chunk_runner(
                self.mesh,
                self.hparams.batch_size,
                take,
                precision=self.precision,
                state_sharding=self.state_sharding,
                grad_accum=self.grad_accum,
                fwd_bwd=self.train_fwd_bwd,
                comms=self.comms,
                fault_injection=self._step_faults,
                monitor=self.compile_monitor,
                state_layout=self._state_layout,
            )
            self._device_runners[take] = runner
        return runner

    # ------------------------------------------------------------------ train

    def fit(self) -> int:
        """Epoch loop; returns the version number (reference ``fit`` contract,
        ``src/single/trainer.py:109-120``)."""
        hp = self.hparams
        self.logger.info(
            f"[{hp.backend.upper()} Version {self.version}] start training: "
            f"{hp.epoch} epochs, {self.steps_per_epoch} steps/epoch, "
            f"global batch {hp.batch_size}, mesh {dict(self.mesh.shape)}, "
            f"{self.precision}"
        )
        t_start = time.perf_counter()
        self.goodput.add("init", self._init_secs)
        profile_epoch = (
            self.start_epoch + 1
            if hp.epoch - self.start_epoch > 1
            else self.start_epoch
        )
        epoch = self.start_epoch
        bar = self._progress_bar(range(self.start_epoch, hp.epoch), desc="epochs")
        while epoch < hp.epoch:
            profiling = getattr(hp, "profile_dir", None) and epoch == profile_epoch
            if profiling:
                # the host spans are TraceAnnotations (obs/spans.py), so the
                # capture holds them on its own clock beside the device's ops
                jax.profiler.start_trace(hp.profile_dir)
            try:
                self.bus.emit("epoch_start", epoch=epoch)
                t0 = time.perf_counter()
                try:
                    with self.tracer.span("epoch", epoch=epoch):
                        if self.data_mode == "device":
                            losses, top1 = self._train_epoch_device(epoch)
                        else:
                            losses, top1 = self._train_epoch_host(epoch)
                except MidEpochRollback as ctl:
                    # a chunk-boundary policy rollback unwound the epoch (the
                    # barrier already booked its step time): apply the same
                    # verified restore as the epoch-boundary path, then
                    # re-enter the loop at the restored epoch.  This partial
                    # epoch never validates, checkpoints, or blesses a best —
                    # exactly the property the boundary move must preserve.
                    next_epoch = self._apply_control_rollback(
                        epoch, time.perf_counter() - t0, ctl
                    )
                    if next_epoch is not None:
                        epoch = next_epoch
                    # an unappliable rollback re-enters the SAME epoch from
                    # its start: the state was never touched and the per-step
                    # key fold replays it deterministically
                    continue
                # the train program's results are on the host: from here to
                # the next ``epoch_start`` is the boundary, the part of an
                # epoch where the host paces the chip.  Each thing it does
                # is a child span (``_boundary``), so a profiler trace books
                # the device's idle time to the line the host was in.
                with self.tracer.span("boundary", epoch=epoch):
                    epoch_time = time.perf_counter() - t0
                    self.goodput.add("step", epoch_time)
                    if self.ckpt_writer is not None:
                        # the writer starts no job, and stops one at work,
                        # while the host paces the chip: released at the
                        # next train dispatch
                        self.ckpt_writer.hold()
                    redo = self._boundary(epoch, losses, top1, epoch_time)
            finally:
                if profiling:
                    # where ``boundary`` closes, not where the train part
                    # ends: an operator's capture shows the chip waiting too
                    jax.profiler.stop_trace()
                    self.logger.info(f"profiler trace written to {hp.profile_dir}")
            if redo is not None:  # a rollback: re-enter at the restored epoch
                epoch = redo
                continue
            epoch += 1
            if bar is not None:
                bar.update(1)
        if bar is not None:
            bar.close()
        if self.ckpt_writer is not None:
            with self.goodput.phase("ckpt", span="ckpt_drain"):
                self.ckpt_writer.wait()
        self.logger.info(
            f"[{hp.backend.upper()} Version {self.version}] done in "
            f"{time.perf_counter() - t_start:.1f}s, best val acc {self.best_acc:.2f}%"
        )
        self.bus.emit(
            "run_end",
            epoch=hp.epoch - 1,
            best_acc=round(self.best_acc, 4),
            wall_s=round(time.perf_counter() - t_start, 4),
        )
        self._write_goodput()
        return self.version

    def _boundary(
        self, epoch: int, losses, top1: float, epoch_time: float
    ) -> int | None:
        """What the host does between an epoch's train program and the next
        ``epoch_start``, in order, each under a span of its own (children of
        ``fit()``'s ``boundary``; ``benchmark/harness/host_spans.py GROUPS``
        names the group each feeds).  A span brackets what the lines already
        did: none adds a wait, a fetch or a reordering.  Returns the epoch
        to re-enter after a rollback, or None to go on to the next."""
        hp, span = self.hparams, self.tracer.span
        imgs = len(losses) * hp.batch_size
        labels_seen = imgs * self._labels_per_example

        # failure detection + recovery, BEFORE this epoch validates or
        # checkpoints (a bad epoch must neither save its state nor be
        # blessed as best).  With the watchdog on, sustained badness
        # rolls back to the last good checkpoint and replays; with
        # --no-health, the first non-finite loss aborts (pre-PR-3
        # behavior — the compiled guard still kept the state clean).
        with span("health"):
            if self.watchdog is not None:
                rollback_to = self._health_check(epoch, losses, epoch_time)
                if rollback_to is not None:
                    return rollback_to
            elif not np.isfinite(losses).all() or (
                np.asarray(self._epoch_health.get("skipped", ())) > 0.5
            ).any():
                # skipped steps mean non-finite grads: the guard held the
                # state, but without the watchdog there is no recovery
                # policy — abort exactly like the pre-guard divergence check
                self._abort_nonfinite(epoch, losses)

        # closed-loop autopilot (ops/policy.py): apply any deferred
        # policy actions at this boundary — rollback/abort decisions
        # queued by the in-process engine's bus tap, or requests the
        # supervisor's engine wrote to <ckpt>/fleet/policy-*.req.
        # After the health check (the watchdog's own verdict has
        # priority) and BEFORE this epoch validates or checkpoints, so
        # a policy rollback never blesses the state it is revoking.
        with span("policy"):
            policy_next = self._apply_policy_requests(epoch, epoch_time)
        if policy_next is not None:
            return policy_next

        with span("step_log"):
            step_base = self._epoch_step_base
            meter = AverageMeter()
            for i, loss in enumerate(losses):
                gstep = epoch * self.steps_per_epoch + step_base + i
                if np.isfinite(loss):
                    # skipped (non-finite) steps applied no update; they are
                    # counted by the watchdog, not averaged into the epoch
                    meter.update(float(loss))
                if (gstep + 1) % hp.eval_step == 0:
                    # instantaneous batch loss, like the reference's
                    # ``loss.item()`` line (src/single/trainer.py:150-153)
                    self.logger.info(
                        f"[{hp.backend.upper()} Version {self.version} "
                        f"Epoch {epoch}] global step {gstep + 1}, "
                        f"train loss: {float(loss):.4f}"
                    )
                if getattr(hp, "log_every_step", False):
                    self._log_tb("loss/step", float(loss), gstep)

        with self.goodput.phase("eval", span="eval"):
            val = self.validate(epoch)
        with span("epoch_log"):
            lr_now = float(self.lr_schedule(epoch * self.steps_per_epoch))
            self.logger.info(
                f"[{hp.backend.upper()} Version {self.version} Epoch {epoch}] "
                f"train loss: {meter.avg:.4f}, train acc: {100.0 * top1 / labels_seen:.2f}%, "
                f"val loss: {val['val_loss']:.4f}, val acc: {val['val_acc']:.2f}%, "
                f"lr: {lr_now:.4f}, {imgs / epoch_time:.0f} img/s"
            )
            self._log_tb("lr", lr_now, epoch)
            self._log_tb("loss/epoch/train", meter.avg, epoch)
            self._log_tb("loss/epoch/val", val["val_loss"], epoch)
            self._log_tb("acc/epoch/val", val["val_acc"], epoch)
            self._log_tb("throughput/images_per_sec", imgs / epoch_time, epoch)
            for phase_name, secs in self._step_meter.seconds.items():
                # overlap health per epoch: h2d_wait climbing toward
                # epoch_time means the input pipeline stopped hiding behind
                # compute; near-zero means the chip never waited on data
                self._log_tb(f"overlap/{phase_name}_s", secs, epoch)
            self._overlap_totals.merge(self._step_meter)
        with span("epoch_end_emit"):
            self.bus.emit(
                "epoch_end",
                epoch=epoch,
                train_loss=round(meter.avg, 6),
                val_loss=round(val["val_loss"], 6),
                val_acc=round(val["val_acc"], 4),
                lr=lr_now,
                secs=round(epoch_time, 4),
                images_per_sec=round(imgs / epoch_time, 2),
                step_breakdown=self._step_meter.summary(),
            )
        # drain the sketches at every epoch boundary regardless of the
        # step budget: per-attempt stats reconstruct exactly, and a
        # preempted next epoch can lose at most ITS OWN steps' samples
        with span("metrics_flush"):
            self.resources.sample(self.metrics)
            self.metrics.flush(self.bus, epoch=epoch)
        with span("heartbeat"):
            self.heartbeat.beat(
                epoch=epoch,
                step=(epoch + 1) * self.steps_per_epoch,
                flush_seq=self.metrics.flushes,
            )
        if getattr(self, "_moe_health", None):
            with span("moe_log"):
                for k, v in self._moe_health.items():
                    # moe_dropped_frac → moe/dropped_frac, moe_load_max →
                    # moe/load_max: a collapsed router (load_max → 1.0) or
                    # capacity thrash (dropped_frac climbing) shows up per
                    # epoch
                    self._log_tb(f"moe/{k[len('moe_'):]}", v, epoch)
                self.logger.info(
                    f"[{hp.backend.upper()} Version {self.version} Epoch "
                    f"{epoch}] moe: "
                    + ", ".join(
                        f"{k[len('moe_'):]} {v:.4f}"
                        for k, v in self._moe_health.items()
                    )
                )

        # Checkpoint decisions are computed on EVERY process from
        # replicated values (val metrics are identical across hosts) so
        # that the collective-fetch path below runs symmetrically.
        # The comms error-feedback residual is dropped up front: no
        # save path serializes it (checkpoint._state_dict), so fetching
        # or snapshotting it would move a params-sized tree per save
        # for data that is thrown away.
        with span("ckpt_decide"):
            state_ref, vdir = self._ckpt_view(self.state), self.version_dir
            want_best = val["val_acc"] > self.best_acc
            if want_best:
                self.best_acc = val["val_acc"]
            is_last_epoch = epoch == hp.epoch - 1
            due = (epoch + 1) % getattr(hp, "save_last_every", 1) == 0
            # throttle: the full-state device→host fetch can exceed a
            # fast epoch's compute time; cap the save rate (final epoch
            # always saves so resume never loses the finished state).
            # Wall-clock throttling can diverge across hosts, so it is
            # only applied when the fetch involves no collective.
            sync_fetch = jax.process_count() > 1 and needs_collective_fetch(
                state_ref
            )
            min_secs = getattr(hp, "save_last_min_secs", 0.0) or 0.0
            throttled = not sync_fetch and (
                time.monotonic() - self._last_resume_save < min_secs
            )
            if jax.process_count() > 1 and not sync_fetch:
                # the wall-clock throttle can diverge across hosts, and the
                # writer snapshot below is a COMPUTATION every process must
                # enter together — follow process 0's verdict (one tiny
                # broadcast, in a mode whose epochs already run collectives)
                from jax.experimental import multihost_utils

                throttled = bool(
                    multihost_utils.broadcast_one_to_all(np.asarray(throttled))
                )
            want_last = getattr(hp, "save_last", True) and (
                is_last_epoch or (due and not throttled)
            )
        if (want_best or want_last) and sync_fetch:
            # Cross-host-partitioned (tensor-parallel) leaves: the
            # device→host fetch is an all-gather COLLECTIVE — run it
            # here, on every process and on the main thread.  The
            # process-0 writer thread then only serializes host numpy.
            # Best-only saves need just params+batch_stats; the full
            # state (opt_state included) is gathered only when the
            # resumable last.ckpt is due — halves the DCN volume on
            # best-improvement epochs.
            with self.goodput.phase("ckpt", span="ckpt_fetch"):
                if want_last:
                    state_ref = fetch_to_host(state_ref)
                else:
                    state_ref = state_ref.replace(
                        params=fetch_to_host(state_ref.params),
                        batch_stats=fetch_to_host(state_ref.batch_stats),
                    )
        elif want_best or want_last:
            # The scanned runners DONATE the input state, so the next
            # epoch's dispatch reuses these buffers — the async writer
            # must get its own device-side snapshot (HBM→HBM copy,
            # dispatched async; a computation, so under multi-host it
            # runs on EVERY process), never a reference donation would
            # invalidate mid-fetch.
            # A best-only save writes params and statistics, so only
            # they are copied: at a language model's size the optimizer
            # state's copy beside the next epoch's step is what would
            # bound the batch.
            with self.goodput.phase("ckpt", span="ckpt_snapshot"):
                state_ref = self._snapshot_state(state_ref, whole=want_last)
        if self.is_main:
            # write-behind: the worker thread fetches + serializes while
            # the next epoch computes (from the snapshot/host copy above
            # — never the live state the donated dispatch will reuse).
            # The first job to run moves the snapshot to the host
            # and lets the device copy go (``_WriterSnapshot``).  The
            # writer's ``ckpt_write`` spans name this one as their parent
            # (``AsyncCheckpointer.submit``).
            with span("ckpt_submit"):
                state_ref = _WriterSnapshot(state_ref)
                if want_best:
                    self.ckpt_writer.submit(
                        lambda s=state_ref, e=epoch, b=self.best_acc: (
                            ckpt.save_checkpoint(
                                vdir, s.on_host(), e, b,
                                state_layout=self._state_layout,
                                pace=self.ckpt_writer.pace,
                            )
                        ),
                        key="best",
                    )
                if want_last:
                    self._last_resume_save = time.monotonic()
                    hook = (
                        self.fault_plan.ckpt_hook(epoch)
                        if self.fault_plan is not None
                        else None
                    )
                    self.ckpt_writer.submit(
                        lambda s=state_ref, e=epoch, b=self.best_acc, h=hook: (
                            ckpt.save_resume_state(
                                vdir, s.on_host(), e, b,
                                fault_hook=h,
                                meta=self._ckpt_meta(),
                                state_layout=self._state_layout,
                                pace=self.ckpt_writer.pace,
                            )
                        ),
                        key="last",
                    )
        with span("writer_stats"):
            if self.ckpt_writer is not None:
                # periodic writer gauge: queue depth climbing epoch over
                # epoch (or busy_frac → 1.0) means write-behind stopped
                # hiding the checkpoint cost
                wstats = self.ckpt_writer.stats()
                self.bus.emit("writer", epoch=epoch, **wstats)
                self._log_tb("ckpt/writer_busy_frac", wstats["busy_frac"], epoch)
                self._log_tb("ckpt/queue_depth", wstats["queue_depth"], epoch)
            self._log_tb(
                "goodput/productive_frac", self.goodput.productive_frac(), epoch
            )
        # --- resilience hooks, at the epoch boundary (the epoch itself
        # is one device program — the smallest interruptible unit)
        with span("resilience"):
            if self.fault_plan is not None:
                stall = self.fault_plan.stall_secs(epoch)
                if stall > 0:
                    self.logger.warning(
                        f"injected stall: {stall:.2f}s after epoch {epoch}"
                    )
                    time.sleep(stall)
                    self.goodput.add("stall", stall)
            if self._preempt_due(epoch):
                self._preempt_exit(epoch, state_ref, want_last, sync_fetch)
            if epoch == self.start_epoch:
                # steady state for the recompilation sentinel: the first
                # full epoch built every hot-path executable (chunk runner
                # + remainder, val eval) — a sentinel-tracked compile from
                # here on is bucket churn / an unexpected reshape, and
                # bumps compile/recompiles_after_warmup
                self.compile_monitor.warm()
        return None

    # -------------------------------------------------------- training health

    def _abort_nonfinite(self, epoch: int, losses, note: str = "") -> None:
        """Divergence abort (absent in the reference, SURVEY.md §5): stop at
        the first non-finite loss and point at the last good state — a
        diverged run must not burn the remaining epochs or poison any later
        checkpoint.  The guarded update already kept the in-memory state
        clean; this is the loud exit when no recovery path remains."""
        finite = np.isfinite(losses)
        if not finite.all():
            bad = int(np.argmin(finite))
        else:
            # finite losses but non-finite grads: point at the first step
            # the compiled guard skipped
            skipped = np.asarray(
                self._epoch_health.get("skipped", np.zeros(len(losses)))
            ) > 0.5
            bad = int(np.argmax(skipped)) if skipped.any() else 0
        if self.ckpt_writer is not None:
            # drain in-flight best/last writes: the daemon writer must not
            # die mid-save when the exception exits.  A failed earlier
            # write is logged but must not replace the diagnostics below.
            try:
                self.ckpt_writer.wait()
            except Exception as e:
                self.logger.error(f"checkpoint writer error: {e}")
        last_good = (
            self.version_dir / ckpt.LAST_NAME
            if self.version_dir is not None
            else None
        )
        if last_good is not None and not last_good.exists():
            last_good = None
        msg = (
            f"non-finite train loss/grads at epoch {epoch}, step {bad} "
            f"(global step {epoch * self.steps_per_epoch + bad}){note} — "
            f"aborting; last saved state: {last_good or 'none'}"
        )
        self.logger.error(msg)
        # flight recorder: the abort is exactly the moment a post-mortem
        # wants the final ring of events for
        self.bus.emit("abort", epoch=epoch, step=bad, reason=msg)
        self.bus.dump_crash(msg, directory=self._obs_dir)
        raise FloatingPointError(msg)

    def _health_check(self, epoch: int, losses, epoch_time: float) -> int | None:
        """The watchdog's per-epoch verdict, BEFORE validation/checkpointing.

        Returns the epoch to re-enter after a rollback, or None to proceed.
        Every input to the decision (per-step losses, skip flags, gathered
        fingerprints) is replicated/identical across processes, so under
        multi-host every process reaches the same verdict and the rollback
        collectives below run symmetrically.
        """
        skipped = np.asarray(
            self._epoch_health.get("skipped", np.zeros(len(losses)))
        )
        # spike baselines are per LR phase (the StepLR staircase shifts the
        # whole loss distribution at each decay); the phase label is the
        # schedule's value at this epoch's first step, so any schedule
        # shape keys its own plateaus
        phase = f"lr={float(self.lr_schedule(epoch * self.steps_per_epoch)):.6g}"
        verdict = self.watchdog.observe_epoch(
            epoch, np.asarray(losses), skipped, phase=phase
        )
        if verdict.skipped:
            self._log_tb("health/skipped_steps", verdict.skipped, epoch)
            self.logger.warning(
                f"health: {verdict.skipped} non-finite step(s) skipped in "
                f"epoch {epoch} (guarded update held the state)"
            )
        if verdict.spikes:
            self._log_tb("health/spike_steps", verdict.spikes, epoch)

        desync = None
        cfg = self.watchdog.cfg
        inject = (
            self.fault_plan.desync_due(epoch)
            if self.fault_plan is not None
            else False
        )
        if inject or (cfg.desync_every > 0 and (epoch + 1) % cfg.desync_every == 0):
            desync = self._desync_check(inject)
            if desync["mismatch"]:
                self.watchdog.note_desync(epoch, desync)

        reason = verdict.reason
        if desync is not None and desync["mismatch"]:
            reason = (
                f"cross-replica desync (fingerprint spread "
                f"{desync['spread']:.6g}"
                + (", injected)" if desync["injected"] else ")")
            )
        if reason is None:
            if self.is_main:
                self.watchdog.flush_events(self.version_dir)
            return None

        self.logger.warning(f"health: rollback wanted at epoch {epoch}: {reason}")
        if self.watchdog.exhausted():
            if verdict.nonfinite or verdict.skipped:
                self._abort_nonfinite(
                    epoch, losses,
                    note=f" after {self.watchdog.rollbacks} rollbacks",
                )
            msg = (
                f"health watchdog: rollback budget "
                f"({cfg.max_rollbacks}) exhausted at epoch {epoch}: {reason}"
            )
            self.bus.emit("abort", epoch=epoch, reason=msg)
            self.bus.dump_crash(msg, directory=self._obs_dir)
            raise RuntimeError(msg)
        with self.tracer.span("rollback", epoch=epoch):
            next_epoch = self._rollback(epoch, epoch_time, reason, verdict)
        if next_epoch is None:  # nothing to roll back to
            if verdict.nonfinite or verdict.skipped:
                self._abort_nonfinite(
                    epoch, losses, note=" (no rollback checkpoint exists)"
                )
            self.logger.error(
                "health: no rollback checkpoint available; continuing "
                "(spiked updates are already applied)"
            )
            if self.is_main:
                self.watchdog.flush_events(self.version_dir)
            return None
        return next_epoch

    def _desync_check(self, inject: bool) -> dict:
        """Param fingerprint, all-gathered and compared across processes (a
        COLLECTIVE under multi-host — reached identically by every process).
        One scalar device→host read; see health/desync.py.

        When the model axis is actually sharded (``model_parallel > 1``)
        the post-collective scalar is blind to per-replica drift INSIDE the
        sharded leaves, so a partial-reduce pass (per-device checksums
        grouped by mesh coordinate, compared down the replicated data axis)
        runs alongside it — it costs a host fetch of the local shards, so
        it is gated to the meshes that have the blind spot."""
        if self._fingerprint_fn is None:
            self._fingerprint_fn = observed_jit(
                param_fingerprint, self.compile_monitor,
                "param_fingerprint", sentinel=False,
            )
        report = check_desync(
            float(self._fingerprint_fn(self.state.params)), inject=inject
        )
        sharded_axes = self.mesh.shape["model"] > 1 or (
            self.mesh.shape.get("pipe", 1) > 1
        )
        if sharded_axes and not report["mismatch"]:
            from ..health import check_partial_desync

            partial = check_partial_desync(self._partial_matrix())
            if partial["mismatch"]:
                report = {**partial, "injected": inject}
        return report

    def _partial_matrix(self) -> np.ndarray:
        """The per-device ``(data, model)`` partial-fingerprint matrix.

        Preferred path: the compiled shard_map reduce
        (``health.make_partial_fingerprint_fn``) — each device folds its
        own shards to one scalar IN the program, so the device→host
        traffic per check is ``data × model`` floats instead of the full
        local shard set (multi-GB states paid that fetch every epoch).
        Any failure degrades permanently to the original host-side path;
        desync detection must never die with its optimization.

        The degrade decision is FLEET-SYMMETRIC: both branches end in a
        collective under multi-host (the device path's partitioned fetch,
        the host path's allgather), so one host silently falling back
        while its peers stay on the device path would put the processes
        in mismatched collectives and wedge the fleet.  Every process
        therefore reports its local build/dispatch success and the fleet
        takes the path ONLY if every process can (one tiny allgather per
        check — noise next to the fingerprint collectives this method
        already runs).
        """
        from ..health import (
            gather_partial_fingerprints,
            make_partial_fingerprint_fn,
            partial_fingerprints,
        )

        if self._partial_fp_fn is None:
            try:
                self._partial_fp_fn = self.compile_monitor.instrument(
                    make_partial_fingerprint_fn(
                        self.mesh, self.state_sharding.params
                    ),
                    "partial_fingerprint", sentinel=False,
                )
            except Exception as e:
                self.logger.warning(
                    f"health: per-device partial-fingerprint reduce "
                    f"unavailable ({e}); falling back to the host fetch"
                )
                self._partial_fp_fn = False
        result = None
        if self._partial_fp_fn:
            try:
                # dispatch only — the (collective-bearing) fetch waits
                # until every process has agreed the dispatch succeeded
                result = self._partial_fp_fn(self.state.params)
            except Exception as e:
                self.logger.warning(
                    f"health: per-device partial-fingerprint reduce failed "
                    f"({e}); falling back to the host fetch"
                )
                self._partial_fp_fn = False
        ok = result is not None
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            ok = bool(
                np.all(multihost_utils.process_allgather(np.asarray(ok)))
            )
            if not ok and self._partial_fp_fn:
                # a PEER degraded: follow it permanently so every later
                # check re-agrees trivially instead of re-paying a doomed
                # dispatch per epoch
                self.logger.warning(
                    "health: a peer process degraded the per-device "
                    "partial-fingerprint reduce; following to the host "
                    "fetch fleet-wide"
                )
                self._partial_fp_fn = False
        if ok:
            return np.asarray(fetch_to_host(result))
        return gather_partial_fingerprints(
            partial_fingerprints(self.state.params, self.mesh)
        )

    def _rollback(
        self, epoch: int, epoch_time: float, reason: str, verdict=None
    ) -> int | None:
        """Restore the last good checkpoint (verified bytes, prev- fallback)
        and return the epoch to replay from; None when no verified
        checkpoint exists.  The epoch(s) being discarded move from the
        goodput 'step' phase to 'rollback' — wasted compute must not count
        as productive.  With ``--health-quarantine`` (host data mode) the
        bad step window's batch example indices are handed to the loader
        before the replay, so a persistently corrupt shard cannot re-fire
        the same rollback."""
        if self.ckpt_writer is not None:
            # drain in-flight saves so the newest last.ckpt is durable
            # before it is read back; a failed save falls through to the
            # prev- fallback rather than killing the recovery
            with self.goodput.phase("ckpt"):
                try:
                    self.ckpt_writer.wait()
                except Exception as e:
                    self.logger.error(
                        f"checkpoint writer error during rollback drain: {e}"
                    )
        hit = (
            ckpt.valid_resume_bytes_in(self.version_dir)
            if self.version_dir is not None
            else None
        )
        if hit is None and self.is_main and self._rollback_source:
            # fresh version dir with no save yet (explicit --resume): fall
            # back to the read-only source checkpoint the run started from
            source = Path(self._rollback_source)
            if source.exists():
                data, digest = read_and_hash(source)
                ok, why = verify_checkpoint(source, data=data, digest=digest)
                if ok:
                    self.logger.warning(
                        "health: no checkpoint in this run's version dir "
                        f"yet; rolling back to the resume source {source}"
                    )
                    hit = (source, data)
                else:
                    self.logger.warning(
                        f"health: resume source {source} no longer "
                        f"verifies ({why}); cannot use it as rollback target"
                    )
        if jax.process_count() > 1:
            # Only process 0 owns the version dir; agree on whether a
            # target exists, then ship the restored host state to everyone
            # (same idiom as test()'s best-checkpoint broadcast) — every
            # collective entered by every process.
            from jax.experimental import multihost_utils

            found = bool(
                multihost_utils.broadcast_one_to_all(np.asarray(hit is not None))
            )
            if not found:
                return None
            # the comms error-feedback residual never rides the rollback
            # broadcast: a rolled-back residual belonged to the discarded
            # trajectory, so every process resets it below — and the live
            # (possibly cross-host-sharded) leaf could not be np.asarray'd
            # symmetrically anyway
            def _no_residual(sd: dict) -> dict:
                return {k: v for k, v in sd.items() if k != "comms_residual"}

            template = _no_residual(ckpt._state_dict(self.state))
            if self.is_main:
                path, data = hit
                state0, next_epoch, best = ckpt.load_resume_state(
                    path, self.state, raw_bytes=data,
                    state_layout=self._state_layout,
                )
                host = jax.tree_util.tree_map(
                    np.asarray, _no_residual(ckpt._state_dict(state0))
                )
                meta = np.asarray([next_epoch, best], np.float64)
            else:
                host = jax.tree_util.tree_map(
                    lambda l: np.zeros(l.shape, l.dtype), template
                )
                meta = np.zeros(2, np.float64)
            synced = multihost_utils.broadcast_one_to_all(host)
            meta = multihost_utils.broadcast_one_to_all(meta)
            state = self.state.replace(
                step=synced["step"],
                params=synced["params"],
                batch_stats=synced["batch_stats"],
                opt_state=synced["opt_state"],
            )
            next_epoch, best = int(meta[0]), float(meta[1])
        else:
            if hit is None:
                return None
            path, data = hit
            state, next_epoch, best = ckpt.load_resume_state(
                path, self.state, raw_bytes=data,
                state_layout=self._state_layout,
            )
        state = self._reset_comms_residual(state)
        self.state = place_tree(state, self.state_sharding)
        self.best_acc = best
        # corrupt-shard quarantine (--health-quarantine, host data mode):
        # the replay must not re-train the condemned window's examples —
        # the loader substitutes deterministically drawn clean ones, so a
        # corrupt shard that deterministically re-fires stops doing so.
        # Each host quarantines its OWN shard's slice of the bad steps (the
        # verdict is replicated, so the decision is symmetric).
        if (
            self.watchdog.cfg.quarantine
            and verdict is not None
            and verdict.bad_steps
            and self.train_loader is not None
            and hasattr(self.train_loader, "quarantine")
        ):
            step_base = self._epoch_step_base
            bad_steps = [step_base + int(s) for s in verdict.bad_steps]
            try:
                ids = np.concatenate(
                    [
                        self.train_loader.batch_example_indices(epoch, s)
                        for s in bad_steps
                    ]
                )
                added = self.train_loader.quarantine(ids)
            except ValueError as e:  # quarantining everything is worse
                self.logger.error(f"health: quarantine refused: {e}")
            else:
                self.watchdog.note_quarantine(epoch, bad_steps, added)
                self.logger.warning(
                    f"health: quarantined {added} example(s) from the bad "
                    f"step window {bad_steps[:8]} of epoch {epoch}; the "
                    "replay substitutes clean examples"
                )
                # persist THIS rank's set next to the checkpoints: the
                # manifest (rank 0's write) carries only rank 0's shard,
                # so every rank drops a quarantine-p{i}.json sidecar and a
                # relaunch unions them all back (union_quarantine)
                from ..resilience.ckpt_io import write_quarantine_sidecar

                write_quarantine_sidecar(
                    self._obs_dir or self.version_dir,
                    jax.process_index(),
                    self.train_loader.quarantined,
                )
        self._resume_step_offset = 0  # a rollback replays whole epochs
        wasted_epochs = max(1, epoch - next_epoch + 1)
        wasted_s = self.goodput.transfer(
            "step", "rollback", epoch_time * wasted_epochs
        )
        self.watchdog.record_rollback(
            epoch, next_epoch,
            wasted_steps=wasted_epochs * self.steps_per_epoch,
            wasted_s=wasted_s, reason=reason,
        )
        self.logger.warning(
            f"health: rolled back to end of epoch {next_epoch - 1} "
            f"(replaying from epoch {next_epoch}; ~{wasted_s:.1f}s of step "
            f"time wasted): {reason}"
        )
        if self.is_main:
            self.watchdog.flush_events(self.version_dir)
        return next_epoch

    # ---------------------------------------------------------- autopilot

    def _apply_policy_requests(
        self, epoch: int, epoch_time: float
    ) -> int | None:
        """Apply deferred policy actions at an epoch boundary.

        Sources: the in-process engine's queued decisions (unsupervised
        runs) and the supervisor's request files (supervised — process 0
        polls; under multi-host the fold is allgather-OR'd so every
        process enters the rollback collectives together, the
        ``_preempt_due`` idiom).  Returns the epoch to re-enter after a
        policy rollback, or None.  ``abort_with_evidence`` raises
        :class:`~..ops.policy.PolicyAbort` after dumping the evidence.
        """
        if (
            self.policy_engine is None
            and self._policy_poller is None
            and self._control_poller is None
        ):
            return None
        reqs, self._policy_requests = self._policy_requests, []
        if self.is_main:
            # consume (read + unlink) HERE, where application immediately
            # follows in the same call — a pickup earlier in the epoch
            # would widen the window in which a crash loses a consumed-
            # but-unapplied request to an unrecoverable pending state
            if self._policy_poller is not None:
                reqs.extend(self._policy_poller.poll())
            if self._control_poller is not None:
                # decisions that landed during the epoch's FINAL chunk
                # (the mid-epoch barrier stops one boundary early) apply
                # here instead of waiting out another epoch
                reqs.extend(self._control_poller.poll())
        reqs = self._discard_stale_controls(
            reqs, epoch=epoch, step=(epoch + 1) * self.steps_per_epoch,
            boundary="epoch",
        )
        abort_reqs = [
            r for r in reqs if r.get("action") == "abort_with_evidence"
        ]
        roll_reqs = [r for r in reqs if r.get("action") == "rollback"]
        drain_reqs = [r for r in reqs if r.get("action") == "drain"]
        abort_req = abort_reqs[0] if abort_reqs else None
        roll_req = roll_reqs[0] if roll_reqs else None
        drain_req = drain_reqs[0] if drain_reqs else None
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            flags = np.any(
                multihost_utils.process_allgather(
                    np.asarray([
                        abort_req is not None,
                        roll_req is not None,
                        drain_req is not None,
                    ])
                ),
                axis=0,
            )
            # a peer received the request this process didn't see (only
            # process 0 reads the file): act on the agreed decision, but
            # leave completion emission to the process holding the id
            if flags[0] and abort_req is None:
                abort_req = {"action": "abort_with_evidence"}
            if flags[1] and roll_req is None:
                roll_req = {"action": "rollback"}
            if flags[2] and drain_req is None:
                drain_req = {"action": "drain"}
        from ..ops import policy as policy_mod

        if drain_req is not None and abort_req is None:
            # a drain_host/replan control request reaching the epoch
            # boundary: arm the drain flag — this epoch checkpoints
            # normally, then the boundary preempt poll below the save
            # drains through the proven _preempt_exit path
            for r in drain_reqs:
                self._emit_control(
                    r, state="applied", epoch=epoch,
                    step=(epoch + 1) * self.steps_per_epoch,
                    boundary="epoch",
                )
            self._drain_requested = True
            self._drain_reqs.extend(drain_reqs)
        if abort_req is not None:
            # the abort supersedes everything else queued this boundary:
            # close every OTHER id first (as 'coalesced' — the superseded
            # actions were never performed) so no 'requested' event is
            # left orphaned behind the raise
            for r in abort_reqs[1:] + roll_reqs:
                if r.get("id") is not None:
                    policy_mod.emit_completion(
                        self.bus, r, state="coalesced",
                        coalesced_into=abort_req.get("id"),
                    )
            self._policy_abort_exit(
                epoch, abort_req,
                step=(epoch + 1) * self.steps_per_epoch, boundary="epoch",
            )  # raises PolicyAbort
        if roll_req is None:
            return None

        def fail(why: str) -> None:
            self.logger.error(f"policy rollback not applied: {why}")
            for r in roll_reqs:
                if r.get("id") is not None:
                    policy_mod.emit_completion(
                        self.bus, r, ok=False, error=why
                    )

        if self.watchdog is None:
            fail("the health watchdog is disabled (--no-health)")
            return None
        if self.watchdog.exhausted():
            fail(
                f"rollback budget "
                f"({self.watchdog.cfg.max_rollbacks}) already exhausted"
            )
            return None
        reason = f"policy action ({roll_req.get('rule') or 'rollback'})"
        self.logger.warning(
            f"policy: rollback requested at epoch {epoch}: {reason}"
        )
        with self.tracer.span("rollback", epoch=epoch):
            next_epoch = self._rollback(epoch, epoch_time, reason)
        if next_epoch is None:
            fail("no verified rollback checkpoint available")
            return None
        # ONE rollback satisfies every request queued this boundary; each
        # id gets its outcome so none reads as pending
        for r in roll_reqs:
            self._emit_control(
                r, state="applied", epoch=epoch,
                step=(epoch + 1) * self.steps_per_epoch, boundary="epoch",
                from_epoch=epoch, to_epoch=next_epoch,
            )
            if r.get("id") is not None:
                policy_mod.emit_completion(
                    self.bus, r, from_epoch=epoch, to_epoch=next_epoch
                )
        return next_epoch

    def _policy_abort_exit(
        self, epoch: int, req: dict, *, step: int | None = None,
        boundary: str = "epoch",
    ) -> None:
        """``abort_with_evidence``: drain the writer (the last good
        checkpoint stays durable), attach the alert + policy timelines to
        ``crash_dump.json`` next to the flight-recorder ring, and raise.
        The supervisor's executor already asked the restart loop to stop,
        so the evidence is the run's last word, not a relaunch input."""
        from ..ops import policy as policy_mod

        msg = (
            f"policy abort_with_evidence at epoch {epoch} "
            f"(rule {req.get('rule') or '?'}, trigger {req.get('trigger') or '?'})"
        )
        self.logger.error(msg)
        if self.ckpt_writer is not None:
            try:
                self.ckpt_writer.wait()
            except Exception as e:
                self.logger.error(f"checkpoint writer error: {e}")
        if step is None:
            step = (epoch + 1) * self.steps_per_epoch
        self._emit_control(
            req, state="applied", epoch=epoch, step=step, boundary=boundary,
        )
        if req.get("id") is not None:
            policy_mod.emit_completion(self.bus, req, epoch=epoch)
        self.bus.emit("abort", epoch=epoch, reason=msg)
        # the alert/policy timeline: this process's ring (the in-process
        # engine emits here) plus the supervisor's root event file (a
        # supervised run's engine lives over there)
        timeline = [
            ev for ev in self.bus.ring_events()
            if ev.get("kind") in ("alert", "policy")
        ]
        root = getattr(self.hparams, "ckpt_path", None)
        if self._policy_poller is not None and root:
            try:
                for path in sorted(Path(root).glob("events*.jsonl")):
                    timeline.extend(
                        ev for ev in obs.load_events(path)
                        if ev.get("kind") in ("alert", "policy")
                    )
            except OSError:
                pass
        self.bus.dump_crash(
            msg,
            directory=self._obs_dir,
            evidence={
                "request": {
                    k: req[k]
                    for k in ("rule", "id", "trigger", "alert_source")
                    if req.get(k) is not None
                },
                "alert_timeline": [
                    ev for ev in timeline if ev.get("kind") == "alert"
                ],
                "policy_timeline": [
                    ev for ev in timeline if ev.get("kind") == "policy"
                ],
            },
        )
        raise policy_mod.PolicyAbort(msg)

    # --------------------------------------------- mid-epoch control plane

    def _gstep_at(self, t_wall: float) -> int | None:
        """The global step the run was at when ``t_wall`` happened —
        the latest chunk-boundary mark not after it (None before the
        first mark), dating a supervisor decision on the step axis."""
        marks = self._ttm_marks
        if not marks:
            return None
        idx = bisect.bisect_right([t for t, _ in marks], t_wall) - 1
        if idx < 0:
            return 0
        return marks[idx][1]

    def _emit_control(
        self, req: dict, *, state: str, epoch: int, step: int,
        boundary: str, **extra,
    ) -> None:
        """One registered ``control`` event per request reaching a
        boundary: identity + decide→apply latency in seconds and steps."""
        from ..resilience import control as control_mod

        step_at_decide = None
        t_decide = req.get("t_decide")
        if isinstance(t_decide, (int, float)):
            step_at_decide = self._gstep_at(float(t_decide))
        self.bus.emit(
            control_mod.CONTROL_KIND, epoch=epoch, step=step,
            **control_mod.control_event_payload(
                req, state=state, boundary=boundary, step=step,
                step_at_decide=step_at_decide, **extra,
            ),
        )

    def _discard_stale_controls(
        self, reqs: list[dict], *, epoch: int, step: int, boundary: str,
    ) -> list[dict]:
        """Drop attempt-scoped control requests decided for an earlier
        attempt (the boundary they asked for already happened — the
        supervisor restarted before the trainer consumed the file) with
        a ``superseded`` control event each, so nothing dangles and
        nothing double-applies: the one-shot-across-restarts contract
        mid-epoch preemption already keeps (``FaultPlan.preempt_step_due``
        fires once per window)."""
        from ..resilience import control as control_mod

        fresh = []
        for r in reqs:
            if control_mod.is_stale(r, self._attempt_index):
                self.logger.warning(
                    f"control: stale {r.get('action')} request from "
                    f"attempt {r.get('attempt')} discarded (now attempt "
                    f"{self._attempt_index}: its boundary already ran)"
                )
                self._emit_control(
                    r, state="superseded", epoch=epoch, step=step,
                    boundary=boundary,
                )
            else:
                fresh.append(r)
        return fresh

    def _rollback_target_exists(self) -> bool:
        """Is there anything a rollback could restore — a verified save
        in this run's version dir, or the read-only resume source?  The
        mid-epoch barrier asks BEFORE unwinding the chunk loop; process
        0 owns the version dir, so the answer is broadcast (the
        ``_rollback`` found-target idiom, one boundary earlier)."""
        hit = False
        if self.is_main:
            if self.ckpt_writer is not None:
                # an in-flight async save IS a target: drain it before
                # validating, or the mid-rewrite last/prev-last pair
                # reads as "no checkpoint" and a viable rollback is
                # needlessly deferred to the epoch boundary
                try:
                    self.ckpt_writer.wait()
                except Exception:
                    pass  # a failed save falls through to prev-/resume
            try:
                hit = (
                    self.version_dir is not None
                    and ckpt.valid_resume_bytes_in(self.version_dir)
                    is not None
                )
            except Exception:
                hit = False
            if not hit and self._rollback_source:
                hit = Path(self._rollback_source).exists()
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            hit = bool(
                multihost_utils.broadcast_one_to_all(np.asarray(hit))
            )
        return hit

    def _control_barrier(self, epoch: int, step: int) -> list[dict] | None:
        """The chunk-boundary control poll (the tentpole seam): consume
        any queued policy decisions and apply them INSIDE the epoch.

        Sources and symmetry are the ``_apply_policy_requests`` idiom —
        the in-process engine's queue plus process 0's read of the
        control files, allgather-OR'd under multi-host so every process
        enters the drain/rollback collectives together.  Application per
        action: ``abort_with_evidence`` dumps evidence and raises here;
        a ``drain`` request arms ``_drain_requested`` so the preempt
        poll one line below this call drains through the proven
        mid-epoch checkpoint path; ``rollback`` cannot run under the
        live chunk iterators, so its requests are returned for the call
        site to unwind to ``fit()`` (``MidEpochRollback``).  Returns
        None when nothing rollback-shaped is due."""
        if self._control_boundary != "chunk":
            return None
        if self.policy_engine is None and self._control_poller is None:
            return None
        reqs: list[dict] = []
        if self._policy_requests:
            # requests parked for the EPOCH boundary (a rollback decided
            # before the first verified save — see below) stay queued for
            # _apply_policy_requests; everything else is consumed here
            pend, self._policy_requests = self._policy_requests, []
            self._policy_requests = [r for r in pend if r.get("_epoch_only")]
            reqs = [r for r in pend if not r.get("_epoch_only")]
        if self._control_poller is not None and self.is_main:
            reqs.extend(self._control_poller.poll())
        gstep = epoch * self.steps_per_epoch + step
        reqs = self._discard_stale_controls(
            reqs, epoch=epoch, step=gstep, boundary="chunk"
        )
        abort_reqs = [
            r for r in reqs if r.get("action") == "abort_with_evidence"
        ]
        roll_reqs = [r for r in reqs if r.get("action") == "rollback"]
        drain_reqs = [r for r in reqs if r.get("action") == "drain"]
        if jax.process_count() > 1 and (
            self.policy_engine is not None or self._control_poller is not None
        ):
            from jax.experimental import multihost_utils

            flags = np.any(
                multihost_utils.process_allgather(
                    np.asarray([
                        bool(abort_reqs), bool(roll_reqs), bool(drain_reqs),
                    ])
                ),
                axis=0,
            )
            # a peer holds the request this process didn't see; act
            # together, leave completion emission to the id holder
            if flags[0] and not abort_reqs:
                abort_reqs = [{"action": "abort_with_evidence"}]
            if flags[1] and not roll_reqs:
                roll_reqs = [{"action": "rollback"}]
            if flags[2] and not drain_reqs:
                drain_reqs = [{"action": "drain"}]
        if not (abort_reqs or roll_reqs or drain_reqs):
            return None
        from ..ops import policy as policy_mod

        if drain_reqs:
            # drain_host/replan: arm the drain — the preempt poll at this
            # same boundary takes the proven mid-epoch drain-checkpoint
            # exit, and the supervisor re-renders the world / re-plans at
            # the attempt boundary this exit creates
            for r in drain_reqs:
                self._emit_control(
                    r, state="applied", epoch=epoch, step=gstep,
                    boundary="chunk",
                )
            self._drain_requested = True
            self._drain_reqs.extend(drain_reqs)
        if abort_reqs:
            # the abort supersedes everything else queued this boundary
            for r in abort_reqs[1:] + roll_reqs:
                if r.get("id") is not None:
                    policy_mod.emit_completion(
                        self.bus, r, state="coalesced",
                        coalesced_into=abort_reqs[0].get("id"),
                    )
            self._policy_abort_exit(
                epoch, abort_reqs[0], step=gstep, boundary="chunk",
            )  # raises PolicyAbort
        if not roll_reqs:
            return None
        # rollback viability is checked HERE, before unwinding the epoch:
        # a request that cannot apply must not abandon the chunk loop
        why = None
        if self.watchdog is None:
            why = "the health watchdog is disabled (--no-health)"
        elif self.watchdog.exhausted():
            why = (
                f"rollback budget "
                f"({self.watchdog.cfg.max_rollbacks}) already exhausted"
            )
        if why is not None:
            self.logger.error(f"policy rollback not applied: {why}")
            for r in roll_reqs:
                if r.get("id") is not None:
                    policy_mod.emit_completion(self.bus, r, ok=False, error=why)
            return None
        if not self._rollback_target_exists():
            # decided before this run's first verified save: the epoch
            # boundary right after the save is the EARLIEST boundary that
            # can apply it.  Park the request there (the legacy path)
            # instead of unwinding a chunk loop with nothing to restore
            # — or failing a decision that becomes viable one save later.
            self.logger.warning(
                "control: rollback requested before the first verified "
                "checkpoint; deferring to the epoch boundary"
            )
            self._policy_requests.extend(
                dict(r, _epoch_only=True) for r in roll_reqs
            )
            return None
        return roll_reqs

    def _apply_control_rollback(
        self, epoch: int, epoch_time: float, ctl,
    ) -> int | None:
        """Apply a chunk-boundary rollback after ``MidEpochRollback``
        unwound the epoch: the same verified restore + replay as the
        epoch-boundary path (identical checkpoint source, identical
        restored leaves — pinned by tests/test_control.py), entered from
        ``fit()`` where no chunk iterator is live.  Returns the epoch to
        re-enter, or None when no verified checkpoint exists (the epoch
        is then re-entered from its start: the state was never touched,
        and the per-step key fold replays it deterministically)."""
        from ..ops import policy as policy_mod

        roll_reqs = ctl.requests
        gstep = epoch * self.steps_per_epoch + ctl.steps_done
        reason = f"policy action ({roll_reqs[0].get('rule') or 'rollback'})"
        self.logger.warning(
            f"policy: rollback requested mid-epoch {epoch} "
            f"(step {ctl.steps_done}/{self.steps_per_epoch}): {reason}"
        )
        with self.tracer.span("rollback", epoch=epoch):
            next_epoch = self._rollback(epoch, epoch_time, reason)
        if next_epoch is None:
            why = "no verified rollback checkpoint available"
            self.logger.error(f"policy rollback not applied: {why}")
            for r in roll_reqs:
                if r.get("id") is not None:
                    policy_mod.emit_completion(self.bus, r, ok=False, error=why)
            return None
        for r in roll_reqs:
            self._emit_control(
                r, state="applied", epoch=epoch, step=gstep,
                boundary="chunk", from_epoch=epoch, to_epoch=next_epoch,
            )
            if r.get("id") is not None:
                policy_mod.emit_completion(
                    self.bus, r, from_epoch=epoch, to_epoch=next_epoch
                )
        return next_epoch

    # ------------------------------------------------------------- resilience

    def _preempt_due(
        self, epoch: int, step: int | None = None, start_offset: int = 0
    ) -> bool:
        """Preemption pending at the end of ``epoch`` (``step=None``) or at
        a chunk boundary ``step`` steps into it (both data modes poll per
        chunk — the drain no longer waits for the epoch boundary; device
        mode's grace window is one ``--device-chunk-steps`` chunk)?

        SIGTERM delivery is per-host and need not be simultaneous (a
        partial spot reclaim can evict one VM of the slice), but the drain
        path runs collectives (symmetric fetch of partitioned state) — so
        under multi-host the per-host flags are OR-reduced and every
        process acts on ANY host's preemption together (every process runs
        the same chunk loop, so the per-chunk reduce stays symmetric).  The
        collective only runs for resilient runs (handler or fault plan
        present): non-resilient multi-host training keeps its schedule
        unchanged.
        """
        if (
            self.preempt_handler is None
            and self.fault_plan is None
            and not self._drain_requested
        ):
            return False
        # a control-plane drain (drain_host/replan applied at a chunk or
        # epoch boundary) rides this poll: _control_barrier armed the
        # flag symmetrically (its own allgather), so every process exits
        # through the same drain-checkpoint path together
        due = bool(
            self.preempt_handler is not None and self.preempt_handler.triggered
        ) or self._drain_requested
        if self.fault_plan is not None:
            if step is None:
                # boundary check: step=S events normally fire mid-epoch
                # (below — BOTH data modes run chunked dispatches now) and
                # must not double-fire here; one that lands in the epoch's
                # FINAL chunk (the mid-epoch poll stops one boundary early
                # so a full epoch drains normally) — or past the epoch's
                # step count — fires here instead of being silently dropped.
                due = due or self.fault_plan.preempt_due(
                    epoch, include_step_events=False
                ) or self.fault_plan.preempt_step_due(
                    epoch,
                    self.steps_per_epoch,
                    self._epoch_step_base,
                    cap=self.steps_per_epoch,
                )
            else:
                due = due or self.fault_plan.preempt_step_due(
                    epoch, step, start_offset, cap=self.steps_per_epoch
                )
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            due = bool(
                np.any(multihost_utils.process_allgather(np.asarray(due)))
            )
        return due

    def _preempt_exit(self, epoch: int, state_ref, already_saved: bool, sync_fetch: bool):
        """Drain and exit distinctly: force a final ``last.ckpt`` if this
        epoch's wasn't already queued (e.g. suppressed by the save
        throttle), wait out the async writer, record goodput, and raise
        ``Preempted`` for the entry point to map to ``EXIT_PREEMPTED``."""
        from ..resilience.preempt import EXIT_PREEMPTED

        self.logger.warning(
            f"preemption at end of epoch {epoch}: draining checkpoints, "
            f"then exiting with code {EXIT_PREEMPTED} for the supervisor"
        )
        self.bus.emit(
            "preempt", epoch=epoch,
            step=(epoch + 1) * self.steps_per_epoch, mid_epoch=False,
        )
        if getattr(self.hparams, "save_last", True) and not already_saved:
            if sync_fetch:  # throttled epochs skipped the symmetric fetch
                with self.goodput.phase("ckpt"):
                    state_ref = fetch_to_host(state_ref)
            if self.is_main:
                self.ckpt_writer.submit(
                    lambda s=state_ref, e=epoch, b=self.best_acc: (
                        ckpt.save_resume_state(
                            self.version_dir, s, e, b,
                            meta=self._ckpt_meta(),
                            state_layout=self._state_layout,
                        )
                    ),
                    key="last",
                )
        if self.ckpt_writer is not None:
            with self.goodput.phase("ckpt"):
                self.ckpt_writer.wait()
        self._write_goodput(preempted=True)
        raise Preempted(
            epoch=epoch, step=(epoch + 1) * self.steps_per_epoch
        )

    def _preempt_exit_mid_epoch(self, epoch: int, steps_done: int):
        """Mid-epoch drain (host data mode, chunk-boundary poll): save the
        partial-epoch state with its progress recorded in the manifest
        (``epoch_in_progress``/``epoch_steps_done``), so the relaunch
        fast-forwards the loader and the per-step key fold past the steps
        already trained — the trajectory continues exactly, and the grace
        window shrinks from a whole epoch to one chunk."""
        from ..resilience.preempt import EXIT_PREEMPTED

        self.logger.warning(
            f"preemption mid-epoch {epoch} "
            f"({steps_done}/{self.steps_per_epoch} steps done): draining "
            f"checkpoints, then exiting with code {EXIT_PREEMPTED} for the "
            "supervisor"
        )
        self.bus.emit(
            "preempt", epoch=epoch,
            step=epoch * self.steps_per_epoch + steps_done, mid_epoch=True,
        )
        state_ref = self._ckpt_view(self.state)
        sync_fetch = jax.process_count() > 1 and needs_collective_fetch(state_ref)
        if getattr(self.hparams, "save_last", True):
            if sync_fetch:
                with self.goodput.phase("ckpt"):
                    state_ref = fetch_to_host(state_ref)
            if self.is_main:
                self.ckpt_writer.submit(
                    lambda s=state_ref, e=epoch, b=self.best_acc, n=steps_done: (
                        ckpt.save_resume_state(
                            self.version_dir, s, e - 1, b,
                            meta={
                                **self._ckpt_meta(),
                                "epoch_in_progress": e,
                                "epoch_steps_done": n,
                            },
                            state_layout=self._state_layout,
                        )
                    ),
                    key="last",
                )
        if self.ckpt_writer is not None:
            with self.goodput.phase("ckpt"):
                self.ckpt_writer.wait()
        self._write_goodput(preempted=True)
        raise Preempted(
            epoch=epoch, step=epoch * self.steps_per_epoch + steps_done
        )

    def _write_goodput(self, preempted: bool = False) -> None:
        """Append this attempt's goodput record to the run dir's
        ``goodput.jsonl`` (the supervisor aggregates records across restarts
        into GOODPUT.json); also honor a direct --goodput-json for
        unsupervised runs."""
        if self.goodput.written or not self.is_main or self.version_dir is None:
            return
        self.goodput.written = True
        record = self.goodput.summary()
        record.update(
            preempted=preempted,
            version=self.version,
            topology=elastic.topology(),
            start_epoch=self.start_epoch,
            # the unified-timeline join keys (obs/): every attempt record
            # names the run and restart index that produced it
            run_id=self.bus.run_id,
            attempt=self.bus.attempt,
            # lets the supervisor aggregate only ITS run's attempts when
            # the ckpt root also holds older runs' version dirs
            written_at=time.time(),
        )
        if self.watchdog is not None:
            record["health"] = self.watchdog.counters()
        if self._overlap_totals.chunks:
            # where the main thread's time went inside the step phase:
            # h2d_wait > 0 means the input pipeline failed to hide behind
            # compute for that long (the overlap design's health gauge)
            record["step_breakdown"] = self._overlap_totals.summary()
        if self.ckpt_writer is not None:
            # writer-thread utilization: visible when write-behind stops
            # hiding the device→host fetch + serialize cost
            record["ckpt_writer"] = self.ckpt_writer.stats()
        # the attempt's phase totals (+ breakdown/writer/health gauges) on
        # the unified timeline — run_report reads goodput straight off the
        # event stream
        self.bus.emit(
            "goodput",
            **{k: v for k, v in record.items() if k not in self.bus.stamp()},
        )
        try:
            goodput_mod.append_goodput_record(
                self.version_dir / "goodput.jsonl", record
            )
            out = getattr(self.hparams, "goodput_json", None)
            if out:
                records = goodput_mod.load_goodput_records(
                    self.version_dir / "goodput.jsonl"
                )
                goodput_mod.write_goodput(
                    out, goodput_mod.aggregate_goodput(records)
                )
        except OSError as e:  # accounting must never kill training
            self.logger.error(f"goodput record write failed: {e}")
        if self.watchdog is not None:
            self.watchdog.flush_events(self.version_dir)
            out = getattr(self.hparams, "health_json", None)
            if out:
                try:
                    write_health(out, self.watchdog.summary())
                except OSError as e:
                    self.logger.error(f"health report write failed: {e}")

    def _step_fault_for(self, epoch: int):
        """This epoch's injected ``(scale, start, stop)`` step-fault window
        (consumed on fetch — a rollback replay runs clean), or None."""
        if not self._step_faults:
            return None
        fault = self.fault_plan.step_fault(epoch, self.steps_per_epoch)
        if fault[2] > fault[1]:
            self.logger.warning(
                f"injected step fault: loss/grads x{fault[0]} on steps "
                f"[{fault[1]}, {fault[2]}) of epoch {epoch}"
            )
        return fault

    # ------------------------------------------------- eager-parity capture
    #
    # --parity-check N records the first N steps of the first trained epoch
    # — one step per dispatch, bit-identical to any other chunking by the
    # runners' pinned contract — then replays them through a fresh instance
    # of the SAME scanned executable family (bitwise replay gate) and
    # through the no-jit eager rail (tolerance-gated reference gate).  See
    # parity/diff.py for the gate semantics and the bisection.

    def _parity_capture_for(self, epoch: int):
        """The live capture when THIS epoch should record steps, else None
        (the capture binds to the first trained epoch; a later epoch never
        resumes a stale capture)."""
        cap = self.parity
        if cap is None or cap.checked or cap.complete:
            return None
        if cap.epoch is not None and cap.epoch != epoch:
            return None
        return cap

    def _parity_begin(self, cap, epoch: int, offset: int, mode: str) -> None:
        """Snapshot the initial state (host copy) before the capture
        epoch's first dispatch; device mode also pre-derives the runner's
        per-step key table and permutation rows via the parity key-table
        helpers (the SAME fold graph the scanned runners trace)."""
        if cap.initial is not None:
            return
        cap.n = min(cap.n, self.steps_per_epoch - offset)
        cap.snapshot_initial(self.state, mode, epoch)
        if mode == "device":
            from .. import parity as parity_mod

            n = int(self.trn_images.shape[0])
            self._parity_rows = parity_mod.device_epoch_rows(
                self.data_key, epoch, n, self.hparams.batch_size
            )
            self._parity_keys = parity_mod.device_step_keys(
                self.data_key, epoch, self.steps_per_epoch
            )

    def _parity_record(self, cap, *, epoch, index, images, labels, key,
                       fault, loss) -> None:
        """Record one captured step: apply the optional --parity-corrupt
        bit flip to the REAL carried state (the flip becomes part of the
        recorded trajectory — the clean replay then localizes it), then
        checksum the state and keep the rails' inputs host-side.  Runs the
        two-gate check as soon as the capture is complete."""
        from ..parity import StepRecord, checksum_state, f32_bits

        self.state = cap.maybe_corrupt(self.state, index)
        scale = 1.0
        if fault is not None and fault[1] <= index < fault[2]:
            scale = float(fault[0])
        cap.record(StepRecord(
            index=int(index),
            images=np.asarray(images),
            labels=np.asarray(labels),
            key=key,
            fault_scale=scale,
            checksums=checksum_state(self.state),
            loss_bits=f32_bits(jax.device_get(loss)),
        ))
        if cap.complete:
            self._run_parity_check()

    def _parity_split_chunks(self, chunks):
        """Re-chunk the host stream to one step per dispatch while the
        capture is filling (bit-identical by the chunk runner's any-K
        contract); chunks pass through untouched once it completes."""
        for start, take, batch in chunks:
            k = 0
            while (k < take and self.parity is not None
                   and self.parity.capturing and not self.parity.checked):
                yield start + k, 1, {n: v[k:k + 1] for n, v in batch.items()}
                k += 1
            if k == 0:
                yield start, take, batch
            elif k < take:
                yield start + k, take - k, {n: v[k:] for n, v in batch.items()}

    def _run_parity_check(self) -> None:
        """Both parity gates over the completed capture, emitted as ONE
        registered ``parity`` event (rendered/gated by ``run_report.py
        --parity``)."""
        from .. import parity as parity_mod

        cap = self.parity
        common = dict(
            precision=self.precision,
            state_sharding=self.state_sharding,
            grad_accum=self.grad_accum,
            fwd_bwd=self.train_fwd_bwd,
            comms=self.comms,
            fault_injection=self._step_faults,
            state_layout=self._state_layout,
        )
        if cap.mode == "host":
            rp = make_replay_step(self.mesh, **common)
            epoch_key = jax.random.fold_in(self.data_key, cap.epoch)

            def replay(st, rec):
                return rp(st, jnp.asarray(rec.images), jnp.asarray(rec.labels),
                          epoch_key, rec.index)
        else:
            rp = make_device_replay_step(
                self.mesh, self.hparams.batch_size, **common
            )

            def replay(st, rec):
                return rp(st, self.trn_images, self.trn_labels,
                          self.data_key, cap.epoch, rec.index)

        wire_true = (
            self.comms is not None and self.comms.active
            and self.comms.wire_inline
        )
        eager_step = eager_state = reason = None
        if wire_true:
            reason = (
                "wire-true compressed pipeline: the per-device "
                "error-feedback residual lives in the schedule layout, "
                "which the eager rail does not model (replay gate still ran)"
            )
        else:
            estep = parity_mod.make_eager_step(
                precision=self.precision,
                grad_accum=self.grad_accum,
                comms=parity_mod.eager_comms_like(self.comms),
            )
            # the eager reference forward is the PLAIN model.apply: the
            # pipeline schedules and sequence rings are layout transforms
            # around that same math, which is exactly the claim the diff
            # checks
            # the eager rail always speaks the canonical (contiguous)
            # trunk — a chunk-resident capture canonicalizes its initial
            # snapshot here and its replayed states through the
            # canonicalize_state hook below (bitwise-neutral reshapes)
            eager_state = parity_mod.eager_state_like(
                layouts_mod.state_to_canonical(
                    cap.initial, self._state_layout
                ),
                self.model.apply,
            )

            def eager_step(st, rec):
                return estep(st, rec.images, rec.labels, rec.key)

        layout = {
            "dp": int(self.mesh.shape.get("data", 1)),
            "tp": int(self.mesh.shape.get("model", 1)),
            "pp": int(self.mesh.shape.get("pipe", 1)),
            "zero": bool(self.shard_optim),
            "wire": (
                self.comms.grad_comms
                if self.comms is not None and self.comms.active else "fp32"
            ),
            "schedule": getattr(self.hparams, "pipeline_schedule", None)
            or "none",
            "state_layout": self._state_layout.tag,
        }
        report = parity_mod.run_parity_check(
            cap,
            replay_step=replay,
            place_state=lambda t: place_tree(t, self.state_sharding),
            eager_step=eager_step,
            eager_state=eager_state,
            eager_unsupported_reason=reason,
            layout=layout,
            canonicalize_state=lambda s: layouts_mod.state_to_canonical(
                s, self._state_layout
            ),
        )
        self.bus.emit("parity", **report)
        div = report["replay_divergence"] or report["reference_divergence"]
        if report["verdict"] == "ok":
            self.logger.info(
                f"parity: {report['steps']} steps ok under {report['tol']} "
                f"(replay bitwise, eager {report['eager_reference']}, "
                f"max ulp {report['max_ulp']})"
            )
        else:
            self.logger.warning(
                "parity DIVERGENT at step "
                f"{div['step']} stage={div['stage']} leaf={div['leaf']} "
                f"(replay={report['replay']}, "
                f"eager={report['eager_reference']}, tol={report['tol']})"
            )

    def _train_epoch_device(self, epoch: int) -> tuple[np.ndarray, float]:
        """Chunked scanned epoch over the HBM-resident split.

        ``--device-chunk-steps`` steps per dispatch (default: the whole
        epoch as one program).  Each chunk recomputes the epoch's whole
        permutation and per-step key table (``make_device_chunk_runner``)
        and slices its ``[start, start+K)`` rows, so the
        trajectory is bit-identical for ANY chunk size; what smaller chunks
        buy is a host touch point mid-epoch — the preemption poll (and an
        injected ``preempt@epoch=K:step=S``) drains at the next chunk
        boundary with the steps-done count in the manifest, shrinking the
        device-mode grace window from a whole epoch to one chunk, and a
        mid-epoch resume fast-forwards ``start`` past the trained steps.
        """
        steps = self.steps_per_epoch
        chunk = self._device_chunk
        offset = self._resume_step_offset if epoch == self.start_epoch else 0
        self._resume_step_offset = 0  # one-shot: only the resumed epoch skips
        self._epoch_step_base = offset
        fault = self._step_fault_for(epoch)
        cap = self._parity_capture_for(epoch)
        if cap is not None:
            self._parity_begin(cap, epoch, offset, "device")
        meter = self._step_meter
        meter.reset()
        epoch_arr = jnp.asarray(epoch)
        chunk_metrics = []
        bar = self._progress_bar(range(steps), desc=f"epoch {epoch}")
        if bar is not None and offset:
            bar.update(offset)
        done = offset
        t_epoch = time.perf_counter()
        while done < steps:
            take = min(chunk, steps - done)
            if cap is not None and cap.capturing:
                take = 1  # bit-identical by the runner's any-chunking contract
            runner = self._device_runner_for(take)
            args = (
                self.state,
                self.trn_images,
                self.trn_labels,
                self.data_key,
                epoch_arr,
                jnp.asarray(done),
            )
            # step= is the chunk's first global step, a plain attribute of
            # the span; taint= keeps a compile-bearing dispatch sample out
            # of the straggler-scored step/dispatch_s sketch
            t_disp = time.monotonic()
            with meter.phase(
                "dispatch", taint=self.compile_monitor.take_taint,
                step=epoch * steps + done,
            ):
                if fault is not None:
                    self.state, metrics = runner(*args, fault)
                else:
                    self.state, metrics = runner(*args)
            if self.ckpt_writer is not None:
                self.ckpt_writer.release()  # held over the boundary (fit)
            meter.note_chunk()
            if self._pipe_meta is not None:
                self._note_pipeline_obs(t_disp, time.monotonic())
            chunk_metrics.append(metrics)  # (take,) device arrays; no sync
            if cap is not None and cap.capturing and take == 1:
                self._parity_record(
                    cap, epoch=epoch, index=done,
                    images=jax.device_get(
                        self.trn_images[self._parity_rows[done]]
                    ),
                    labels=jax.device_get(
                        self.trn_labels[self._parity_rows[done]]
                    ),
                    key=self._parity_keys[done],
                    fault=fault, loss=metrics["loss"][0],
                )
            done += take
            self.metrics.note_steps(take)
            self._obs_tick(epoch=epoch, step=epoch * steps + done)
            if bar is not None:
                bar.update(take)
            if done < steps:
                # control barrier first: a queued drain arms the preempt
                # poll below; a rollback unwinds to fit(); an abort
                # raises from inside the barrier
                roll_reqs = self._control_barrier(epoch, step=done)
                if roll_reqs is not None:
                    if bar is not None:
                        bar.close()
                    # fit() re-enters after the rollback; book step time
                    self.goodput.add("step", time.perf_counter() - t_epoch)
                    raise MidEpochRollback(
                        epoch=epoch, steps_done=done, requests=roll_reqs
                    )
                if self._preempt_due(epoch, step=done, start_offset=offset):
                    if bar is not None:
                        bar.close()
                    # fit() never sees this partial epoch; book its step time
                    self.goodput.add("step", time.perf_counter() - t_epoch)
                    self._preempt_exit_mid_epoch(epoch, done)
        if bar is not None:
            bar.close()
        return self._collect_epoch_metrics(chunk_metrics)

    def _collect_epoch_metrics(
        self, chunk_metrics: list[dict]
    ) -> tuple[np.ndarray, float]:
        """ONE bulk host fetch for the epoch's stacked per-chunk metrics:
        loss/top1, the numerics-guard flags and (MoE models only) the
        routing-health scalars are fetched together — separate
        np.asarray calls would each pay a blocking device→host
        round-trip.  This fetch is also where the main thread
        finally blocks on the device, so it is the ``compute`` leg of the
        step-time breakdown."""
        keep = ("loss", "top1_count", "skipped", "grad_norm", "comms_err")
        with self._step_meter.phase("compute"):
            fetched = jax.device_get(
                [
                    {
                        k: v
                        for k, v in m.items()
                        if k in keep or k.startswith(LAYER_GAUGES)
                    }
                    for m in chunk_metrics
                ]
            )
        losses = np.concatenate([np.asarray(m["loss"]) for m in fetched])
        if "comms_err" in fetched[0]:
            # compressed-sync health: per-step error-feedback residual norm
            # (one sketch per flush; p99 growing epoch over epoch means the
            # wire precision is too narrow for this gradient distribution)
            self.metrics.histogram("comms/residual_norm").record_many(
                np.concatenate([np.asarray(m["comms_err"]) for m in fetched])
            )
        top1 = float(sum(np.asarray(m["top1_count"]).sum() for m in fetched))
        # stashed for fit()'s TB/log/health pass rather than widening the return
        self._epoch_health = {
            key: np.concatenate([np.asarray(m[key]) for m in fetched])
            for key in ("skipped", "grad_norm")
        }
        gauges = {  # each layer gauge's mean over the epoch's steps
            k: float(
                np.mean(np.concatenate([np.atleast_1d(m[k]) for m in fetched]))
            )
            for k in fetched[0]
            if k.startswith(LAYER_GAUGES)
        }
        self._moe_health = {
            k: v for k, v in gauges.items() if k.startswith("moe_")
        }
        if "moe_rows" in fetched[0]:
            # top-k expert layers (models/moe.py TopKMoE): pairs routed to
            # the experts held here, summed over the epoch's steps and
            # layers, how far the fullest expert is above the mean, and the
            # share of layer calls whose rows overflowed the held prefix
            self.metrics.counter("moe/rows").inc(
                int(sum(np.asarray(m["moe_rows"]).sum() for m in fetched))
            )
            self.metrics.gauge("moe/load_max_over_mean").set(
                self._moe_health["moe_load_max_over_mean"]
            )
            self.metrics.gauge("moe/full_buffer_share").set(
                self._moe_health["moe_full_buffer_share"]
            )
        if "moe_bias_spread" in self._moe_health:
            # a selection bias that the step moves: max - min, the mean over
            # the expert layers and the epoch's steps (0 while it holds the
            # rows even by itself; it widens while the rule pulls them back)
            self.metrics.gauge("moe/bias_spread").set(
                self._moe_health["moe_bias_spread"]
            )
        for sown, gauge in DECAY_GAUGES.items():
            # recurrent mixers (Gated DeltaNet, Mamba-2): the mean decay
            # factor over tokens, heads, layers and the epoch's steps — how
            # fast the state forgets
            if sown in gauges:
                self.metrics.gauge(gauge).set(gauges[sown])
        # the per-step signals land in the metric sketches here — one
        # vectorized pass over the stacked arrays, no per-step Python loop;
        # non-finite samples count into the sketch's side counter, so a
        # skipped step's inf grad norm can't poison the log buckets
        self.metrics.histogram("train/loss").record_many(losses)
        self.metrics.histogram("train/grad_norm").record_many(
            self._epoch_health["grad_norm"]
        )
        n_skipped = int((np.asarray(self._epoch_health["skipped"]) > 0.5).sum())
        if n_skipped:
            self.metrics.counter("train/skipped_steps").inc(n_skipped)
        return losses, top1

    def _train_epoch_host(self, epoch: int) -> tuple[np.ndarray, float]:
        """Streaming epoch: loader batches are stacked into chunks of
        ``--host-chunk-steps`` and each chunk runs as ONE scanned dispatch
        (the large-dataset / multi-host path; reference analogue is the
        DataLoader loop, ``src/ddp/trainer.py:143-174``).

        Per-step dispatch + H2D round-trips leave the chip idle between
        tiny step programs; chunking amortizes that latency K×, and the
        ``DevicePrefetcher`` stacks the NEXT chunk and issues its
        ``device_put`` on a background thread while the current chunk's
        scan is still executing — H2D transfer fully hidden behind compute,
        bounded by ``--device-prefetch`` staged chunks of HBM (0 = stage
        synchronously on the main thread, the pre-overlap path).  Keys are
        folded from the global step index inside the chunk, so the
        trajectory is identical for any chunk size or prefetch depth.

        Chunk boundaries also poll for preemption (``_preempt_due`` with a
        step index): a SIGTERM — or an injected ``preempt@epoch=K:step=S``
        — drains at the NEXT boundary instead of the epoch's end, saving a
        mid-epoch checkpoint whose manifest records the steps already done.
        A mid-epoch resume fast-forwards the loader and starts the chunk
        scan at that global step index, so the continued trajectory is
        exactly the uninterrupted one.
        """
        self.train_loader.set_epoch(epoch)
        epoch_key = jax.random.fold_in(self.data_key, epoch)
        chunk = max(1, getattr(self.hparams, "host_chunk_steps", HOST_CHUNK_STEPS_DEFAULT))
        offset = self._resume_step_offset if epoch == self.start_epoch else 0
        self._resume_step_offset = 0  # one-shot: only the resumed epoch skips
        self._epoch_step_base = offset
        steps = self.steps_per_epoch
        fault = self._step_fault_for(epoch)
        cap = self._parity_capture_for(epoch)
        if cap is not None:
            self._parity_begin(cap, epoch, offset, "host")
        meter = self._step_meter
        meter.reset()
        chunk_metrics = []
        it = iter(self.train_loader)
        for _ in range(offset):  # mid-epoch resume: skip already-trained steps
            next(it)
        place = lambda b: shard_batch(b, self.mesh, batch_axis=1)  # noqa: E731
        if self._device_prefetch > 0:
            chunks = DevicePrefetcher(
                it, steps, chunk, place,
                start=offset, depth=self._device_prefetch,
            )
        else:
            chunks = (
                (s, k, place(b))
                for s, k, b in chunked_batches(it, steps, chunk, offset)
            )
        chunk_iter = (
            chunks if cap is None else self._parity_split_chunks(chunks)
        )
        bar = self._progress_bar(range(steps), desc=f"epoch {epoch}")
        if bar is not None and offset:
            bar.update(offset)
        done = offset
        t_epoch = time.perf_counter()
        try:
            while done < steps:
                with meter.phase("h2d_wait"):
                    start, take, batch = next(chunk_iter)
                recording = cap is not None and cap.capturing and take == 1
                if recording:
                    # host copies BEFORE the dispatch donates the buffers
                    par_x = jax.device_get(batch["x"][0])
                    par_y = jax.device_get(batch["y"][0])
                # step= and taint=: see the device-mode loop
                t_disp = time.monotonic()
                with meter.phase(
                    "dispatch", taint=self.compile_monitor.take_taint,
                    step=epoch * steps + start,
                ):
                    args = (
                        self.state, batch["x"], batch["y"],
                        epoch_key, jnp.asarray(start),
                    )
                    if fault is not None:
                        self.state, metrics = self.chunk_runner(*args, fault)
                    else:
                        self.state, metrics = self.chunk_runner(*args)
                if self.ckpt_writer is not None:
                    self.ckpt_writer.release()  # held over the boundary (fit)
                meter.note_chunk()
                if self._pipe_meta is not None:
                    self._note_pipeline_obs(t_disp, time.monotonic())
                del batch  # donated at dispatch; drop the dead references
                chunk_metrics.append(metrics)  # (take,) device arrays; no sync
                if recording:
                    from ..parity import host_step_key

                    self._parity_record(
                        cap, epoch=epoch, index=start,
                        images=par_x, labels=par_y,
                        key=host_step_key(self.data_key, epoch, start),
                        fault=fault, loss=metrics["loss"][0],
                    )
                done = start + take
                self.metrics.note_steps(take)
                self._obs_tick(epoch=epoch, step=epoch * steps + done)
                if bar is not None:
                    bar.update(take)
                if done < steps:
                    # control barrier first (see the device-mode loop);
                    # the finally below joins the prefetcher on unwind
                    roll_reqs = self._control_barrier(epoch, step=done)
                    if roll_reqs is not None:
                        if bar is not None:
                            bar.close()
                        self.goodput.add(
                            "step", time.perf_counter() - t_epoch
                        )
                        raise MidEpochRollback(
                            epoch=epoch, steps_done=done, requests=roll_reqs
                        )
                    if self._preempt_due(epoch, step=done, start_offset=offset):
                        if bar is not None:
                            bar.close()
                        # fit() never sees this partial epoch; book its
                        # step time
                        self.goodput.add("step", time.perf_counter() - t_epoch)
                        self._preempt_exit_mid_epoch(epoch, done)
        finally:
            # preemption drain / error unwind must join the staging thread
            if isinstance(chunks, DevicePrefetcher):
                chunks.close()
        if bar is not None:
            bar.close()
        return self._collect_epoch_metrics(chunk_metrics)

    # ------------------------------------------------------------------- eval

    def _run_eval(self, arrays, eval_runner):
        images, labels, weights = arrays
        # the call and the fetch are two spans: the first is the host's
        # dispatch, the second is where it blocks until the device is done
        with self.tracer.span("eval_dispatch"):
            device_totals = eval_runner(self.state, images, labels, weights)
        with self.tracer.span("eval_fetch"):
            totals = {k: float(v) for k, v in device_totals.items()}  # one fetch
        return {
            "loss": totals["loss_sum"] / totals["count"],
            "top1": 100.0 * totals["top1_count"] / totals["count"],
            "top5": 100.0 * totals["top5_count"] / totals["count"],
        }

    def validate(self, epoch: int) -> dict[str, float]:
        """Whole-val-set metrics (reference ``validate``,
        ``src/single/trainer.py:175-194``)."""
        out = self._run_eval(self._val, self.eval_runner)
        return {"val_loss": out["loss"], "val_acc": out["top1"]}

    def test(self, state=None) -> dict[str, float]:
        """Test-set loss/top-1/top-5 (reference ``test``,
        ``src/single/trainer.py:196-228``).  ``state=None`` loads the best
        checkpoint from this run's version dir, mirroring the reference's
        glob-and-load phase (``src/single/main.py:22-28``)."""
        if state is None:
            if self.ckpt_writer is not None:
                self.ckpt_writer.wait()  # drain pending writes before reading
            best = (
                ckpt.find_best_checkpoint(self.version_dir)
                if self.version_dir is not None
                else None
            )
            if best is not None:
                self.logger.info(f"Loading best checkpoint: {best.name}")
                self.state = ckpt.load_checkpoint(
                    best, self.state, state_layout=self._state_layout
                )
            if jax.process_count() > 1:
                # Only process 0 has the checkpoint on disk; broadcast its
                # params/BN stats so every host evaluates the same model
                # (the reference instead lets rank 0 test alone on 1/N of
                # the data — SURVEY.md §5 quirk 1).  Every collective here
                # must be entered by every process: first agree on whether a
                # checkpoint was found, then broadcast host values — process
                # 0 holds loaded numpy, the others contribute zero-filled
                # placeholders of the same (global) shape, so no process
                # ever needs an asymmetric device→host collective fetch.
                from jax.experimental import multihost_utils

                found = bool(
                    multihost_utils.broadcast_one_to_all(
                        np.asarray(best is not None)
                    )
                )
                if found:
                    tree = (self.state.params, self.state.batch_stats)
                    if self.is_main:
                        host = jax.tree_util.tree_map(np.asarray, tree)
                    else:
                        host = jax.tree_util.tree_map(
                            lambda l: np.zeros(l.shape, l.dtype), tree
                        )
                    synced = multihost_utils.broadcast_one_to_all(host)
                    self.state = self.state.replace(
                        params=place_tree(synced[0], self.state_sharding.params),
                        batch_stats=place_tree(
                            synced[1], self.state_sharding.batch_stats
                        ),
                    )
        else:
            self.state = state
        out = self._run_eval(self._tst, self.test_eval_runner)
        self.logger.info(
            f"[{self.hparams.backend.upper()} Version {self.version}] "
            f"test loss: {out['loss']:.4f}, "
            f"test top-1 acc: {out['top1']:.2f}%, top-5 acc: {out['top5']:.2f}%"
        )
        return {
            "test_loss": out["loss"],
            "test_top1": out["top1"],
            "test_top5": out["top5"],
        }

    def close(self) -> None:
        # crash path: fit() never reached its goodput write — record what
        # was accumulated so the attempt still shows up in the aggregate
        self._write_goodput()
        if self.train_loader is not None and hasattr(self.train_loader, "close"):
            # an aborted epoch may leave the batch-prefetch producer alive;
            # join it deterministically rather than waiting on GC
            self.train_loader.close()
        if self.preempt_handler is not None:
            self.preempt_handler.restore()
        if self.ckpt_writer is not None:
            self.ckpt_writer.close()
        if self.writer is not None:
            self.writer.close()
        # obs teardown: drain any sketches the last partial epoch recorded,
        # export this attempt's host spans as a Chrome trace next to its
        # events, then release the process-current bus/recorder (sequential
        # Trainers in one process must not cross-write)
        self.metrics.flush(self.bus)
        if self.exporter is not None:
            self.exporter.close()
        if self.alert_engine is not None:
            self.alert_engine.close()
            self.bus.unsubscribe(self.alert_engine.observe_event)
        if self.policy_engine is not None:
            self.bus.unsubscribe(self.policy_engine.observe_event)
        if self._obs_enabled and self._obs_dir is not None:
            obs.write_chrome_trace(
                self._obs_dir
                / obs.trace_filename(self.bus.attempt, self.bus.process_index),
                self.tracer,
                label=f"run {self.bus.run_id} attempt {self.bus.attempt} "
                f"process {self.bus.process_index}",
            )
            if self.tracer.dropped:
                self.logger.warning(
                    f"span trace truncated: {self.tracer.dropped} spans "
                    f"dropped past the {self.tracer.max_spans}-span cap"
                )
        obs.set_recorder(self._prev_recorder)
        obs.reset(self.bus)
