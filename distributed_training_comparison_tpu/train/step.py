"""Compiled train/eval steps and the scanned chunk runners.

Parity: reference ``_train_epoch`` / ``validate`` / ``test`` hot loops
(``src/single/trainer.py:122-228``) — forward, CrossEntropy, backward, SGD
step, AMP autocast, loss/accuracy tracking.

TPU-native redesign:

- The step is a pure jitted function over the mesh.  Gradient averaging
  across devices needs **no** ``lax.pmean`` and no DDP wrapper: the batch is
  sharded on the ``data`` axis, params are replicated, so when XLA computes
  ``mean(loss)`` / its gradient it inserts the ICI all-reduce itself — the
  single-source-of-truth replacement for NCCL all-reduce + per-step
  ``dist.barrier()`` (``src/ddp/trainer.py:156-164``).
- BatchNorm statistics are computed over the **global** batch for the same
  reason — cross-replica SyncBN for free, where the reference explicitly
  punted (``README.md:40``).
- AMP (``autocast`` + ``GradScaler``, ``src/single/trainer.py:134-140``)
  becomes a bf16 activation policy; params/grads/optimizer state stay fp32,
  and bf16's fp32-sized exponent needs no loss scaling.
- ``make_device_chunk_runner`` runs ``chunk_steps`` steps of an epoch (by
  default all) as one ``lax.scan`` over a device-resident dataset: shuffle
  (device-side permutation), gather, augment, step — zero host round-trips
  per step.  Per-step losses come back as one stacked array per dispatch,
  so the reference's every-``eval_step`` log lines can be reconstructed
  without its per-step ``loss.item()`` sync (``src/single/trainer.py:147-153``).
"""

from __future__ import annotations

import warnings
from functools import partial, wraps
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from .._compat import donated_cache_write_barred
from ..data.cifar100 import CIFAR100_MEAN, CIFAR100_STD
from ..data.sampler import epoch_permutation
from ..health.guards import global_norm, select_tree, step_finite
from ..parallel.sharding import batch_sharding, replicated_sharding
from .state import TrainState

Metrics = dict[str, jnp.ndarray]


def _named(fun, name):
    """``fun`` under the name its compiled program should carry: ``name``
    without its ``@k...`` suffix.  ``jax.jit`` names the XLA module
    ``jit_<__name__>``, and that is what a device trace shows on its
    module line — a ``<lambda>`` or an inner ``run`` there says nothing."""
    base = name.split("@", 1)[0]
    if getattr(fun, "__name__", None) == base:
        return fun

    @wraps(fun)
    def named(*args, **kwargs):
        return fun(*args, **kwargs)

    named.__name__ = named.__qualname__ = base
    return named


def observed_jit(fun, monitor, name, sentinel=True, **jit_kw):
    """``jax.jit(fun)`` as the program ``name``, routed through the compile
    monitor when one is wired (obs/compilation.py): every distinct
    executable it builds then emits a ``compile`` event under ``name`` with
    its HLO cost/memory analysis, and dispatches are accounted per
    executable.  The XLA module is named ``jit_<name>`` (less an ``@k...``
    suffix, which tells the monitor's families apart and is no part of a
    function's name).  ``monitor=None`` (tests, library embedders,
    ``--no-obs``) returns the plain jitted function."""
    jitted = jax.jit(_named(fun, name), **jit_kw)
    if monitor is None:
        return jitted
    return monitor.instrument(jitted, name, sentinel=sentinel)


def _donated_jit(
    fun, mesh: Mesh, *, donate_argnums, monitor=None, name=None, **jit_kw
):
    """``jax.jit`` with buffer donation.  Off the TPU its executables are
    never WRITTEN to the persistent compile cache: donated executables
    deserialized from the on-disk cache misbehave on this jax's CPU backend
    (segfaults / silently corrupted carries — see
    ``_compat.donated_cache_write_barred``), and barring the write means no
    process can ever load one.  The bar is keyed on the platform of
    ``mesh``'s devices — what the executable is compiled FOR — and is down
    on ``"tpu"``, where the train programs are the costliest compiles of a
    run and are cached like any other.  The context wraps every call
    (compilation happens at the first call per shape); steady-state calls
    pay only a thread-local config flip.

    The compile monitor wraps INSIDE this context, so an observed AOT
    compile of a donated runner happens under the same write bar as the
    jit path it replaces."""
    jitted = observed_jit(
        fun, monitor, name or getattr(fun, "__name__", "donated"),
        donate_argnums=donate_argnums, **jit_kw,
    )
    platform = mesh.devices.flat[0].platform

    def call(*args):
        # An input uint8 chunk can rarely alias any float output, so a
        # donated image buffer that XLA finds no aliasing slot for triggers
        # the unusable-donation advisory — the donation still releases the
        # buffer at dispatch (the point: the chunk is consumed, its HBM must
        # not outlive the call), so the warning is noise for these runners
        # specifically; the scoped filter keeps it live for every other
        # donated program in the process (e.g. serving's predict buffers).
        # catch_warnings mutates process-global filter state for the span
        # of the dispatch — acceptable here because nothing registers
        # filters concurrently with a multi-second scan dispatch, and the
        # global alternative would hide the advisory process-wide.
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            with donated_cache_write_barred(platform):
                return jitted(*args)

    return call


def _declare_state_layout(runner, fwd_bwd, state_layout):
    """Bind the resident state layout a runner was built for.

    The runners themselves are layout-agnostic by construction — the
    update is elementwise and the shardings arrive via ``state_sharding``,
    whose optimizer specs suffix-match whatever shapes the params carry —
    but the layout is a construction-time contract (``parallel/
    layouts.py``): the state, the shardings, and the schedule's
    ``fwd_bwd`` must all have been built for the SAME resident layout.
    This cross-checks the declared layout against the schedule's and tags
    the runner for introspection (the parity rail reads it back).
    """
    declared = getattr(fwd_bwd, "state_layout", None)
    if (
        state_layout is not None
        and declared is not None
        and getattr(declared, "tag", "contiguous")
        != getattr(state_layout, "tag", "contiguous")
    ):
        raise ValueError(
            f"runner built for state layout {state_layout.tag!r} but its "
            f"fwd_bwd declares {declared.tag!r} — the resident layout is "
            "fixed at construction (parallel/layouts.py); rebuild the "
            "schedule and the runner together"
        )
    try:
        runner.state_layout = state_layout if state_layout is not None else declared
    except AttributeError:  # jitted callables may refuse new attributes
        pass
    return runner


def _cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)


# what ``_moe_health`` names its scalars by: the expert layers', the Gated
# DeltaNet layers' and the state-space layers' (all sown into
# ``"moe_metrics"``)
LAYER_GAUGES = ("moe_", "gdn_", "ssm_")
# a recurrent mixer's mean decay factor a token, by the name it is sown
# under (models/qwen3_next.py: mean exp(g); models/nemotron_h.py: mean
# exp(dt A)) and the gauge the trainer sets from it
DECAY_GAUGES = {
    "gdn_decay_mean": "gdn/decay_mean", "ssm_decay_mean": "ssm/decay_mean",
}


def _moe_health(coll) -> Metrics:
    """Aggregate the routing stats MoE layers sow into ``"moe_metrics"``
    (models/moe.py) into two scalars: mean dropped-token fraction and mean
    per-layer max expert load (1/E at perfect balance, → 1.0 when the
    router collapses onto one expert).  Empty for dense models."""
    from jax.tree_util import tree_flatten_with_path

    dropped, load_max, rows, imbalance, full_buffer = [], [], [], [], []
    bias_spread, decay = [], {}
    for path, leaf in tree_flatten_with_path(coll)[0]:
        keys = {getattr(p, "key", getattr(p, "name", "")) for p in path}
        if "dropped_frac" in keys:
            dropped.append(jnp.mean(leaf))
        elif "expert_load" in keys:
            # leaf: (..., depth, E) — max over experts, mean over layers
            load_max.append(jnp.mean(jnp.max(leaf, axis=-1)))
        elif "rows" in keys:  # TopKMoE: pairs routed to the held experts
            rows.append(jnp.sum(leaf))
        elif "load_max_over_mean" in keys:
            imbalance.append(jnp.mean(leaf))
        elif "full_buffer" in keys:  # 1.0 where the held prefix overflowed
            full_buffer.append(jnp.mean(leaf))
        elif "bias_spread" in keys:  # max - min of a selection bias that moves
            bias_spread.append(jnp.mean(leaf))
        elif sown := keys & DECAY_GAUGES.keys():
            decay.setdefault(sown.pop(), []).append(jnp.mean(leaf))
    out: Metrics = {}
    if rows:  # summed over the layers; the fullest expert's, their mean
        out["moe_rows"] = jnp.sum(jnp.stack(rows))
        out["moe_load_max_over_mean"] = jnp.mean(jnp.stack(imbalance))
        out["moe_full_buffer_share"] = jnp.mean(jnp.stack(full_buffer))
    if bias_spread:
        out["moe_bias_spread"] = jnp.mean(jnp.stack(bias_spread))
    for name, layers in decay.items():  # the mean over the mixer's layers
        out[name] = jnp.mean(jnp.stack(layers))
    if dropped:
        out["moe_dropped_frac"] = jnp.mean(jnp.stack(dropped))
    if load_max:
        out["moe_load_max"] = jnp.mean(jnp.stack(load_max))
    return out


def _make_step_core(
    precision: str,
    augment: bool,
    mean,
    std,
    grad_accum: int = 1,
    accum_sharding=None,
    fwd_bwd=None,
    comms=None,
    repl_sharding=None,
) -> Callable[[TrainState, jnp.ndarray, jnp.ndarray, jax.Array], tuple[TrainState, Metrics]]:
    """The shared train core: augment → normalize → fwd/bwd → SGD update.

    The state's ``task`` (``train/task.py``) is the model family's seam:
    how a batch becomes the model's input (images: augment and normalise;
    tokens: as they are) and how hits are counted.  The loss is the mean
    over every label either way.

    Used by the per-step path (``make_train_step``), the device-resident
    scanned path (``make_device_chunk_runner``) and the chunked streaming
    path (``make_chunk_runner``) so they can never diverge.

    ``grad_accum > 1`` splits the batch into that many sequential
    micro-batches, averages their gradients, and applies ONE optimizer
    update — peak activation memory scales with the micro-batch, so
    spec-scale global batches fit on few chips.  Gradient averaging is
    exact (mean of micro-grads == grad of mean loss); BatchNorm statistics
    are computed per micro-batch (the same semantics torch DDP has without
    cross-accumulation SyncBN).

    ``fwd_bwd`` — optional ``(params, x, labels) -> (loss, logits, grads)``
    replacing the ``value_and_grad`` step for schedules that must own their
    own backward (the 1F1B pipeline, ``parallel/pipeline.py``); the
    augmentation/normalization prologue and the optimizer epilogue are
    shared either way.  Only BN-free models are eligible (the hook carries
    no batch-stats plumbing).

    The epilogue carries the compiled numerics guards (``health/guards.py``):
    every step computes the gradient global-norm and a finite flag in-jit,
    and a non-finite step SKIPS the optimizer apply entirely (params, BN
    stats, optimizer state and step counter all keep their old values) —
    the ``grad_norm`` / ``skipped`` metrics ride the existing stacked
    fetch, so the happy path pays no extra device→host sync.  ``core``'s
    optional trailing ``fault_scale`` is the fault-injection seam
    (``resilience/faults.py`` step faults): when traced in, it multiplies
    both the loss metric and the gradients — NaN/Inf scales exercise the
    guard, large finite scales exercise the spike detector — and costs
    nothing when absent (the default ``None`` traces no fault ops at all).

    ``comms`` — the run's communications plan (``parallel/comms.py``):
    when active it replaces the plain ``apply_gradients`` epilogue with
    the ZeRO-sharded / compressed update (reduce-scatter → per-shard
    optimizer step → all-gather; quantize with error feedback).  The
    numerics guards are unchanged either way — ``grad_norm``/``finite``
    are computed on the RAW gradients, before any compression, and a
    non-finite step still keeps the entire old state (residual included).
    ``None`` or an inactive plan traces exactly the pre-comms update, so
    the benign path's executable is byte-identical.
    """
    comms_active = comms is not None and comms.active
    # a fwd_bwd that OWNS its gradient-sync wire (the compressed pipeline
    # schedule) threads the per-device error-feedback residual through the
    # step: state.comms_residual rides in, the schedule's new residual
    # rides out (and a guarded non-finite step keeps the old one, like
    # every other state field)
    residual_through_fwd_bwd = fwd_bwd is not None and getattr(
        fwd_bwd, "carries_residual", False
    )
    compute_dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32

    def forward_backward(state, batch_stats, images, labels, key, residual=None):
        params, apply_fn, task = state.params, state.apply_fn, state.task
        x = task.prepare(
            images, key, augment=augment, mean=mean, std=std,
            dtype=compute_dtype, draw_sharding=repl_sharding,
        )

        if fwd_bwd is not None:
            if jax.tree_util.tree_leaves(batch_stats):
                # enforce the BN-free contract at the boundary (advisor r3 /
                # VERDICT r3 weak #5): the hook bypasses apply_fn and has no
                # batch-stats plumbing, so a BN model wired here would
                # silently freeze its running statistics
                raise ValueError(
                    "fwd_bwd hook supports only BN-free models (it bypasses "
                    "apply_fn, so BatchNorm running statistics would "
                    "silently freeze); got a non-empty batch_stats tree"
                )
            if residual_through_fwd_bwd:
                loss, logits, grads, residual = fwd_bwd(
                    params, x, labels, residual
                )
            else:
                loss, logits, grads = fwd_bwd(params, x, labels)
            with jax.named_scope("loss"):
                top1, _ = task.hits(logits, labels)
            return grads, batch_stats, loss, top1.sum(), {}, residual

        def loss_fn(p):
            logits, mutated = apply_fn(
                {"params": p, "batch_stats": batch_stats},
                x,
                train=True,
                # "losses": auxiliary objectives sown by the model (the MoE
                # load-balance loss, models/moe.py); "moe_metrics": routing
                # health sown next to it; both collections come back empty
                # for every dense zoo model
                mutable=["batch_stats", "losses", "moe_metrics"],
            )
            # inside the differentiated function JAX wraps the scope: the
            # trace reads jvp(loss) forward, transpose(jvp(loss)) backward
            with jax.named_scope("loss"):
                aux = sum(
                    jnp.sum(leaf)
                    for leaf in jax.tree_util.tree_leaves(
                        mutated.get("losses", {})
                    )
                )
                loss = _cross_entropy(logits, labels).mean() + aux
            return loss, (logits, mutated)

        (loss, (logits, mutated)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        with jax.named_scope("loss"):
            top1, _ = task.hits(logits, labels)
        # BN-free models mutate nothing; keep the (empty) stats tree stable
        new_stats = mutated.get("batch_stats", batch_stats)
        extras = _moe_health(mutated.get("moe_metrics", {}))
        return grads, new_stats, loss, top1.sum(), extras, residual

    def core(state: TrainState, images, labels, key: jax.Array, fault_scale=None):
        res0 = state.comms_residual if residual_through_fwd_bwd else None
        if grad_accum <= 1:
            grads, new_stats, loss, top1_count, extras, new_residual = (
                forward_backward(
                    state, state.batch_stats, images, labels, key, res0,
                )
            )
        else:
            a = grad_accum
            b = images.shape[0]
            micro_images = images.reshape(a, b // a, *images.shape[1:])
            micro_labels = labels.reshape(a, b // a)
            if accum_sharding is not None:
                # pin each micro-batch to the data axis: GSPMD otherwise
                # resolves the unconstrained reshape by REPLICATING every
                # micro-batch to all devices — each chip would redundantly
                # compute the full micro-batch and data parallelism is lost
                micro_images = jax.lax.with_sharding_constraint(
                    micro_images, accum_sharding
                )
                micro_labels = jax.lax.with_sharding_constraint(
                    micro_labels, accum_sharding
                )
            micro_keys = jax.random.split(key, a)

            def micro_step(carry, inp):
                grads_sum, batch_stats, res = carry
                bx, by, k = inp
                grads, new_stats, loss, top1_count, extras, res = (
                    forward_backward(state, batch_stats, bx, by, k, res)
                )
                grads_sum = jax.tree_util.tree_map(jnp.add, grads_sum, grads)
                return (grads_sum, new_stats, res), {
                    "loss": loss, "top1": top1_count, **extras
                }

            zero_grads = jax.tree_util.tree_map(jnp.zeros_like, state.params)
            (grads_sum, new_stats, new_residual), stacked = jax.lax.scan(
                micro_step,
                (zero_grads, state.batch_stats, res0),
                (micro_images, micro_labels, micro_keys),
            )
            grads = jax.tree_util.tree_map(lambda g: g / a, grads_sum)
            loss = stacked["loss"].mean()
            top1_count = stacked["top1"].sum()
            extras = {
                k: stacked[k].sum() if k == "moe_rows" else stacked[k].mean()
                for k in stacked if k.startswith(LAYER_GAUGES)
            }

        if fault_scale is not None:
            loss = loss * fault_scale
            grads = jax.tree_util.tree_map(lambda g: g * fault_scale, grads)

        # compiled numerics guards: a non-finite step keeps the ENTIRE old
        # state (the skipped update costs one batch, never a poisoned run)
        with jax.named_scope("guards"):
            grad_norm = global_norm(grads)
            finite = step_finite(loss, grad_norm)
        with jax.named_scope("optimizer"):
            if comms_active:
                new_state = comms.apply_gradients(
                    state, grads=grads, batch_stats=new_stats
                )
            else:
                new_state = state.apply_gradients(
                    grads=grads, batch_stats=new_stats
                )
        if residual_through_fwd_bwd and new_residual is not None:
            # the schedule's own wire residual (comms.wire_inline left the
            # field alone); a skipped step still reverts it via select_tree
            new_state = new_state.replace(comms_residual=new_residual)
        with jax.named_scope("guards"):
            state = select_tree(finite, new_state, state)
            metrics = {
                "loss": loss,
                "top1_count": top1_count,
                "count": labels.size,
                "grad_norm": grad_norm,
                "skipped": 1.0 - finite.astype(jnp.float32),
                **extras,
            }
            if (
                comms_active and comms.compressing
                and state.comms_residual is not None
            ):
                # compression health: the error-feedback residual's global
                # norm rides the stacked fetch like the guard metrics (zero
                # extra host syncs); a residual norm growing without bound
                # means the wire is too narrow for this gradient distribution
                metrics["comms_err"] = global_norm(state.comms_residual)
        return state, metrics

    return core


def make_train_step(
    mesh: Mesh,
    *,
    precision: str = "fp32",
    augment: bool = True,
    mean=CIFAR100_MEAN,
    std=CIFAR100_STD,
    state_sharding=None,
    grad_accum: int = 1,
    fwd_bwd=None,
    comms=None,
    monitor=None,
    state_layout=None,
) -> Callable[[TrainState, jnp.ndarray, jnp.ndarray, jax.Array], tuple[TrainState, Metrics]]:
    """Build the compiled ``(state, images_u8, labels, key) -> (state, metrics)``.

    ``images_u8`` is the raw uint8 global batch (augmentation and
    normalization are fused into the compiled step); metrics are on-device
    scalars (no implicit host sync).

    ``state_sharding`` — a ``TrainState``-shaped pytree of shardings (see
    ``parallel.state_shardings``) pinning the tensor-parallel layout; when
    ``None`` the state is fully replicated (pure data parallelism).

    ``state_layout`` — the resident trunk layout the state carries
    (``parallel/layouts.py``); declarative for this layout-agnostic
    runner, cross-checked against ``fwd_bwd``'s schedule layout.
    """
    data_shard = batch_sharding(mesh)
    accum_shard = batch_sharding(mesh, axis=1)  # micro-batch layout (a, b/a, ...)
    repl = replicated_sharding(mesh)
    state_sh = state_sharding if state_sharding is not None else repl
    core = _make_step_core(
        precision, augment, mean, std, grad_accum, accum_shard, fwd_bwd,
        comms, repl,
    )

    # No buffer donation here: this per-step path serves benchmarks and
    # tests that re-read their inputs after the call (the scanned runners
    # donate — they own the train loop's hot path; see
    # make_device_chunk_runner).
    return _declare_state_layout(
        observed_jit(
            core, monitor, "train_step",
            in_shardings=(state_sh, data_shard, data_shard, repl),
            out_shardings=(state_sh, repl),
        ),
        fwd_bwd, state_layout,
    )


# a (scale, start, stop) step-fault tuple whose window can never contain a
# real step index: the replay rail passes it so a fault-injection replay
# executable runs every step CLEAN (``_step_fault_scale`` selects exactly
# 1.0 outside the window; record and replay share one executable family,
# so the clean path is bit-reproducible)
BENIGN_FAULT = (1.0, 1 << 30, 1 << 30)


def make_replay_step(
    mesh: Mesh,
    *,
    precision: str = "fp32",
    augment: bool = True,
    mean=CIFAR100_MEAN,
    std=CIFAR100_STD,
    state_sharding=None,
    grad_accum: int = 1,
    fwd_bwd=None,
    comms=None,
    fault_injection: bool = False,
    state_layout=None,
) -> Callable[..., tuple[TrainState, Metrics]]:
    """One-step host-mode replay for the parity rail (``parity/diff.py``).

    This is NOT a fresh per-step ``jit`` of the step core: XLA fuses an
    inlined step body differently from the same body inside a ``lax.scan``,
    so a per-step executable drifts a few ulp from the scanned runners --
    measured on the CPU backend, and the reason a per-step replay gate
    could never be bitwise against a chunk-runner recording.  Instead the
    replay IS ``make_chunk_runner`` at K=1 with ``donate=False`` -- the
    same scan-shaped program family that produced the recording (chunk
    size and donation are bitwise-neutral, verified by
    ``tests/test_parity.py``), so determinism makes record vs replay
    bit-equal on the benign path.

    ``fault_injection`` must MATCH the recording run's runner family: the
    benign fault multiply is itself not bitwise-neutral ACROSS executables
    (a traced ``*1.0`` changes fusion even though the multiply is
    IEEE-exact), so a fault-family recording must be replayed by a
    fault-family executable -- fed ``BENIGN_FAULT`` so the replay runs
    clean and any recorded fault window shows up as a localized
    divergence.

    No monitor: replay legitimately compiles mid-epoch on the debug rail
    and must not trip the compile-sentinel alert.
    """
    runner = make_chunk_runner(
        mesh, precision=precision, augment=augment, mean=mean, std=std,
        state_sharding=state_sharding, grad_accum=grad_accum,
        fwd_bwd=fwd_bwd, comms=comms, fault_injection=fault_injection,
        donate=False, state_layout=state_layout,
    )
    benign = tuple(jnp.asarray(v) for v in BENIGN_FAULT)

    def replay(state: TrainState, images, labels, epoch_key, index):
        args = [state, images[None], labels[None], epoch_key,
                jnp.asarray(index)]
        if fault_injection:
            args.append(benign)
        state, stacked = runner(*args)
        return state, {k: v[0] for k, v in stacked.items()}

    return _declare_state_layout(replay, fwd_bwd, state_layout)


def make_device_replay_step(
    mesh: Mesh,
    batch_size: int,
    *,
    precision: str = "fp32",
    augment: bool = True,
    mean=CIFAR100_MEAN,
    std=CIFAR100_STD,
    state_sharding=None,
    grad_accum: int = 1,
    fwd_bwd=None,
    comms=None,
    fault_injection: bool = False,
    state_layout=None,
) -> Callable[..., tuple[TrainState, Metrics]]:
    """One-step device-mode replay: ``make_device_chunk_runner`` at
    ``chunk_steps=1`` with ``donate=False`` -- the same executable-family
    argument as :func:`make_replay_step`.  The device key table and batch
    rows are derived in-program from ``(data_key, epoch, index)``, so the
    replay takes the device-resident split rather than recorded batches."""
    runner = make_device_chunk_runner(
        mesh, batch_size, 1, precision=precision, augment=augment,
        mean=mean, std=std, state_sharding=state_sharding,
        grad_accum=grad_accum, fwd_bwd=fwd_bwd, comms=comms,
        fault_injection=fault_injection, donate=False,
        state_layout=state_layout,
    )
    benign = tuple(jnp.asarray(v) for v in BENIGN_FAULT)

    def replay(state: TrainState, images, labels, data_key, epoch, index):
        args = [state, images, labels, data_key, jnp.asarray(epoch),
                jnp.asarray(index)]
        if fault_injection:
            args.append(benign)
        state, stacked = runner(*args)
        return state, {k: v[0] for k, v in stacked.items()}

    return _declare_state_layout(replay, fwd_bwd, state_layout)


def _make_eval_core(mesh: Mesh, precision: str, mean, std):
    """Per-batch eval metrics fn shared by the one-shot step and the scanned
    runner (so the two can never diverge).  ``weights`` masks whole
    examples; an example of several labels (a token sequence) counts each
    of them."""
    compute_dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
    data_shard = batch_sharding(mesh)

    def core(state: TrainState, images, labels, weights) -> Metrics:
        # reshard in-program so callers can pass slices of a replicated
        # device-resident split as well as pre-sharded batches
        images = jax.lax.with_sharding_constraint(images, data_shard)
        labels = jax.lax.with_sharding_constraint(labels, data_shard)
        weights = jax.lax.with_sharding_constraint(weights, data_shard)
        x = state.task.prepare(
            images, None, augment=False, mean=mean, std=std,
            dtype=compute_dtype,
        )
        logits = state.apply_fn(
            {"params": state.params, "batch_stats": state.batch_stats},
            x,
            train=False,
        )
        labels_each = labels.size // labels.shape[0]
        count = weights.sum()
        if labels_each > 1:
            weights = jnp.expand_dims(weights, tuple(range(1, labels.ndim)))
            count = count * labels_each
        per_example = _cross_entropy(logits, labels) * weights
        top1, top5 = state.task.hits(logits, labels)
        return {
            "loss_sum": per_example.sum(),
            "top1_count": (top1 * weights).sum(),
            "top5_count": (top5 * weights).sum(),
            "count": count,
        }

    return core


def make_eval_step(
    mesh: Mesh,
    *,
    precision: str = "fp32",
    mean=CIFAR100_MEAN,
    std=CIFAR100_STD,
    monitor=None,
) -> Callable[..., Metrics]:
    """Compiled eval step with padding mask.

    ``weights`` (1.0 real / 0.0 pad) lets fixed-shape batches cover a split
    whose size doesn't divide the batch — every example counted exactly once
    (the reference instead drops or double-counts under ddp sharding,
    SURVEY.md §5 quirk 1).
    """
    repl = replicated_sharding(mesh)
    core = _make_eval_core(mesh, precision, mean, std)
    # sentinel=False: eval programs legitimately compile one executable
    # per split shape whenever a new split first evaluates — steady state
    # does not mean "no eval compiles", unlike the train/serve hot paths
    return observed_jit(
        core, monitor, "eval_step", sentinel=False, out_shardings=repl
    )


def make_eval_runner(
    mesh: Mesh,
    batch_size: int,
    *,
    precision: str = "fp32",
    mean=CIFAR100_MEAN,
    std=CIFAR100_STD,
    monitor=None,
    name: str = "eval_runner",
) -> Callable[..., Metrics]:
    """A whole eval split as ONE compiled ``lax.scan`` over padded batches.

    Mirrors the train path's one-dispatch-per-epoch design: the reference
    (and the round-1 ``_run_eval``) dispatches per batch — 79 dispatches per
    CIFAR-100 test pass; this is a single device program returning the four
    reduction totals.  One executable per split shape (val/test differ).
    """
    repl = replicated_sharding(mesh)
    core = _make_eval_core(mesh, precision, mean, std)

    def run(state: TrainState, images, labels, weights) -> Metrics:
        nb = images.shape[0] // batch_size
        bshape = lambda a: a.reshape(nb, batch_size, *a.shape[1:])  # noqa: E731

        def body(totals, batch):
            m = core(state, *batch)
            return {k: totals[k] + m[k] for k in totals}, None

        zeros = {
            k: jnp.zeros((), jnp.float32)
            for k in ("loss_sum", "top1_count", "top5_count", "count")
        }
        totals, _ = jax.lax.scan(
            body, zeros, (bshape(images), bshape(labels), bshape(weights))
        )
        return totals

    # sentinel=False: one executable per split shape is the design (val
    # and test differ), and the test split's first compile may land long
    # after the trainer declared steady state
    return observed_jit(
        run, monitor, name, sentinel=False, out_shardings=repl
    )


def _step_fault_scale(i, fault):
    """Per-step fault multiplier from a ``(scale, start, stop)`` plan tuple:
    ``scale`` on steps in ``[start, stop)``, exactly 1.0 elsewhere (the
    multiply-by-one is IEEE-exact, so a benign tuple leaves the trajectory
    untouched)."""
    scale, start, stop = fault
    return jnp.where(
        (i >= start) & (i < stop),
        jnp.asarray(scale, jnp.float32),
        jnp.float32(1.0),
    )


def make_chunk_runner(
    mesh: Mesh,
    *,
    precision: str = "fp32",
    augment: bool = True,
    mean=CIFAR100_MEAN,
    std=CIFAR100_STD,
    state_sharding=None,
    grad_accum: int = 1,
    fwd_bwd=None,
    comms=None,
    fault_injection: bool = False,
    donate: bool = True,
    monitor=None,
    state_layout=None,
) -> Callable[..., tuple[TrainState, Metrics]]:
    """K loader steps as ONE compiled ``lax.scan`` dispatch (host streaming).

    The streaming path can't pre-stage the whole split in HBM, but paying a
    dispatch + H2D round-trip per step leaves the chip idle between tiny
    step programs.  Stacking K batches ``(K, B, ...)`` and scanning K steps per
    dispatch amortizes that latency K× while keeping memory bounded.

    Per-step PRNG keys are folded from ``(epoch_key, start + k)`` — the
    global step index — inside the scan, so the loss trajectory is
    bit-identical for ANY chunk size (chunk=1 reproduces the plain per-step
    path exactly).  One executable per distinct K (at most two per run: the
    full chunk and the remainder).

    ``donate=True`` (default) donates the input state AND the consumed
    image/label chunk: the state output aliases the state input (no
    per-dispatch state copy in HBM — the trainer device-copies a snapshot
    before handing the state to the async checkpoint writer), and the
    single-use chunk buffers are released at dispatch instead of outliving
    the call.  Callers that re-read an input after the call (none in the
    train loop) must pass ``donate=False``.

    ``fault_injection=True`` appends a traced ``(scale, start, stop)``
    step-fault argument (indices are GLOBAL within the epoch, matching the
    key fold) — built only when a fault plan carries step faults, so the
    normal path's executable is byte-identical to before.
    """
    chunk_shard = batch_sharding(mesh, axis=1)
    repl = replicated_sharding(mesh)
    state_sh = state_sharding if state_sharding is not None else repl
    core = _make_step_core(
        precision, augment, mean, std, grad_accum, chunk_shard, fwd_bwd,
        comms, repl,
    )

    def _run(state: TrainState, images, labels, epoch_key: jax.Array, start, fault):
        def body(state, inp):
            k, bx, by = inp
            key = jax.random.fold_in(epoch_key, start + k)
            if fault is None:
                return core(state, bx, by, key)
            return core(state, bx, by, key, _step_fault_scale(start + k, fault))

        ks = jnp.arange(images.shape[0])
        state, stacked = jax.lax.scan(body, state, (ks, images, labels))
        return state, stacked

    if fault_injection:
        run = lambda state, images, labels, epoch_key, start, fault: (  # noqa: E731
            _run(state, images, labels, epoch_key, start, fault)
        )
        in_sh = (state_sh, chunk_shard, chunk_shard, repl, repl, (repl, repl, repl))
    else:
        run = lambda state, images, labels, epoch_key, start: (  # noqa: E731
            _run(state, images, labels, epoch_key, start, None)
        )
        in_sh = (state_sh, chunk_shard, chunk_shard, repl, repl)
    if donate:
        return _declare_state_layout(
            _donated_jit(
                run,
                mesh,
                donate_argnums=(0, 1, 2),
                monitor=monitor,
                name="chunk_runner",
                in_shardings=in_sh,
                out_shardings=(state_sh, repl),
            ),
            fwd_bwd, state_layout,
        )
    return _declare_state_layout(
        observed_jit(
            run, monitor, "chunk_runner",
            in_shardings=in_sh, out_shardings=(state_sh, repl),
        ),
        fwd_bwd, state_layout,
    )


def make_device_chunk_runner(
    mesh: Mesh,
    batch_size: int,
    chunk_steps: int,
    *,
    precision: str = "fp32",
    augment: bool = True,
    mean=CIFAR100_MEAN,
    std=CIFAR100_STD,
    state_sharding=None,
    grad_accum: int = 1,
    fwd_bwd=None,
    comms=None,
    fault_injection: bool = False,
    donate: bool = True,
    monitor=None,
    state_layout=None,
) -> Callable[..., tuple[TrainState, Metrics]]:
    """``chunk_steps`` steps of a device-resident epoch as ONE scanned
    dispatch; ``chunk_steps = steps`` with ``start = 0`` is the whole epoch
    as a single program.

    Inputs are the device-resident split (uint8 images + labels), the root
    PRNG key, the epoch number and the chunk's first step (both traced, so
    every epoch and every full-size chunk reuse one executable).  Per-epoch
    shuffling is a device-side permutation folded from (key, epoch);
    ``drop_last=True`` semantics match the reference's train loader
    (``src/single/dataset.py:97``).

    Bit-identity contract (the same one the host chunk runner documents):
    every chunk derives the epoch's whole tables — the permutation
    ``epoch_permutation(key, epoch, n)`` cut to whole batches and the key
    table ``split(fold_in(fold_in(key, epoch), 1), steps)``, one key per
    step of the epoch — and dynamic-slices rows ``[start, start + K)`` out
    of both, so the loss/param trajectory is bit-identical for ANY chunk
    size (``tests/test_overlap.py`` pins it against ``K = steps``).  What
    chunking buys is a host touch point every K steps: the health watchdog
    and the preemption poll gain chunk-boundary granularity in device data
    mode, where a whole-epoch chunk is one uninterruptible program.  The
    permutation recompute per chunk is O(n log n) device work — noise next
    to K training steps for any practical K.

    ``start`` is traced, so every full-size chunk shares one executable (at
    most two per run: the full chunk and the remainder).  Callers must keep
    ``start + chunk_steps <= steps`` — ``dynamic_slice`` clamps an
    out-of-range start instead of failing, which would silently replay
    batches.  ``donate=True`` donates only the state: the output state
    aliases it, so the runner keeps no second copy in HBM (the trainer hands
    the async checkpoint writer an explicit device-side snapshot instead —
    see ``Trainer.fit``).  The split arrays are the epoch-persistent dataset
    and are never donated; the eval runners likewise keep donation off.

    ``fault_injection=True`` appends a traced ``(scale, start, stop)``
    step-fault argument (``resilience/faults.py`` step faults; indices are
    steps within the epoch); the default runner's signature and executable
    are unchanged.
    """
    data_shard = batch_sharding(mesh)
    repl = replicated_sharding(mesh)
    state_sh = state_sharding if state_sharding is not None else repl
    accum_shard = batch_sharding(mesh, axis=1)
    core = _make_step_core(
        precision, augment, mean, std, grad_accum, accum_shard, fwd_bwd,
        comms, repl,
    )

    def _run(state: TrainState, images, labels, key: jax.Array, epoch, start, fault):
        n = images.shape[0]
        steps = n // batch_size
        k = min(chunk_steps, steps)
        epoch_key = jax.random.fold_in(key, epoch)
        perm = epoch_permutation(key, epoch, n)[: steps * batch_size]
        perm = perm.reshape(steps, batch_size)
        step_keys = jax.random.split(jax.random.fold_in(epoch_key, 1), steps)
        rows = jax.lax.dynamic_slice_in_dim(perm, start, k, axis=0)
        keys = jax.lax.dynamic_slice_in_dim(step_keys, start, k, axis=0)

        def body(state, inp):
            idx, step_key, i = inp
            bx = jax.lax.with_sharding_constraint(images[idx], data_shard)
            by = jax.lax.with_sharding_constraint(labels[idx], data_shard)
            if fault is None:
                return core(state, bx, by, step_key)
            return core(state, bx, by, step_key, _step_fault_scale(i, fault))

        state, stacked = jax.lax.scan(
            body, state, (rows, keys, start + jnp.arange(k))
        )
        return state, stacked

    if fault_injection:
        run = lambda state, images, labels, key, epoch, start, fault: (  # noqa: E731
            _run(state, images, labels, key, epoch, start, fault)
        )
    else:
        run = lambda state, images, labels, key, epoch, start: (  # noqa: E731
            _run(state, images, labels, key, epoch, start, None)
        )
    # the chunk length is a STATIC of this runner (two runners over the
    # same split take identically-shaped args) — it must be part of the
    # observed family name or the full-chunk and remainder executables
    # would collide on one fingerprint
    obs_name = f"device_chunk_runner@k{chunk_steps}"
    if donate:
        return _declare_state_layout(
            _donated_jit(
                run, mesh, donate_argnums=(0,), monitor=monitor,
                name=obs_name, out_shardings=(state_sh, repl),
            ),
            fwd_bwd, state_layout,
        )
    return _declare_state_layout(
        observed_jit(
            run, monitor, obs_name, out_shardings=(state_sh, repl)
        ),
        fwd_bwd, state_layout,
    )
