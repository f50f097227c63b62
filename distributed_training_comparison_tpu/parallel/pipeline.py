"""Pipeline parallelism (GPipe, 1F1B, interleaved 1F1B) over stacked
homogeneous layers, composable with DP×TP.

The reference has no pipeline parallelism (SURVEY.md §2.2 — absent).  This
module completes the framework's parallelism axes (data / tensor /
sequence / pipeline) for the transformer family, whose scanned trunk
already stores its ``depth`` identical blocks as one stacked pytree
``(depth, ...)`` — the natural thing to shard across pipeline stages.

Axes (one mesh, ``parallel/mesh.py``):

- Historically the ``"model"`` mesh axis doubled as the **pipe** axis; the
  default ``pipe_axis=MODEL_AXIS`` arguments keep that configuration alive
  (``--parallel-style pipeline``).
- With ``--pipeline-parallel P`` the schedule runs on the DEDICATED
  ``"pipe"`` axis and composes with tensor parallelism on ``"model"``
  (``tp_axis=MODEL_AXIS``): the stacked trunk is sharded
  ``(pipe on the depth axis, model on the feature dims)``, so model size
  scales past one tensor-parallel group's HBM — the DP×TP×PP mesh the
  MPMD pipeline paper (PAPERS.md, arxiv 2412.14374) composes.

Tensor parallelism inside a stage is MANUAL (Megatron f/g operators): the
schedule bodies run under fully-manual ``shard_map`` (the per-tick
``ppermute`` handoff demands it), and on this jax a ``jax.vjp`` taken
*inside* a shard_map body mis-transposes a bare ``psum`` (the cotangent is
replicated, so psum-as-its-own-transpose double-counts by the axis size —
verified empirically on the pinned 0.4.37).  The ``_tp_ops`` pair makes
the backward correct by construction: ``f`` = identity forward / psum
backward at the entry of each column-parallel region, ``g`` = psum forward
/ identity backward at the exit of each row-parallel region.

Schedules:

- **GPipe** (``pipeline_stages``): unrolled forward, autodiff backward,
  O(M) stashed microbatches.  Bubble ``(P-1)/(M+P-1)``.
- **1F1B** (``make_1f1b_fwd_bwd``): hand-scheduled backward with per-stage
  activation recompute, O(P) stash.  Same bubble, the memory headroom that
  lets M grow.
- **Interleaved 1F1B** (``make_interleaved_fwd_bwd`` with ``virtual > 1``):
  each device owns ``v`` NON-contiguous layer chunks (chunk ``c`` of
  ``v·P`` lives on device ``c mod P``), and the tick loop alternates
  virtual stages — per-tick work shrinks ``v×`` while the warmup/cooldown
  tick count grows sub-``v×``, so the bubble fraction at fixed M drops
  from ``(2P-2)/(M+2P-2)`` toward ``((v+1)P-2)/(vM+(v+1)P-2)`` (the
  schedule arithmetic ``schedule_meta`` records).
  The stash stays O(P·v) microbatch *inputs* of chunks ``1/v`` the size —
  the same O(P) activation memory as plain 1F1B.

SPMD shape: every stage runs the same unrolled program; per-stage behavior
(which unit, valid or garbage) is selected by traced ``axis_index``
arithmetic.  The one genuinely per-device branch is the loss head: only
the LAST stage ever needs it, and it runs under ``lax.cond`` so non-last
stages skip the compute entirely.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS, MODEL_AXIS, PIPE_AXIS

PIPELINE_SCHEDULES = ("gpipe", "1f1b", "interleaved")


def _microbatch_error(
    batch: int, microbatches: int, data_axis_size: int, pipe: int | None = None
) -> ValueError:
    """The trace-time divisibility refusal, routed through the same
    actionable-numbers helper as the batch-split error (satellite of
    ISSUE 12): names the legal microbatch counts instead of a bare
    ``b % m`` traceback."""
    from ..resilience.elastic import microbatch_help

    return ValueError(
        "pipeline microbatch split impossible: "
        + microbatch_help(batch, microbatches, data_axis_size, pipe=pipe)
    )


def schedule_meta(
    schedule: str, pipe: int, microbatches: int, virtual: int = 1
) -> dict:
    """The static tick arithmetic of a schedule — one source of truth for
    the bubble fraction the obs plane reports (per-stage span lanes,
    ``run_report``'s bubble table; pinned by ``tests/test_pipeline.py``).

    ``useful_ticks`` counts ticks where a device performs valid unit work;
    every other tick is warmup/cooldown — computed (and on real silicon,
    lockstepped) but discarded: the pipeline bubble.  ``fill_ticks`` /
    ``drain_ticks`` are per-stage leading/trailing bubble ticks — the
    trapezoid the span lanes render.  GPipe is a forward program (stage
    ``s`` starts at tick ``s``, finishes ``P-1-s`` ticks early); the 1F1B
    family ENDS with the backward ripple toward stage 0, so stage ``s``
    both starts at tick ``s`` and finishes ``s`` ticks early (its last
    backward unit lands at tick ``T-1-s``) — the last stage carries the
    whole ``2(P-1)`` edge bubble, while stage 0's share sits mid-schedule
    as half-busy ticks the edge trapezoid deliberately does not render
    (``bubble_frac`` is the exact account).
    """
    if schedule not in PIPELINE_SCHEDULES:
        raise ValueError(
            f"unknown pipeline schedule {schedule!r}; "
            f"one of {PIPELINE_SCHEDULES}"
        )
    v = virtual if schedule == "interleaved" else 1
    m, p = microbatches, pipe
    if schedule == "gpipe":
        ticks, useful = m + p - 1, m
        drain = [p - 1 - s for s in range(p)]
    else:
        n = v * p
        ticks, useful = m * v + n + p - 2, m * v
        drain = list(range(p))
    return {
        "schedule": schedule,
        "pipe": p,
        "microbatches": m,
        "virtual": v,
        "ticks": ticks,
        "useful_ticks": useful,
        "bubble_frac": round((ticks - useful) / ticks, 6),
        "fill_ticks": list(range(p)),
        "drain_ticks": drain,
    }


# ------------------------------------------------------------- manual TP


def _tp_ops(axis: str):
    """The Megatron ``f``/``g`` conjugate pair for manual tensor
    parallelism inside a shard_map body whose backward is driven by an
    in-body ``jax.vjp``:

    - ``f``: identity forward, ``psum`` backward — placed at the entry of
      a column-parallel region (the replicated activation feeds every
      shard's columns, so its cotangent is the SUM of the per-shard
      partials);
    - ``g``: ``psum`` forward, identity backward — placed at the exit of a
      row-parallel region (the output is the sum of per-shard partials,
      and its replicated cotangent IS each shard's partial cotangent).

    ``custom_vjp`` pins both transposes; the bare-psum transpose a shard
    map-internal vjp would pick is wrong by a factor of the axis size.
    """

    @jax.custom_vjp
    def f(x):
        return x

    f.defvjp(lambda x: (x, None), lambda _, dy: (jax.lax.psum(dy, axis),))

    @jax.custom_vjp
    def g(x):
        return jax.lax.psum(x, axis)

    g.defvjp(lambda x: (jax.lax.psum(x, axis), None), lambda _, dy: (dy,))
    return f, g


def pipeline_stages(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    local_params: Any,
    microbatches: jnp.ndarray,
    *,
    axis_name: str,
) -> jnp.ndarray:
    """Run the GPipe schedule; call inside ``shard_map``.

    ``local_params``: this stage's layer slice (leaves ``(L/P, ...)``).
    ``microbatches``: ``(M, mb, ...)`` inputs, replicated across the pipe
    axis.  Returns ``(M, mb, ...)`` outputs, replicated (broadcast from
    the last stage).
    """
    p_size = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = microbatches.shape[0]
    is_first = idx == 0
    is_last = idx == p_size - 1
    perm = [(j, (j + 1) % p_size) for j in range(p_size)]

    state = jnp.zeros_like(microbatches[0])
    outs = jnp.zeros_like(microbatches)
    for t in range(m + p_size - 1):
        feed = microbatches[min(t, m - 1)]  # garbage past M; never collected
        y = stage_fn(local_params, jnp.where(is_first, feed, state))
        j = t - (p_size - 1)  # microbatch leaving the last stage this tick
        if 0 <= j < m:
            outs = outs.at[j].set(jnp.where(is_last, y, outs[j]))
        if t + 1 < m + p_size - 1:
            state = jax.lax.ppermute(y, axis_name, perm)
    # broadcast the last stage's outputs to every stage (replicated out)
    return jax.lax.psum(
        jnp.where(is_last, outs, jnp.zeros_like(outs)), axis_name
    )


def pp_trunk_specs(blocks, *, pipe_axis: str = MODEL_AXIS, tp_axis: str | None = None):
    """Partition specs for the stacked trunk under the composed layout:
    the leading ``depth`` axis shards over ``pipe_axis``; with ``tp_axis``
    the feature dims additionally carry the Megatron column/row layout
    (``parallel/tp.py`` ``_vit_trunk_specs`` — q/k/v/mlp_up output-sharded,
    proj/mlp_down input-sharded, norms/biases-of-row replicated)."""
    if tp_axis is None:
        return jax.tree_util.tree_map(lambda _: P(pipe_axis), blocks)
    from .tp import _vit_trunk_specs

    tp_specs = _vit_trunk_specs(blocks)

    def compose(leaf, spec):
        parts = tuple(spec)
        parts = parts + (None,) * (len(leaf.shape) - len(parts))
        return P(pipe_axis, *parts[1:])

    return jax.tree_util.tree_map(compose, blocks, tp_specs)


def make_pipeline_trunk(
    mesh: Mesh,
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    *,
    num_microbatches: int,
    pipe_axis: str = MODEL_AXIS,
    data_axis: str | None = DATA_AXIS,
    param_specs=None,
):
    """Global-array wrapper: ``(stacked_params, tokens) -> tokens`` with the
    layer stack sharded over ``pipe_axis`` and the batch over ``data_axis``.
    ``param_specs`` overrides the per-leaf layout (the DP×TP×PP composition
    passes ``pp_trunk_specs``; default = pipe-sharded stack only)."""

    def run(stacked_params, tokens: jnp.ndarray) -> jnp.ndarray:
        b = tokens.shape[0]
        m = num_microbatches
        if b % m:
            raise _microbatch_error(
                b, m, mesh.shape.get(data_axis, 1) if data_axis else 1
            )
        mb = tokens.reshape(m, b // m, *tokens.shape[1:])
        specs = (
            param_specs
            if param_specs is not None
            else jax.tree_util.tree_map(lambda _: P(pipe_axis), stacked_params)
        )
        mb_spec = P(None, data_axis, *([None] * (mb.ndim - 2)))
        staged = shard_map(
            partial(pipeline_stages, stage_fn, axis_name=pipe_axis),
            mesh=mesh,
            in_specs=(specs, mb_spec),
            out_specs=mb_spec,
            check_vma=False,
        )
        return staged(stacked_params, mb).reshape(b, *tokens.shape[1:])

    return run


def pp_state_shardings(
    mesh: Mesh,
    state,
    *,
    pipe_axis: str = MODEL_AXIS,
    blocks_key: str = "blocks",
    tp_axis: str | None = None,
    state_layout=None,
):
    """``TrainState`` shardings for the pipeline layout: the stacked trunk
    is sharded across pipeline stages — and, under the DP×TP×PP
    composition (``tp_axis``), its feature dims across the tensor-parallel
    axis — everything else (embed/head params, (empty) batch stats) is
    replicated; the optimizer's momentum mirrors the params via the shared
    suffix-matching builder (``tp.build_state_shardings``).

    The CARRIED trunk layout is whatever the installed schedule declares
    (``parallel/layouts.py``): the contiguous pipe-sharded stack for
    GPipe/1F1B (stage ``s`` holds layers ``[s·L/P, (s+1)·L/P)``), the
    resident ``(v, P, K)`` chunk view for the interleaved schedule — so
    the per-step relayout is gone and ``state.params[blocks_key]`` must
    already be in ``state_layout``'s resident form when this is called.
    ``state_layout=None`` keeps the legacy contiguous specs."""
    from .tp import build_state_shardings

    repl = P()

    def pspec(mod, sub):
        if mod == blocks_key:
            if state_layout is not None:
                return state_layout.specs(sub)
            return pp_trunk_specs(sub, pipe_axis=pipe_axis, tp_axis=tp_axis)
        return jax.tree_util.tree_map(lambda _: repl, sub)

    pspecs = {mod: pspec(mod, sub) for mod, sub in state.params.items()}
    bspecs = jax.tree_util.tree_map(lambda _: repl, state.batch_stats)
    return build_state_shardings(mesh, state, pspecs, bspecs)


def make_pipelined_apply_fn(
    model,
    mesh: Mesh,
    *,
    num_microbatches: int,
    pipe_axis: str = MODEL_AXIS,
    tp_axis: str | None = None,
    state_layout=None,
):
    """An ``apply_fn`` drop-in for ``TrainState`` that runs the pipelined
    forward with the train step's calling conventions (``train=``,
    ``mutable=`` — the transformer family has no mutable collections).

    ``state_layout``: the resident trunk layout the carried variables
    arrive in; a chunked-resident trunk is canonicalized per eval batch
    (off the train hot path — the one reader that still pays a relayout,
    documented in ``parallel/layouts.py``)."""

    def apply_fn(variables, x, train=False, mutable=()):
        logits = pipelined_vit_apply(
            model, variables, x, mesh,
            num_microbatches=num_microbatches,
            pipe_axis=pipe_axis, tp_axis=tp_axis,
            state_layout=state_layout,
        )
        return (logits, {}) if mutable else logits

    return apply_fn


def vit_stage_fn(
    model,
    *,
    attn_impl: str | None = None,
    tp_axis: str | None = None,
    manual_vjp: bool = True,
) -> Callable[[Any, jnp.ndarray], jnp.ndarray]:
    """Scan a slice of a zoo ViT's stacked block params over its input.

    Without ``tp_axis`` the stage applies the *same* ``ViTBlock`` module
    the model's scanned trunk uses, on slices of the model's own stacked
    parameters — so a staged/sharded trunk can never diverge from
    ``model.trunk``.  Shared by pipeline parallelism (per-stage layer
    slices) and sequence parallelism (full stack, ``attn_impl`` overridden
    to the sequence-parallel dispatch).

    With ``tp_axis`` the stage runs the MANUAL tensor-parallel form of the
    same block math on locally-sharded kernels (q/k/v/mlp_up hold
    ``1/T`` of their output features, proj/mlp_down ``1/T`` of their input
    features).  Attention runs head-local (``heads % T == 0``, validated
    by the Trainer); norms ride the same ``norm_policy`` dtype contract as
    ``ViTBlock``.  ``manual_vjp`` picks the collective flavor to match the
    differentiation regime — the two disagree on this jax and mixing them
    halves/doubles sharded-leaf gradients by the axis size:

    - ``True`` (the 1F1B schedules, which run ``jax.vjp`` INSIDE the
      shard_map body): the Megatron ``f``/``g`` ``custom_vjp`` pair pins
      both transposes (a bare in-body psum mis-transposes to psum);
    - ``False`` (GPipe, whose backward is OUTER autodiff through the whole
      shard_map): bare ``jax.lax.psum`` — shard_map's own transpose
      machinery pairs the unmentioned-axis out-spec factor with the
      psum-as-psum transpose exactly, and the custom pair would break that
      pairing (both verified empirically on the pinned 0.4.37).
    """
    from ..models.vit import ViTBlock

    if tp_axis is None:
        block_cls = ViTBlock
        if model.remat:  # honor --remat: param structure is unchanged
            block_cls = nn.remat(ViTBlock, prevent_cse=False)
        block = block_cls(
            dim=model.dim,
            heads=model.heads,
            mlp_ratio=model.mlp_ratio,
            dtype=model.dtype,
            norm_dtype=model.norm_dtype,
            attn_impl=model.attn_impl if attn_impl is None else attn_impl,
            block_fusion=getattr(model, "block_fusion", "off"),
        )

        def stage(local_params, x):
            def body(c, layer_params):
                y, _ = block.apply({"params": layer_params}, c, None)
                return y, None

            x, _ = jax.lax.scan(body, x, local_params)
            return x

        return stage

    from ..models.norms import norm_policy
    from ..ops import attention

    if manual_vjp:
        f_op, g_op = _tp_ops(tp_axis)
    else:
        f_op = lambda x: x  # noqa: E731
        g_op = lambda x: jax.lax.psum(x, tp_axis)  # noqa: E731
    dt = model.dtype
    head_dim = model.dim // model.heads
    impl = model.attn_impl if attn_impl is None else attn_impl
    ln = norm_policy(nn.LayerNorm, model.norm_dtype, dt)()

    def dense(p, x):
        return jnp.dot(x.astype(dt), p["kernel"].astype(dt)) + p["bias"].astype(dt)

    def tp_block(lp, x):
        b, s, dim = x.shape
        h = f_op(ln.apply({"params": lp["ln_attn"]}, x).astype(dt))
        local_heads = lp["q_proj"]["kernel"].shape[-1] // head_dim
        q = dense(lp["q_proj"], h).reshape(b, s, local_heads, head_dim)
        k = dense(lp["k_proj"], h).reshape(b, s, local_heads, head_dim)
        v = dense(lp["v_proj"], h).reshape(b, s, local_heads, head_dim)
        o = attention(q, k, v, impl=impl, layout="bshd")
        o = o.reshape(b, s, local_heads * head_dim)
        # row-parallel proj: partial product, psum at g, bias added once
        x = x + (
            g_op(jnp.dot(o.astype(dt), lp["proj"]["kernel"].astype(dt)))
            + lp["proj"]["bias"].astype(dt)
        )
        h = f_op(ln.apply({"params": lp["ln_mlp"]}, x).astype(dt))
        u = nn.gelu(dense(lp["mlp_up"], h))
        x = x + (
            g_op(jnp.dot(u.astype(dt), lp["mlp_down"]["kernel"].astype(dt)))
            + lp["mlp_down"]["bias"].astype(dt)
        )
        return x

    block_apply = tp_block
    if model.remat:
        block_apply = jax.checkpoint(tp_block, prevent_cse=False)

    def stage(local_params, x):
        def body(c, layer_params):
            return block_apply(layer_params, c), None

        x, _ = jax.lax.scan(body, x, local_params)
        return x

    return stage


# ------------------------------------------------- 1F1B (v=1) / interleaved
#
# GPipe above leans on autodiff: the unrolled forward schedule is plain
# differentiable code, so jax.grad emits the reversed pipeline — but that
# means EVERY microbatch's stage activations are live between the forward
# and backward passes: O(M) stashed microbatches per stage.  The 1F1B
# (one-forward-one-backward / PipeDream-flush) family interleaves each
# microbatch's backward as soon as the last stage has consumed it, so a
# stage only ever holds the units currently in flight.  The stage forward
# is recomputed under ``jax.vjp`` at backward time (activation
# recomputation, the Megatron trade): FLOP cost matches
# GPipe-with---remat; stash drops from O(M) to O(P·v) chunk inputs.
#
# Generalized unit arithmetic (virtual stages v ≥ 1, N = v·P chunks; chunk
# c holds layers [c·K, (c+1)·K), K = L/N, and lives on device c mod P):
#
# - FORWARD: at tick t, device s executes forward unit u = t - s.
#   Unit u maps to virtual chunk i = (u mod N) // P and microbatch
#   m = (u // N)·P + (u mod P) — microbatches advance in groups of P
#   through each chunk (the Megatron interleaving; for v > 1 this is why
#   M must be a multiple of P; for v = 1 the mapping is the identity and
#   any M is legal).  The ring invariant: device s-1's previous-tick
#   output is EXACTLY unit u's input (same chunk index for s > 0; chunk
#   i-1's last stage wrapping to device 0 for s = 0) — one ppermute per
#   tick, no per-chunk special cases.
# - BACKWARD: mirrored ring: at tick t device s executes backward unit
#   w = t - (N-1) - (P-1-s), mapping to virtual chunk
#   i_b = v-1 - ((w mod N) // P) and the same group microbatch arithmetic.
#   The head cotangent enters on the last stage in the same tick its
#   chunk-(N-1) forward completes, exactly like plain 1F1B.
#
# Total ticks T = M·v + N + P - 2 (v = 1 recovers M + 2P - 2); per-tick
# chunk work is 1/v of the plain-1F1B slab, so the bubble *time* shrinks
# ~v× at fixed M — the step-time win schedule_meta quantifies (no chip
# measurement of it exists: there is no pipeline cell, ROADMAP.md D3).


def _interleaved_1f1b(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    head_loss_fn: Callable[[Any, jnp.ndarray, jnp.ndarray], tuple],
    chunk_params: Any,
    head_params: Any,
    microbatches: jnp.ndarray,
    labels: jnp.ndarray,
    residual: Any,
    *,
    axis_name: str,
    data_axis: str | None,
    virtual: int,
    grad_comms: str = "fp32",
):
    """The interleaved-1F1B schedule body; call inside ``shard_map``.

    ``chunk_params``: this device's ``v`` layer chunks, leaves
    ``(v, 1, K, ...)`` (the shard_map-local view of the ``(v, P, K, ...)``
    chunk layout).  ``microbatches``: ``(M, mb, ...)`` trunk inputs
    (post-embed tokens), replicated over the pipe axis, batch-sharded over
    ``data_axis``.  ``labels``: ``(M, mb)``.  ``head_loss_fn(head_params,
    y, labels) -> (scaled_loss_sum, logits)`` is differentiated on the
    last stage — under ``lax.cond``, so it COSTS nothing on the other
    stages — the moment it finishes a microbatch's chunk-(N-1) forward;
    its ``dy`` cotangent enters the backward pipeline in the same tick.

    ``residual``: per-device error-feedback state for the wire-true
    compressed gradient sync (``grad_comms`` fp16/int8), or ``None``;
    carried across steps by the train state in the schedule layout.

    Returns ``(loss, chunk_grads_local, head_grads, dtokens, logits,
    new_residual)``, already reduced over the data axis where the quantity
    is batch-reduced (through the quantized wire when compression is on).
    """
    p_size = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    v = virtual
    n_chunks = v * p_size
    m = microbatches.shape[0]
    units = m * v
    is_first = idx == 0
    is_last = idx == p_size - 1
    fwd_perm = [(j, (j + 1) % p_size) for j in range(p_size)]
    bwd_perm = [(j, (j - 1) % p_size) for j in range(p_size)]
    # max units in flight on any device between a unit's forward and its
    # backward: 2N - 2 (chunk 0 of a group on stage 0), +1 slot in use
    depth = 2 * n_chunks - 1
    ticks = units + n_chunks + p_size - 2

    # squeeze the shard axis: (v, 1, K, ...) -> (v, K, ...)
    chunks = jax.tree_util.tree_map(
        lambda l: l.reshape(l.shape[0], *l.shape[2:]), chunk_params
    )

    def chunk_at(tree, i):
        return jax.tree_util.tree_map(
            lambda l: jax.lax.dynamic_index_in_dim(l, i, 0, keepdims=False),
            tree,
        )

    state = jnp.zeros_like(microbatches[0])   # incoming forward activation
    dstate = jnp.zeros_like(microbatches[0])  # incoming backward cotangent
    # rolling stash of chunk INPUTS keyed by forward unit index; slot
    # `depth` is the spill slot for ticks where this device has no valid
    # forward (garbage never clobbers a live unit)
    stash = jnp.zeros((depth + 1, *state.shape), state.dtype)
    loss = jnp.zeros((), jnp.float32)
    g_chunks = jax.tree_util.tree_map(
        lambda p_: jnp.zeros(p_.shape, jnp.float32), chunks
    )
    g_head = jax.tree_util.tree_map(
        lambda p_: jnp.zeros(p_.shape, jnp.float32), head_params
    )
    dtokens = jnp.zeros_like(microbatches)
    # head output types without running the head: the zero branch of the
    # per-stage lax.cond needs shapes only
    loss_sh, logits_sh = jax.eval_shape(
        head_loss_fn, head_params, microbatches[0], labels[0]
    )
    logits_out = jnp.zeros((m, *logits_sh.shape), logits_sh.dtype)

    def run_head(y, lbl):
        (mb_loss, h_vjp, mb_logits) = jax.vjp(
            lambda hp, yy: head_loss_fn(hp, yy, lbl),
            head_params,
            y,
            has_aux=True,
        )
        dh, dy = h_vjp(jnp.ones((), mb_loss.dtype))
        return (
            mb_loss.astype(jnp.float32),
            jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), dh),
            dy,
            mb_logits,
        )

    def zero_head(y, lbl):
        return (
            jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(
                lambda p_: jnp.zeros(p_.shape, jnp.float32), head_params
            ),
            jnp.zeros_like(y),
            jnp.zeros(logits_sh.shape, logits_sh.dtype),
        )

    for t in range(ticks):
        in_fwd_phase = t < units + p_size - 1
        in_bwd_phase = t >= n_chunks - 1
        head_dy = None

        if in_fwd_phase:
            u = t - idx  # this device's forward unit (traced)
            valid_f = jnp.logical_and(u >= 0, u < units)
            iu = jnp.clip(u, 0, units - 1)
            i_f = (iu % n_chunks) // p_size          # virtual chunk index
            m_f = (iu // n_chunks) * p_size + iu % p_size  # microbatch
            feed = jax.lax.dynamic_index_in_dim(
                microbatches, m_f, 0, keepdims=False
            )
            # the model's FIRST chunk (chunk 0 = virtual 0 on stage 0)
            # takes the embedded microbatch; every other chunk takes the
            # ring — device s-1's previous-tick output is exactly this
            # unit's input (see the unit-arithmetic derivation above)
            x_in = jnp.where(
                jnp.logical_and(is_first, i_f == 0), feed, state
            )
            y = stage_fn(chunk_at(chunks, i_f), x_in)
            slot = jnp.where(valid_f, iu % depth, depth)
            stash = jax.lax.dynamic_update_index_in_dim(
                stash, x_in, slot, axis=0
            )
            # loss head: ONLY where the unit is chunk N-1 on the last
            # stage — a real per-device branch (lax.cond), not masked
            # compute, so the other P-1 stages skip the head flops that
            # round 1 paid (and discarded) on every stage every tick
            lbl_i = jax.lax.dynamic_index_in_dim(labels, m_f, 0, keepdims=False)
            head_pred = jnp.logical_and(
                valid_f, jnp.logical_and(is_last, i_f == v - 1)
            )
            mb_loss, dh, head_dy, mb_logits = jax.lax.cond(
                head_pred, run_head, zero_head, y, lbl_i
            )
            loss = loss + mb_loss
            g_head = jax.tree_util.tree_map(jnp.add, g_head, dh)
            prev = jax.lax.dynamic_index_in_dim(
                logits_out, m_f, axis=0, keepdims=False
            )
            logits_out = jax.lax.dynamic_update_index_in_dim(
                logits_out, jnp.where(head_pred, mb_logits, prev), m_f, axis=0
            )

        if in_bwd_phase:
            w = t - (n_chunks - 1) - (p_size - 1 - idx)  # backward unit
            valid_b = jnp.logical_and(w >= 0, w < units)
            iw = jnp.clip(w, 0, units - 1)
            i_b = v - 1 - (iw % n_chunks) // p_size
            # the forward unit this backward retires, for the stash slot
            u_b = (iw // n_chunks) * n_chunks + i_b * p_size + iw % p_size
            x_back = jax.lax.dynamic_index_in_dim(
                stash, u_b % depth, axis=0, keepdims=False
            )
            if head_dy is None:
                head_dy = jnp.zeros_like(dstate)
            # chunk N-1's cotangent is the head's, same tick; every other
            # chunk's arrives on the backward ring
            dy = jnp.where(
                jnp.logical_and(is_last, i_b == v - 1),
                head_dy.astype(dstate.dtype),
                dstate,
            )
            # recompute this chunk's forward and pull the cotangent back
            _, s_vjp = jax.vjp(stage_fn, chunk_at(chunks, i_b), x_back)
            dp, dx = s_vjp(dy)
            g_i = chunk_at(g_chunks, i_b)
            g_i = jax.tree_util.tree_map(
                lambda g, d: g
                + jnp.where(valid_b, d, jnp.zeros_like(d)).astype(g.dtype),
                g_i,
                dp,
            )
            g_chunks = jax.tree_util.tree_map(
                lambda g, gi: jax.lax.dynamic_update_index_in_dim(
                    g, gi, i_b, axis=0
                ),
                g_chunks,
                g_i,
            )
            # chunk 0's dx is the embed cotangent
            take_dx = jnp.logical_and(
                valid_b, jnp.logical_and(is_first, i_b == 0)
            )
            m_b = (iw // n_chunks) * p_size + iw % p_size
            prev_dt = jax.lax.dynamic_index_in_dim(
                dtokens, m_b, axis=0, keepdims=False
            )
            dtokens = jax.lax.dynamic_update_index_in_dim(
                dtokens,
                jnp.where(take_dx, dx.astype(dtokens.dtype), prev_dt),
                m_b,
                axis=0,
            )

        # hand activations downstream / cotangents upstream for next tick
        if in_fwd_phase and t + 1 < units + p_size - 1:
            state = jax.lax.ppermute(y, axis_name, fwd_perm)
        if in_bwd_phase and t + 1 < ticks:
            dstate = jax.lax.ppermute(dx, axis_name, bwd_perm)

    # loss / head grads / logits / dtokens live on one stage each —
    # broadcast over the pipe axis; batch-reduced quantities also reduce
    # over the data axis (inside shard_map GSPMD does not insert these).
    # The data-axis legs of the PARAMETER gradients are the run's gradient
    # sync wire: with compression on they cross quantized (wire-true — the
    # schedule owns its backward, so unlike the GSPMD runners the fp16/int8
    # payload genuinely is what moves), with per-device error feedback.
    loss = jax.lax.psum(loss, axis_name)
    g_head = jax.lax.psum(g_head, axis_name)
    dtokens = jax.lax.psum(dtokens, axis_name)
    logits_out = jax.lax.psum(logits_out, axis_name)
    new_residual = residual
    if data_axis is not None:
        from .comms import wire_psum

        loss = jax.lax.psum(loss, data_axis)
        # NOT dtokens: they are per-example cotangents, batch-sharded over
        # the data axis — the outer embed_vjp's GSPMD reduction sums the
        # embed grads across the batch
        r_blocks = None if residual is None else residual["blocks"]
        r_head = None if residual is None else residual["head"]
        g_chunks, r_blocks = wire_psum(
            g_chunks, data_axis, grad_comms, residual=r_blocks
        )
        g_head, r_head = wire_psum(
            g_head, data_axis, grad_comms, residual=r_head
        )
        if residual is not None:
            new_residual = {"blocks": r_blocks, "head": r_head}
    # restore the shard axis: (v, K, ...) -> (v, 1, K, ...)
    g_chunks = jax.tree_util.tree_map(
        lambda l: l.reshape(l.shape[0], 1, *l.shape[1:]), g_chunks
    )
    return loss, g_chunks, g_head, dtokens, logits_out, new_residual


_HEAD_MODS = ("ln_head", "head")


def _chunk_view_specs(blocks, *, pipe_axis: str, tp_axis: str | None):
    """Specs for the in-schedule ``(v, P, K, ...)`` chunk view of the
    stacked trunk: chunk index ``c = i·P + s`` lives at ``[i, s]`` and the
    shard axis is axis 1; feature dims keep the TP layout."""
    if tp_axis is None:
        return jax.tree_util.tree_map(
            lambda _: P(None, pipe_axis), blocks
        )
    from .tp import _vit_trunk_specs

    tp_specs = _vit_trunk_specs(blocks)

    def compose(leaf, spec):
        parts = tuple(spec) + (None,) * (len(leaf.shape) - len(tuple(spec)))
        return P(None, pipe_axis, None, *parts[1:])

    return jax.tree_util.tree_map(compose, blocks, tp_specs)


def pipeline_residual_spec(
    params,
    mesh: Mesh,
    *,
    virtual: int = 1,
    pipe_axis: str = MODEL_AXIS,
    tp_axis: str | None = None,
    data_axis: str = DATA_AXIS,
    blocks_key: str = "blocks",
    state_layout=None,
):
    """``(host_zeros, shardings)`` for the pipeline wire's error-feedback
    residual, laid out exactly as the schedule computes it: per-DEVICE
    state, so each data replica carries the error its own wire dropped.

    - ``blocks``: ``(D, v, P, K, feature...)`` — the chunk view with a
      leading data axis (sharded ``P(data, None, pipe, None, tp...)``);
    - ``head``: ``(D, ...)`` per head-params leaf (sharded ``P(data)``).

    NOT params-shaped (unlike the GSPMD comms residual): the wire error is
    device-local by construction.  Like every comms residual it is never
    checkpointed — resume/rollback restart it at zero.

    ``state_layout``: the resident layout ``params`` arrives in — the
    shapes here derive from the canonical depth, so a resident-chunked
    trunk is canonicalized first (callers pass host/abstract trees; the
    reshape is free).  The residual itself stays chunk-laid either way.
    """
    import numpy as np

    d_size = int(mesh.shape[data_axis])
    p_size = int(mesh.shape[pipe_axis])
    blocks = params[blocks_key]
    if state_layout is not None:
        blocks = state_layout.canonicalized(blocks)
    depth = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    k = depth // (virtual * p_size)
    head_params = {kk: vv for kk, vv in params.items() if kk != blocks_key}

    def b_zero(leaf):
        return np.zeros(
            (d_size, virtual, p_size, k, *leaf.shape[1:]), np.float32
        )

    host = {
        "blocks": jax.tree_util.tree_map(b_zero, blocks),
        "head": jax.tree_util.tree_map(
            lambda l: np.zeros((d_size, *l.shape), np.float32), head_params
        ),
    }
    chunk_specs = _chunk_view_specs(blocks, pipe_axis=pipe_axis, tp_axis=tp_axis)
    shardings = {
        "blocks": jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, P(data_axis, *tuple(spec))),
            chunk_specs,
        ),
        "head": jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P(data_axis)), head_params
        ),
    }
    return host, shardings


def make_interleaved_fwd_bwd(
    model,
    mesh: Mesh,
    *,
    num_microbatches: int,
    virtual: int = 1,
    pipe_axis: str = MODEL_AXIS,
    data_axis: str | None = DATA_AXIS,
    tp_axis: str | None = None,
    grad_comms: str = "fp32",
    state_layout=None,
):
    """Build the (interleaved-)1F1B forward+backward for a zoo ViT.

    Returns ``fwd_bwd(params, x, labels) -> (loss, logits, grads)`` — or,
    when ``grad_comms`` compresses (``fwd_bwd.carries_residual``),
    ``fwd_bwd(params, x, labels, residual) -> (loss, logits, grads,
    new_residual)`` — a drop-in for the train step's ``value_and_grad``
    (``train/step.py`` ``fwd_bwd`` hook).  Unlike GPipe (an ``apply_fn``
    swap, backward via autodiff), the 1F1B family must own the whole
    fwd+bwd: interleaving unit ``i``'s backward with ``i+1``'s forward
    requires the loss cotangent *inside* the schedule.  Embed and head
    still run via the model's own methods on the same parameters (embed
    under outer autodiff, head inside the schedule on the last stage —
    and ONLY there, under ``lax.cond``).

    ``state_layout`` (``parallel/layouts.py``) declares the layout
    ``params["blocks"]`` ARRIVES in.  With a chunked layout the trunk is
    already the resident ``(v, P, K)`` chunk view the schedule consumes —
    no per-step relayout; gradients return in the same layout.  With
    ``None``/contiguous (the legacy baseline, and the ``v == 1`` case
    where the layouts coincide) the carried contiguous stack is re-laid
    to the chunk view at the schedule boundary (one sharding-constraint
    relayout per step — an all-to-all of the trunk params on real
    silicon; free only for ``v == 1``).
    """
    import optax

    p_size = int(mesh.shape[pipe_axis])
    d_size = int(mesh.shape.get(data_axis, 1)) if data_axis else 1
    v = int(virtual)
    if v < 1:
        raise ValueError(f"virtual stages must be >= 1, got {v}")
    resident = (
        state_layout is not None
        and getattr(state_layout, "kind", "contiguous") == "chunked"
    )
    if resident and (state_layout.virtual != v or state_layout.pipe != p_size):
        raise ValueError(
            f"state layout {state_layout.tag} does not match the schedule "
            f"(v={v}, P={p_size})"
        )
    if model.depth % (v * p_size):
        raise ValueError(
            f"model depth ({model.depth}) must divide into "
            f"{v} virtual x {p_size} pipeline stages"
        )
    if v > 1 and num_microbatches % p_size:
        raise _microbatch_error(
            0, num_microbatches, d_size, pipe=p_size
        )
    stage = vit_stage_fn(model, tp_axis=tp_axis)
    k = model.depth // (v * p_size)

    def head_loss(head_params, y, lbl):
        logits = model.apply({"params": head_params}, y, method="head_out")
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, lbl)
        return ce.sum(), logits

    compressing = grad_comms not in (None, "fp32")

    def fwd_bwd(params, x, labels, residual=None):
        b = labels.shape[0]
        mth = num_microbatches
        if b % (mth * max(1, d_size)):
            raise _microbatch_error(b, mth, d_size, pipe=p_size)
        scale = 1.0 / b

        def scaled_head_loss(hp, y, lbl):
            loss_sum, logits = head_loss(hp, y, lbl)
            return loss_sum * scale, logits

        tokens, embed_vjp = jax.vjp(
            lambda p: model.apply({"params": p}, x, method="embed"), params
        )
        mb = tokens.reshape(mth, b // mth, *tokens.shape[1:])
        lb = labels.reshape(mth, b // mth)
        # everything but the trunk: head_out only touches ln_head/head, but
        # ViT.setup eagerly binds pos_emb via self.param, so the in-schedule
        # apply needs the (tiny) embed params present too; their gradients
        # from this vjp are zero and discarded (embed grads come from the
        # outer embed_vjp)
        head_params = {kk: vv for kk, vv in params.items() if kk != "blocks"}

        if resident:
            # schedule-native resident layout: the carried trunk IS the
            # (v, P, K) chunk view — nothing to re-lay, nothing to
            # constrain; the specs name the layout the state already has
            chunked = params["blocks"]
            chunk_specs = state_layout.specs(params["blocks"])
        else:
            # the (v, P, K) chunk view: chunk c = i*P + s at [i, s] —
            # layer order i-major means the reshape IS the chunk
            # assignment; the sharding constraint is the (documented)
            # relayout for v > 1
            chunked = jax.tree_util.tree_map(
                lambda l: l.reshape(v, p_size, k, *l.shape[1:]),
                params["blocks"],
            )
            chunk_specs = _chunk_view_specs(
                params["blocks"], pipe_axis=pipe_axis, tp_axis=tp_axis
            )
        head_specs = jax.tree_util.tree_map(lambda _: P(), head_params)
        mb_spec = P(None, data_axis, *([None] * (mb.ndim - 2)))
        lb_spec = P(None, data_axis)
        logits_spec = P(None, data_axis, None)
        res_specs = None
        if residual is not None:
            res_specs = {
                "blocks": jax.tree_util.tree_map(
                    lambda spec: P(data_axis, *tuple(spec)), chunk_specs
                ),
                "head": jax.tree_util.tree_map(
                    lambda _: P(data_axis), head_params
                ),
            }

        def body(chunk_params, hp, mbx, lbx, res):
            if res is not None:
                # shed the shard axes: blocks (1, v, 1, K, ...) ->
                # (v, K, ...); head (1, ...) -> (...)
                res = {
                    "blocks": jax.tree_util.tree_map(
                        lambda l: l.reshape(
                            l.shape[1], *l.shape[3:]
                        ),
                        res["blocks"],
                    ),
                    "head": jax.tree_util.tree_map(
                        lambda l: l.reshape(l.shape[1:]), res["head"]
                    ),
                }
            out = _interleaved_1f1b(
                stage, scaled_head_loss, chunk_params, hp, mbx, lbx, res,
                axis_name=pipe_axis, data_axis=data_axis, virtual=v,
                grad_comms=grad_comms,
            )
            loss_v, g_chunks, g_head, dtok, logits, new_res = out
            if res is not None:
                new_res = {
                    "blocks": jax.tree_util.tree_map(
                        lambda l: l.reshape(1, l.shape[0], 1, *l.shape[1:]),
                        new_res["blocks"],
                    ),
                    "head": jax.tree_util.tree_map(
                        lambda l: l.reshape(1, *l.shape), new_res["head"]
                    ),
                }
            return loss_v, g_chunks, g_head, dtok, logits, new_res

        in_specs = (chunk_specs, head_specs, mb_spec, lb_spec)
        out_specs = (P(), chunk_specs, head_specs, mb_spec, logits_spec)
        if residual is None:
            staged = shard_map(
                lambda cp, hp, mbx, lbx: body(cp, hp, mbx, lbx, None)[:5],
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            )
            loss_v, g_chunks, g_head, dtok, logits = staged(
                chunked, head_params, mb, lb
            )
            new_residual = None
        else:
            staged = shard_map(
                body,
                mesh=mesh,
                in_specs=(*in_specs, res_specs),
                out_specs=(*out_specs, res_specs),
                check_vma=False,
            )
            loss_v, g_chunks, g_head, dtok, logits, new_residual = staged(
                chunked, head_params, mb, lb, residual
            )

        dtokens = dtok.reshape(b, *tokens.shape[1:])
        grads = dict(embed_vjp(dtokens)[0])  # embed grads; zeros elsewhere
        if resident:
            # grads stay in the resident chunk layout — they already
            # match params["blocks"] leaf-for-leaf, shape-for-shape
            grads["blocks"] = g_chunks
        else:
            grads["blocks"] = jax.tree_util.tree_map(
                lambda g, p_: g.reshape(p_.shape), g_chunks, params["blocks"]
            )
        for kk in _HEAD_MODS:
            grads[kk] = g_head[kk]
        out = (loss_v, logits.reshape(b, *logits.shape[2:]), grads)
        if compressing or residual is not None:
            return (*out, new_residual)
        return out

    fwd_bwd.carries_residual = compressing
    fwd_bwd.schedule_meta = schedule_meta(
        "interleaved" if v > 1 else "1f1b", p_size, num_microbatches, v
    )
    fwd_bwd.state_layout = state_layout
    return fwd_bwd


def make_1f1b_fwd_bwd(
    model,
    mesh: Mesh,
    *,
    num_microbatches: int,
    pipe_axis: str = MODEL_AXIS,
    data_axis: str | None = DATA_AXIS,
    tp_axis: str | None = None,
    grad_comms: str = "fp32",
):
    """Plain 1F1B: the ``virtual == 1`` configuration of the interleaved
    schedule (the tick arithmetic degenerates exactly — same warmup, same
    stash depth, same per-tick one-forward-one-backward steady state)."""
    return make_interleaved_fwd_bwd(
        model, mesh,
        num_microbatches=num_microbatches, virtual=1,
        pipe_axis=pipe_axis, data_axis=data_axis, tp_axis=tp_axis,
        grad_comms=grad_comms,
    )


def pipelined_vit_apply(
    model,
    variables,
    images: jnp.ndarray,
    mesh: Mesh,
    *,
    num_microbatches: int,
    pipe_axis: str = MODEL_AXIS,
    data_axis: str | None = DATA_AXIS,
    tp_axis: str | None = None,
    state_layout=None,
) -> jnp.ndarray:
    """Forward a zoo ViT with its trunk pipelined over ``pipe_axis`` (and,
    with ``tp_axis``, tensor-parallel inside each stage).

    Embed and head run as ordinary (data-parallel) computations via the
    model's own methods on the same ``variables``; only the trunk is
    staged.  Semantically identical to ``model.apply(variables, images)``.

    ``state_layout``: the resident layout the carried trunk arrives in.
    GPipe consumes the contiguous stack, so a chunked-resident trunk
    (interleaved training) is canonicalized here — one relayout per eval
    batch, the price of keeping the TRAIN hot path relayout-free.
    """
    p_size = mesh.shape[pipe_axis]
    if model.depth % p_size:
        raise ValueError(
            f"depth {model.depth} not divisible by pipeline stages {p_size}"
        )
    tokens = model.apply(variables, images, method="embed")
    blocks = variables["params"]["blocks"]
    if state_layout is not None:
        blocks = state_layout.canonicalized(blocks)
    trunk = make_pipeline_trunk(
        mesh,
        # manual_vjp=False: GPipe's backward is OUTER autodiff through the
        # shard_map — bare psums pair with its transpose (vit_stage_fn)
        vit_stage_fn(model, tp_axis=tp_axis, manual_vjp=False),
        num_microbatches=num_microbatches,
        pipe_axis=pipe_axis,
        data_axis=data_axis,
        param_specs=pp_trunk_specs(blocks, pipe_axis=pipe_axis, tp_axis=tp_axis),
    )
    y = trunk(blocks, tokens)
    return model.apply(variables, y, method="head_out")
