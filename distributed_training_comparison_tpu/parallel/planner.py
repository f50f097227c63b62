"""Ledger-fit auto-parallel planner: pick the fastest *legal* DP×TP×PP
layout, not the widest one.

Layouts were hand-picked flags (``--model-parallel``,
``--pipeline-parallel``, ``--shard-optim``, ``--grad-comms``) even though
the PR-8 compile ledger already prices every executable: per-executable
FLOPs and peak-HBM from the ``compile`` events, measured seconds from the
``exec/*/dispatch_s`` sketches, comms bytes from the PR-10 ``comms/*``
gauges.  This module closes the loop in the spirit of AMP (PAPERS.md,
arxiv 2210.07297) — enumerate candidate layouts, predict step time and
footprint, emit the flag set — but the cost model is **fit to the
empirical ledger** instead of re-derived analytic FLOPs, and every
prediction is explainable from committed events (veScale's consistent-
semantics argument, arxiv 2509.07003): the ``plan`` event carries the fit
provenance, every candidate considered, and each one's predicted step
seconds + HBM, so ``run_report --plan`` can render prediction vs measured
after the fact.

The pipeline, end to end:

1. **Enumerate** — every ``(dp, tp, pp, virtual)`` that tiles the device
   count, crossed with ``--shard-optim`` on/off and the ``--grad-comms``
   tiers the operator already authorized (the planner never *lowers*
   numerics below the flag: ``--grad-comms fp32`` keeps every candidate
   at fp32; ``int8`` admits fp32/fp16/int8 — the operator accepted the
   int8 error-feedback semantics by passing the flag).
2. **Feasibility-filter** through the existing gates: mesh legality
   (``parallel.mesh.elastic_mesh_shape``), batch divisibility
   (``elastic.divisibility_help`` numbers ride every refusal), the
   pipeline divisibility rules (``elastic.pipeline_help`` /
   ``microbatch_help``), TP head/MLP divisibility, and — when the ledger
   knows the HBM limit (``res/hbm_limit_bytes``) — a predicted-footprint
   gate.  ``ops/vmem.py``'s static weight-footprint arithmetic marks
   which candidates keep the fused-block fast path available.
3. **Score** with the :class:`CostModel`: seconds-per-FLOP regressed from
   the ledger's ``(flops, dispatch seconds)`` points (device-kind keyed;
   falling back to ``PEAK_FLOPS_BY_DEVICE_KIND`` × an assumed MFU, then
   to a flat default, when no ledger exists), a per-dispatch overhead
   intercept, the interleaved-pipeline bubble
   ``((v+1)P-2)/(vM+(v+1)P-2)``, and a gradient-sync term priced from
   the same byte arithmetic the ``comms/*`` gauges commit.
4. **Install** — ``--parallel-plan auto`` writes the winning flag set
   into hparams at Trainer construction (one registered ``plan`` event
   records the decision); the elastic fleet re-plans at every attempt
   boundary, so a ``resize`` lands on the best legal layout rather than
   the widest, and the autopilot's ``replan`` action can drive a fresh
   plan off an HBM-ledger alert.

Predictions are planning numbers, not measurements: on captures with no
usable ledger the absolute seconds come from documented per-device-kind
planning constants, and the CPU CI container (host==device) can never
show a wire saving.  What binds is (a) the *relative* ranking under one
fit and (b) the prediction-vs-measured table ``run_report --plan``
renders from a run's own ``plan`` and ``compile`` events, which makes any
mis-prediction inspectable (``tests/test_planner.py`` pins the ranking
on synthetic ledgers; no chip run has fit the model yet — ROADMAP.md D6).
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

from ..resilience.elastic import (
    divisibility_help,
    microbatch_help,
    pipeline_help,
)
from .mesh import elastic_mesh_shape

PLAN_KIND = "plan"

# --grad-comms tiers in authorization order: the planner may pick any tier
# at or ABOVE the flag's numerics (never below — compression changes the
# training math, so it stays an operator decision; see module docstring)
GRAD_COMMS_TIERS = ("fp32", "fp16", "int8")
WIRE_BITS = {"fp32": 32, "fp16": 16, "int8": 8}

# per-chip interconnect bandwidth planning numbers (bytes/s) by jax
# device_kind prefix — the comms term's denominator when the ledger has
# nothing better.  Rough public ICI figures; the committed plan event
# records which number was used, so a bad constant is inspectable, and a
# TPU recapture can fit the real slope from multi-layout ledgers.
WIRE_BYTES_PER_S_BY_DEVICE_KIND = {
    "TPU v3": 70e9,
    "TPU v4": 100e9,
    "TPU v5 lite": 45e9,
    "TPU v5e": 45e9,
    "TPU v5p": 180e9,
    "TPU v6 lite": 90e9,
    "TPU v6e": 90e9,
}
# the CPU backend (tests, rehearsals) has no entry: a flat planning number
# so the comms term still *ranks* layouts; absolute seconds are then
# labeled fit_source="default" in the plan event.  A TPU kind with no
# entry is an error (CostModel.fit), never this default.
DEFAULT_WIRE_BYTES_PER_S = 10e9
# peak-table fallback assumes this MFU when no dispatch sketches exist
ASSUMED_MFU = 0.3
# flat compute-throughput fallback, CPU backend only (see above)
DEFAULT_FLOPS_PER_S = 5e10
# the HBM feasibility gate refuses candidates predicted past this share
# of the device limit (headroom for allocator slack + staging buffers)
HBM_GATE_FRAC = 0.9
# candidates carried verbatim in the plan event (the rest are counted):
# the event must stay well under the bus's oversize-stub bound
PLAN_EVENT_CANDIDATES = 12

# the runtime carries the trunk stack RESIDENT in the schedule's native
# layout (parallel/layouts.py), so interleaved v>1 candidates pay no
# per-step chunk relayout — predict() prices the term and zeroes it when
# this is active.  --no-pipeline-resident-layout (the legacy per-step
# relayout) flips it back per run; plan_layout reads the hparams flag.
SCHEDULE_NATIVE_STATE_LAYOUT = True


class PlanError(ValueError):
    """No feasible layout exists for this device count / batch / model.
    The message carries every gate's refusal with the actual numbers
    (``elastic.divisibility_help`` and friends), never a bare "no plan
    found"."""


# ------------------------------------------------------------- model spec


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """The static facts the planner needs about a model WITHOUT building
    it: whether the trunk can stage (pipeline) or channel-shard (tensor),
    and the divisibility constants.  ``params`` and ``step_flops`` are
    analytic planning estimates used only when no ledger exists."""

    name: str
    kind: str  # "vit" | "vit_moe" | "generic"
    depth: int = 0
    dim: int = 0
    heads: int = 0
    mlp_ratio: int = 4
    patch: int = 4
    num_experts: int = 0
    tokens: int = 0  # sequence length (vit) — the activation-comms term
    params: float = 0.0  # parameter count (planning estimate)
    fwd_flops_per_image: float = 0.0

    @property
    def can_pipeline(self) -> bool:
        # MoE trunks are refused by the staged apply paths (trainer gate)
        return self.kind == "vit"

    @property
    def can_tensor(self) -> bool:
        return self.kind in ("vit", "vit_moe")

    def tp_legal(self, tp: int) -> tuple[bool, str]:
        """Can the model axis shard ``tp`` ways?  Returns (ok, why-not)."""
        if tp == 1:
            return True, ""
        if not self.can_tensor:
            return False, (
                f"model {self.name} has no tensor-parallel trunk "
                "(the planner shards vit_* models only)"
            )
        if self.kind == "vit_moe":
            if self.num_experts % tp:
                return False, (
                    f"expert parallelism needs num_experts "
                    f"({self.num_experts}) divisible by tp={tp}"
                )
            return True, ""
        if self.heads % tp:
            return False, (
                f"tensor parallelism needs attention heads ({self.heads}) "
                f"divisible by tp={tp}"
            )
        if (self.mlp_ratio * self.dim) % tp:
            return False, (
                f"tensor parallelism needs the MLP hidden width "
                f"({self.mlp_ratio * self.dim}) divisible by tp={tp}"
            )
        return True, ""

    def step_flops(self, batch_size: int) -> float:
        """Analytic global train FLOPs per optimizer step (fwd+bwd ≈ 3×
        fwd) — the no-ledger fallback; ledger flops always win."""
        return 3.0 * self.fwd_flops_per_image * batch_size

    def param_bytes(self) -> float:
        return 4.0 * self.params  # params are stored fp32


def _vit_spec(name, depth, dim, heads, *, mlp_ratio=4, patch=4,
              num_experts=0, image_size=32) -> ModelSpec:
    tokens = (image_size // patch) ** 2
    # dense layers dominate: per block 12·d² MACs/token + attention's
    # 2·S·d; patch embed + head (tests/test_planner.py holds it to
    # benchmark/harness/flops.py on the DeiT-S configuration)
    macs_per_token = depth * ((4 + 2 * mlp_ratio) * dim * dim + 2 * tokens * dim)
    fwd = 2.0 * (tokens * (macs_per_token + patch * patch * 3 * dim) + dim * 100)
    block_params = (4 + 2 * mlp_ratio) * dim * dim
    if num_experts:
        block_params += num_experts * 2 * mlp_ratio * dim * dim
    params = depth * block_params + patch * patch * 3 * dim + dim * 100
    return ModelSpec(
        name=name, kind="vit_moe" if num_experts else "vit",
        depth=depth, dim=dim, heads=heads, mlp_ratio=mlp_ratio,
        patch=patch, num_experts=num_experts, tokens=tokens,
        params=float(params), fwd_flops_per_image=fwd,
    )


# per-image forward GFLOPs of the ResNet zoo at 32px CIFAR stem (the
# conv-MAC walk of benchmark/harness/flops.py, a multiply-accumulate as two
# operations like _vit_spec; tests/test_planner.py holds resnet18 to it) —
# scaled by (image_size/32)² below
_RESNET_FWD_GFLOPS_32PX = {
    "resnet18": 1.11, "resnet34": 2.32, "resnet50": 2.60,
    "resnet101": 5.02, "resnet152": 7.44,
}
_RESNET_PARAMS = {
    "resnet18": 11.2e6, "resnet34": 21.3e6, "resnet50": 23.6e6,
    "resnet101": 42.6e6, "resnet152": 58.2e6,
}


def model_spec(hparams, model=None) -> ModelSpec:
    """The planner's view of the configured model.  When the caller built
    the model object itself (``Trainer(hp, model=...)``), its actual
    dims win over the zoo table — the plan must constrain the model that
    will really run."""
    name = str(getattr(hparams, "model", "") or "")
    image_size = int(getattr(hparams, "image_size", 32) or 32)
    patch = int(getattr(hparams, "patch_size", 0) or 0)
    if model is not None and all(
        hasattr(model, a) for a in ("depth", "dim", "heads")
    ):
        # a caller-built model may not match the --model flag (tests'
        # nets): its own dims — and name — win
        return _vit_spec(
            name if name.startswith("vit") else type(model).__name__,
            int(model.depth), int(model.dim), int(model.heads),
            mlp_ratio=int(getattr(model, "mlp_ratio", 4)),
            patch=int(getattr(model, "patch", 4)),
            num_experts=int(getattr(model, "num_experts", 0) or 0),
            image_size=image_size,
        )
    if name == "vit_tiny":
        return _vit_spec(name, 12, 192, 3, patch=patch or 4, image_size=image_size)
    if name == "vit_small":
        return _vit_spec(name, 12, 384, 6, patch=patch or 4, image_size=image_size)
    if name == "vit_long":
        return _vit_spec(name, 8, 512, 4, patch=patch or 4,
                         image_size=image_size or 256)
    if name == "vit_moe":
        return _vit_spec(name, 8, 192, 3, num_experts=8,
                         patch=patch or 4, image_size=image_size)
    fwd = _RESNET_FWD_GFLOPS_32PX.get(name, 0.5) * 1e9 * (image_size / 32) ** 2
    return ModelSpec(
        name=name or "generic", kind="generic",
        params=float(_RESNET_PARAMS.get(name, 10e6)),
        fwd_flops_per_image=fwd,
    )


# ------------------------------------------------------------- candidates


@dataclasses.dataclass
class Candidate:
    """One layout the planner considered: the mesh axes plus the comms
    knobs, and — after scoring — the predicted step seconds / HBM."""

    data: int
    model: int
    pipe: int
    virtual: int = 1
    microbatches: int = 0  # 0 when pipe == 1
    schedule: str = "gpipe"
    shard_optim: bool = False
    grad_comms: str = "fp32"
    devices: int = 0
    predicted_step_s: float | None = None
    predicted_hbm_bytes: float | None = None
    terms: dict = dataclasses.field(default_factory=dict)
    block_fusion_eligible: bool = False

    @property
    def key(self) -> str:
        parts = [f"dp{self.data}"]
        if self.model > 1:
            parts.append(f"tp{self.model}")
        if self.pipe > 1:
            parts.append(f"pp{self.pipe}")
            if self.virtual > 1:
                parts.append(f"v{self.virtual}")
        if self.shard_optim:
            parts.append("zero")
        if self.grad_comms != "fp32":
            parts.append(self.grad_comms)
        return "x".join(parts)

    def layout(self) -> dict:
        """The comparison key ``run_report --plan`` checks against the
        attempt's ``run_start`` payload (its ``mesh`` + comms flags +
        resident state layout)."""
        from .layouts import layout_tag_for

        return {
            "data": self.data, "model": self.model, "pipe": self.pipe,
            "shard_optim": bool(self.shard_optim),
            "grad_comms": self.grad_comms,
            "state_layout": layout_tag_for(
                self.schedule if self.pipe > 1 else None,
                virtual=self.virtual, pipe=self.pipe,
            ),
        }

    def flags(self) -> list[str]:
        """The winning layout as the CLI flag set it installs."""
        out = [
            "--model-parallel", str(self.model),
            "--pipeline-parallel", str(self.pipe),
            "--grad-comms", self.grad_comms,
            "--shard-optim" if self.shard_optim else "--no-shard-optim",
        ]
        if self.pipe > 1:
            out += [
                "--pipeline-schedule", self.schedule,
                "--pipeline-microbatches", str(self.microbatches),
            ]
            if self.virtual > 1:
                out += ["--pipeline-virtual-stages", str(self.virtual)]
        return out

    def describe(self) -> dict:
        d = {
            "key": self.key, **self.layout(),
            "virtual": self.virtual, "microbatches": self.microbatches,
            "schedule": self.schedule if self.pipe > 1 else None,
            "devices": self.devices,
            "predicted_step_s": self.predicted_step_s,
            "predicted_hbm_bytes": self.predicted_hbm_bytes,
        }
        if self.terms:
            d["terms"] = self.terms
        return d


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def enumerate_candidates(
    devices: int,
    spec: ModelSpec,
    *,
    batch_size: int,
    grad_accum: int = 1,
    grad_comms_cap: str = "fp32",
    microbatches: int = 0,
    shard_optim_only: bool | None = None,
) -> tuple[list[Candidate], list[str]]:
    """Every feasible ``(dp, tp, pp, v) × shard_optim × grad_comms``
    layout for ``devices`` chips, plus the refusal reasons for the shapes
    that were ruled out (each carries the actual numbers — the nearest
    legal batch/width/microbatch counts, via ``elastic``'s help text).

    ``grad_comms_cap`` bounds the wire tiers (the operator's flag is the
    authorization ceiling; see module docstring).  ``shard_optim_only``
    pins the ZeRO dimension instead of enumerating both (tests)."""
    unit = max(1, int(grad_accum))
    refusals: list[str] = []
    layouts: list[tuple[int, int, int, int, int]] = []
    seen_batch_refusal = set()
    for tp in _divisors(devices):
        ok, why = spec.tp_legal(tp)
        if not ok:
            refusals.append(f"tp={tp}: {why}")
            continue
        for pp in _divisors(devices // tp):
            if pp > 1 and not spec.can_pipeline:
                refusals.append(
                    f"pp={pp}: model {spec.name} has no stageable trunk "
                    "(pipeline parallelism needs a dense vit_* model)"
                )
                continue
            shape = elastic_mesh_shape(devices, tp, pp)
            if shape is None:
                continue
            dp = shape[0]
            if batch_size % (dp * unit):
                if dp not in seen_batch_refusal:
                    seen_batch_refusal.add(dp)
                    refusals.append(
                        f"dp={dp}: " + divisibility_help(batch_size, dp, unit)
                    )
                continue
            for v in (1, 2) if pp > 1 else (1,):
                if pp > 1 and spec.depth % (pp * v):
                    refusals.append(
                        f"pp={pp} v={v}: " + pipeline_help(spec.depth, pp, v)
                    )
                    continue
                micro = int(microbatches) or 4 * pp
                if pp > 1:
                    if v > 1 and micro % pp:
                        refusals.append(
                            f"pp={pp} v={v}: "
                            + microbatch_help(
                                batch_size // unit, micro, dp, pipe=pp
                            )
                        )
                        continue
                    per_update = batch_size // unit
                    if micro and per_update % (micro * dp):
                        refusals.append(
                            f"pp={pp} micro={micro}: "
                            + microbatch_help(
                                per_update, micro, dp,
                                pipe=pp if v > 1 else None,
                            )
                        )
                        continue
                layouts.append((dp, tp, pp, v, micro if pp > 1 else 0))
    tiers = GRAD_COMMS_TIERS[: GRAD_COMMS_TIERS.index(
        grad_comms_cap if grad_comms_cap in GRAD_COMMS_TIERS else "fp32"
    ) + 1]
    out: list[Candidate] = []
    for dp, tp, pp, v, micro in layouts:
        zero_dims = (
            (bool(shard_optim_only),)
            if shard_optim_only is not None
            else ((False, True) if dp > 1 else (False,))
        )
        for zero in zero_dims:
            for gc in tiers:
                if gc != "fp32" and dp == 1:
                    continue  # nothing crosses the wire at dp=1
                out.append(
                    Candidate(
                        data=dp, model=tp, pipe=pp, virtual=v,
                        microbatches=micro,
                        schedule=(
                            "interleaved" if v > 1
                            else ("1f1b" if pp > 1 else "gpipe")
                        ),
                        shard_optim=zero, grad_comms=gc, devices=devices,
                    )
                )
    return out, refusals


# -------------------------------------------------------------- the ledger


@dataclasses.dataclass
class LedgerFit:
    """What the committed event stream says about the captured run: the
    global step FLOPs, the captured layout, the per-device footprint
    split, and the HBM limit — everything a candidate prediction scales
    from.  ``None`` fields mean the stream didn't carry that plane."""

    device_kind: str | None = None
    devices: int = 0
    captured_mesh: dict | None = None
    batch_size: int = 0
    step_flops_total: float | None = None  # across all devices
    measured_step_s: float | None = None
    arg_bytes: float | None = None   # captured train exec, per device
    temp_bytes: float | None = None
    peak_bytes: float | None = None
    hbm_limit_bytes: float | None = None
    points: list = dataclasses.field(default_factory=list)  # (flops, secs)


_K_SUFFIX = re.compile(r"@k(\d+)$")
_TRAIN_EXEC_PREFIXES = ("device_chunk_runner", "chunk_runner")


def _payload(ev: dict) -> dict:
    p = ev.get("payload")
    return p if isinstance(p, dict) else {}


def fit_ledger(events) -> LedgerFit:
    """Fold a merged event stream into the :class:`LedgerFit` — compile
    events (flops, memory, device identity), ``run_start`` (captured
    layout), the merged ``exec/*/dispatch_s`` sketches (measured
    seconds), and the ``res/hbm_limit_bytes`` gauge."""
    from ..obs.metrics import merge_metric_events

    fit = LedgerFit()
    compiles: dict[str, tuple] = {}  # fingerprint -> (payload, run key)
    run_starts: dict[tuple, dict] = {}  # (run_id, attempt) -> payload
    metric_events = []
    for ev in events or ():
        if not isinstance(ev, dict) or int(ev.get("process_index", 0) or 0):
            continue
        kind = ev.get("kind")
        key = (ev.get("run_id"), int(ev.get("attempt", 0) or 0))
        p = _payload(ev)
        if kind == "metrics":
            metric_events.append(ev)
        elif kind == "compile":
            compiles[str(p.get("fingerprint", len(compiles)))] = (p, key)
        elif kind == "run_start":
            run_starts[key] = p
            # the stream-order fallback when the chosen train executable
            # has no matching run_start (partial captures)
            fit.captured_mesh = p.get("mesh") or fit.captured_mesh
            fit.batch_size = int(p.get("batch_size", 0) or 0) or fit.batch_size
    merged = merge_metric_events(metric_events)
    limit = (merged.get("res/hbm_limit_bytes") or {}).get("value")
    if limit:
        fit.hbm_limit_bytes = float(limit)
    best_train = None
    best_train_key = None
    for p, run_key in compiles.values():
        name = str(p.get("name", ""))
        flops = p.get("flops")
        fit.device_kind = fit.device_kind or p.get("device_kind")
        sketch = merged.get(f"exec/{name}:{str(p.get('fingerprint', ''))[:8]}/dispatch_s")
        n = int((sketch or {}).get("count", 0) or 0)
        if flops and n:
            # one (per-device flops, seconds) point per executable with
            # measured dispatches — the cost-model regression's input.
            # Compile-event flops follow run_report's MFU convention
            # (whole-program, across the executable's devices), so the
            # per-device rate divides by the event's device count.
            fit.points.append(
                (
                    float(flops) / max(1, int(p.get("devices") or 1)),
                    float(sketch["sum"]) / n,
                )
            )
        if name.startswith(_TRAIN_EXEC_PREFIXES) and flops:
            # >= : ties (the same program recompiled by a later attempt)
            # keep the LATEST attempt's executable — its mesh below
            if best_train is None or float(flops) >= float(
                best_train.get("flops") or 0
            ):
                best_train, best_train_key = p, run_key
    if best_train is not None:
        p = best_train
        # the footprint split must come from the SAME attempt as the
        # chosen executable: a resized fleet's later run_start can carry
        # a different mesh than the attempt that compiled best_train,
        # and predict()'s activation-HBM scaling divides the captured
        # batch by the captured data axis — mixing attempts would
        # mis-scale every candidate's predicted HBM
        rs = run_starts.get(best_train_key)
        if rs is not None:
            fit.captured_mesh = rs.get("mesh") or fit.captured_mesh
            fit.batch_size = (
                int(rs.get("batch_size", 0) or 0) or fit.batch_size
            )
        m = _K_SUFFIX.search(str(p.get("name", "")))
        k = int(m.group(1)) if m else 1
        fit.devices = int(p.get("devices") or 1)
        # compile-event flops are whole-program (run_report's MFU
        # convention) per dispatch of K steps → global flops per step
        fit.step_flops_total = float(p["flops"]) / max(1, k)
        for field, key in (
            ("arg_bytes", "argument_bytes"),
            ("temp_bytes", "temp_bytes"),
            ("peak_bytes", "peak_bytes"),
        ):
            if p.get(key) is not None:
                setattr(fit, field, float(p[key]))
        name = str(p.get("name", ""))
        sketch = merged.get(
            f"exec/{name}:{str(p.get('fingerprint', ''))[:8]}/dispatch_s"
        )
        n = int((sketch or {}).get("count", 0) or 0)
        if n:
            fit.measured_step_s = float(sketch["sum"]) / n / max(1, k)
    return fit


def load_ledger_events(ckpt_root) -> list[dict]:
    """Every ``events*.jsonl`` under a checkpoint root (the root's own
    files plus every version dir's), time-ordered — the planner's view of
    the runs that came before it."""
    from ..obs import load_events

    if not ckpt_root:
        return []
    root = Path(ckpt_root)
    if not root.exists():
        return []
    files = sorted(root.glob("events*.jsonl")) + sorted(
        root.glob("version-*/events*.jsonl")
    )
    events: list[dict] = []
    for f in files:
        events.extend(load_events(f))
    events.sort(key=lambda ev: ev.get("t_wall", 0.0) or 0.0)
    return events


# ------------------------------------------------------------- cost model


@dataclasses.dataclass
class CostModel:
    """``step_s = secs_per_flop × per-device FLOPs + overhead_s`` plus a
    ``bytes / wire_bytes_per_s`` comms term.  ``source`` says where the
    numbers came from — ``ledger-fit`` (regressed from dispatch
    sketches), ``peak-table`` (``PEAK_FLOPS_BY_DEVICE_KIND`` × assumed
    MFU), or ``default`` — so every plan event is explainable."""

    secs_per_flop: float
    overhead_s: float = 0.0
    wire_bytes_per_s: float = DEFAULT_WIRE_BYTES_PER_S
    device_kind: str | None = None
    source: str = "default"
    n_points: int = 0

    @classmethod
    def fit(cls, ledger: LedgerFit | None, device_kind: str | None = None
            ) -> "CostModel":
        from ..obs.compilation import peak_flops_for

        kind = device_kind or (ledger.device_kind if ledger else None)
        # every jax TPU device_kind starts "TPU"; pricing one the tables do
        # not know at the CPU's made-up defaults would rank layouts for a
        # chip nobody measured
        on_tpu = bool(kind) and str(kind).startswith("TPU")
        wire = next(
            (
                bw for prefix, bw in WIRE_BYTES_PER_S_BY_DEVICE_KIND.items()
                if kind and str(kind).startswith(prefix)
            ),
            None,
        )
        if wire is None:
            if on_tpu:
                raise PlanError(
                    f"device kind {kind!r} has no entry in "
                    "WIRE_BYTES_PER_S_BY_DEVICE_KIND: add its interconnect "
                    "bandwidth (with its source) before planning for it"
                )
            wire = DEFAULT_WIRE_BYTES_PER_S
        points = list(ledger.points) if ledger else []
        if len(points) >= 2:
            # least squares t = a·f + b, clamped non-negative: a is the
            # achieved seconds-per-flop, b the fixed dispatch overhead
            n = len(points)
            sf = sum(f for f, _ in points)
            st = sum(t for _, t in points)
            sff = sum(f * f for f, _ in points)
            sft = sum(f * t for f, t in points)
            den = n * sff - sf * sf
            if den > 0:
                a = (n * sft - sf * st) / den
                b = (st - a * sf) / n
            else:
                a, b = st / sf if sf else 0.0, 0.0
            if a <= 0:  # degenerate fit (all points one flops value)
                f, t = max(points)
                a, b = t / f, 0.0
            return cls(
                secs_per_flop=a, overhead_s=max(0.0, b),
                wire_bytes_per_s=wire, device_kind=kind,
                source="ledger-fit", n_points=n,
            )
        if len(points) == 1:
            f, t = points[0]
            return cls(
                secs_per_flop=t / f if f else 1.0 / DEFAULT_FLOPS_PER_S,
                wire_bytes_per_s=wire, device_kind=kind,
                source="ledger-fit", n_points=1,
            )
        peak = peak_flops_for(kind)
        if peak:
            return cls(
                secs_per_flop=1.0 / (peak * ASSUMED_MFU),
                wire_bytes_per_s=wire, device_kind=kind, source="peak-table",
            )
        if on_tpu:
            raise PlanError(
                f"device kind {kind!r} has no entry in obs.compilation."
                "PEAK_FLOPS_BY_DEVICE_KIND and the ledger holds no "
                "dispatch sketch to fit: add its peak (with its source) "
                "before planning for it"
            )
        return cls(
            secs_per_flop=1.0 / DEFAULT_FLOPS_PER_S,
            wire_bytes_per_s=wire, device_kind=kind, source="default",
        )

    def describe(self) -> dict:
        return {
            "secs_per_flop": self.secs_per_flop,
            "overhead_s": self.overhead_s,
            "wire_bytes_per_s": self.wire_bytes_per_s,
            "device_kind": self.device_kind,
            "source": self.source,
            "n_points": self.n_points,
        }


def bubble_fraction(pipe: int, micro: int, virtual: int = 1) -> float:
    """The interleaved-1F1B warmup/cooldown bubble
    ``((v+1)P-2)/(vM+(v+1)P-2)`` — v=1 degenerates to the plain
    ``(P-1)/(M+P-1)``-family form the schedules measure."""
    if pipe <= 1 or micro <= 0:
        return 0.0
    v = max(1, virtual)
    num = (v + 1) * pipe - 2
    return num / (v * micro + num)


def predict(
    cand: Candidate,
    cost: CostModel,
    spec: ModelSpec,
    *,
    batch_size: int,
    ledger: LedgerFit | None = None,
    native_layout: bool = SCHEDULE_NATIVE_STATE_LAYOUT,
) -> Candidate:
    """Fill in the candidate's predicted step seconds / HBM from the cost
    model.  Every term lands in ``cand.terms`` so the plan event (and
    ``run_report --plan``) can show WHY a layout won.

    ``native_layout``: whether the run carries the trunk resident in the
    schedule's layout (``parallel/layouts.py``).  When False (the legacy
    per-step relayout) interleaved v>1 candidates pay term (4) below —
    without it they were silently under-priced relative to measured step
    seconds."""
    # --- compute: global step flops / devices, ledger flops preferred.
    # The scale-from-ledger step assumes the same global batch; callers
    # that change the batch re-fit.
    if ledger is not None and ledger.step_flops_total:
        step_flops = ledger.step_flops_total
        flops_src = "ledger"
    else:
        step_flops = spec.step_flops(batch_size)
        flops_src = "analytic"
    per_dev = step_flops / max(1, cand.devices)
    compute_s = cost.secs_per_flop * per_dev + cost.overhead_s
    bubble = bubble_fraction(cand.pipe, cand.microbatches, cand.virtual)
    if bubble:
        compute_s = compute_s / (1.0 - bubble)
    # --- comms, three first-order terms priced at the wire bandwidth:
    # (1) the gradient sync: each (tp, pp) rank owns 1/(tp·pp) of the
    #     gradients and ring-all-reduces its shard across dp replicas —
    #     2(dp-1)/dp of the wire payload, whose width is the grad_comms
    #     tier (the same arithmetic the comms/grad_sync_bytes gauge
    #     commits; --shard-optim's reduce-scatter + all-gather moves the
    #     same volume);
    # (2) TP activation sync: the Megatron f/g pair is 2 all-reduces per
    #     block (attention out + MLP down) of a per-device activation
    #     (batch/dp × tokens × dim fp32), forward + backward ≈ 2×;
    # (3) PP activation handoff: one activation tensor per stage
    #     boundary per direction, (pipe-1)/pipe of the per-device batch's
    #     activation bytes (the per-tick ppermute is one ICI hop).
    # Without (2)/(3) TP would strictly dominate DP — halving the grad
    # sync while its own traffic went unpriced.
    grad_bytes = spec.param_bytes() * WIRE_BITS[cand.grad_comms] / 32.0
    sync_bytes = (
        2.0 * (cand.data - 1) / cand.data * grad_bytes
        / (cand.model * cand.pipe)
        if cand.data > 1
        else 0.0
    )
    act_bytes = (
        (batch_size / cand.data) * spec.tokens * spec.dim * 4.0
        if spec.tokens and spec.dim
        else 0.0
    )
    tp_bytes = (
        2.0 * 2.0 * spec.depth * act_bytes
        * 2.0 * (cand.model - 1) / cand.model
        if cand.model > 1 and act_bytes
        else 0.0
    )
    pp_bytes = (
        2.0 * act_bytes * (cand.pipe - 1) / cand.pipe
        if cand.pipe > 1 and act_bytes
        else 0.0
    )
    # (4) the per-step chunk relayout of the LEGACY interleaved path: the
    #     sharding-constraint reshape to the (v, P, K) chunk view moves
    #     every trunk layer whose stage assignment differs between the
    #     contiguous and round-robin-chunk layouts — a (1 - 1/v) fraction
    #     of the (TP-sharded) trunk params, each way (params in, grads
    #     back), every step.  Zero under the schedule-native resident
    #     layout (the relayout happens once at construction/restore) and
    #     for v=1, where the two layouts coincide.  The term is always
    #     recorded so the plan event shows what the resident layout saved.
    relayout_bytes = (
        2.0 * (1.0 - 1.0 / cand.virtual) * spec.param_bytes() / cand.model
        if cand.pipe > 1 and cand.virtual > 1
        else 0.0
    )
    relayout_s = (
        0.0 if native_layout else relayout_bytes / cost.wire_bytes_per_s
    )
    comms_s = (
        (sync_bytes + tp_bytes + pp_bytes) / cost.wire_bytes_per_s
        + relayout_s
    )
    cand.predicted_step_s = compute_s + comms_s
    cand.terms = {
        "compute_s": compute_s,
        "bubble_frac": bubble,
        "comms_s": comms_s,
        "sync_bytes": sync_bytes,
        "tp_act_bytes": tp_bytes,
        "pp_act_bytes": pp_bytes,
        "relayout_bytes": relayout_bytes,
        "relayout_s": relayout_s,
        "native_layout": bool(native_layout),
        "flops_source": flops_src,
        "per_device_flops": per_dev,
    }
    # --- HBM: params + optimizer state shard over (tp·pp) — and over dp
    # too for the optimizer under ZeRO; the activation/temp term scales
    # from the captured ledger by per-device batch when available.  The
    # error-feedback residual of a compressed wire is a params-shaped
    # fp32 carry.
    model_cells = cand.model * cand.pipe
    p_bytes = spec.param_bytes() / model_cells
    opt_bytes = spec.param_bytes() / model_cells  # SGD momentum: 1× fp32
    if cand.shard_optim:
        opt_bytes /= cand.data
    resid_bytes = p_bytes if cand.grad_comms != "fp32" else 0.0
    hbm = p_bytes + opt_bytes + resid_bytes
    if ledger is not None and ledger.temp_bytes and ledger.captured_mesh:
        cap_dp = int(ledger.captured_mesh.get("data", 1) or 1)
        cap_per_dev_batch = (ledger.batch_size or batch_size) / cap_dp
        per_dev_batch = batch_size / cand.data
        if cap_per_dev_batch > 0:
            hbm += ledger.temp_bytes * (per_dev_batch / cap_per_dev_batch)
    cand.predicted_hbm_bytes = hbm
    # fused-block availability: tensor/pipeline sharding turns the fused
    # Pallas block off; otherwise the static VMEM weight gate decides
    # (ops/vmem.py — the same arithmetic the auto gate runs)
    if spec.kind == "vit" and model_cells == 1:
        from ..ops.vmem import fits_weight_budget, fused_block_weight_bytes
        import jax.numpy as jnp

        cand.block_fusion_eligible = fits_weight_budget(
            fused_block_weight_bytes(spec.dim, spec.mlp_ratio, jnp.bfloat16)
        )
    return cand


# ------------------------------------------------------------------ plans


@dataclasses.dataclass
class Plan:
    """One planning decision: the winner, everything considered, and the
    provenance that makes the prediction explainable."""

    chosen: Candidate
    candidates: list[Candidate]
    refusals: list[str]
    cost: CostModel
    ledger: LedgerFit | None
    devices: int
    batch_size: int
    spec_name: str

    @property
    def predicted_step_s(self) -> float:
        return float(self.chosen.predicted_step_s or 0.0)

    def payload(self, *, installed: bool, reason: str = "construction",
                attempt: int | None = None) -> dict:
        """The registered ``plan`` event body."""
        ranked = sorted(
            self.candidates, key=lambda c: (c.predicted_step_s or 0.0, c.key)
        )
        body = {
            "chosen": self.chosen.describe(),
            "layout": self.chosen.layout(),
            "flags": self.chosen.flags(),
            "installed": bool(installed),
            "reason": reason,
            "devices": self.devices,
            "batch_size": self.batch_size,
            "model": self.spec_name,
            "predicted_step_s": self.chosen.predicted_step_s,
            "predicted_hbm_bytes": self.chosen.predicted_hbm_bytes,
            "candidates": [c.describe() for c in ranked[:PLAN_EVENT_CANDIDATES]],
            "candidates_considered": len(self.candidates),
            "candidates_elided": max(
                0, len(self.candidates) - PLAN_EVENT_CANDIDATES
            ),
            "refused": len(self.refusals),
            "refusals": self.refusals[:8],
            "fit": self.cost.describe(),
        }
        if attempt is not None:
            body["attempt"] = int(attempt)
        if self.ledger is not None and self.ledger.step_flops_total:
            body["ledger"] = {
                "step_flops_total": self.ledger.step_flops_total,
                "measured_step_s": self.ledger.measured_step_s,
                "captured_mesh": self.ledger.captured_mesh,
                "hbm_limit_bytes": self.ledger.hbm_limit_bytes,
            }
        return body


def plan_layout(
    hparams,
    *,
    devices: int | None = None,
    device_kind: str | None = None,
    events=None,
    ledger: LedgerFit | None = None,
    model=None,
    spec: ModelSpec | None = None,
) -> Plan:
    """The whole pipeline: enumerate → feasibility-filter → fit → score →
    choose.  Raises :class:`PlanError` (with every gate's numbers) when
    nothing survives the filter.

    ``devices`` defaults to the runtime's (``--num-devices`` or all);
    ``events`` is the ledger stream (``load_ledger_events``) — absent or
    empty falls back to the documented analytic/peak-table estimates.
    ``ledger`` is an already-fit :class:`LedgerFit` and wins over
    ``events`` (the fleet supervisor folds the event history ONCE per
    boundary, not once per candidate world)."""
    if devices is None:
        import jax

        devices = int(getattr(hparams, "num_devices", 0) or 0) or jax.device_count()
    if device_kind is None:
        try:
            import jax

            device_kind = jax.devices()[0].device_kind
        except Exception:
            device_kind = None
    spec = spec or model_spec(hparams, model=model)
    batch_size = int(getattr(hparams, "batch_size", 0) or 0)
    grad_accum = int(getattr(hparams, "grad_accum", 1) or 1)
    if ledger is None:
        ledger = fit_ledger(events) if events else None
    if ledger is not None and ledger.batch_size and (
        ledger.batch_size != batch_size
    ):
        # a ledger captured at a different global batch scales neither the
        # flops nor the activation bytes honestly — fall back to analytic
        ledger = None
    cost = CostModel.fit(ledger, device_kind=device_kind)
    cands, refusals = enumerate_candidates(
        devices, spec,
        batch_size=batch_size, grad_accum=grad_accum,
        grad_comms_cap=str(getattr(hparams, "grad_comms", "fp32") or "fp32"),
        microbatches=int(getattr(hparams, "pipeline_microbatches", 0) or 0),
    )
    if not cands:
        raise PlanError(
            f"no feasible DP×TP×PP layout for {devices} device(s), batch "
            f"{batch_size}, model {spec.name}: "
            + ("; ".join(refusals) if refusals else divisibility_help(
                batch_size, devices, grad_accum
            ))
        )
    native_layout = bool(
        getattr(hparams, "pipeline_resident_layout", SCHEDULE_NATIVE_STATE_LAYOUT)
    )
    scored = [
        predict(
            c, cost, spec, batch_size=batch_size, ledger=ledger,
            native_layout=native_layout,
        )
        for c in cands
    ]
    # the HBM feasibility gate, when the ledger knows the limit
    limit = ledger.hbm_limit_bytes if ledger is not None else None
    if limit:
        fitting = [
            c for c in scored
            if (c.predicted_hbm_bytes or 0) <= HBM_GATE_FRAC * limit
        ]
        for c in scored:
            if c not in fitting:
                refusals.append(
                    f"{c.key}: predicted HBM "
                    f"{int(c.predicted_hbm_bytes or 0)} B exceeds "
                    f"{HBM_GATE_FRAC:.0%} of the {int(limit)} B device limit"
                )
        if not fitting:
            raise PlanError(
                f"every feasible layout's predicted HBM exceeds "
                f"{HBM_GATE_FRAC:.0%} of the {int(limit)} B device limit: "
                + "; ".join(refusals[-4:])
            )
        scored = fitting
    # deterministic choice: fastest predicted step; ties break toward the
    # SIMPLEST layout (pure DP, no ZeRO, fp32 wire) so an uninformative
    # fit never installs needless machinery
    def rank(c: Candidate):
        return (
            round(float(c.predicted_step_s or 0.0), 12),
            c.model * c.pipe,            # fewer sharded axes first
            c.pipe, c.model, c.virtual,
            int(c.shard_optim),
            GRAD_COMMS_TIERS.index(c.grad_comms),
        )

    scored.sort(key=rank)
    return Plan(
        chosen=scored[0], candidates=scored, refusals=refusals,
        cost=cost, ledger=ledger, devices=devices,
        batch_size=batch_size, spec_name=spec.name,
    )


def install_plan(plan: Plan, hparams) -> dict:
    """Write the winning layout into hparams (BEFORE the Trainer builds
    its mesh/model/comms) and return the fields changed — the ``auto``
    half of ``--parallel-plan``."""
    c = plan.chosen
    changed: dict = {}

    def set_field(name, value):
        if getattr(hparams, name, None) != value:
            changed[name] = {"from": getattr(hparams, name, None), "to": value}
        setattr(hparams, name, value)

    set_field("model_parallel", c.model)
    set_field("pipeline_parallel", c.pipe)
    set_field("shard_optim", bool(c.shard_optim))
    set_field("grad_comms", c.grad_comms)
    # the planner owns the whole layout: every candidate is priced as the
    # tensor-compose (DP×TP×PP) family, so a caller's legacy
    # --parallel-style pipeline/sequence* must not survive installation —
    # style "pipeline" with the installed model_parallel would silently
    # run the legacy single-axis pipeline the cost model never priced
    set_field("parallel_style", "tensor")
    if c.pipe > 1:
        set_field("pipeline_schedule", c.schedule)
        set_field("pipeline_microbatches", c.microbatches)
        set_field("pipeline_virtual_stages", c.virtual)
        if c.virtual > 1:
            # thread the chosen resident layout: a replanned resize onto
            # an interleaved winner lands with the chunk view resident
            # (the layout the candidate was priced at — its relayout term
            # was zeroed on this assumption)
            set_field("pipeline_resident_layout", True)
    return changed


def format_plan(plan: Plan, *, top: int = 6) -> str:
    """Human-readable decision table (``--parallel-plan dump``, and the
    Trainer's log line)."""
    lines = [
        f"auto-parallel plan: {plan.devices} device(s), batch "
        f"{plan.batch_size}, model {plan.spec_name} "
        f"(fit: {plan.cost.source}"
        + (f", {plan.cost.n_points} ledger point(s)" if plan.cost.n_points else "")
        + ")",
        f"{'layout':<22} {'pred step_s':>12} {'pred HBM':>12} "
        f"{'bubble':>7} {'comms_s':>10}",
    ]
    ranked = sorted(
        plan.candidates, key=lambda c: (c.predicted_step_s or 0.0, c.key)
    )
    for c in ranked[:top]:
        mark = " <- chosen" if c is plan.chosen else ""
        hbm = (
            f"{c.predicted_hbm_bytes / 2**20:.1f}MB"
            if c.predicted_hbm_bytes
            else "-"
        )
        lines.append(
            f"{c.key:<22} {c.predicted_step_s or 0:>12.6f} {hbm:>12} "
            f"{c.terms.get('bubble_frac', 0):>7.3f} "
            f"{c.terms.get('comms_s', 0):>10.6f}{mark}"
        )
    if len(ranked) > top:
        lines.append(f"  (+{len(ranked) - top} more candidate(s))")
    if plan.refusals:
        lines.append(f"  refused {len(plan.refusals)} shape(s); first: "
                     f"{plan.refusals[0]}")
    return "\n".join(lines)


# ---------------------------------------------- per-host staging depth


def hbm_free_bytes(device=None) -> int | None:
    """Free HBM on this host's (first) device via the same
    ``_compat.device_memory_stats`` probe the resource sampler uses —
    ``None`` on backends that expose no stats (the CPU CI)."""
    from .._compat import device_memory_stats

    try:
        import jax

        dev = device if device is not None else jax.local_devices()[0]
    except Exception:
        return None
    stats = device_memory_stats(dev)
    if not stats:
        return None
    limit = stats.get("bytes_limit")
    used = stats.get("bytes_in_use")
    if not limit:
        return None
    return max(0, int(limit) - int(used or 0))


def auto_staging_depth(
    chunk_bytes: float,
    free_bytes: int | None = None,
    *,
    default: int = 2,
    cap: int = 8,
    frac: float = 0.25,
) -> int:
    """``--device-prefetch auto``: staged chunks sized from THIS host's
    free HBM headroom instead of one fleet-global constant — a straggler
    host with less headroom stages shallower locally instead of stalling
    the collective dispatch at a depth it cannot afford.  At most
    ``frac`` of the free headroom goes to staging; unknown headroom (CPU
    CI, stats API absent) keeps the documented default."""
    if free_bytes is None or chunk_bytes <= 0:
        return default
    return max(1, min(int(cap), int(frac * free_bytes // chunk_bytes)))
