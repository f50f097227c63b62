"""Serving subsystem: a routed, SLO-classed, continuously-batched
inference fleet + load-generating bench.

The train side of this repo ends at the Trainer's eval loop; this package
is the inference path the ROADMAP's "serves heavy traffic" north star
asks for, built on the same assets — the SPMD mesh/sharding layer, the
Pallas kernels, and ``train/checkpoint.py``'s files:

- ``engine.py``   — per-bucket AOT-compiled predict over any mesh layout
                    training produces (DP/TP/MoE); donates nothing, so
                    executables persist (``utils/compile_cache.py``) and
                    a cold replica warm-starts by fingerprint;
- ``batcher.py``  — the SLO-class request queue (priority + deadline +
                    class-aware shedding), continuous and bucketed
                    admission, the single-worker ``MicroBatcher``;
- ``router.py``   — the serving fleet: N health-checked replicas over
                    one shared queue, drain-on-preempt, ledger-scored
                    sizing (``plan_serve``), ``serve_route``/``replica``
                    events;
- ``loadgen.py``  — closed/open loops + diurnal ramps, flash crowds,
                    mixed tenancy;
- ``metrics.py``  — global and per-class latency series, throughput,
                    queue depth, shed counts, wired into
                    ``utils/{logging,tensorboard}`` and the obs bus.

``serve_main`` is the CLI entry behind ``--serve`` (``entry.py`` /
``src/tpu_jax/run_serve.sh``): build the replica fleet from the run's
flags and checkpoint dir, drive it with the configured load shape, and
report.
"""

from __future__ import annotations

import warnings

import jax.numpy as jnp

from .batcher import (
    DEFAULT_CLASS,
    BatcherClosed,
    ClassQueue,
    DeadlineExceeded,
    MicroBatcher,
    QueueOverflow,
    ReplicaDead,
    ServeError,
    ServeFuture,
    SLOClass,
    SLOClassError,
    parse_slo_classes,
)
from .engine import DEFAULT_BUCKETS, ServeEngine
from .loadgen import (
    closed_loop,
    diurnal_ramp,
    flash_crowd,
    fold_seed,
    mixed_tenants,
    open_loop,
    open_loop_profile,
    request_pool,
)
from .metrics import ServeMetrics, latency_summary_ms
from .router import ServeRouter, plan_serve

__all__ = [
    "ServeEngine",
    "DEFAULT_BUCKETS",
    "MicroBatcher",
    "ClassQueue",
    "ServeRouter",
    "plan_serve",
    "ServeFuture",
    "ServeError",
    "QueueOverflow",
    "DeadlineExceeded",
    "BatcherClosed",
    "ReplicaDead",
    "SLOClass",
    "SLOClassError",
    "parse_slo_classes",
    "DEFAULT_CLASS",
    "ServeMetrics",
    "latency_summary_ms",
    "closed_loop",
    "open_loop",
    "open_loop_profile",
    "diurnal_ramp",
    "flash_crowd",
    "mixed_tenants",
    "request_pool",
    "fold_seed",
    "build_engine",
    "serve_main",
]


def build_engine(
    hparams, mesh=None, monitor=None, aot_cache=None,
    arm_sentinel: bool = True,
) -> ServeEngine:
    """A ``ServeEngine`` from a parsed flag namespace (``config.py``).

    Model construction mirrors the Trainer's flag mapping (dtype from
    ``--precision``/``--amp``, ViT image/patch sizing, MoE dispatch and
    block-fusion policies) so a checkpoint trains and serves from the
    same flags.  Only the tensor parallel style serves; pipeline and
    sequence styles shard *activations through training-only apply fns*
    and have no serving form here.
    """
    style = getattr(hparams, "parallel_style", "tensor")
    mp = getattr(hparams, "model_parallel", 1)
    if mp > 1 and style != "tensor":
        raise ValueError(
            f"--serve supports the tensor parallel style only (got "
            f"--parallel-style {style} with --model-parallel {mp})"
        )
    compute = "bf16" if hparams.precision == "bf16" else "fp32"
    model_kw: dict = {
        "dtype": jnp.bfloat16 if compute == "bf16" else jnp.float32,
        "stem": getattr(hparams, "stem", "cifar"),
    }
    image_size = getattr(hparams, "image_size", 32) or 32
    if hparams.model.startswith("vit"):
        model_kw["image_size"] = image_size
        if getattr(hparams, "patch_size", 0):
            model_kw["patch"] = hparams.patch_size
        model_kw["moe_dispatch"] = getattr(hparams, "moe_dispatch", "auto")
        model_kw["block_fusion"] = getattr(hparams, "block_fusion", "auto")

    ckpt_path = getattr(hparams, "serve_ckpt", None)
    if ckpt_path is None:
        from ..train.checkpoint import find_serving_checkpoint

        found = find_serving_checkpoint(hparams.ckpt_path)
        if found is None:
            warnings.warn(
                f"no checkpoint under {hparams.ckpt_path!r}; serving "
                "fresh-initialized weights (load-testing mode)",
                UserWarning,
            )
        ckpt_path = found

    return ServeEngine(
        model_name=hparams.model,
        model_kw=model_kw,
        checkpoint_path=ckpt_path,
        mesh=mesh,
        model_parallel=mp,
        num_devices=getattr(hparams, "num_devices", 0),
        buckets=getattr(hparams, "serve_buckets", DEFAULT_BUCKETS),
        precision=compute,
        image_size=image_size,
        monitor=monitor,
        aot_cache=aot_cache,
        arm_sentinel=arm_sentinel,
    )


def serve_aot_cache_from_hparams(hparams):
    """The ``--serve-aot-cache`` flag resolved to a
    ``utils.PersistedServeCache`` (or None): ``off`` disables, ``auto``
    keys the store under the checkpoint root (``<ckpt>/serve-aot``) so a
    relaunched replica fleet finds its predecessors' executables, any
    other value is an explicit directory."""
    spec = str(getattr(hparams, "serve_aot_cache", "auto") or "off")
    if spec == "off":
        return None
    from pathlib import Path

    from ..utils import PersistedServeCache

    if spec == "auto":
        root = getattr(hparams, "ckpt_path", None)
        if not root:
            return None
        return PersistedServeCache(Path(root) / "serve-aot")
    return PersistedServeCache(spec)


def _run_load_shape(hparams, router, images, deadline) -> dict:
    """Dispatch the configured traffic shape against the router."""
    shape = str(getattr(hparams, "serve_shape", "auto") or "auto")
    rate = float(getattr(hparams, "serve_rate", 0.0) or 0.0)
    n = int(hparams.serve_requests)
    seed = int(hparams.seed)
    if shape == "auto":
        shape = "open" if rate > 0 else "closed"
    if shape == "closed":
        return closed_loop(
            router, images, num_requests=n,
            concurrency=hparams.serve_concurrency, deadline_ms=deadline,
        )
    base = rate if rate > 0 else 64.0
    if shape == "open":
        return open_loop(
            router, images, rate_rps=base, num_requests=n,
            deadline_ms=deadline, seed=seed,
        )
    if shape == "flash":
        return flash_crowd(
            router, images, base_rps=base,
            flash_mult=float(getattr(hparams, "serve_flash_mult", 8.0)),
            num_requests=n, deadline_ms=deadline, seed=seed,
        )
    if shape == "diurnal":
        return diurnal_ramp(
            router, images, base_rps=base, peak_rps=4.0 * base,
            num_requests=n, deadline_ms=deadline, seed=seed,
        )
    if shape == "mixed":
        # one open loop per DECLARED SLO class, rate split evenly — the
        # auto-appended synthetic 'default' class gets no tenant of its
        # own (it exists so class-less submit() works, not as traffic;
        # splitting the rate with a phantom tenant would measure every
        # declared class at the wrong offered rate)
        names = [
            n for n in sorted(router.classes) if n != DEFAULT_CLASS
        ] or [DEFAULT_CLASS]
        tenants = {
            name: {"rate_rps": base / len(names),
                   "num_requests": max(1, n // len(names)),
                   # the flag-level deadline rides along (None falls
                   # back to each class's own default at submit time)
                   "deadline_ms": deadline}
            for name in names
        }
        return mixed_tenants(router, images, tenants=tenants, seed=seed)
    raise ValueError(f"unknown --serve-shape {shape!r}")


def serve_main(hparams) -> dict:
    """The ``--serve`` entry: replica fleet + load shape + report.

    Artifacts mirror a training run's: one log line per phase via the
    experiment logger, TB scalars under ``<ckpt-path>/serve-tb``, the
    run-event stream (``serve_route``/``replica``/``compile``/``metrics``
    kinds + the closing ``serve`` summary) in the ckpt root's
    events.jsonl, and the report dict returned (``entry.run`` prints it
    on process 0).
    """
    from pathlib import Path

    import jax

    from ..parallel import is_main_process
    from ..utils import setup_logger

    if jax.process_count() > 1:
        # Each process would run its own router/load generator with
        # independently-timed admission — mismatched bucket programs
        # across hosts deadlock the sharded executables.  Serving is
        # single-controller until a cross-host dispatch protocol exists.
        raise ValueError(
            "--serve is single-process: run it on one host (a multi-host "
            "launch would dispatch desynchronized bucket programs)"
        )
    if (
        str(getattr(hparams, "serve_transport", "thread")) == "process"
        and jax.devices()[0].platform == "tpu"
    ):
        # One process owns a chip.  This process — the router — has opened
        # the host's chips by now (entry.run initialized the backend), so a
        # replica process cannot: found on a v5e, the worker dies on
        # libtpu's multi-process lockfile and the fleet burns its restart
        # budget before failing.  Per-chip placement of replica processes
        # does not exist yet; refuse before spawning.
        raise ValueError(
            "--serve-transport process cannot run on a TPU host: this "
            "router process holds the chip(s), so replica processes cannot "
            "open them, and replicas are not placed per chip yet — use "
            "--serve-transport thread (N engines in this process), or "
            "JAX_PLATFORMS=cpu for the CPU process fleet"
        )
    logger = setup_logger(None, is_main_process=is_main_process())
    # obs wiring happens BEFORE the engines exist so the warmup compiles
    # are observed: the bus buffers pre-bind emits and flushes them when
    # the ckpt root binds below, so nothing from engine construction is
    # lost.  The compile monitor gives every bucket compile a `compile`
    # event + compile/* metrics, and — once warmup() marks it warm — a
    # bucket compiled mid-serving (bucket churn, the recompile cliff)
    # trips the compile/recompiles_after_warmup sentinel --alert rules
    # can page on.
    from .. import obs

    bus = None
    if getattr(hparams, "obs", True):
        bus = obs.current_bus()
    registry = obs.MetricRegistry()
    monitor = obs.CompileMonitor(
        bus=bus, registry=registry, enabled=bus is not None
    )
    aot_cache = serve_aot_cache_from_hparams(hparams)
    classes = parse_slo_classes(getattr(hparams, "serve_classes", None))
    buckets = tuple(getattr(hparams, "serve_buckets", DEFAULT_BUCKETS))
    warm = getattr(hparams, "serve_warm_buckets", ()) or None

    # --- replica count + ladder: flag-pinned, or scored by the planner's
    # ledger-fit cost model over the committed event history (the AMP
    # argument: configuration from a cost model, not a grid of flags)
    n_replicas = int(getattr(hparams, "serve_replicas", 1) or 0)
    plan = None
    if n_replicas < 1:
        from ..parallel.planner import load_ledger_events

        # initial sizing prices the same G/G/m tail the live autoscaler
        # fits: an explicit --serve-scale-target is the p99 budget, else
        # the class deadlines are (plan_serve's own fallback chain)
        from .fleet.autoscale import parse_scale_targets

        scale_spec = getattr(hparams, "serve_scale_target", None)
        plan = plan_serve(
            load_ledger_events(hparams.ckpt_path),
            buckets=buckets,
            rate_rps=float(getattr(hparams, "serve_rate", 0.0) or 0.0),
            classes=classes,
            scale_targets=(
                parse_scale_targets(scale_spec) if scale_spec else None
            ),
        )
        n_replicas = plan["replicas"]
        buckets = tuple(plan["buckets"]) or buckets
        logger.info(
            f"[serve] plan: {n_replicas} replica(s), ladder "
            f"{list(buckets)} (sized_by {plan['sized_by']}, fit "
            f"{plan['fit']['source']})"
        )
        if warm:
            # config.py validated warm against the FLAG ladder; the plan
            # may have trimmed buckets out from under it, and warming a
            # bucket the engines no longer carry would kill every
            # replica at startup
            kept = tuple(b for b in warm if b in buckets)
            if kept != warm:
                logger.warning(
                    f"[serve] --serve-warm-buckets "
                    f"{[b for b in warm if b not in buckets]} dropped: "
                    f"not in the planner-trimmed ladder {list(buckets)}"
                )
            warm = kept or None

    # every replica builds its own engine through this factory (in its
    # own worker thread, so N replicas warm in parallel); the shared
    # monitor keys records by fingerprint and the shared persisted cache
    # means replica 1's compile is replica 2's millisecond load
    first_engine: list = []

    def engine_factory(rid: int) -> ServeEngine:
        hp = hparams
        if tuple(getattr(hp, "serve_buckets", ())) != buckets:
            import copy

            hp = copy.copy(hparams)
            hp.serve_buckets = buckets
        # arm_sentinel=False: the ROUTER arms the shared monitor once,
        # after the whole fleet warmed — a fast replica must not turn
        # its siblings' remaining warmup compiles into sentinel findings
        eng = build_engine(
            hp, monitor=monitor, aot_cache=aot_cache, arm_sentinel=False
        )
        if rid == 0:
            first_engine.append(eng)
        return eng

    # bind the run-event bus BEFORE replicas start so warmup `compile`
    # events and the periodic `metrics`/`serve_route`/`replica` events
    # (the live SLO feed `run_report --follow` tails) land in the ckpt
    # root's events.jsonl
    if bus is not None:
        bus.bind_dir(hparams.ckpt_path)
    # live operations for the serving path: the latency histograms and
    # queue/shed gauges mirror into a metric registry the OpenMetrics
    # endpoint renders (--metrics-port), the router's ticker flushes that
    # registry onto the bus periodically (so compile/* counters — the
    # recompile-storm sentinel — reach rules MID-session), and the
    # --alert rules evaluate in-process over those periodic emits
    # (serving runs unsupervised, so there is no fleet watcher to do it).
    alert_engine = None
    specs = getattr(hparams, "alert", None)
    if specs and bus is not None:
        alert_engine = obs.AlertEngine(obs.parse_alert_specs(specs), bus=bus)
        bus.subscribe(alert_engine.observe_event)
    metrics = ServeMetrics(bus=bus, registry=registry, classes=classes)
    # end-to-end request tracing (obs/reqtrace.py): every request carries
    # a (trace_id, span_id); tail-based keep means shed / expired /
    # breached / requeued / errored requests always trace, healthy ones
    # at --serve-trace-sample.  Only built when the bus exists — span
    # records without an event file would have nowhere to go.
    tracer = None
    if bus is not None:
        tracer = obs.RequestTracer(
            bus=bus,
            sample_rate=float(
                getattr(hparams, "serve_trace_sample", 0.0) or 0.0
            ),
            seed=int(getattr(hparams, "seed", 0) or 0),
        )
    # --- transport: thread (N engines here) or process (serve/fleet/ —
    # each replica a supervised OS process behind the socket transport)
    transport = str(getattr(hparams, "serve_transport", "thread"))
    process_spec = None
    if transport == "process":
        import os

        from .fleet.replica import worker_hparams_dict

        wk = worker_hparams_dict(hparams)
        wk["serve_buckets"] = list(buckets)
        process_spec = {
            "fleet_dir": str(Path(hparams.ckpt_path) / "serve-fleet"),
            "events_dir": str(hparams.ckpt_path) if bus is not None else "",
            "hparams": wk,
            "port_base": int(getattr(hparams, "serve_port_base", 0) or 0),
            "metrics_port_base": int(
                getattr(hparams, "metrics_port", 0) or 0
            ),
            "platform": os.environ.get("JAX_PLATFORMS") or None,
            "run_id": getattr(bus, "run_id", None),
            "attempt": getattr(bus, "attempt", 0),
            "aot_dir": str(aot_cache.dir) if aot_cache is not None else "",
            "warm_buckets": list(warm) if warm else None,
        }
    router = ServeRouter(
        engine_factory,
        replicas=n_replicas,
        classes=classes,
        mode=str(getattr(hparams, "serve_mode", "continuous")),
        max_wait_ms=hparams.max_wait_ms,
        queue_limit=hparams.queue_limit,
        metrics=metrics,
        bus=bus,
        registry=registry,
        warm_buckets=warm,
        plan=plan,
        monitor=monitor,
        transport=transport,
        process_spec=process_spec,
        tracer=tracer,
        start=False,
    )
    # --- queueing-aware autoscaling (--serve-scale-target): fit a G/G/m
    # tail to the measured arrival/service sketches, re-size against the
    # p99 targets live (the router ticker steps it), every decision a
    # serve_scale event
    autoscaler = None
    scale_spec = getattr(hparams, "serve_scale_target", "") or ""
    if scale_spec:
        from .fleet.autoscale import Autoscaler, parse_scale_targets

        autoscaler = Autoscaler(
            metrics,
            parse_scale_targets(scale_spec),
            min_replicas=1,
            max_replicas=int(getattr(hparams, "serve_max_replicas", 8)),
            bus=bus,
        )
        router.attach_autoscaler(autoscaler)
    router.start()
    # closed-loop autopilot for the serving path (ops/policy.py): the one
    # action that lives HERE is rewarm_serve — a post-warmup recompile
    # storm (the sentinel alert above) re-runs warmup() on the affected
    # bucket subset of EVERY replica, turning the compile cliff back
    # into a warmed ladder.
    policy_engine = None
    if bus is not None:
        from ..ops import policy as policy_mod

        policy_engine = policy_mod.engine_from_hparams(
            hparams, bus=bus, log=logger.warning
        )
    if policy_engine is not None:
        from ..ops.policy import serve_actions

        policy_engine.bind_actions(serve_actions(router, autoscaler))
        bus.subscribe(policy_engine.observe_event)
    exporter = obs.start_exporter(
        getattr(hparams, "metrics_port", 0),
        registry=registry,
        alerts=alert_engine,
    )
    if exporter is not None:
        logger.info(f"[serve] OpenMetrics endpoint on :{exporter.port}/metrics")
    deadline = getattr(hparams, "deadline_ms", 0.0) or None
    try:
        router.warmup()
        if transport == "process":
            # the engines live in the worker processes; introspect from
            # the flags + the workers' health-reported stats instead
            image_size = int(getattr(hparams, "image_size", 32) or 32)
            stats = router.stats().get("engine", {})
            logger.info(
                f"[serve] model {hparams.model}, {n_replicas} process "
                f"replica(s), buckets {list(buckets)} "
                f"(warmed {list(warm) if warm else 'all'}), "
                f"{stats.get('persisted_hits', 0)} programs loaded from "
                "the persisted AOT cache"
            )
        else:
            # replica 0's factory may have failed while another replica
            # warmed fine (warmup() only needs ONE ready) — introspect
            # any replica that actually built an engine
            eng = first_engine[0] if first_engine else next(
                r.engine for r in router.replicas if r.engine is not None
            )
            image_size = eng.image_size
            ck = eng.checkpoint_meta
            logger.info(
                f"[serve] model {hparams.model}, mesh "
                f"{dict(eng.mesh.shape)}, "
                f"{n_replicas} replica(s), buckets {list(eng.buckets)} "
                f"(warmed {list(warm) if warm else 'all'}), "
                + (
                    f"checkpoint epoch {ck['epoch']} (acc {ck['acc']:.4f})"
                    if ck
                    else "fresh weights (no checkpoint)"
                )
            )
            stats = router.stats().get("engine", {})
            logger.info(
                f"[serve] warm: {stats.get('compiles', 0)} bucket "
                f"programs compiled, {stats.get('persisted_hits', 0)} "
                "loaded from the persisted AOT cache"
            )
        # per-attempt seed fold: a restarted serve session (or a sibling
        # process) must not replay byte-identical request pools
        images = request_pool(
            max(256, max(buckets)),
            image_size=image_size,
            seed=hparams.seed,
            fold=("serve", getattr(bus, "attempt", 0) if bus else 0),
        )
        report = _run_load_shape(hparams, router, images, deadline)
    finally:
        # an aborted session must not leak the listening /metrics port or
        # leave a stale rule engine tapping the process-current bus
        router.close()
        if exporter is not None:
            exporter.close()
        if alert_engine is not None and bus is not None:
            bus.unsubscribe(alert_engine.observe_event)
        if policy_engine is not None and bus is not None:
            bus.unsubscribe(policy_engine.observe_event)
    metrics.log_summary(logger)
    router_stats = router.stats()  # one snapshot: router/engine agree
    report["router"] = router_stats
    report["engine"] = router_stats.get("engine", {})
    if policy_engine is not None:
        report["policy"] = policy_engine.summary()
    if bus is not None:
        # one closing flush puts the session's compile/* counters and the
        # per-bucket exec/... dispatch sketches on the event stream — the
        # rows run_report --compute renders for a serving session
        registry.flush(bus)
    if is_main_process():
        metrics.write_tensorboard(Path(hparams.ckpt_path) / "serve-tb")
        # one summary record on the unified run-event bus: a serving
        # session's artifacts join training's on the same timeline
        # schema (ckpt-root events.jsonl, next to the supervisor's) —
        # carrying the load shape's phase split when there is one, so
        # the chaos gauntlet can judge p99 recovery from the stream
        extra = {}
        if "phases" in report:
            extra["phases"] = report["phases"]
            extra["shape"] = report.get("mode")
        metrics.emit_event(
            bus if bus is not None else obs.current_bus(), extra=extra
        )
    return report
