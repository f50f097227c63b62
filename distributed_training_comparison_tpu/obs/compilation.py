"""Compiler & memory observability: compile events, the HLO cost/memory
ledger, and the recompilation sentinel.

Everything the obs stack records so far explains *runtime* — steps,
spans, stalls, stragglers.  The compiler is invisible: a serve bucket
miss or an elastic reshape triggers a multi-second recompile that shows
up only as a mysteriously slow chunk, the persistent compile cache's
hit rate is unknowable from the event stream, and "where did HBM go"
has no answer short of an offline profiler pass.  This module closes
that gap:

- ``CompileMonitor.instrument(fn, name)`` wraps a jitted function so
  every distinct executable it builds is *observed*: the wrapper keys
  calls on the abstract input signature (shape/dtype per leaf — ~60 µs
  on a 300-leaf state, paid once per dispatch, not per step), compiles
  new signatures itself through the AOT path (``lower().compile()``,
  timed), and dispatches through the compiled executable from then on.
  Owning the compile is what makes the executable *inspectable*:
  ``cost_analysis()`` / ``memory_analysis()`` (via ``_compat`` — an
  executable that reports nothing degrades to "no data") yield the per-executable FLOPs and the
  argument/output/temp HBM footprint no post-hoc hook could recover.
  Any failure anywhere in the instrumented path falls back to the plain
  jitted call — compile telemetry must never take training down.
- Every compile emits ONE registered ``compile`` bus event: a stable
  **fingerprint** (sha256 over name + abstract in-shapes/dtypes +
  sharding specs + mesh axes — identical across processes of one fleet),
  compile wall time, persistent-cache ``hit``/``miss``/``off`` (a
  monitoring listener catches the cache's own hit events), the
  cost/memory analysis, the device kind/count the ``run_report
  --compute`` MFU reconstruction needs, which path each kernel gate took
  while the program traced (``kernel_paths``, see ``note_kernel_path``),
  which backward each flash-attention call site took (``flash_backward``,
  see ``note_flash_backward``), which form each Gated DeltaNet mixer's
  pointwise stages took (``gdn_pointwise``, see ``note_gdn_pointwise``)
  and how many compiled Pallas kernels the executable carries
  (``tpu_custom_calls``).
- ``compile/*`` metrics ride the existing registry (and therefore every
  ``metrics`` flush, the OpenMetrics exporter, and ``--alert`` rules):
  compile counts total and per family, a compile-time histogram,
  persistent-cache hit/miss counters, executable-count and peak-HBM
  gauges, and per-executable ``exec/{family}:{fp}/dispatch_s`` sketches
  (count = dispatches, sum = dispatch-span seconds — the denominator of
  the measured MFU).
- The **recompilation sentinel**: after ``warm()`` (the serve engine
  calls it when its bucket warmup finishes; the trainer after its first
  full epoch) any compile of a sentinel-tracked family increments
  ``compile/recompiles_after_warmup`` and stamps the event — the
  serve-bucket-churn and elastic-reshape failure modes become one
  rule-able metric (``compile/recompiles_after_warmup:n>0``).

Dispatch-span caveat: dispatches are async, so a single call's wall time
is launch latency, not device time.  With the donated runners a dispatch
blocks until the *previous* executable's buffers free, so in steady
state the per-call span converges on the executable's execution time —
the basis run_report's measured MFU documents (and the reason the final
chunk of an epoch, drained at the metrics fetch, undercounts slightly).
"""

from __future__ import annotations

import hashlib
import threading
import time

from .._compat import (
    compilation_cache_dir,
    executable_cost_analysis,
    executable_memory_analysis,
    register_monitoring_listener,
)

COMPILE_KIND = "compile"

# per-chip peak dense-matmul FLOP/s (bf16) by jax device_kind prefix — the
# denominator of measured MFU.  Kinds without an entry (notably the CPU CI
# backend) yield None and run_report prints '-' unless --peak-flops
# overrides (MFU against an unknown peak would be a made-up number).
PEAK_FLOPS_BY_DEVICE_KIND = {
    "TPU v3": 123e12 / 2,  # jax exposes cores; per-core peak
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops_for(device_kind: str | None) -> float | None:
    """Peak per-chip FLOP/s for a ``device_kind`` string (prefix match),
    or None when the kind is unknown (``benchmark/harness/peaks.json``,
    the benchmark's own table, makes an unknown kind an error)."""
    if not device_kind:
        return None
    for prefix, peak in PEAK_FLOPS_BY_DEVICE_KIND.items():
        if str(device_kind).startswith(prefix):
            return peak
    return None


# ------------------------------------------------- persistent-cache probe
#
# The persistent compile cache announces hits on jax's internal monitoring
# stream; one process-wide listener (installed lazily, never removed —
# the API has no unregister contract) bumps a per-thread counter, and the
# probe brackets a compile on its own thread: hits observed → "hit",
# none but a cache dir configured → "miss", no dir → "off".

_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_probe_local = threading.local()
_probe_lock = threading.Lock()
_probe_installed = False


def _on_monitoring_event(event, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _probe_local.hits = getattr(_probe_local, "hits", 0) + 1


def _ensure_probe() -> None:
    global _probe_installed
    with _probe_lock:
        if not _probe_installed:
            register_monitoring_listener(_on_monitoring_event)
            _probe_installed = True


def note_kernel_path(kernel: str, path: str) -> None:
    """Record, at TRACE time, which path a kernel gate took (``"pallas"``,
    ``"pallas-interpret"`` or ``"composed"``).  The gates in models/vit.py,
    models/moe.py and ops/attention.py choose from the backend and their
    own shape limits; the choice is a fact about the executable being
    built, so an observed compile collects what was noted while it lowered
    and puts it on its ``compile`` event (``kernel_paths``).  A no-op
    outside an observed compile."""
    notes = getattr(_probe_local, "notes", None)
    if notes is not None:
        notes["kernel_paths"][kernel] = path


def _count_call_site(key: str, form: str) -> None:
    notes = getattr(_probe_local, "notes", None)
    if notes is not None:
        notes[key][form] = notes[key].get(form, 0) + 1


def note_flash_backward(form: str) -> None:
    """Count, at TRACE time, one flash-attention call site whose backward
    took ``form``: ``"fused"`` (one kernel) or ``"tiled"`` (two; what
    ``ops/attention.py flash_plan`` leaves to a call whose residents do not
    fit VMEM).  Like ``note_kernel_path`` a fact about the executable being
    built: on its ``compile`` event as ``flash_backward: {form: call
    sites}``, a key of its own.  A no-op outside an observed compile."""
    _count_call_site("flash_backward", form)


def note_gdn_pointwise(form: str) -> None:
    """Count, at TRACE time, one Gated DeltaNet mixer call site whose
    pointwise stages took ``form``: ``"fused"`` (``ops/gdn_pointwise.py``'s
    kernels) or ``"composed"`` (what ``gdn_pointwise_plan`` leaves to XLA).
    On the ``compile`` event as ``gdn_pointwise: {form: call sites}``, a key
    of its own as ``flash_backward`` is: ``kernel_paths`` keeps the kernels
    a cell's ``expect`` lists.  A no-op outside an observed compile."""
    _count_call_site("gdn_pointwise", form)


class _CacheProbe:
    """Bracket one lower+compile: classify its persistent-cache outcome and
    collect what its trace noted (``notes``: a ``compile`` event's key to
    its value, left out where nothing was noted)."""

    def __enter__(self) -> "_CacheProbe":
        _ensure_probe()
        self._before = getattr(_probe_local, "hits", 0)
        self.notes: dict[str, dict] = {
            "kernel_paths": {}, "flash_backward": {}, "gdn_pointwise": {},
        }
        _probe_local.notes = self.notes
        return self

    def __exit__(self, *exc) -> None:
        _probe_local.notes = None

    def outcome(self) -> str:
        if getattr(_probe_local, "hits", 0) > self._before:
            return "hit"
        return "miss" if compilation_cache_dir() else "off"


# ------------------------------------------------------------ fingerprint


def _leaf_desc(leaf) -> str:
    """One abstract-input leaf as a stable string: dtype[shape]@placement.
    Process-independent by construction — shapes, dtype names, partition
    specs, and mesh axis sizes are identical on every host of a fleet;
    device ids and object addresses never enter (the sharding term comes
    from ``parallel.sharding.sharding_desc``, which owns that contract).
    """
    from ..parallel.sharding import sharding_desc

    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if dtype is None:  # python scalar leaf (e.g. a fault tuple's floats)
        dtype = type(leaf).__name__
    desc = f"{getattr(dtype, 'name', dtype)}{list(shape) if shape is not None else '?'}"
    return f"{desc}@{sharding_desc(leaf)}"


def fingerprint_of(name: str, parts) -> str:
    """16-hex sha256 fingerprint of an executable identity: the family
    name plus its abstract-signature parts (strings)."""
    h = hashlib.sha256()
    h.update(str(name).encode())
    for part in parts:
        h.update(b"|")
        h.update(str(part).encode())
    return h.hexdigest()[:16]


def signature_fingerprint(name: str, args) -> str:
    """The instrumented-call fingerprint: family name + per-leaf abstract
    descs, each carrying its partition spec and mesh axes (stable across
    processes — the cross-host join key for ``run_report --compute``)."""
    import jax

    leaves = jax.tree_util.tree_leaves(args)
    return fingerprint_of(name, [_leaf_desc(l) for l in leaves])


# ------------------------------------------------------------ the monitor


class ExecutableRecord:
    """One observed executable: identity, compile accounting, analyses."""

    __slots__ = (
        "name", "fingerprint", "compile_s", "cache", "flops",
        "bytes_accessed", "memory", "peak_bytes", "compiles",
        "recompile_after_warmup", "device_kind", "platform", "devices",
        "_dispatch_hist",
    )

    def __init__(self, name: str, fingerprint: str) -> None:
        self.name = name
        self.fingerprint = fingerprint
        self.compile_s = 0.0
        self.cache = "unknown"
        self.flops: float | None = None
        self.bytes_accessed: float | None = None
        self.memory: dict | None = None
        self.peak_bytes: int | None = None
        self.compiles = 0
        self.recompile_after_warmup = False
        self.device_kind: str | None = None
        self.platform: str | None = None
        self.devices: int | None = None
        self._dispatch_hist = None  # registry histogram, bound at compile

    @property
    def metric_name(self) -> str:
        return f"exec/{self.name}:{self.fingerprint[:8]}/dispatch_s"


class CompileMonitor:
    """The process's compile observer: wraps jitted functions and AOT
    compile sites, emits ``compile`` events + ``compile/*`` metrics, and
    keeps the per-executable ledger.

    ``enabled=False`` (``--no-obs``) turns every method into a
    passthrough: ``instrument`` returns the function unchanged,
    ``aot_compile`` just runs the builder — a disabled run's executables,
    dispatch path, and event stream are byte-identical to before this
    module existed.
    """

    def __init__(self, bus=None, registry=None, enabled: bool = True) -> None:
        self.bus = bus
        self.registry = registry
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self.records: dict[str, ExecutableRecord] = {}
        self._warm = False
        self._taint = threading.local()

    # ------------------------------------------------------------ public

    def warm(self) -> None:
        """Declare steady state: every compile of a sentinel-tracked
        family from here on is a recompilation-sentinel finding (the
        serve engine calls this after bucket warmup; the trainer after
        its first full epoch builds chunk + eval executables)."""
        self._warm = True

    @property
    def is_warm(self) -> bool:
        return self._warm

    def take_taint(self) -> bool:
        """True iff a compile happened on THIS thread since the last
        call — the step-time meter's cue to keep a compile-bearing
        dispatch sample out of the straggler-scored phase sketch."""
        tainted = getattr(self._taint, "flag", False)
        self._taint.flag = False
        return tainted

    def instrument(self, fn, name: str, *, sentinel: bool = True):
        """Wrap a ``jax.jit``-ed callable: compiles observed + analysed,
        steady-state calls dispatched through the owned executable.
        Returns ``fn`` unchanged when the monitor is disabled."""
        if not self.enabled:
            return fn
        return _InstrumentedFunction(self, fn, name, sentinel)

    def aot_compile(
        self, name: str, build, *, parts, sentinel: bool = True
    ):
        """Observe an explicit AOT compile site (the serve engine's
        ``lower().compile()``): times ``build()``, analyses its result.
        Returns ``(compiled, record | None)`` — the compiled executable
        always, the record only when the monitor is live."""
        if not self.enabled:
            return build(), None
        with _CacheProbe() as probe:
            t0 = time.perf_counter()
            compiled = build()
            compile_s = time.perf_counter() - t0
        rec = self._record_compile(
            name, fingerprint_of(name, parts), compile_s,
            compiled, probe.outcome(), sentinel, probe.notes,
        )
        return compiled, rec

    def adopt_compile(self, name: str, parts, compiled, *, load_s: float = 0.0):
        """Observe an executable that was NOT compiled here — it was
        deserialized from the persisted serve AOT cache
        (``utils/compile_cache.py``).  Emits the same ``compile`` event
        shape with ``cache: "persisted"`` and the load seconds where the
        compile seconds would be, so the ledger records the warm-start's
        measured compile-time drop; ``sentinel=False`` always — a
        millisecond-scale deserialization is not a compile cliff, so a
        flash crowd landing on a persisted (if unwarmed) bucket must not
        page the recompilation sentinel.  Returns the record (None when
        disabled)."""
        if not self.enabled:
            return None
        return self._record_compile(
            name, fingerprint_of(name, parts), load_s,
            compiled, "persisted", False,
        )

    def time_dispatch(self, record: ExecutableRecord | None):
        """Context manager recording one dispatch span into the record's
        ``exec/...`` sketch (serve's hot path; instrumented functions do
        this internally)."""
        return _DispatchTimer(record)

    def ledger(self) -> list[dict]:
        """The per-executable view (tests, debugging): one dict per
        observed executable, compile-order stable."""
        with self._lock:
            recs = list(self.records.values())
        return [
            {
                "name": r.name, "fingerprint": r.fingerprint,
                "compiles": r.compiles, "compile_s": round(r.compile_s, 4),
                "cache": r.cache, "flops": r.flops,
                "peak_bytes": r.peak_bytes, "memory": r.memory,
                "recompile_after_warmup": r.recompile_after_warmup,
            }
            for r in recs
        ]

    # ---------------------------------------------------------- internal

    def _record_compile(
        self, name, fingerprint, compile_s, compiled, cache, sentinel,
        notes=None,
    ) -> ExecutableRecord:
        """Fold one observed compile into the ledger, the registry, and
        the bus.  Never raises (the caller is the training hot path)."""
        try:
            return self._record_compile_inner(
                name, fingerprint, compile_s, compiled, cache, sentinel,
                notes,
            )
        except Exception:
            rec = ExecutableRecord(name, fingerprint)
            rec.compile_s = compile_s
            return rec

    def _record_compile_inner(
        self, name, fingerprint, compile_s, compiled, cache, sentinel,
        notes,
    ) -> ExecutableRecord:
        self._taint.flag = True
        cost = executable_cost_analysis(compiled) if compiled is not None else None
        memory = (
            executable_memory_analysis(compiled) if compiled is not None else None
        )
        with self._lock:
            rec = self.records.get(fingerprint)
            if rec is None:
                rec = self.records[fingerprint] = ExecutableRecord(
                    name, fingerprint
                )
            rec.compiles += 1
            rec.compile_s += compile_s
            rec.cache = cache
            flagged = bool(sentinel and self._warm)
            rec.recompile_after_warmup = rec.recompile_after_warmup or flagged
            if cost:
                rec.flops = cost.get("flops")
                rec.bytes_accessed = cost.get("bytes accessed")
            if memory:
                rec.memory = memory
                rec.peak_bytes = sum(
                    memory.get(k, 0)
                    for k in ("argument_bytes", "output_bytes", "temp_bytes")
                )
            rec.platform, rec.device_kind, rec.devices = _device_identity(
                compiled
            )
            n_execs = len(self.records)
            peak_hbm = max(
                (r.peak_bytes for r in self.records.values()
                 if r.peak_bytes is not None),
                default=None,
            )
        if self.registry is not None:
            self.registry.counter("compile/total").inc()
            self.registry.counter(f"compile/by/{name}").inc()
            self.registry.histogram("compile/time_s").record(compile_s)
            if cache == "hit":
                self.registry.counter("compile/persistent_cache_hits").inc()
            elif cache == "miss":
                self.registry.counter("compile/persistent_cache_misses").inc()
            elif cache == "persisted":
                # not a compile at all: a serve executable deserialized
                # from the persisted AOT store (utils/compile_cache.py)
                self.registry.counter("compile/persisted_loads").inc()
            if flagged:
                self.registry.counter("compile/recompiles_after_warmup").inc()
            self.registry.gauge("compile/executables").set(n_execs)
            if peak_hbm is not None:
                self.registry.gauge("compile/peak_hbm_bytes").set(peak_hbm)
            rec._dispatch_hist = self.registry.histogram(rec.metric_name)
        if self.bus is not None:
            payload = {
                "name": name,
                "fingerprint": fingerprint,
                "compile_s": round(compile_s, 6),
                "cache": cache,
                "compiles_of_fingerprint": rec.compiles,
                "recompile_after_warmup": flagged,
                "platform": rec.platform,
                "device_kind": rec.device_kind,
                "devices": rec.devices,
            }
            if rec.flops is not None:
                payload["flops"] = float(rec.flops)
            if rec.bytes_accessed is not None:
                payload["bytes_accessed"] = float(rec.bytes_accessed)
            if rec.memory:
                payload.update(rec.memory)
                payload["peak_bytes"] = rec.peak_bytes
            payload.update(
                {key: dict(noted) for key, noted in (notes or {}).items() if noted}
            )
            mosaic = _mosaic_kernel_count(compiled)
            if mosaic is not None:
                payload["tpu_custom_calls"] = mosaic
            self.bus.emit(COMPILE_KIND, **payload)
        return rec

    def _note_dispatch(self, rec: ExecutableRecord, seconds: float) -> None:
        hist = rec._dispatch_hist
        if hist is not None:
            hist.record(seconds)


def _mosaic_kernel_count(compiled) -> int | None:
    """How many compiled Pallas (Mosaic) kernels the executable's HLO
    carries — ``tpu_custom_call`` targets.  A kernel run through the
    Pallas interpreter, or a gate that composed instead, leaves none, so
    with ``kernel_paths`` this says whether the kernel a model chose is
    really in the program.  None when the executable has no text (a
    deserialized one)."""
    try:
        return compiled.as_text().count('custom_call_target="tpu_custom_call"')
    except Exception:
        return None


def _device_identity(compiled=None) -> tuple[str | None, str | None, int | None]:
    """(platform, device_kind, device count) of the executable — read
    from the devices it actually compiled for (its input shardings'
    mesh), because ``jax.devices()`` names the DEFAULT backend, which on
    hosts with both a CPU client and an accelerator plugin may not be
    the backend the mesh runs on (observed: a TPU run whose compile
    events said "cpu").  Falls back to the default backend only when the
    executable exposes no devices."""
    dev = None
    try:
        shardings = compiled.input_shardings[0] if compiled is not None else []
        import jax

        for s in jax.tree_util.tree_leaves(shardings):
            device_set = getattr(s, "device_set", None)
            if device_set:
                dev = next(iter(device_set))
                return dev.platform, dev.device_kind, len(device_set)
    except Exception:
        pass
    try:
        import jax

        dev = jax.devices()[0]
        return dev.platform, dev.device_kind, jax.device_count()
    except Exception:
        return None, None, None


class _DispatchTimer:
    __slots__ = ("_rec", "_t0")

    def __init__(self, rec: ExecutableRecord | None) -> None:
        self._rec = rec

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        rec = self._rec
        if rec is not None and rec._dispatch_hist is not None and exc[0] is None:
            rec._dispatch_hist.record(time.perf_counter() - self._t0)


class _InstrumentedFunction:
    """The ``instrument`` wrapper: signature-keyed AOT dispatch with a
    plain-jit fallback.

    The fast path per call is one pytree flatten + a (shape, dtype) tuple
    key (~60 µs on a 300-leaf train state — per *dispatch*, i.e. per
    chunk of K steps, so sub-µs per trained step at any practical K).
    Shardings deliberately stay out of the fast key: every call site in
    this repo pins input shardings per maker, so the abstract shapes
    determine the layout — they DO enter the slow-path fingerprint.
    Any error while keying, lowering, compiling, or dispatching marks
    that signature (or, for keying errors, the whole wrapper) broken and
    routes calls to the original jitted function — jit then compiles its
    own executable once, and training proceeds unobserved but unharmed.
    """

    __slots__ = ("_monitor", "_fn", "_name", "_sentinel", "_cache", "_broken")

    def __init__(self, monitor, fn, name, sentinel) -> None:
        self._monitor = monitor
        self._fn = fn
        self._name = name
        self._sentinel = sentinel
        self._cache: dict = {}
        self._broken = False

    def __call__(self, *args):
        if self._broken:
            return self._fn(*args)
        try:
            import jax

            leaves, treedef = jax.tree_util.tree_flatten(args)
            # python-scalar leaves (a fault tuple's floats/ints) have no
            # shape/dtype; their TYPE is what distinguishes signatures
            # (values are traced, not baked in)
            key = (
                treedef,
                tuple(
                    (getattr(l, "shape", ()), getattr(l, "dtype", type(l)))
                    for l in leaves
                ),
            )
        except Exception:
            self._broken = True
            return self._fn(*args)
        entry = self._cache.get(key)
        if entry is None:
            entry = self._compile(key, args, leaves)
        exe, rec = entry
        if exe is None:
            return self._fn(*args)
        t0 = time.perf_counter()
        try:
            out = exe(*args)
        except Exception:
            # AOT call-convention drift (arg validation fails before any
            # buffer is consumed): permanent fallback for this signature
            self._cache[key] = (None, rec)
            return self._fn(*args)
        self._monitor._note_dispatch(rec, time.perf_counter() - t0)
        return out

    def _compile(self, key, args, leaves):
        try:
            with _CacheProbe() as probe:
                t0 = time.perf_counter()
                compiled = self._fn.lower(*args).compile()
                compile_s = time.perf_counter() - t0
            cache = probe.outcome()
        except Exception:
            entry = (None, None)
            self._cache[key] = entry
            return entry
        fingerprint = fingerprint_of(
            self._name, [_leaf_desc(l) for l in leaves]
        )
        rec = self._monitor._record_compile(
            self._name, fingerprint, compile_s, compiled, cache,
            self._sentinel, probe.notes,
        )
        entry = (compiled, rec)
        self._cache[key] = entry
        return entry
