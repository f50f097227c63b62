"""The run-event bus and the flight recorder.

One versioned record shape for every event a run emits — health verdicts,
goodput summaries, checkpoint-writer gauges, preemption drains, supervisor
attempts, serve reports::

    {"v": 1, "run_id": "9f2c4e71a0b3d852", "attempt": 0,
     "process_index": 0, "t_wall": 1754200000.123, "t_mono": 512.456,
     "kind": "rollback", "epoch": 3, "payload": {...}}

``run_id`` names the whole supervised run: generated once (by the
supervisor, or by process 0 of an unsupervised run and broadcast like the
save throttle) and inherited by every attempt through the environment, so
records written by different attempts, processes, and subsystems join on
it.  ``attempt`` is the restart index; ``t_wall`` (unix) orders events
across attempts and hosts, ``t_mono`` orders them exactly within one
process.

Events append to the bound directory's ``events.jsonl`` (process 0) /
``events-p{i}.jsonl`` (other processes — per-process files, because
cross-host appends to one shared file interleave).  Every event also lands
in a bounded in-memory ring — the **flight recorder** — which
``dump_crash`` writes to ``crash_dump.json`` on abort, watchdog budget
exhaustion, or an unhandled exception, so post-mortems read the final ring
instead of scraping log files.  ``attach_ring`` additionally mirrors the
ring into an mmap'd fixed-slot file (``blackbox.py``) that survives even
SIGKILL — the deaths no in-process dump can catch.

Writes are accounting: an ``OSError`` is swallowed (after disabling the
sink) — telemetry must never kill training.
"""

from __future__ import annotations

import json
import os
import threading
import time
import traceback
from collections import deque
from pathlib import Path

SCHEMA_VERSION = 1
EVENTS_NAME = "events.jsonl"
CRASH_DUMP_NAME = "crash_dump.json"
RING_SIZE_DEFAULT = 256

# environment seam the supervisor uses to hand every attempt the same
# run_id and its restart index (resilience/supervisor.py)
RUN_ID_ENV = "DTC_RUN_ID"
ATTEMPT_ENV = "DTC_ATTEMPT"

# the top-level keys the versioned schema admits, and the required subset
_REQUIRED = ("v", "run_id", "attempt", "process_index", "t_wall", "t_mono", "kind")
_OPTIONAL = ("epoch", "step", "payload")

# The event-kind registry: every kind any module of this package emits.
# ``validate_event`` rejects unregistered kinds, so a new emitter that
# forgets to register (and document — the README kind table is linted by
# tests/test_fleet.py) fails ``run_report --check`` instead of silently
# forking the schema.  Embedders emitting their own kinds register them
# with ``register_kind`` first.
KNOWN_KINDS = {
    # trainer lifecycle
    "run_start", "epoch_start", "epoch_end", "preempt", "abort", "run_end",
    # health watchdog
    "skip", "spike", "rollback", "desync",
    # accounting + gauges
    "writer", "goodput", "metrics", "serve",
    # supervisor restart loop; `resize` is the elastic fleet supervisor's
    # world-size re-render (shrink on host loss, re-expand on re-admission)
    "attempt_start", "attempt_end", "backoff", "give_up", "run_summary",
    "resize",
    # health corrupt-shard quarantine: bad batch indices excluded on replay
    "quarantine",
    # live fleet operations (obs/heartbeat, straggler, alerts)
    "heartbeat", "stall", "straggler", "alert",
    # compiler observability (obs/compilation): one event per executable
    # built, carrying the HLO cost/memory analysis + cache outcome
    "compile",
    # pipeline parallelism (parallel/pipeline): one event per attempt with
    # the schedule's static tick arithmetic (ticks, useful ticks, bubble
    # fraction, virtual stages) — run_report joins it with the measured
    # dispatch sketches into the per-executable bubble table
    "pipeline",
    # closed-loop autopilot (ops/policy): one event per policy decision —
    # rule, triggering alert, action, cooldown/budget state, dry-run flag
    # — whether the action ran, deferred, or was suppressed
    "policy",
    # chaos gauntlet (resilience/faults scenario catalog, run by
    # tools/chaos_matrix.py):
    # one event per named scenario with its outcome counts
    "chaos",
    # auto-parallel planner (parallel/planner): one event per planning
    # decision — chosen layout + flags, every candidate's predicted
    # step-s/HBM, refusal counts, and the cost-model fit provenance;
    # run_report --plan fails a stream whose installed plan disagrees
    # with the attempt's run_start layout
    "plan",
    # serving fleet (serve/router): `replica` = one replica's lifecycle
    # (starting/ready/draining/stopped/dead transitions + rate-limited
    # heartbeats); `serve_route` = the router's periodic routing summary
    # — cumulative per-SLO-class counters + per-replica counts + the
    # installed capacity plan — the stream-only input of
    # `run_report --serve`'s attainment gate
    "replica", "serve_route",
    # queueing-aware autoscaler (serve/fleet/autoscale): one event per
    # sizing decision — proposed vs current fleet, the G/G/m fit inputs
    # (λ, ca², service sketch) and per-class predicted-vs-target p99
    # rows, whether it applied, held (cooldown / scale-down hysteresis),
    # or was forced by the `scale_serve` autopilot action
    "serve_scale",
    # mid-epoch control plane (resilience/control): one event per control
    # request reaching its end state — applied at a chunk/epoch boundary,
    # superseded (stale attempt-scoped drain discarded), or expired (run
    # ended with the request queued) — carrying the decide->apply
    # time-to-mitigation (t_decide/t_apply/ttm_s/steps_since_decide);
    # run_report --policy renders and gates on it
    "control",
    # eager-parity debug rail (parity/): one event per completed
    # --parity-check capture — both gate verdicts (bitwise replay vs the
    # recorded trajectory, tolerance-gated eager reference), the first
    # divergent (step, stage, leaf, ulp) when either gate trips, and the
    # layout under test; run_report --parity renders and gates on it
    "parity",
    # request tracing (obs/reqtrace): one event per KEPT trace on the
    # router's bus (the span tree: admit/queue/coalesce/batch/rpc/reply,
    # keep reason, requeue count), plus per-batch device spans on each
    # replica process's own bus (events-p{1+rid}.jsonl) joined on
    # trace_id; run_report --trace merges and decomposes them per class
    "trace",
}


def register_kind(kind: str) -> str:
    """Admit an embedder-defined event kind to the schema."""
    KNOWN_KINDS.add(str(kind))
    return kind


def events_filename(process_index: int = 0) -> str:
    """Per-process event file name: process 0 owns ``events.jsonl``."""
    return EVENTS_NAME if process_index == 0 else f"events-p{process_index}.jsonl"


def crash_dump_filename(attempt: int = 0, process_index: int = 0) -> str:
    """Per-attempt (and, off process 0, per-process) crash-dump name —
    suffixed like the event/trace files, so a relaunched attempt (same
    version dir) or another host never clobbers an earlier dump's
    forensics."""
    if attempt == 0 and process_index == 0:
        return CRASH_DUMP_NAME
    if process_index == 0:
        return f"crash_dump-a{attempt}.json"
    return f"crash_dump-a{attempt}-p{process_index}.json"


def new_run_id() -> str:
    """A fresh 16-hex-char run id (64 random bits)."""
    return os.urandom(8).hex()


def _jsonable(obj):
    """Best-effort JSON coercion for payload leaves (numpy scalars/arrays,
    paths, sets) — an event must serialize, whatever a caller hands it."""
    if hasattr(obj, "item") and not hasattr(obj, "__len__"):
        try:
            return obj.item()  # numpy / jax scalar
        except Exception:
            pass
    if hasattr(obj, "tolist"):
        try:
            return obj.tolist()
        except Exception:
            pass
    if isinstance(obj, (set, frozenset, tuple)):
        return list(obj)
    return str(obj)


class EventBus:
    """One process's event sink for one training attempt.

    Thread-safe: the trainer loop, the checkpoint writer, and the
    prefetcher producer all emit concurrently.  Events emitted before
    ``bind_dir`` (Trainer construction happens before the version dir is
    known) buffer in memory and flush on bind; a bus that is never bound
    keeps only the flight-recorder ring.
    """

    def __init__(
        self,
        run_id: str | None = None,
        attempt: int = 0,
        process_index: int = 0,
        ring_size: int = RING_SIZE_DEFAULT,
        persist: bool = True,
    ) -> None:
        self.run_id = run_id or new_run_id()
        self.attempt = int(attempt)
        self.process_index = int(process_index)
        # persist=False (--no-obs): ring-only — no pre-bind buffering, so a
        # bus that will never be bound can't grow an unbounded pending list
        self._persist = bool(persist)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, int(ring_size)))
        self._pending: list[str] = []
        self._file = None
        self._path: Path | None = None
        self._broken = False  # sink died (OSError); ring keeps recording
        self._crash_path: Path | None = None  # first dump wins
        self._mmap_ring = None  # durable twin of the in-memory ring
        self._subscribers: list = []  # live taps (alert engine, exporter)

    # -------------------------------------------------------------- emit

    def stamp(self) -> dict:
        """The identity fields every record (bus event or legacy jsonl
        row) carries — health.jsonl/goodput.jsonl merge these in so the
        old files join the new timeline on run_id/attempt."""
        return {
            "v": SCHEMA_VERSION,
            "run_id": self.run_id,
            "attempt": self.attempt,
            "process_index": self.process_index,
        }

    def emit(
        self, kind: str, *, epoch: int | None = None, step: int | None = None,
        **payload,
    ) -> dict:
        ev = {
            **self.stamp(),
            "t_wall": time.time(),
            "t_mono": time.monotonic(),
            "kind": str(kind),
        }
        if epoch is not None:
            ev["epoch"] = int(epoch)
        if step is not None:
            ev["step"] = int(step)
        if payload:
            ev["payload"] = payload
        line = json.dumps(ev, default=_jsonable)
        with self._lock:
            self._ring.append(ev)
            if self._mmap_ring is not None:
                try:
                    self._mmap_ring.append(self._ring_line(ev, line))
                except (OSError, ValueError):
                    self._mmap_ring = None  # durability lost, training isn't
            if self._file is not None:
                self._write(line)
            elif self._persist and not self._broken:
                self._pending.append(line)
        # taps run OUTSIDE the emit lock (a subscriber may itself emit —
        # the in-process alert engine does, on a rule transition) and
        # behind a blanket except: a live consumer must never kill the
        # producer it watches
        for fn in self._subscribers:
            try:
                fn(ev)
            except Exception:
                pass
        return ev

    def subscribe(self, fn) -> None:
        """Call ``fn(event)`` on every subsequent emit (in the emitter's
        thread, outside the bus lock).  Subscribers guarding against
        their own kinds may emit; exceptions are swallowed."""
        self._subscribers.append(fn)

    def unsubscribe(self, fn) -> None:
        """Detach a tap installed by ``subscribe`` (no-op if absent) —
        sessions sharing one process-current bus must not leave stale
        consumers behind."""
        try:
            self._subscribers.remove(fn)
        except ValueError:
            pass

    def _write(self, line: str) -> None:
        # under self._lock
        try:
            self._file.write(line + "\n")
            self._file.flush()
        except OSError:
            self._broken = True
            try:
                self._file.close()
            except OSError:
                pass
            self._file = None

    # -------------------------------------------------------------- sink

    def bind_dir(self, directory: str | Path, filename: str | None = None) -> Path:
        """Open the append-only event file under ``directory`` and flush
        everything emitted so far."""
        path = Path(directory) / (filename or events_filename(self.process_index))
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None
            try:
                path.parent.mkdir(parents=True, exist_ok=True)
                self._file = open(path, "a")
            except OSError:
                self._broken = True
                self._pending.clear()
                return path
            self._path = path
            self._broken = False
            pending, self._pending = self._pending, []
            for line in pending:
                if self._file is None:
                    break
                self._write(line)
        return path

    @property
    def bound_path(self) -> Path | None:
        return self._path

    def _ring_line(self, ev: dict, line: str) -> str:
        """The serialization of ``ev`` that goes into a fixed-slot ring: the
        full line when it fits, otherwise the envelope with the payload
        replaced by a ``{"truncated": <bytes>}`` stub — a blindly cut JSON
        line would decode as a TORN slot, losing the event's kind and
        timing along with its bulk."""
        cap = self._mmap_ring.capacity
        if len(line.encode("utf-8", "replace")) <= cap:
            return line
        stub = {k: v for k, v in ev.items() if k != "payload"}
        stub["payload"] = {"truncated": len(line)}
        return json.dumps(stub, default=_jsonable)

    def attach_ring(
        self, path: str | Path, slots: int | None = None,
        slot_size: int | None = None,
    ) -> Path | None:
        """Back the flight recorder with an mmap'd fixed-slot file at
        ``path`` (blackbox.py): from here on every emit is also copied
        into the ring's next slot, and the file survives the process
        dying by ANY signal — including the SIGKILL/OOM deaths
        ``dump_crash`` can never catch.  The in-memory ring that was
        recorded before the attach seeds the file, so pre-bind events are
        not lost to the black box.  Never raises; returns the path or
        None when the ring could not be created."""
        from .blackbox import SLOT_SIZE_DEFAULT, MmapRing

        with self._lock:
            prev = self._mmap_ring
            try:
                ring = MmapRing(
                    path,
                    slots=slots or self._ring.maxlen,
                    slot_size=slot_size or SLOT_SIZE_DEFAULT,
                )
                self._mmap_ring = ring  # _ring_line reads its capacity
                for ev in self._ring:
                    ring.append(
                        self._ring_line(ev, json.dumps(ev, default=_jsonable))
                    )
            except (OSError, ValueError):
                self._mmap_ring = prev  # a failed attach keeps the old ring
                return None
            if prev is not None:
                prev.close()
        return ring.path

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None
            if self._mmap_ring is not None:
                self._mmap_ring.close()
                self._mmap_ring = None

    # --------------------------------------------------- flight recorder

    def ring_events(self) -> list[dict]:
        with self._lock:
            return list(self._ring)

    def dump_crash(
        self,
        reason: str,
        exc: BaseException | None = None,
        directory: str | Path | None = None,
        evidence: dict | None = None,
    ) -> Path | None:
        """Write ``crash_dump.json`` — the final ring of events plus the
        triggering reason/traceback — into ``directory`` (default: the
        bound event dir).  Returns the path, or None when there is nowhere
        to write.  Never raises.

        ``evidence`` (optional) lands under the dump's ``"evidence"`` key:
        the policy engine's ``abort_with_evidence`` attaches the alert and
        policy timelines here, so the post-mortem opens on WHY the run was
        stopped, not just its final ring.

        Idempotent per bus: the FIRST dump wins — an in-flight abort dumps
        with its specific reason, and the entry point's unhandled-exception
        net must not overwrite it with the generic re-raise."""
        if self._crash_path is not None:
            return self._crash_path
        target = Path(directory) if directory is not None else (
            self._path.parent if self._path is not None else None
        )
        if target is None:
            return None
        dump = {
            **self.stamp(),
            "t_wall": time.time(),
            "t_mono": time.monotonic(),
            "reason": str(reason),
            "ring": self.ring_events(),
        }
        if evidence:
            dump["evidence"] = evidence
        if exc is not None:
            dump["exception"] = {
                "type": type(exc).__name__,
                "message": str(exc),
                "traceback": traceback.format_exception(
                    type(exc), exc, exc.__traceback__
                ),
            }
        path = target / crash_dump_filename(self.attempt, self.process_index)
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            with open(path, "w") as f:
                json.dump(dump, f, indent=1, default=_jsonable)
        except OSError:
            return None
        self._crash_path = path
        return path


# ---------------------------------------------------------- process-current

_current: EventBus | None = None
_current_lock = threading.Lock()


def configure(
    run_id: str | None = None,
    attempt: int = 0,
    process_index: int = 0,
    ring_size: int = RING_SIZE_DEFAULT,
    persist: bool = True,
) -> EventBus:
    """Install a fresh bus as the process-current one and return it."""
    global _current
    bus = EventBus(
        run_id=run_id, attempt=attempt,
        process_index=process_index, ring_size=ring_size, persist=persist,
    )
    with _current_lock:
        old, _current = _current, bus
    if old is not None:
        old.close()
    return bus


def current_bus() -> EventBus:
    """The process-current bus (a default ring-only bus if none was ever
    configured — emits are never errors)."""
    global _current
    with _current_lock:
        if _current is None:
            # ring-only (persist=False): a default bus may never be bound,
            # and an unbounded pre-bind pending list would grow for the
            # life of the embedding process
            _current = EventBus(
                run_id=os.environ.get(RUN_ID_ENV) or new_run_id(),
                attempt=int(os.environ.get(ATTEMPT_ENV, "0") or 0),
                persist=False,
            )
        return _current


def emit(kind: str, **kwargs) -> dict:
    """Emit through the process-current bus."""
    return current_bus().emit(kind, **kwargs)


def reset(bus: EventBus | None = None) -> None:
    """Drop the process-current bus (tests; sequential Trainers in one
    process).  With ``bus`` given, only resets if that bus is still the
    current one — a Trainer closing must not tear down its successor's."""
    global _current
    with _current_lock:
        if bus is not None and _current is not bus:
            return
        old, _current = _current, None
    if old is not None:
        old.close()


# ----------------------------------------------------------------- schema


def validate_event(ev: object) -> list[str]:
    """Violations of the versioned schema (empty list = valid).

    Strict on the envelope — unknown top-level keys are violations, so
    schema drift fails ``run_report --check`` instead of silently forking
    the format — and permissive on the payload (any JSON object).
    """
    if not isinstance(ev, dict):
        return [f"event is {type(ev).__name__}, not an object"]
    errs = []
    for key in _REQUIRED:
        if key not in ev:
            errs.append(f"missing required field {key!r}")
    for key in ev:
        if key not in _REQUIRED and key not in _OPTIONAL:
            errs.append(f"unknown field {key!r}")
    if "v" in ev and ev["v"] != SCHEMA_VERSION:
        errs.append(f"schema version {ev['v']!r} != {SCHEMA_VERSION}")
    for key, types in (
        ("run_id", str), ("kind", str),
        ("attempt", int), ("process_index", int),
        ("t_wall", (int, float)), ("t_mono", (int, float)),
        ("epoch", int), ("step", int),
    ):
        if key in ev and (
            not isinstance(ev[key], types) or isinstance(ev[key], bool)
        ):
            errs.append(f"field {key!r} has type {type(ev[key]).__name__}")
    if "run_id" in ev and isinstance(ev["run_id"], str) and not ev["run_id"]:
        errs.append("run_id is empty")
    if "kind" in ev and isinstance(ev["kind"], str):
        if not ev["kind"]:
            errs.append("kind is empty")
        elif ev["kind"] not in KNOWN_KINDS:
            errs.append(
                f"kind {ev['kind']!r} is not registered "
                "(obs.bus.KNOWN_KINDS / register_kind)"
            )
    for key in ("attempt", "process_index"):
        if isinstance(ev.get(key), int) and ev[key] < 0:
            errs.append(f"field {key!r} is negative")
    if "payload" in ev and not isinstance(ev["payload"], dict):
        errs.append(f"payload has type {type(ev['payload']).__name__}")
    return errs


def load_events(path: str | Path) -> list[dict]:
    """Parse one ``events*.jsonl`` file; a torn trailing line (the writer
    died mid-append) must not void the good records."""
    path = Path(path)
    if not path.exists():
        return []
    events = []
    for line in path.read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            events.append(json.loads(line))
        except ValueError:
            continue
    return events
