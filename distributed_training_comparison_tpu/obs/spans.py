"""Host-side span tracing: nestable begin/end pairs on every thread.

``span("epoch")`` / ``span("h2d_stage")`` context managers record wall
intervals per thread — the trainer loop, the ``DevicePrefetcher``
producer, the ``AsyncCheckpointer`` writer — into one process-wide
recorder.  Export is Chrome-trace JSON (``chrome_trace``): open it in
Perfetto / ``chrome://tracing`` and the threads render as lanes, so
compute, input staging, checkpointing, and rollback visibly overlap (or
fail to).

Spans nest strictly by construction: each is a context manager pushed and
popped on a per-thread stack, so a thread's spans at depth d always lie
inside its enclosing depth d-1 span — the invariant the export test pins.

A span is a node of a tree: besides name, interval, thread and depth it
records an ``id``, the ``parent`` id — the span open beneath it on its
thread, or the span another thread names as its cause (``span(...,
parent=)``: the checkpoint writer's ``ckpt_write`` names the trainer's
``ckpt_submit`` that queued its job) — and the ``epoch`` it belongs to,
given as an attribute or inherited from the parent, so one epoch's spans
share an identifier.

Every span also enters a ``jax.profiler.TraceAnnotation``, which is inert
while no profiler session runs.  So the host lines of ANY profiler trace —
a ``--profile-dir`` capture, or one an embedder starts around ``fit()`` —
carry these names on the trace's own clock, beside the device's ops
(``benchmark/harness/host_spans.py`` books the device's idle time to them).
With no session the cost of a span is two clock reads, one dict append and
the annotation's no-op enter and exit.
"""

from __future__ import annotations

import functools
import itertools
import json
import threading
import time
import zlib
from contextlib import nullcontext
from pathlib import Path

TRACE_NAME = "trace.json"
MAX_SPANS_DEFAULT = 200_000


def trace_filename(attempt: int = 0, process_index: int = 0) -> str:
    """Per-attempt (and, off process 0, per-process) trace file name."""
    if attempt == 0 and process_index == 0:
        return TRACE_NAME
    if process_index == 0:
        return f"trace-a{attempt}.json"
    return f"trace-a{attempt}-p{process_index}.json"


class SpanRecorder:
    """Collects closed spans for one process; thread-safe."""

    def __init__(
        self, process_index: int = 0, max_spans: int = MAX_SPANS_DEFAULT
    ) -> None:
        self.process_index = int(process_index)
        self.max_spans = int(max_spans)
        self._lock = threading.Lock()
        self._spans: list[dict] = []
        self._dropped = 0
        self._local = threading.local()
        self._ids = itertools.count(1)  # next() is atomic under the GIL

    def _stack(self) -> "list[_Span]":
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
            # looked up once a thread: a span pays no current_thread()
            thread = threading.current_thread()
            self._local.thread = (thread.ident, thread.name)
        return stack

    def span(self, name: str, parent: "_Span | None" = None, **attrs):
        """Context manager recording one span on the calling thread; it
        yields the open span.  ``parent`` names a span of ANOTHER thread as
        this one's cause, in place of the one open beneath it here."""
        return _Span(self, name, parent, attrs)

    def open_span(self) -> "_Span | None":
        """The innermost span open on the calling thread: what a job handed
        to another thread names as its cause."""
        stack = self._stack()
        return stack[-1] if stack else None

    def _append(self, rec: dict) -> None:
        with self._lock:
            if len(self._spans) < self.max_spans:
                self._spans.append(rec)
            else:
                self._dropped += 1

    def record(
        self, name: str, t0: float, t1: float, *, lane: str | None = None,
        **attrs,
    ) -> None:
        """Append an externally-timed span (``time.monotonic`` values,
        same clock as :meth:`span`).  ``lane`` names a SYNTHETIC timeline
        lane — a stable pseudo thread id derived from the lane name — so
        derived timelines (the pipeline's per-(host, stage) lanes, where
        one dispatch interval is subdivided by the schedule's tick
        structure) render as their own Perfetto rows instead of
        interleaving with the recording thread's real spans."""
        if lane is None:
            thread = threading.current_thread()
            tid, tname = thread.ident, thread.name
        else:
            # high bit keeps pseudo-ids clear of real thread idents
            tid = 0x5A000000 | (zlib.crc32(str(lane).encode()) & 0xFFFFFF)
            tname = str(lane)
        rec = {
            "name": str(name),
            "t0": float(t0),
            "t1": float(t1),
            "thread_id": tid,
            "thread_name": tname,
            "depth": 0,
            "id": next(self._ids),
            "parent": None,
            "epoch": attrs.get("epoch"),
        }
        if attrs:
            rec["args"] = attrs
        self._append(rec)

    def spans(self) -> list[dict]:
        with self._lock:
            return list(self._spans)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped


class _Span:
    """One open span: a plain class, not a generator, since spans wrap hot
    host paths (every chunk dispatch) and a generator's frame costs more
    than the span's own work."""

    __slots__ = (
        "rec", "name", "cause", "attrs", "stack", "ann", "t0", "id", "parent",
        "epoch",
    )

    def __init__(
        self, rec: SpanRecorder, name: str, cause: "_Span | None", attrs: dict
    ) -> None:
        self.rec, self.name, self.cause, self.attrs = rec, name, cause, attrs

    def __enter__(self) -> "_Span":
        rec = self.rec
        stack = self.stack = rec._stack()
        cause = self.cause or (stack[-1] if stack else None)
        self.id = next(rec._ids)
        if cause is None:
            self.parent, self.epoch = None, self.attrs.get("epoch")
        else:
            self.parent = cause.id
            self.epoch = self.attrs.get("epoch", cause.epoch)
        stack.append(self)
        self.ann = _trace_annotation(self.name)
        self.t0 = time.monotonic()
        self.ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self.ann.__exit__(*exc)
        t1 = time.monotonic()
        self.stack.pop()
        thread_id, thread_name = self.rec._local.thread
        rec = {
            "name": str(self.name),
            "t0": self.t0,
            "t1": t1,
            "thread_id": thread_id,
            "thread_name": thread_name,
            "depth": len(self.stack),
            "id": self.id,
            "parent": self.parent,
            "epoch": self.epoch,
        }
        if self.attrs:
            rec["args"] = self.attrs
        self.rec._append(rec)


@functools.cache
def _annotation_class():
    """``jax.profiler.TraceAnnotation`` if this jax exposes one, looked up
    once: a span pays no import machinery."""
    try:
        import jax.profiler

        return jax.profiler.TraceAnnotation
    except (ImportError, AttributeError):  # pragma: no cover - exotic jax
        return lambda name: nullcontext()


def _trace_annotation(name: str):
    return _annotation_class()(name)


# ---------------------------------------------------------- chrome export


def chrome_trace(
    spans: list[dict],
    process_index: int = 0,
    label: str | None = None,
    dropped: int = 0,
) -> dict:
    """Spans → the Chrome Trace Event JSON object Perfetto loads.

    Complete ("X") events carry begin+duration in one record, so the
    strict nesting the recorder guarantees arrives intact; thread/process
    metadata events name the lanes.
    """
    events: list[dict] = []
    threads: dict[int, str] = {}
    for s in spans:
        tid = int(s.get("thread_id") or 0)
        threads.setdefault(tid, str(s.get("thread_name") or f"thread-{tid}"))
        ev = {
            "ph": "X",
            "name": s["name"],
            "pid": process_index,
            "tid": tid,
            "ts": round(s["t0"] * 1e6, 3),   # microseconds, Chrome's unit
            "dur": round(max(0.0, s["t1"] - s["t0"]) * 1e6, 3),
        }
        # the tree rides in args: Perfetto shows them on a click, and a
        # reader of the file rebuilds parent -> children from them
        tree = {k: s[k] for k in ("id", "parent", "epoch") if s.get(k) is not None}
        if s.get("args") or tree:
            ev["args"] = {**(s.get("args") or {}), **tree}
        events.append(ev)
    events.sort(key=lambda e: (e["tid"], e["ts"], -e["dur"]))
    name = label or f"process {process_index}"
    if dropped:
        # a trace that hit the recorder cap is TRUNCATED, not quiet — name
        # the lane so a Perfetto reader can't mistake the cutoff for the
        # run going idle
        name += f" [TRUNCATED: {dropped} spans dropped at cap]"
    meta: list[dict] = [
        {
            "ph": "M", "name": "process_name", "pid": process_index,
            "args": {"name": name},
        }
    ]
    for tid, tname in sorted(threads.items()):
        meta.append(
            {
                "ph": "M", "name": "thread_name", "pid": process_index,
                "tid": tid, "args": {"name": tname},
            }
        )
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def write_chrome_trace(
    path: str | Path,
    recorder: "SpanRecorder | list[dict]",
    label: str | None = None,
) -> Path | None:
    """Export a recorder (or raw span list) to ``path``; never raises —
    trace export is accounting."""
    if isinstance(recorder, SpanRecorder):
        spans, pidx, dropped = (
            recorder.spans(), recorder.process_index, recorder.dropped,
        )
    else:
        spans, pidx, dropped = list(recorder), 0, 0
    path = Path(path)
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        with open(path, "w") as f:
            json.dump(chrome_trace(spans, pidx, label=label, dropped=dropped), f)
    except OSError:
        return None
    return path


# ---------------------------------------------------------- process-current

_current: SpanRecorder | None = None
_current_lock = threading.Lock()


def set_recorder(recorder: SpanRecorder | None) -> SpanRecorder | None:
    """Install ``recorder`` as process-current; returns the previous one."""
    global _current
    with _current_lock:
        old, _current = _current, recorder
    return old


def current_recorder() -> SpanRecorder:
    """The process-current recorder (created on first use)."""
    global _current
    with _current_lock:
        if _current is None:
            _current = SpanRecorder()
        return _current


def span(name: str, **attrs):
    """Record a span on the process-current recorder."""
    return current_recorder().span(name, **attrs)


def open_span():
    """The innermost span open on the calling thread, on the
    process-current recorder (``SpanRecorder.open_span``)."""
    return current_recorder().open_span()
