"""Merge a run's event stream into one timeline + summary; validate it.

Usage::

    python tools/run_report.py CKPT_ROOT              # summary + timeline
    python tools/run_report.py CKPT_ROOT --check      # schema validation
    python tools/run_report.py RUN_A RUN_B --diff     # compare two runs
    python tools/run_report.py version-0/events.jsonl --timeline 50
    python tools/run_report.py CKPT_ROOT --follow     # tail an in-flight run
    python tools/run_report.py CKPT_ROOT --blackbox   # decode flight rings
    python tools/run_report.py CKPT_ROOT --alerts     # alert timeline; rc=1
                                                      # while any rule fires
    python tools/run_report.py CKPT_ROOT --policy     # autopilot decision
                                                      # timeline; rc=1 on any
                                                      # action still pending
    python tools/run_report.py CKPT_ROOT --compute    # per-executable
                                                      # cost/memory/MFU table
    python tools/run_report.py CKPT_ROOT --plan       # auto-parallel plan
                                                      # prediction vs measured;
                                                      # rc=1 when an installed
                                                      # plan was ignored
    python tools/run_report.py CKPT_ROOT --serve      # per-SLO-class serving
                                                      # attainment table; rc=1
                                                      # on any class below its
                                                      # target
    python tools/run_report.py CKPT_ROOT --trace      # per-SLO-class request
                                                      # critical-path table
                                                      # from kept traces; rc=1
                                                      # when a deadlined class
                                                      # breached with zero
                                                      # kept traces
    python tools/run_report.py CKPT_ROOT --export-openmetrics [OUT]
                                                      # offline scrape render

``CKPT_ROOT`` is a training run's checkpoint root: every ``events*.jsonl``
under it — the supervisor's at the root, each attempt's (and, multi-host,
each process's) in the ``version-*`` dirs — is merged into ONE timeline
ordered by wall clock, with per-attempt summaries: epochs trained, goodput
phases, rollback causes, preemption points, checkpoint-writer busy
fraction, h2d wait, and the per-step metric sketches (``metrics`` events)
reconstructed into grad-norm / step-phase p50/p95/p99.  A version dir or a
single jsonl file also works.

Cross-host merge no longer trusts NTP: per-host clock offsets are fitted
from the ``run_start`` events every process emits together (post-broadcast,
so near-simultaneous on the true timeline) and subtracted before ordering —
one offset per host *per attempt*, so clock drift across a multi-day run's
restarts is refitted at every relaunch.  One-host runs and runs without
shared anchors merge unshifted.

``--check`` validates every record against the versioned event schema
(``obs/bus.py``) and exits nonzero on any violation — bench legs run it so
a capture self-validates before anyone trusts the numbers.

``--diff`` compares the FIRST run against the second: the question an
observability change answers is "did the second run absorb the same
faults with less waste".

``--follow`` tails every event file under the root (new attempts' files
are picked up as they appear) and prints timeline lines as events land —
the live view of an in-flight run.

``--blackbox`` decodes every mmap flight ring (``flight*.ring`` — written
by the SIGKILL-surviving recorder, torn pages dropped slot-wise) into one
``blackbox.json`` at the root, the same pull the supervisor does after
every attempt.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from distributed_training_comparison_tpu.obs import (  # noqa: E402
    alert_timeline,
    collect_black_box,
    decode_ring,
    final_states,
    find_rings,
    histogram_summary,
    load_events,
    merge_metric_events,
    peak_flops_for,
    render_openmetrics,
    straggler,
    validate_event,
)

TIMELINE_TAIL = 20
# supervisor-side kinds: their envelope attempt is the supervisor's own
# (0); the payload names the child attempt they concern.  `resize` is the
# elastic fleet supervisor's world-size re-render (shrink/expand).
SUPERVISOR_KINDS = {
    "attempt_start", "attempt_end", "backoff", "give_up", "run_summary",
    "resize",
}
# live-operations kinds: summarized fleet-wide, not per attempt (stall/
# straggler/alert payloads name the attempt+process they concern)
FLEET_KINDS = {"stall", "straggler", "alert"}


def find_event_files(path: str | Path) -> list[Path]:
    p = Path(path)
    if p.is_file():
        return [p]
    return sorted(p.glob("events*.jsonl")) + sorted(
        p.glob("version-*/events*.jsonl")
    )


def load_run(
    path: str | Path, skew_out: dict | None = None
) -> tuple[list[dict], list[Path]]:
    """All events under ``path``, merged and wall-clock ordered (per-host
    clock skew estimated and removed before ordering).  ``skew_out``, if
    given, receives the fitted per-(process, attempt) offsets — callers
    that report them don't re-read the files."""
    files = find_event_files(path)
    events: list[dict] = []
    for f in files:
        events.extend(load_events(f))
    offsets = estimate_clock_skew_by_attempt(events)
    if skew_out is not None:
        skew_out.update(offsets)
    events = apply_clock_skew(events, offsets)
    events.sort(key=lambda e: (e.get("t_wall", 0.0), e.get("t_mono", 0.0)))
    return events, files


# -------------------------------------------------------------- clock skew
#
# Cross-host ordering used to assume NTP-sane clocks.  The anchor that
# frees it from that assumption: every process emits ``run_start`` right
# after a broadcast collective (the run-id agreement), so for one attempt
# all hosts' ``run_start`` stamps name nearly the same true instant —
# their differences are (almost entirely) clock offset, and every attempt
# contributes one more anchor pair per host.  The supervisor's
# ``attempt_start`` rows are NOT anchors: a single emitter (process 0's
# timebase) has nothing to pair against, which is also why its events
# need no fitting.

# event kinds emitted near-simultaneously by every process of an attempt
_SYNC_KINDS = ("run_start",)


def estimate_clock_skew(events: list[dict]) -> dict[int, float]:
    """Per-process wall-clock offset (seconds, relative to process 0)
    fitted from the sync-anchor events: ``offset[p]`` is the median of
    ``t_wall(anchor@p) - t_wall(anchor@0)`` over every shared
    ``(attempt, kind)`` anchor.  One-host runs, processes with no shared
    anchor (e.g. an attempt that died pre-``run_start``), and empty event
    lists all yield offset 0 — the estimator degrades to the old merge,
    never breaks it."""
    # anchor[(attempt, kind)][process] = first t_wall seen
    anchors: dict[tuple, dict[int, float]] = defaultdict(dict)
    for ev in events:
        kind = ev.get("kind")
        if kind not in _SYNC_KINDS or ev.get("t_wall") is None:
            continue
        key = (ev.get("attempt", 0), kind)
        anchors[key].setdefault(int(ev.get("process_index", 0)), ev["t_wall"])
    deltas: dict[int, list[float]] = defaultdict(list)
    for per_proc in anchors.values():
        if 0 not in per_proc:
            continue
        for p, t in per_proc.items():
            if p != 0:
                deltas[p].append(t - per_proc[0])
    processes = {int(e.get("process_index", 0)) for e in events}
    offsets = {p: 0.0 for p in processes}
    for p, ds in deltas.items():
        ds = sorted(ds)
        mid = len(ds) // 2
        offsets[p] = (
            ds[mid] if len(ds) % 2 else 0.5 * (ds[mid - 1] + ds[mid])
        )
    return offsets


def estimate_clock_skew_by_attempt(events: list[dict]) -> dict:
    """Per-(process, attempt) wall-clock offsets — the multi-day-drift
    refinement of ``estimate_clock_skew``: one constant per host was fine
    for one attempt, but a run whose attempts span days accumulates real
    drift between them, and each attempt's ``run_start`` anchors already
    measure their own instant.  Returns ``{(process, attempt): offset}``
    plus a ``(process, None)`` fallback (the across-attempt median) for
    events of an attempt that died before its anchor."""
    anchors: dict[tuple, dict[int, float]] = defaultdict(dict)
    for ev in events:
        kind = ev.get("kind")
        if kind not in _SYNC_KINDS or ev.get("t_wall") is None:
            continue
        key = (ev.get("attempt", 0), kind)
        anchors[key].setdefault(int(ev.get("process_index", 0)), ev["t_wall"])
    offsets: dict = {}
    per_proc: dict[int, list[float]] = defaultdict(list)
    for (attempt, _kind), procs in anchors.items():
        if 0 not in procs:
            continue
        for p, t in procs.items():
            if p == 0:
                continue
            delta = t - procs[0]
            # multiple anchor kinds per attempt would land here twice;
            # the first fitted one wins (today there is one: run_start)
            offsets.setdefault((p, attempt), delta)
            per_proc[p].append(delta)
    processes = {int(e.get("process_index", 0)) for e in events}
    for p in processes:
        ds = sorted(per_proc.get(p, []))
        mid = len(ds) // 2
        offsets[(p, None)] = (
            0.0 if not ds
            else ds[mid] if len(ds) % 2 else 0.5 * (ds[mid - 1] + ds[mid])
        )
    return offsets


def apply_clock_skew(events: list[dict], offsets: dict) -> list[dict]:
    """Shift each event's ``t_wall`` onto process 0's clock.  Accepts the
    per-process shape (``{process: offset}``) and the per-attempt shape
    (``{(process, attempt): offset}`` with ``(process, None)`` fallbacks);
    events with a zero/absent offset pass through untouched."""
    if not offsets or not any(abs(v) > 1e-9 for v in offsets.values()):
        return events
    by_attempt = any(isinstance(k, tuple) for k in offsets)
    out = []
    for ev in events:
        p = int(ev.get("process_index", 0))
        if by_attempt:
            off = offsets.get((p, int(ev.get("attempt", 0))))
            if off is None:
                off = offsets.get((p, None), 0.0)
        else:
            off = offsets.get(p, 0.0)
        if abs(off) > 1e-9 and ev.get("t_wall") is not None:
            ev = dict(ev, t_wall=ev["t_wall"] - off)
        out.append(ev)
    return out


def check_run(
    path: str | Path,
    counts: list | None = None,
    require_kinds=(),
) -> list[str]:
    """Schema violations across every event file under ``path`` (one read
    per file).  ``counts``, when given, receives the per-file parsed-event
    counts so the caller can report totals without re-reading.
    ``require_kinds`` names event kinds the merged stream MUST contain —
    the bench legs assert their captures carry ``compile`` events, so a
    silently-degraded compile hook fails the capture's self-validation
    instead of committing a record with the ledger missing."""
    problems: list[str] = []
    files = find_event_files(path)
    if not files:
        problems.append(f"{path}: no events*.jsonl found")
        return problems
    seen_kinds: set = set()
    for f in files:
        parsed: list[dict] = []
        torn = 0
        for line in f.read_text().splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                parsed.append(json.loads(line))
            except ValueError:
                torn += 1
        if torn:
            problems.append(f"{f}: {torn} unparseable line(s)")
        for i, ev in enumerate(parsed):
            if isinstance(ev, dict) and ev.get("kind"):
                seen_kinds.add(ev["kind"])
            for err in validate_event(ev):
                problems.append(f"{f}:{i + 1}: {err}")
        if counts is not None:
            counts.append(len(parsed))
    for kind in require_kinds or ():
        if kind not in seen_kinds:
            problems.append(
                f"{path}: no {kind!r} events in the stream "
                "(--require-kind)"
            )
    return problems


# ----------------------------------------------------------------- summary


def _payload(ev: dict) -> dict:
    return ev.get("payload") or {}


def summarize(events: list[dict]) -> dict:
    """Fold one run's merged events into per-attempt and overall stats."""
    attempts: dict[int, dict] = defaultdict(
        lambda: {
            "epochs": 0, "rollbacks": 0, "rollback_causes": [],
            "skips": 0, "spikes": 0, "desyncs": 0, "aborts": [],
            "preempt": None, "goodput": None, "writer": None,
            "t_first": None, "t_last": None, "processes": set(),
            "metrics_events": 0, "metrics": {}, "heartbeats": 0,
            "state_layout": None,
        }
    )
    run_ids: set[str] = set()
    supervisor: list[dict] = []
    fleet: list[dict] = []
    for ev in events:
        if ev.get("run_id"):
            run_ids.add(ev["run_id"])
        kind = ev.get("kind")
        if kind in SUPERVISOR_KINDS:
            supervisor.append(ev)
            continue
        if kind in FLEET_KINDS:
            fleet.append(ev)
            continue
        a = attempts[int(ev.get("attempt", 0))]
        t = ev.get("t_wall")
        if t is not None:
            a["t_first"] = t if a["t_first"] is None else min(a["t_first"], t)
            a["t_last"] = t if a["t_last"] is None else max(a["t_last"], t)
        a["processes"].add(int(ev.get("process_index", 0)))
        if kind == "heartbeat":
            # liveness ticks from EVERY process count (that is their job);
            # they carry no per-attempt work to fold beyond the count
            a["heartbeats"] += 1
            continue
        if int(ev.get("process_index", 0)) != 0:
            # every process emits the same trainer/watchdog events into its
            # own file; count each occurrence once (process 0's) so a
            # 2-host attempt doesn't report doubled epochs/rollbacks
            continue
        p = _payload(ev)
        if kind == "run_start":
            # the resident layout the attempt's trunk stack actually carried
            a["state_layout"] = p.get("state_layout") or "contiguous"
        elif kind == "epoch_end":
            a["epochs"] += 1
        elif kind == "rollback":
            a["rollbacks"] += 1
            if p.get("reason"):
                a["rollback_causes"].append(
                    f"epoch {ev.get('epoch', '?')}: {p['reason']}"
                )
        elif kind == "skip":
            a["skips"] += int(p.get("count", 1))
        elif kind == "spike":
            a["spikes"] += int(p.get("count", 1))
        elif kind == "desync":
            a["desyncs"] += 1
        elif kind == "abort":
            a["aborts"].append(p.get("reason", ""))
        elif kind == "preempt":
            a["preempt"] = {
                "epoch": ev.get("epoch"), "step": ev.get("step"),
                "mid_epoch": p.get("mid_epoch"),
            }
        elif kind == "goodput":
            a["goodput"] = p
        elif kind == "writer":
            a["writer"] = p  # last one wins (latest gauge)
        elif kind == "metrics":
            # fold the flush's sketches into the attempt's running merge —
            # the associativity the sketch format guarantees is exactly
            # what lets a summary accumulate event by event.  Process-0
            # only (the gate above): grad_norm/loss are replicated global
            # values every process records identically, and double-merging
            # them would double every count.
            a["metrics_events"] += 1
            a["metrics"] = merge_metric_events(
                [{"metrics": a["metrics"]}, ev]
            )
        elif kind == "serve" and p.get("latency_hist"):
            # the serve record carries the latency sketch DELTA since the
            # last periodic flush (ServeMetrics.emit_event) — merging it
            # here completes the distribution the `metrics` events began
            # (and IS the whole distribution for sessions shorter than
            # the periodic emit interval)
            a["metrics"] = merge_metric_events([
                {"metrics": a["metrics"]},
                {"metrics": {"serve/latency_s": p["latency_hist"]}},
            ])
    overall = {
        "run_ids": sorted(run_ids),
        "attempts": {k: attempts[k] for k in sorted(attempts)},
        "supervisor": supervisor,
        "fleet": fleet,
        # the per-host step-phase table + findings the straggler module
        # computes straight off the (per-process) metrics events — the
        # cross-host view the per-attempt fold above deliberately dedups
        # away
        "straggler_lines": straggler.format_table(events),
        # the per-executable compile/cost/memory fold (PR 8) — --compute
        # renders it; --diff compares its totals across runs
        "compute": compute_summary(events),
        # per-class trace-segment p95s from kept request traces — the
        # --diff rows; {} when the run kept no traces
        "trace_classes": trace_diff_cells(events),
        "events": len(events),
        "rollbacks": sum(a["rollbacks"] for a in attempts.values()),
        "epochs": sum(a["epochs"] for a in attempts.values()),
        "preemptions": sum(
            1 for a in attempts.values() if a["preempt"] is not None
        ),
        "productive_s": sum(
            float((a["goodput"] or {}).get("step_s", 0.0))
            for a in attempts.values()
        ),
        "wall_s": sum(
            float((a["goodput"] or {}).get("wall_s", 0.0))
            for a in attempts.values()
        ),
        "h2d_wait_s": sum(
            float(
                ((a["goodput"] or {}).get("step_breakdown") or {}).get(
                    "h2d_wait_s", 0.0
                )
            )
            for a in attempts.values()
        ),
    }
    overall["goodput_frac"] = (
        overall["productive_s"] / overall["wall_s"]
        if overall["wall_s"] > 0
        else 0.0
    )
    return overall


def format_summary(name: str, s: dict) -> str:
    lines = [
        f"run {'+'.join(s['run_ids']) or '?'} — {len(s['attempts'])} "
        f"attempt(s), {s['events']} events ({name})"
    ]
    header = (
        f"{'attempt':>7} {'procs':>5} {'epochs':>6} {'wall':>9} "
        f"{'goodput':>8} {'rollbk':>6} {'skips':>5} {'spikes':>6} "
        f"{'preempt':>12} {'wr.busy':>7} {'wr.q':>4} {'h2d_wait':>9}"
    )
    lines += [header, "-" * len(header)]
    for idx, a in s["attempts"].items():
        gp = a["goodput"] or {}
        wall = (
            gp.get("wall_s")
            if gp.get("wall_s") is not None
            else (
                (a["t_last"] - a["t_first"])
                if a["t_first"] is not None
                else 0.0
            )
        )
        writer = a["writer"] or gp.get("ckpt_writer") or {}
        pre = a["preempt"]
        pre_str = (
            "-"
            if pre is None
            else f"e{pre['epoch']}" + (
                f"@s{pre['step']}" if pre.get("mid_epoch") else ""
            )
        )
        h2d = float((gp.get("step_breakdown") or {}).get("h2d_wait_s", 0.0))
        frac = gp.get("productive_frac")
        frac_str = f"{100 * frac:7.1f}%" if frac is not None else f"{'?':>8}"
        lines.append(
            f"{idx:>7} {len(a['processes']):>5} {a['epochs']:>6}"
            f" {wall or 0.0:>8.1f}s {frac_str}"
            f" {a['rollbacks']:>6} {a['skips']:>5} {a['spikes']:>6}"
            f" {pre_str:>12}"
            f" {100 * float(writer.get('busy_frac', 0.0)):>6.1f}%"
            f" {writer.get('queue_depth', 0):>4}"
            f" {h2d:>8.2f}s"
        )
    layouts = {
        idx: a["state_layout"]
        for idx, a in s["attempts"].items()
        if a.get("state_layout")
    }
    if layouts:
        lines.append(
            "  state layout: "
            + ", ".join(
                f"attempt {idx}: {tag}" for idx, tag in layouts.items()
            )
        )
    for idx, a in s["attempts"].items():
        for cause in a["rollback_causes"]:
            lines.append(f"  rollback (attempt {idx}) {cause}")
        for reason in a["aborts"]:
            lines.append(f"  abort (attempt {idx}) {reason}")
    for idx, a in s["attempts"].items():
        # per-step sketches reconstructed across this attempt's flushes:
        # distribution stats nothing per-epoch could provide
        if not a["metrics"]:
            continue
        lines.append(
            f"  metrics (attempt {idx}, {a['metrics_events']} flush(es)):"
        )
        for nm in sorted(a["metrics"]):
            snap = a["metrics"][nm]
            if snap.get("type") == "histogram":
                summ = histogram_summary(snap)
                if summ is None:
                    continue
                lines.append(
                    f"    {nm}: p50={summ['p50']:.4g} p95={summ['p95']:.4g} "
                    f"p99={summ['p99']:.4g} mean={summ['mean']:.4g} "
                    f"max={summ['max']:.4g} (n={summ['count']}"
                    + (
                        f", nonfinite={snap['nonfinite']}"
                        if snap.get("nonfinite")
                        else ""
                    )
                    + ")"
                )
            elif snap.get("type") == "counter":
                lines.append(f"    {nm}: {snap.get('n', 0)}")
            else:
                lines.append(f"    {nm}: {snap.get('value')}")
    beats = sum(a.get("heartbeats", 0) for a in s["attempts"].values())
    if beats:
        lines.append(
            "  heartbeats: "
            + ", ".join(
                f"attempt {idx}: {a['heartbeats']}"
                for idx, a in s["attempts"].items()
                if a.get("heartbeats")
            )
        )
    lines.extend(s.get("straggler_lines") or [])
    pipe = (s.get("compute") or {}).get("pipeline")
    if pipe is not None:
        # the pipeline section: schedule arithmetic + the measured
        # per-executable bubble table (one line each — --compute has the
        # full cost/memory context)
        lines.extend(format_pipeline(pipe["meta"], pipe["rows"]))
    # stall calls condense to one line per process (counts per state +
    # the final state) — a run whose heartbeat cadence undershoots its
    # chunk time can transition hundreds of times, and the echo must not
    # bury the table; the full sequence lives in `--alerts`
    stall_by_proc: dict = {}
    for ev in s.get("fleet") or []:
        p = _payload(ev)
        if ev["kind"] == "stall":
            rec = stall_by_proc.setdefault(
                p.get("process_index", "?"), {"counts": {}, "last": None}
            )
            state = p.get("state", "?")
            rec["counts"][state] = rec["counts"].get(state, 0) + 1
            rec["last"] = p
        elif ev["kind"] == "alert":
            lines.append(
                f"  alert {p.get('state', '?')}: {p.get('spec', '?')} "
                f"(value {p.get('value', '?')}"
                + (
                    f" @ {p['source']}" if p.get("source") else ""
                )
                + ")"
            )
        # straggler events echo what straggler_lines already tabulates
    for proc, rec in sorted(stall_by_proc.items(), key=lambda kv: str(kv[0])):
        counts = ", ".join(
            f"{state}×{n}" for state, n in sorted(rec["counts"].items())
        )
        last = rec["last"] or {}
        lines.append(
            f"  stalls: process {proc} {counts} "
            f"(last: {last.get('state', '?')}, age {last.get('age_s', '?')}s"
            + (
                f", {last['behind_steps']} steps behind"
                if last.get("behind_steps") is not None
                else ""
            )
            + ")"
        )
    # the elastic fleet's per-attempt world sizes + resize timeline: the
    # attempt_start payloads carry the re-rendered launch set, resize
    # events the shrink/expand decisions (ISSUE 10)
    worlds = {}
    for ev in s["supervisor"]:
        p = _payload(ev)
        if ev["kind"] == "attempt_start" and p.get("world_size"):
            worlds[p.get("attempt", "?")] = (
                p["world_size"], p.get("hosts")
            )
        elif ev["kind"] == "resize":
            delta = []
            if p.get("lost"):
                delta.append(f"lost {p['lost']}")
            if p.get("returned"):
                delta.append(f"returned {p['returned']}")
            lines.append(
                f"  resize (attempt {p.get('attempt', '?')}): world "
                f"{p.get('from_world', '?')} -> {p.get('to_world', '?')} "
                f"({p.get('reason', '?')}"
                + (f"; {', '.join(delta)}" if delta else "")
                + ")"
            )
    if worlds:
        lines.append(
            "  world sizes: " + ", ".join(
                f"a{a}={w}" + (f" hosts={h}" if h else "")
                for a, (w, h) in sorted(worlds.items(), key=lambda kv: str(kv[0]))
            )
        )
    if s["supervisor"]:
        sup = ", ".join(
            f"{e['kind']}[a{_sup_attempt(e)}]" for e in s["supervisor"]
        )
        lines.append(f"  supervisor: {sup}")
    lines.append(
        f"  overall: {s['epochs']} epochs over {len(s['attempts'])} "
        f"attempt(s), goodput {100 * s['goodput_frac']:.1f}%, "
        f"{s['rollbacks']} rollback(s), {s['preemptions']} preemption(s)"
    )
    return "\n".join(lines)


def _sup_attempt(ev: dict):
    return _payload(ev).get("attempt", "?")


# ---------------------------------------------------------------- timeline


def format_event(ev: dict, t0: float) -> str:
    """One timeline line (shared by the static tail and ``--follow``)."""
    where = f"a{ev.get('attempt', '?')}/p{ev.get('process_index', '?')}"
    at = ""
    if "epoch" in ev:
        at = f" epoch={ev['epoch']}"
        if "step" in ev:
            at += f" step={ev['step']}"
    p = _payload(ev)
    if ev.get("kind") == "metrics":
        # a flush's payload is sketches — summarize instead of dumping
        names = sorted((p.get("metrics") or {}))
        brief = f"{len(names)} metric(s): " + ", ".join(names[:4]) + (
            ", …" if len(names) > 4 else ""
        )
    else:
        brief = ", ".join(
            f"{k}={p[k]}"
            for k in list(p)[:4]
            if not isinstance(p[k], (dict, list))
        )
    return (
        f"[{ev.get('t_wall', 0.0) - t0:>9.3f}s {where:>7}] "
        f"{ev.get('kind', '?')}{at}"
        + (f"  ({brief})" if brief else "")
    )


def format_timeline(events: list[dict], tail: int = TIMELINE_TAIL) -> str:
    if not events:
        return "(no events)"
    t0 = events[0].get("t_wall", 0.0)
    lines = []
    shown = events[-tail:] if tail and tail > 0 else events
    if len(shown) < len(events):
        lines.append(f"... ({len(events) - len(shown)} earlier events)")
    lines.extend(format_event(ev, t0) for ev in shown)
    return "\n".join(lines)


# ------------------------------------------------------------------ follow


def follow_events(
    path: str | Path,
    poll_s: float = 0.5,
    max_polls: int | None = None,
    sleep=time.sleep,
):
    """Yield batches of new events under ``path`` as they are appended —
    the tail of an in-flight run.  Rescans for NEW files every poll (each
    restart attempt opens its own ``events*.jsonl``), remembers a byte
    offset per file, and never yields a torn trailing line (it stays
    buffered until the writer completes it).  ``max_polls`` bounds the
    loop for tests/scripting; None polls until interrupted.

    One loop over ``obs.EventTailer`` — the same incremental reader the
    supervisor's fleet watcher polls, so the two tails can never drift.
    """
    from distributed_training_comparison_tpu.obs import EventTailer

    tailer = EventTailer(path)
    polls = 0
    while True:
        batch = tailer.poll()
        if batch:
            yield batch
        polls += 1
        if max_polls is not None and polls >= max_polls:
            return
        sleep(poll_s)


# ---------------------------------------------------------------- blackbox


def blackbox_report(path: str | Path, out=print) -> int:
    """Decode every mmap flight ring under ``path`` into ``blackbox.json``
    (the same pull the supervisor runs after every attempt) and print a
    per-ring summary.  Exit 0 when rings decoded, 2 when none exist."""
    rings = find_rings(path)
    if not rings:
        out(f"{path}: no flight*.ring files found")
        return 2
    for ring in rings:
        events, torn = decode_ring(ring)
        last = events[-1] if events else {}
        out(
            f"{ring}: {len(events)} event(s), {torn} torn slot(s)"
            + (
                f", last kind={last.get('kind')!r} "
                f"epoch={last.get('epoch')}"
                if events
                else ""
            )
        )
    box = collect_black_box(path)
    if box is None:
        out(f"{path}: black box write failed")
        return 1
    out(f"black box written: {box}")
    return 0


# ------------------------------------------------------------------ alerts


def alerts_report(path: str | Path, out=print) -> int:
    """The ``--alerts`` view: every ``alert`` event under ``path`` as a
    firing/resolved timeline, plus the stall calls for context.  Exit 0
    when no rule is left firing — including when no alert/stall event
    exists at all (a run without ``--alert`` rules is not unhealthy; the
    printed note distinguishes it) — 1 while any rule still fires (the
    CI gate: a run whose alerts never resolved is not a run to trust),
    2 when ``path`` holds no events whatsoever."""
    events, _files = load_run(path)
    if not events:
        out(f"{path}: no events found")
        return 2
    timeline = alert_timeline(events)
    stalls = [e for e in events if e.get("kind") == "stall"]
    if not timeline and not stalls:
        out(f"{path}: no alert or stall events (no --alert rules, or "
            "none ever transitioned)")
        return 0
    t0 = events[0].get("t_wall", 0.0)
    for ev in sorted(
        timeline + stalls,
        key=lambda e: (e.get("t_wall", 0.0), e.get("t_mono", 0.0)),
    ):
        p = ev.get("payload") or {}
        if ev.get("kind") == "stall":
            out(
                f"[{ev.get('t_wall', 0.0) - t0:>9.3f}s] stall: "
                f"process {p.get('process_index', '?')} {p.get('state', '?')} "
                f"(age {p.get('age_s', '?')}s)"
            )
        else:
            out(
                f"[{ev.get('t_wall', 0.0) - t0:>9.3f}s] "
                f"{p.get('state', '?').upper():>8}: {p.get('spec', '?')} "
                f"value={p.get('value', '?')} threshold={p.get('threshold', '?')}"
                + (f" source={p['source']}" if p.get("source") else "")
            )
    firing = [
        spec for (spec, _src), state in final_states(events).items()
        if state == "firing"
    ]
    if firing:
        out(f"STILL FIRING: {', '.join(sorted(set(firing)))}")
        return 1
    out("all alerts resolved")
    return 0


# ------------------------------------------------------------------ policy


def policy_report(path: str | Path, out=print) -> int:
    """The ``--policy`` view: every autopilot decision under ``path`` as a
    timeline — dry-runs, cooldown/budget suppressions, requested actions
    and their completions.  Exit 0 when every requested action reached a
    ``completed``/``failed`` outcome (including when there are no policy
    events at all — a run without ``--policy`` rules is not unhealthy),
    1 while any action is still PENDING (requested by the engine but
    never applied — the process meant to apply it died first), 2 when
    ``path`` holds no events whatsoever.

    When the stream carries ``control`` events (the mid-epoch control
    plane), each is rendered with its time-to-mitigation — seconds and
    steps from the decision to the boundary that applied it — and the
    gate also fails (exit 1) any acted ``rollback``/
    ``abort_with_evidence`` decision that completed but never reached an
    ``applied`` control event: the decision was made, the action ran,
    but no boundary ever recorded landing it."""
    from distributed_training_comparison_tpu.ops.policy import (
        pending_actions,
        policy_timeline,
    )
    from distributed_training_comparison_tpu.resilience import control as control_mod

    events, _files = load_run(path)
    if not events:
        out(f"{path}: no events found")
        return 2
    timeline = policy_timeline(events)
    if not timeline:
        out(f"{path}: no policy events (no --policy rules, or none ever "
            "triggered)")
        return 0
    t0 = events[0].get("t_wall", 0.0)
    for ev in timeline:
        p = ev.get("payload") or {}
        state = p.get("state", "?")
        line = (
            f"[{ev.get('t_wall', 0.0) - t0:>9.3f}s] "
            f"{state.upper():>9}: {p.get('action', '?')}"
        )
        if p.get("rule"):
            line += f"  rule={p['rule']}"
        if p.get("alert_source") is not None:
            line += f" source={p['alert_source']}"
        if p.get("id") is not None:
            line += f" id={p['id']}"
        if state == "cooldown":
            line += f" ({p.get('cooldown_remaining_s', '?')}s remaining)"
        if state == "budget":
            line += (
                f" ({p.get('budget_spent', '?')}/{p.get('budget', '?')} spent)"
            )
        if state == "failed" and p.get("error"):
            line += f" error={p['error']}"
        if p.get("dry_run") and state == "dry_run":
            line += "  [no action taken]"
        out(line)
    controls = control_mod.control_timeline(events)
    if controls:
        out("")
        out("mid-epoch control (decide -> apply):")
        for ev in controls:
            p = ev.get("payload") or {}
            line = (
                f"[{ev.get('t_wall', 0.0) - t0:>9.3f}s] "
                f"{str(p.get('state', '?')).upper():>10}: "
                f"{p.get('verb') or p.get('action', '?')}"
                f"  boundary={p.get('boundary', '?')}"
            )
            if p.get("ttm_s") is not None:
                line += f" ttm={p['ttm_s']:.3f}s"
            if p.get("steps_since_decide") is not None:
                line += f" (+{p['steps_since_decide']} steps)"
            if p.get("id") is not None:
                line += f" id={p['id']}"
            out(line)
    rc = 0
    pending = pending_actions(events)
    if pending:
        out(
            "STILL PENDING: "
            + ", ".join(
                f"{p.get('action', '?')} (id {p.get('id', '?')})"
                for p in pending
            )
        )
        rc = 1
    unapplied = control_mod.unapplied_actions(events)
    if unapplied:
        out(
            "NEVER APPLIED: "
            + ", ".join(
                f"{p.get('action', '?')} (id {p.get('id', '?')})"
                for p in unapplied
            )
            + "  — acted decisions with no 'applied' control event"
        )
        rc = 1
    if rc == 0:
        out("all requested actions completed")
    return rc


def serve_class_table(events: list[dict]) -> dict[str, dict]:
    """Per-SLO-class serving totals from the merged stream alone.

    ``serve_route`` events carry CUMULATIVE per-class counters, so the
    LAST event per ``(run_id, attempt, process_index, router)`` is that
    router session's state (the ``router`` token keeps sequential
    routers of one process apart); sessions sum.  Each class row:
    completed / ok_deadline / expired / shed / failed, attainment =
    ok_deadline ÷ terminal, and the class's configured
    deadline/target/priority (carried on the same events — the gate
    needs no flags re-supplied)."""
    last: dict[tuple, dict] = {}
    for ev in events:
        if not isinstance(ev, dict) or ev.get("kind") != "serve_route":
            continue
        p = _payload(ev)
        if not p.get("classes"):
            continue
        key = (
            ev.get("run_id"), int(ev.get("attempt", 0) or 0),
            int(ev.get("process_index", 0) or 0), p.get("router"),
        )
        last[key] = p  # stream is time-ordered; later wins
    table: dict[str, dict] = {}
    for p in last.values():
        for name, row in (p.get("classes") or {}).items():
            agg = table.setdefault(
                name,
                {
                    "completed": 0, "ok_deadline": 0, "expired": 0,
                    "shed": 0, "failed": 0,
                    "priority": row.get("priority"),
                    "deadline_ms": row.get("deadline_ms"),
                    "target": row.get("target"),
                },
            )
            for k in ("completed", "ok_deadline", "expired", "shed",
                      "failed"):
                agg[k] += int(row.get(k, 0) or 0)
            # config fields: prefer any session that carried them
            for k in ("priority", "deadline_ms", "target"):
                if agg[k] is None and row.get(k) is not None:
                    agg[k] = row[k]
    for agg in table.values():
        terminal = (
            agg["completed"] + agg["expired"] + agg["shed"] + agg["failed"]
        )
        agg["terminal"] = terminal
        agg["attainment"] = (
            agg["ok_deadline"] / terminal if terminal else None
        )
    return table


def serve_replica_table(events: list[dict]) -> dict[str, dict]:
    """Per-replica lifecycle totals merged from the ``replica`` events
    of every process in the stream (the router's dispatcher-side events
    at process_index 0 and — process transport — each worker's own at
    process_index 1+rid).

    Counters (dispatches/routed/restarts) are cumulative on their
    events, so the row keeps the MAX seen; ``drains``/``deaths`` count
    transitions; ``classes`` is the last per-class latency payload a
    transition carried (the stopped event's ``{cls: {n, p99_ms}}``)."""
    table: dict[str, dict] = {}
    for ev in events:
        if not isinstance(ev, dict) or ev.get("kind") != "replica":
            continue
        p = _payload(ev)
        rid = p.get("replica")
        if rid is None:
            continue
        row = table.setdefault(str(rid), {
            "transport": None, "pid": None, "restarts": 0, "drains": 0,
            "deaths": 0, "dispatches": 0, "routed": 0, "state": None,
            "classes": {},
        })
        if p.get("transport"):
            row["transport"] = p["transport"]
        if p.get("pid"):
            row["pid"] = p["pid"]
        for k in ("dispatches", "routed"):
            if p.get(k) is not None:
                row[k] = max(row[k], int(p[k]))
        for k in ("restarts", "attempt"):  # supervisor lifecycle events
            if p.get(k):
                row["restarts"] = max(row["restarts"], int(p[k]))
        if p.get("restart"):
            row["restarts"] = max(row["restarts"], int(p["restart"]))
        state = p.get("state")
        if not p.get("beat") and state:
            if state == "draining":
                row["drains"] += 1
            if state == "dead":
                row["deaths"] += 1
            row["state"] = state
        if p.get("classes"):
            row["classes"] = p["classes"]
    return table


def serve_scale_mismatches(events: list[dict]) -> list[str]:
    """Scale decisions the fleet never honored: for every APPLIED
    ``serve_scale`` event, each added rid must show a ``ready`` replica
    event and each drained rid a ``stopped``/``dead`` one somewhere in
    the stream — a decision that targeted a fleet size the replicas
    never reached is an autoscaler/fleet disagreement worth an exit 1."""
    added: set = set()
    drained: set = set()
    for ev in events:
        if not isinstance(ev, dict) or ev.get("kind") != "serve_scale":
            continue
        p = _payload(ev)
        if p.get("state") != "applied":
            continue
        added.update(str(r) for r in (p.get("added") or ()))
        drained.update(str(r) for r in (p.get("drained") or ()))
    if not added and not drained:
        return []
    seen: dict[str, set] = {}
    for ev in events:
        if not isinstance(ev, dict) or ev.get("kind") != "replica":
            continue
        p = _payload(ev)
        rid = p.get("replica")
        if rid is not None and p.get("state"):
            seen.setdefault(str(rid), set()).add(p["state"])
    problems = []
    for rid in sorted(added):
        if "ready" not in seen.get(rid, set()):
            problems.append(
                f"scale-up added replica {rid} but it never went ready"
            )
    for rid in sorted(drained):
        if not ({"stopped", "dead"} & seen.get(rid, set())):
            problems.append(
                f"scale-down drained replica {rid} but it never stopped"
            )
    return problems


def serve_report(path: str | Path, out=print) -> int:
    """The ``--serve`` view: the per-class SLO attainment table + the
    per-replica lifecycle table from the event stream alone.  Exit 0
    when every class with a declared target meets it AND every applied
    scale decision's fleet change actually came up (including when there
    are no ``serve_route`` events — a run that never served is not
    unhealthy), 1 when any class is below its target or a scale decision
    disagrees with the replicas that materialized, 2 when ``path`` holds
    no events whatsoever."""
    events, _files = load_run(path)
    if not events:
        out(f"{path}: no events found")
        return 2
    table = serve_class_table(events)
    if not table:
        out(f"{path}: no serve_route events (no serving session, or the "
            "router never emitted)")
        return 0
    routes = [
        ev for ev in events
        if isinstance(ev, dict) and ev.get("kind") == "serve_route"
    ]
    plans = [
        _payload(ev)["plan"] for ev in routes if _payload(ev).get("plan")
    ]
    if plans:
        plan = plans[-1]
        out(
            f"capacity plan: {plan.get('replicas')} replica(s), ladder "
            f"{plan.get('buckets')} (sized_by {plan.get('sized_by')}, fit "
            f"{(plan.get('fit') or {}).get('source')})"
        )
    header = (
        f"{'class':<12} {'prio':>4} {'deadline':>9} {'offered':>8} "
        f"{'ok':>7} {'expired':>8} {'shed':>6} {'failed':>7} "
        f"{'attain':>7} {'target':>7}  verdict"
    )
    out(header)
    out("-" * len(header))
    rc = 0
    for name in sorted(
        table, key=lambda n: (table[n].get("priority") or 0, n)
    ):
        row = table[name]
        target = float(row.get("target") or 0.0)
        att = row["attainment"]
        below = target > 0 and (att is None or att < target)
        if below:
            rc = 1
        out(
            f"{name:<12} "
            f"{row.get('priority') if row.get('priority') is not None else '-':>4} "
            f"{(str(round(row['deadline_ms'], 1)) + 'ms') if row.get('deadline_ms') else '-':>9} "
            f"{row['terminal']:>8} {row['ok_deadline']:>7} "
            f"{row['expired']:>8} {row['shed']:>6} {row['failed']:>7} "
            f"{(f'{att * 100:.1f}%' if att is not None else '-'):>7} "
            f"{(f'{target * 100:.1f}%' if target else '-'):>7}  "
            + ("BELOW TARGET" if below else "ok")
        )
    # per-replica lifecycle table: pid/transport/restarts/drains and
    # what each replica actually resolved, merged from every process's
    # replica events (the worker files included, process transport)
    replicas = serve_replica_table(events)
    if replicas:
        out("")
        rheader = (
            f"{'rid':>4} {'transport':>9} {'pid':>8} {'state':>9} "
            f"{'restarts':>8} {'drains':>6} {'dispatches':>10} "
            f"{'routed':>7}  p99 per class"
        )
        out(rheader)
        out("-" * len(rheader))
        for rid in sorted(replicas, key=lambda r: int(r)):
            row = replicas[rid]
            cls = ", ".join(
                f"{c}={v.get('p99_ms', 0):.0f}ms"
                for c, v in sorted((row.get("classes") or {}).items())
            ) or "-"
            out(
                f"{rid:>4} {row.get('transport') or '-':>9} "
                f"{row.get('pid') or '-':>8} {row.get('state') or '-':>9} "
                f"{row['restarts']:>8} {row['drains']:>6} "
                f"{row['dispatches']:>10} {row['routed']:>7}  {cls}"
            )
    # replica lifecycle recap: dead replicas are worth a line even when
    # every SLO held (the fleet absorbed the failure — say so)
    dead = [
        _payload(ev)
        for ev in events
        if isinstance(ev, dict) and ev.get("kind") == "replica"
        and _payload(ev).get("state") == "dead"
    ]
    if dead:
        out(
            f"replicas declared dead: "
            + ", ".join(
                f"{p.get('replica')} ({p.get('reason', '?')})" for p in dead
            )
        )
    # autoscaler/fleet agreement: an applied scale decision whose
    # added/drained replicas never materialized is a failure even when
    # every SLO held — the decision record and the fleet disagree
    mismatches = serve_scale_mismatches(events)
    for msg in mismatches:
        out(f"SCALE MISMATCH: {msg}")
        rc = 1
    if rc:
        out("one or more classes BELOW their SLO target or scale mismatch")
    else:
        out("all SLO targets met")
    return rc


# ------------------------------------------------------------------- trace
#
# Request tracing (obs/reqtrace.py): the router emits one `trace` event
# per KEPT trace (the span tree), each replica process emits per-batch
# device spans on its OWN bus keyed by trace_id.  load_run already
# merged the files and removed clock skew, so joining here is pure
# dictionary work.

TRACE_SEGMENTS = ("admit", "queue", "coalesce", "hop", "device", "reply")


def _quantile(vals: list[float], f: float) -> float:
    vs = sorted(vals)
    return vs[min(len(vs) - 1, int(f * len(vs)))]


def trace_rows(events: list[dict]) -> list[dict]:
    """One row per kept trace: class, keep reason, requeue trail, and the
    critical-path segment durations (seconds).

    Router records (payload carries ``trace_id`` + ``spans``) hold the
    admission/queue/coalesce/rpc/reply tree; worker records (payload
    carries ``trace_ids`` + one device ``span``) are joined on
    ``(trace_id, batch span id)`` to split the final rpc into device
    time and socket hop.  Thread-transport traces carry their device
    span inline (no hop — there is no socket).  A segment that was never
    measured stays ABSENT, never a fabricated zero."""
    # (trace_id, batch_span_id) -> the worker's device span
    worker: dict[tuple, dict] = {}
    for ev in events:
        if not isinstance(ev, dict) or ev.get("kind") != "trace":
            continue
        p = _payload(ev)
        sp = p.get("span")
        if p.get("trace_ids") and sp:
            for tid in p["trace_ids"]:
                worker.setdefault((tid, sp.get("batch")), sp)
    rows: list[dict] = []
    for ev in events:
        if not isinstance(ev, dict) or ev.get("kind") != "trace":
            continue
        p = _payload(ev)
        tid = p.get("trace_id")
        if not tid:
            continue
        spans = p.get("spans") or []
        segments: dict[str, float] = {}
        for s in spans:
            if s.get("name") == "admit" and s.get("dur_s") is not None:
                segments["admit"] = float(s["dur_s"])
            elif s.get("name") == "queue" and s.get("dur_s") is not None:
                segments["queue"] = float(s["dur_s"])
        attempts = [s for s in spans if s.get("name") in ("rpc", "device")]
        ok_attempts = [s for s in attempts if s.get("ok", True)]
        if ok_attempts:
            final = ok_attempts[-1]
            bsid = final.get("parent")
            for s in spans:
                if s.get("parent") != bsid:
                    continue
                if s.get("name") == "coalesce":
                    segments["coalesce"] = float(s.get("dur_s") or 0.0)
                elif s.get("name") == "reply":
                    segments["reply"] = float(s.get("dur_s") or 0.0)
            if final["name"] == "device":
                # thread transport: the engine ran in-process, the span
                # IS the device time and there is no hop to measure
                segments["device"] = float(final.get("dur_s") or 0.0)
            else:
                segments["rpc"] = float(final.get("dur_s") or 0.0)
                dev = worker.get((tid, bsid))
                if dev is not None:
                    segments["device"] = float(dev.get("dur_s") or 0.0)
                    segments["hop"] = max(
                        0.0, segments["rpc"] - segments["device"]
                    )
        rows.append({
            "trace_id": tid,
            "cls": p.get("cls") or "default",
            "keep": p.get("keep"),
            "outcome": p.get("outcome"),
            "breach": bool(p.get("breach")),
            "requeues": int(p.get("requeues") or 0),
            "rids": [s.get("rid") for s in attempts],
            "segments": segments,
        })
    return rows


def trace_class_segments(events: list[dict]) -> dict[str, dict]:
    """Per-class segment sample lists (seconds) from the kept traces."""
    per: dict[str, dict] = {}
    for t in trace_rows(events):
        cls = per.setdefault(
            t["cls"], {"n": 0, **{s: [] for s in TRACE_SEGMENTS}}
        )
        cls["n"] += 1
        for seg, v in t["segments"].items():
            if seg in TRACE_SEGMENTS:
                cls[seg].append(v)
    return per


def trace_diff_cells(events: list[dict]) -> dict[str, dict]:
    """The --diff cells: per-class queue-wait / transport / device p95
    in milliseconds, None (rendered '-') when a segment has no samples —
    a thread-transport run has no hop, a tail-only run with zero kept
    traces has nothing, and neither must read as a measured 0."""
    out: dict[str, dict] = {}
    for cls, segs in trace_class_segments(events).items():
        out[cls] = {
            "n": segs["n"],
            "queue_p95_ms": (
                _quantile(segs["queue"], 0.95) * 1000.0
                if segs["queue"] else None
            ),
            "transport_p95_ms": (
                _quantile(segs["hop"], 0.95) * 1000.0
                if segs["hop"] else None
            ),
            "device_p95_ms": (
                _quantile(segs["device"], 0.95) * 1000.0
                if segs["device"] else None
            ),
        }
    return out


def trace_report(path: str | Path, out=print) -> int:
    """The ``--trace`` view: merge kept trace spans across the router's
    and every replica process's event files (clock skew already removed
    by ``load_run``) and render the per-SLO-class critical-path
    decomposition — p50/p95/p99 of each segment, widest p95 starred.

    Exit 0 normally (including a run with zero kept traces and zero
    breaches), 1 when a class with a declared deadline shows breaches in
    its ``serve_route`` counters but ZERO kept traces — the one state
    tail-based keep is supposed to make impossible, so it must fail the
    gate rather than pass silently, 2 when ``path`` has no events."""
    events, _files = load_run(path)
    if not events:
        out(f"{path}: no events found")
        return 2
    rows = trace_rows(events)
    kept_by: dict[str, int] = {}
    for t in rows:
        kept_by[t["keep"] or "?"] = kept_by.get(t["keep"] or "?", 0) + 1
    out(
        f"kept traces: {len(rows)}"
        + (
            " ("
            + ", ".join(f"{k} {v}" for k, v in sorted(kept_by.items()))
            + ")"
            if kept_by else ""
        )
    )
    rc = 0
    # the tail-keep contract: every deadline breach keeps its trace, so
    # a deadlined class with breaches on the books but no kept traces
    # means the tracer was off or broken for exactly the requests it
    # exists for
    for name, crow in sorted(serve_class_table(events).items()):
        if not crow.get("deadline_ms"):
            continue
        breaches = (
            max(0, crow["completed"] - crow["ok_deadline"])
            + crow["expired"]
        )
        kept = sum(1 for t in rows if t["cls"] == name)
        if breaches > 0 and kept == 0:
            out(
                f"NO TRACES FOR BREACHED CLASS: {name} shows {breaches} "
                f"deadline breach(es) in serve_route but zero kept "
                f"traces — tail-based keep should have kept every one"
            )
            rc = 1
    per = trace_class_segments(events)
    for cls in sorted(per):
        segs = per[cls]
        p95s = {
            s: _quantile(segs[s], 0.95)
            for s in TRACE_SEGMENTS if segs[s]
        }
        widest = max(p95s, key=p95s.get) if p95s else None
        out("")
        out(f"class {cls} — {segs['n']} kept trace(s)")
        header = (
            f"  {'segment':<10} {'n':>5} {'p50 ms':>9} {'p95 ms':>9} "
            f"{'p99 ms':>9}"
        )
        out(header)
        out("  " + "-" * (len(header) - 2))
        for seg in TRACE_SEGMENTS:
            vals = segs[seg]
            if not vals:
                out(f"  {seg:<10} {0:>5} {'-':>9} {'-':>9} {'-':>9}")
                continue
            star = " *widest" if seg == widest else ""
            out(
                f"  {seg:<10} {len(vals):>5} "
                f"{_quantile(vals, 0.50) * 1000:>9.3f} "
                f"{_quantile(vals, 0.95) * 1000:>9.3f} "
                f"{_quantile(vals, 0.99) * 1000:>9.3f}{star}"
            )
    # the requeue trail: one trace spanning every replica it touched
    requeued = [t for t in rows if t["requeues"]]
    if requeued:
        out("")
        for t in requeued:
            rids = ", ".join(
                "?" if r is None else str(r) for r in t["rids"]
            )
            out(
                f"requeued trace {t['trace_id']}: {t['requeues']} "
                f"requeue(s) across replicas [{rids}] — "
                f"outcome {t['outcome']}"
            )
    return rc


def _plan_layout_of_run_start(p: dict) -> dict:
    """The layout a ``run_start`` payload actually ran — the comparison
    frame of a ``plan`` event's ``layout`` dict."""
    mesh = p.get("mesh") or {}
    return {
        "data": int(mesh.get("data", 1) or 1),
        "model": int(mesh.get("model", 1) or 1),
        "pipe": int(mesh.get("pipe", 1) or 1),
        "shard_optim": bool(p.get("shard_optim", False)),
        "grad_comms": str(p.get("grad_comms", "fp32") or "fp32"),
        "state_layout": str(p.get("state_layout") or "contiguous"),
    }


def plan_report(path: str | Path, out=print) -> int:
    """The ``--plan`` view: every auto-parallel planning decision under
    ``path`` — the chosen layout, every candidate's predicted step-s/HBM
    (prediction vs MEASURED for the layout that actually ran, so a
    mis-prediction is inspectable), and the cost-model fit provenance.

    Exit 0 when every *installed* plan's chosen layout agrees with the
    attempt's ``run_start`` layout; 1 on any disagreement — a plan the
    run silently ignored must fail the stream check — and 2 when
    ``path`` holds no events at all.  ``dump``-mode plans (``installed``
    false) are rendered but never gate: ignoring them is their contract.
    """
    events, _files = load_run(path)
    if not events:
        out(f"{path}: no events found")
        return 2
    plans = [ev for ev in events if ev.get("kind") == "plan"]
    if not plans:
        out(f"{path}: no plan events (no --parallel-plan, or the planner "
            "never ran)")
        return 0
    run_starts = [
        ev for ev in events
        if ev.get("kind") == "run_start"
        and int(ev.get("process_index", 0) or 0) == 0
    ]
    # measured seconds-per-step keyed by (run_id, attempt): epoch_end's
    # images_per_sec against that attempt's global batch (median across
    # epochs).  run_id matters — two independent runs sharing a ckpt root
    # (the bench capture + plan legs) both count attempt 0, and blending
    # their epochs would misreport the planned layout's measured seconds.
    def _run_key(ev) -> tuple:
        return (ev.get("run_id"), int(ev.get("attempt", 0) or 0))

    batch_by_attempt = {
        _run_key(ev): int(_payload(ev).get("batch_size", 0) or 0)
        for ev in run_starts
    }
    step_s_by_attempt: dict[tuple, list] = {}
    for ev in events:
        if ev.get("kind") != "epoch_end" or int(
            ev.get("process_index", 0) or 0
        ):
            continue
        ips = _payload(ev).get("images_per_sec")
        batch = batch_by_attempt.get(_run_key(ev))
        if ips and batch:
            step_s_by_attempt.setdefault(_run_key(ev), []).append(
                batch / float(ips)
            )
    rc = 0
    t0 = events[0].get("t_wall", 0.0)
    for ev in plans:
        p = _payload(ev)
        attempt = int(p.get("attempt", ev.get("attempt", 0)) or 0)
        chosen = p.get("chosen") or {}
        fit = p.get("fit") or {}
        out(
            f"[{ev.get('t_wall', 0.0) - t0:>9.3f}s] PLAN attempt {attempt} "
            f"({p.get('reason', '?')}, {'installed' if p.get('installed') else 'dump only'}): "
            f"{chosen.get('key', '?')} on {p.get('devices', '?')} device(s), "
            f"model {p.get('model', '?')}, batch {p.get('batch_size', '?')} "
            f"[fit: {fit.get('source', '?')}"
            + (f", {fit.get('n_points')} pt(s)" if fit.get("n_points") else "")
            + "]"
        )
        measured = step_s_by_attempt.get((ev.get("run_id"), attempt))
        measured_s = sorted(measured)[len(measured) // 2] if measured else None
        header = (
            f"    {'candidate':<22} {'pred step_s':>12} {'pred HBM(MB)':>13} "
            f"{'measured':>10}"
        )
        out(header)
        for c in p.get("candidates") or []:
            is_chosen = c.get("key") == chosen.get("key")
            hbm = c.get("predicted_hbm_bytes")
            meas = (
                f"{measured_s:10.6f}" if (is_chosen and measured_s) else
                f"{'-':>10}"
            )
            out(
                f"    {c.get('key', '?'):<22} "
                f"{c.get('predicted_step_s') or 0:>12.6f} "
                f"{(hbm / 2**20 if hbm else 0):>13.1f} {meas}"
                + ("  <- chosen" if is_chosen else "")
            )
        if p.get("candidates_elided"):
            out(f"    (+{p['candidates_elided']} candidate(s) elided, "
                f"{p.get('refused', 0)} shape(s) refused)")
        if measured_s and chosen.get("predicted_step_s"):
            ratio = measured_s / float(chosen["predicted_step_s"])
            out(
                f"    chosen predicted {chosen['predicted_step_s']:.6f}s "
                f"vs measured {measured_s:.6f}s per step "
                f"(measured/predicted {ratio:.2f}x)"
            )
        if not p.get("installed"):
            continue
        # the gate: an INSTALLED plan must be the layout run_start ran
        following = [
            rs for rs in run_starts
            if int(rs.get("attempt", 0) or 0) == attempt
            and rs.get("run_id") == ev.get("run_id")
            and rs.get("t_wall", 0.0) >= ev.get("t_wall", 0.0) - 1.0
        ]
        if not following:
            out(f"    (no run_start for attempt {attempt} follows this "
                "plan — run died before construction?)")
            continue
        got = _plan_layout_of_run_start(_payload(following[0]))
        want = dict(p.get("layout") or {})
        # supervisor-side plans size the data axis for the whole fleet;
        # the pid-level CPU emulation's rank 0 joins a smaller world than
        # planned (it skips the collectives the pinned jax cannot run on
        # CPU), so the data-axis check scales by the world share — on a
        # real pod the worlds agree and the comparison stays exact
        plan_world = int(p.get("world", 0) or 0)
        got_world = int(_payload(following[0]).get("world_size", 1) or 1)
        if (
            plan_world
            and got_world != plan_world
            and "data" in want
            and (int(want["data"]) * got_world) % plan_world == 0
        ):
            want["data"] = int(want["data"]) * got_world // plan_world
        diffs = {
            k: (want.get(k), got.get(k))
            for k in got
            if k in want and want.get(k) != got.get(k)
        }
        if diffs:
            rc = 1
            out(
                "    PLAN MISMATCH: run_start ran a different layout — "
                + ", ".join(
                    f"{k}: planned {a!r} ran {b!r}"
                    for k, (a, b) in sorted(diffs.items())
                )
            )
    # the manifest gate: every resumable checkpoint's recorded state_layout
    # must be the layout its writing attempt's run_start declared.  A
    # disagreement means the resident-layout seam was bypassed somewhere
    # between construction and save — the checkpoint would restore through
    # the wrong canonicalization on the next attempt.
    layout_by_attempt = {
        (rs.get("run_id"), int(rs.get("attempt", 0) or 0)):
            _plan_layout_of_run_start(_payload(rs))["state_layout"]
        for rs in run_starts
    }
    from distributed_training_comparison_tpu.resilience.ckpt_io import (
        read_manifest,
    )
    root = Path(path)
    ckpts = sorted(root.glob("version-*/last.ckpt")) + sorted(
        root.glob("version-*/prev-last.ckpt")
    )
    for ck in ckpts:
        man = read_manifest(ck) or {}
        saved = man.get("state_layout")
        if saved is None:
            continue  # pre-layout checkpoint: nothing to gate
        key = (man.get("run_id"), int(man.get("attempt", 0) or 0))
        ran = layout_by_attempt.get(key)
        if ran is None:
            continue  # checkpoint from a run this stream never saw
        if str(saved) != ran:
            rc = 1
            out(
                f"    MANIFEST MISMATCH: {ck.parent.name}/{ck.name} saved "
                f"state_layout {saved!r} but attempt {key[1]}'s run_start "
                f"ran {ran!r}"
            )
    if rc:
        out("an installed plan was silently ignored (layout mismatch)")
    else:
        out("every installed plan matches its attempt's run_start layout")
    return rc


# the parity rail's transform-pipeline order (parity/diff.py STAGES) — the
# bisection trail renders the stages before the first divergent one as clean
_PARITY_STAGES = ("grads", "wire", "optimizer", "relayout")


def _parity_trail(div: dict) -> str:
    """Render one gate's bisection trail: the stage ladder with the first
    divergent stage marked, then the named leaf and its distance."""
    stage = div.get("stage")
    marks = []
    for s in _PARITY_STAGES:
        if s == stage:
            marks.append(f"{s} X")
            break
        marks.append(f"{s} ok")
    return " -> ".join(marks)


def parity_report(path: str | Path, out=print) -> int:
    """The ``--parity`` view: every completed ``--parity-check`` capture
    under ``path`` — both gate verdicts, the bisection trail down to the
    first divergent (step, stage, leaf, distance), and the layout under
    test.

    Exit 0 when every parity event's verdict is ``ok``; 1 on any
    divergence (either gate — a bitwise replay mismatch is corruption or
    nondeterminism, a reference-gate trip means the compiled layout left
    the eager semantics beyond the priced tolerance); 2 when ``path``
    holds no events at all.  A stream with events but no ``parity`` kind
    exits 0 with a note (the run didn't ask for the rail)."""
    events, _files = load_run(path)
    if not events:
        out(f"{path}: no events found")
        return 2
    parities = [ev for ev in events if ev.get("kind") == "parity"]
    if not parities:
        out(f"{path}: no parity events (run without --parity-check N)")
        return 0
    rc = 0
    t0 = events[0].get("t_wall", 0.0)
    for ev in parities:
        p = _payload(ev)
        layout = p.get("layout") or {}
        lay = (
            f"dp{layout.get('dp', '?')}*tp{layout.get('tp', '?')}"
            f"*pp{layout.get('pp', '?')} zero="
            f"{'on' if layout.get('zero') else 'off'} "
            f"wire={layout.get('wire', '?')} "
            f"sched={layout.get('schedule', 'none')}"
        )
        out(
            f"[{ev.get('t_wall', 0.0) - t0:>9.3f}s] PARITY epoch "
            f"{p.get('epoch', ev.get('epoch', '?'))}: {p.get('steps', '?')} step(s), "
            f"{p.get('mode', '?')} mode, tol {p.get('tol', '?')}, {lay}"
        )
        if p.get("corrupt"):
            c = p["corrupt"]
            out(
                f"    injected corruption: bit {c.get('bit')} of "
                f"{c.get('leaf')} after step {c.get('step')} "
                "(--parity-corrupt)"
            )
        rdiv = p.get("replay_divergence")
        if rdiv is None:
            out("    replay gate:    ok (bitwise, "
                f"{p.get('steps', '?')} step(s) replayed)")
        else:
            rc = 1
            out(f"    replay gate:    DIVERGENT at step {rdiv.get('step')}")
            out(f"      trail: {_parity_trail(rdiv)}")
            out(
                f"      first leaf {rdiv.get('leaf')} "
                f"[{rdiv.get('divergent_leaves')} divergent leaf/leaves]: "
                f"recorded checksum {rdiv.get('recorded_checksum')} vs "
                f"replay {rdiv.get('replay_checksum')}"
            )
            if rdiv.get("loss_bits_recorded") != rdiv.get("loss_bits_replay"):
                out(
                    f"      loss bits recorded {rdiv.get('loss_bits_recorded')}"
                    f" vs replay {rdiv.get('loss_bits_replay')}"
                    + (
                        f" (recorded fault scale x{rdiv.get('fault_scale')})"
                        if rdiv.get("fault_scale", 1.0) != 1.0 else ""
                    )
                )
        ref = p.get("eager_reference")
        if ref == "unsupported":
            out(
                "    reference gate: unsupported — "
                f"{p.get('eager_reference_reason', 'not modeled')}"
            )
        elif p.get("reference_divergence") is None:
            out(
                f"    reference gate: ok (max {p.get('max_ulp', 0)} "
                f"scale-aware ulp <= {p.get('tol')})"
            )
        else:
            rc = 1
            fdiv = p["reference_divergence"]
            out(f"    reference gate: DIVERGENT at step {fdiv.get('step')}")
            out(f"      trail: {_parity_trail(fdiv)}")
            out(
                f"      first leaf {fdiv.get('leaf')} "
                f"[{fdiv.get('divergent_leaves')} divergent leaf/leaves]: "
                f"{fdiv.get('ulp')} scale-aware ulp vs tol {p.get('tol')} "
                f"(loss ulp {fdiv.get('loss_ulp')})"
            )
    if rc:
        out("parity DIVERGED: the compiled trajectory left its recorded/"
            "eager reference (see the trail above)")
    else:
        out(f"all {len(parities)} parity capture(s) clean")
    return rc


def export_openmetrics(path: str | Path, out_path: str | None = None) -> str:
    """The scrape-less exposition: fold a finished (or in-flight) run's
    ``metrics`` events — plus the serve records' latency deltas — into
    one cumulative registry view and render the same OpenMetrics text the
    live ``--metrics-port`` endpoint serves.  Heartbeat ages are relative
    to the newest event in the stream; alert states are each rule's last
    transition."""
    events, _files = load_run(path)
    payloads = []
    for ev in events:
        if ev.get("kind") == "metrics":
            payloads.append(ev)
        elif ev.get("kind") == "serve" and (ev.get("payload") or {}).get(
            "latency_hist"
        ):
            payloads.append(
                {"metrics": {"serve/latency_s": ev["payload"]["latency_hist"]}}
            )
    metrics = merge_metric_events(payloads)
    t_end = max((e.get("t_wall", 0.0) for e in events), default=0.0)
    ages: dict[str, float] = {}
    for ev in events:
        if ev.get("kind") == "heartbeat" and ev.get("t_wall") is not None:
            key = f"p{int(ev.get('process_index', 0))}"
            age = max(0.0, t_end - ev["t_wall"])
            ages[key] = min(age, ages.get(key, age))
    # firing if ANY source's final state fires — a dict keyed by spec
    # alone would let one process's resolve mask another's live breach
    states: dict[str, bool] = {}
    for (spec, _src), state in final_states(events).items():
        states[spec] = states.get(spec, False) or state == "firing"
    text = render_openmetrics(metrics, ages or None, states or None)
    if out_path and out_path != "-":
        Path(out_path).write_text(text)
    return text


# ----------------------------------------------------------------- compute
#
# The per-executable table: everything below reconstructs from the event
# stream alone — `compile` events carry identity (fingerprint), compile
# accounting, and the HLO cost/memory analysis; the per-executable
# `exec/{name}:{fp8}/dispatch_s` sketches inside the `metrics` flushes
# carry dispatch counts and dispatch-span seconds.  Measured MFU =
# analysis flops × dispatches ÷ dispatch-span seconds ÷ (peak chip
# FLOP/s × devices), with the peak keyed off the device kind the compile
# event recorded (override with --peak-flops; CPU captures have no peak
# table entry, so MFU prints '-' there — dispatch spans on CPU measure
# host time anyway, see the README caveat).


def pipeline_meta(events: list[dict]) -> dict | None:
    """The latest ``pipeline`` event's payload (one per attempt, emitted by
    the Trainer when a pipeline schedule is active): the schedule's static
    tick arithmetic — ticks, useful ticks, bubble fraction, virtual
    stages."""
    meta = None
    for ev in events:
        if ev.get("kind") == "pipeline" and int(ev.get("process_index", 0)) == 0:
            meta = _payload(ev)
    return meta


# executable-name prefixes that dispatch the pipeline schedule (the train
# runners); eval/snapshot/fingerprint programs carry no bubble
_PIPELINE_EXEC_PREFIXES = (
    "device_chunk_runner", "chunk_runner", "epoch_runner", "train_step",
)


def pipeline_bubble_rows(comp: dict, meta: dict) -> list[dict]:
    """Join the schedule's static bubble fraction against each train
    executable's MEASURED dispatch seconds: ``bubble_s`` is the wall time
    that executable spent in warmup/cooldown ticks (computed, on real
    silicon lockstepped, but discarded).  The schedule arithmetic supplies
    the fraction; the dispatch sketches supply the seconds."""
    frac = float(meta.get("bubble_frac", 0.0))
    rows = []
    for row in comp.get("rows", []):
        if not str(row.get("name", "")).startswith(_PIPELINE_EXEC_PREFIXES):
            continue
        if not row.get("dispatches"):
            continue
        span_s = row.get("dispatch_s", 0.0) + row.get("drain_s", 0.0)
        rows.append(
            {
                "name": row["name"],
                "fingerprint": row["fingerprint"],
                "dispatches": row["dispatches"],
                "span_s": round(span_s, 4),
                "bubble_frac": frac,
                "bubble_s": round(span_s * frac, 4),
            }
        )
    return rows


def format_pipeline(meta: dict, rows: list[dict]) -> list[str]:
    """The pipeline section lines: schedule arithmetic + the measured
    per-executable bubble table."""
    lines = [
        "  pipeline: schedule={schedule} P={pipe} virtual={virtual} "
        "M={microbatches} tp={tp} ticks={ticks} useful={useful_ticks} "
        "bubble={frac:.1%}".format(
            frac=float(meta.get("bubble_frac", 0.0)),
            **{
                k: meta.get(k, "?")
                for k in (
                    "schedule", "pipe", "virtual", "microbatches", "tp",
                    "ticks", "useful_ticks",
                )
            },
        )
    ]
    if rows:
        header = (
            f"    {'executable':<28} {'dispatches':>10} {'span':>9} "
            f"{'bubble':>7} {'bubble_s':>9}"
        )
        lines.append(header)
        for r in rows:
            lines.append(
                f"    {r['name']:<28} {r['dispatches']:>10}"
                f" {r['span_s']:>8.2f}s {r['bubble_frac']:>6.1%}"
                f" {r['bubble_s']:>8.2f}s"
            )
    return lines


def compute_summary(events: list[dict], peak_override: float | None = None) -> dict:
    """Fold a merged stream's ``compile`` events + exec dispatch sketches
    into per-executable rows (process-0 events only, like every other
    per-attempt fold: all processes compile the same executables)."""
    rows: dict[str, dict] = {}
    metric_events = []
    for ev in events:
        if int(ev.get("process_index", 0)) != 0:
            continue
        kind = ev.get("kind")
        if kind == "metrics":
            metric_events.append(ev)
            continue
        if kind != "compile":
            continue
        p = _payload(ev)
        fp = str(p.get("fingerprint", "?"))
        row = rows.setdefault(
            fp,
            {
                "name": p.get("name", "?"),
                "fingerprint": fp,
                "compiles": 0,
                "cache_hits": 0,
                "cache_misses": 0,
                "cache": p.get("cache", "unknown"),
                "compile_s": 0.0,
                "flops": None,
                "peak_bytes": None,
                "recompile_after_warmup": False,
                "device_kind": p.get("device_kind"),
                "devices": p.get("devices"),
            },
        )
        row["compiles"] += 1
        row["compile_s"] += float(p.get("compile_s", 0.0))
        if p.get("cache") == "hit":
            row["cache_hits"] += 1
        elif p.get("cache") == "miss":
            row["cache_misses"] += 1
        row["cache"] = p.get("cache", row["cache"])
        if p.get("flops") is not None:
            row["flops"] = float(p["flops"])
        if p.get("peak_bytes") is not None:
            row["peak_bytes"] = int(p["peak_bytes"])
        row["recompile_after_warmup"] = (
            row["recompile_after_warmup"] or bool(p.get("recompile_after_warmup"))
        )
    merged = merge_metric_events(metric_events)
    totals = {
        "executables": len(rows), "compiles": 0, "compile_s": 0.0,
        "cache_hits": 0, "cache_misses": 0, "recompiles_after_warmup": 0,
        "flops_dispatched": 0.0, "dispatch_s": 0.0, "drain_s": 0.0,
    }
    for row in rows.values():
        sketch = merged.get(f"exec/{row['name']}:{row['fingerprint'][:8]}/dispatch_s")
        row["dispatches"] = int((sketch or {}).get("count", 0))
        row["dispatch_s"] = float((sketch or {}).get("sum", 0.0))
    # Drain fold: the epoch's FINAL chunk executes while the main thread
    # blocks in the metrics fetch — that device time lands in the
    # `step/compute_s` span, not in any dispatch span, so dividing flops
    # by dispatch-span seconds alone UNDERcounts the denominator and
    # overstates MFU.  Fold the compute-span seconds into the dispatch
    # seconds pro-rata by each executable's dispatch share (the drain
    # belongs to whichever programs were in flight, and dispatch share is
    # the best stream-reconstructable proxy).
    drain_total = float((merged.get("step/compute_s") or {}).get("sum", 0.0))
    dispatch_total = sum(r["dispatch_s"] for r in rows.values())
    totals["drain_s"] = drain_total
    mfu_num = mfu_den = 0.0
    for row in rows.values():
        row["drain_s"] = (
            drain_total * row["dispatch_s"] / dispatch_total
            if dispatch_total > 0
            else 0.0
        )
        peak = (
            peak_override
            if peak_override
            else peak_flops_for(row["device_kind"])
        )
        row["mfu"] = None
        span_s = row["dispatch_s"] + row["drain_s"]
        if (
            peak
            and row["flops"]
            and row["dispatches"]
            and span_s > 0
        ):
            devices = row["devices"] or 1
            row["mfu"] = (
                row["flops"] * row["dispatches"]
                / span_s / (peak * devices)
            )
            mfu_num += row["flops"] * row["dispatches"]
            mfu_den += span_s * peak * devices
        totals["compiles"] += row["compiles"]
        totals["compile_s"] += row["compile_s"]
        totals["cache_hits"] += row["cache_hits"]
        totals["cache_misses"] += row["cache_misses"]
        totals["recompiles_after_warmup"] += int(row["recompile_after_warmup"])
        if row["flops"] and row["dispatches"]:
            totals["flops_dispatched"] += row["flops"] * row["dispatches"]
        totals["dispatch_s"] += row["dispatch_s"]
    # run-level MFU: flops-weighted over every executable with a peak —
    # the one number --diff compares across runs
    totals["mfu"] = (mfu_num / mfu_den) if mfu_den > 0 else None
    # the array side of the HBM ledger, if the stream carried the census
    census = merged.get("res/live_array_bytes")
    if census is not None:
        totals["live_array_bytes"] = census.get("value")
    comp = {
        "rows": sorted(
            rows.values(), key=lambda r: (r["name"], r["fingerprint"])
        ),
        "totals": totals,
    }
    # pipeline runs: join the schedule's static bubble fraction against
    # the measured dispatch seconds — the per-executable bubble table
    meta = pipeline_meta(events)
    if meta is not None:
        comp["pipeline"] = {
            "meta": meta,
            "rows": pipeline_bubble_rows(comp, meta),
        }
    return comp


def _fmt_bytes(n) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}TB"


def format_compute(comp: dict) -> str:
    """The ``--compute`` view: the per-executable cost/memory table."""
    rows = comp["rows"]
    if not rows:
        return (
            "(no compile events in the stream — a pre-PR-8 capture, or a "
            "--no-obs run)"
        )
    header = (
        f"{'executable':<28} {'fingerprnt':>10} {'compiles':>8} "
        f"{'cache':>7} {'compile_s':>9} {'flops':>10} {'peak_hbm':>9} "
        f"{'dispatches':>10} {'dispatch_s':>10} {'mfu':>7}"
    )
    lines = ["per-executable compute/memory ledger:", header, "-" * len(header)]
    for r in rows:
        name = r["name"][:28]
        flops = f"{r['flops']:.3g}" if r["flops"] is not None else "-"
        mfu = f"{100 * r['mfu']:6.2f}%" if r["mfu"] is not None else f"{'-':>7}"
        mark = " *" if r["recompile_after_warmup"] else ""
        lines.append(
            f"{name:<28} {r['fingerprint'][:8]:>10} {r['compiles']:>8} "
            f"{r['cache']:>7} {r['compile_s']:>9.3f} {flops:>10} "
            f"{_fmt_bytes(r['peak_bytes']):>9} {r['dispatches']:>10} "
            f"{r['dispatch_s']:>10.4f} {mfu}{mark}"
        )
    t = comp["totals"]
    lines.append(
        f"  totals: {t['executables']} executable(s), {t['compiles']} "
        f"compile(s) ({t['compile_s']:.2f}s), persistent cache "
        f"{t['cache_hits']} hit(s) / {t['cache_misses']} miss(es)"
    )
    if t["recompiles_after_warmup"]:
        lines.append(
            f"  * {t['recompiles_after_warmup']} executable(s) compiled "
            "AFTER warmup — the recompilation sentinel's findings "
            "(serve bucket churn / unexpected reshape)"
        )
    if t.get("drain_s"):
        lines.append(
            f"  compute-span drain folded into MFU denominators: "
            f"{t['drain_s']:.4f}s (pro-rata by dispatch share — the "
            "epoch-final chunk executes inside the metrics fetch)"
        )
    if t.get("mfu") is not None:
        lines.append(
            f"  measured MFU (flops-weighted across executables): "
            f"{100 * t['mfu']:.2f}%"
        )
    elif t["dispatch_s"] > 0:
        lines.append(
            "  measured MFU: no peak-FLOPs entry for this device kind "
            "(CPU capture?) — pass --peak-flops to force a denominator"
        )
    if t.get("live_array_bytes") is not None:
        lines.append(
            f"  live-array census (res/live_array_bytes, last sample): "
            f"{_fmt_bytes(t['live_array_bytes'])}"
        )
    pipe = comp.get("pipeline")
    if pipe is not None:
        lines.extend(format_pipeline(pipe["meta"], pipe["rows"]))
    return "\n".join(lines)


# -------------------------------------------------------------------- diff


def format_diff(name_a: str, a: dict, name_b: str, b: dict) -> str:
    ca, cb = a.get("compute", {}).get("totals", {}), b.get("compute", {}).get("totals", {})
    rows = [
        ("attempts", len(a["attempts"]), len(b["attempts"])),
        ("epochs", a["epochs"], b["epochs"]),
        ("rollbacks", a["rollbacks"], b["rollbacks"]),
        ("preemptions", a["preemptions"], b["preemptions"]),
        ("goodput %", 100 * a["goodput_frac"], 100 * b["goodput_frac"]),
        ("productive s", a["productive_s"], b["productive_s"]),
        ("h2d wait s", a["h2d_wait_s"], b["h2d_wait_s"]),
        # the compiler plane (PR 8): did the second run compile more,
        # spend longer in the compiler, trip the recompilation sentinel,
        # or lose measured MFU
        ("compiles", ca.get("compiles", 0), cb.get("compiles", 0)),
        ("compile s", ca.get("compile_s", 0.0), cb.get("compile_s", 0.0)),
        (
            "recompiles",
            ca.get("recompiles_after_warmup", 0),
            cb.get("recompiles_after_warmup", 0),
        ),
        (
            # None (no peak-FLOPs entry — CPU captures) renders '-', NOT
            # 0.0: a fabricated zero would read as a measured regression
            "mfu %",
            100 * ca["mfu"] if ca.get("mfu") is not None else None,
            100 * cb["mfu"] if cb.get("mfu") is not None else None,
        ),
    ]
    # per-class trace-segment p95s (request tracing): absent segments —
    # no kept traces, or a transport with no socket hop — stay None and
    # render '-'; a fabricated 0.0 would read as a measured improvement
    ta = a.get("trace_classes") or {}
    tb = b.get("trace_classes") or {}
    for cls in sorted(set(ta) | set(tb)):
        ra, rb = ta.get(cls) or {}, tb.get(cls) or {}
        for label, key in (
            ("queue p95 ms", "queue_p95_ms"),
            ("transp p95 ms", "transport_p95_ms"),
            ("device p95 ms", "device_p95_ms"),
        ):
            rows.append((f"{cls} {label}", ra.get(key), rb.get(key)))
    w = max(len(name_a), len(name_b), 12)
    lw = max(14, max(len(label) for label, _, _ in rows))
    lines = [
        f"{'':<{lw}} {name_a[:w]:>{w}} {name_b[:w]:>{w}} {'Δ':>10}",
    ]
    for label, va, vb in rows:
        delta = None if va is None or vb is None else vb - va
        fmt = (
            (lambda v: f"{v:.1f}")
            if isinstance(va, float) or isinstance(vb, float)
            else str
        )
        cell = lambda v: "-" if v is None else fmt(v)  # noqa: E731
        lines.append(
            f"{label:<{lw}} {cell(va):>{w}} {cell(vb):>{w}} {cell(delta):>10}"
        )
    return "\n".join(lines)


# -------------------------------------------------------------------- main


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("paths", nargs="+", help="ckpt root / version dir / events jsonl")
    ap.add_argument(
        "--check", action="store_true",
        help="validate every event against the schema; exit 1 on violations",
    )
    ap.add_argument(
        "--require-kind", action="append", default=None, metavar="KIND",
        help="with --check: additionally fail unless the merged stream "
        "contains at least one event of KIND (repeatable; the bench legs "
        "require 'compile' so a degraded compile hook can't pass)",
    )
    ap.add_argument(
        "--compute", action="store_true",
        help="print the per-executable compute/memory ledger reconstructed "
        "from the compile events + exec dispatch sketches: compiles, "
        "persistent-cache outcome, compile time, analysis flops, peak "
        "HBM, dispatches, dispatch-span seconds, measured MFU",
    )
    ap.add_argument(
        "--peak-flops", type=float, default=None, metavar="FLOPS",
        help="per-chip peak FLOP/s override for the --compute MFU column "
        "(default: keyed off the device kind recorded in the compile "
        "events; unknown kinds — e.g. CPU — render '-')",
    )
    ap.add_argument(
        "--diff", action="store_true",
        help="compare the first two paths' summaries",
    )
    ap.add_argument(
        "--timeline", type=int, default=TIMELINE_TAIL, metavar="N",
        help=f"show the last N timeline events (0 = all; default {TIMELINE_TAIL})",
    )
    ap.add_argument(
        "--follow", action="store_true",
        help="tail the event files (new attempts' files picked up live); "
        "Ctrl-C to stop",
    )
    ap.add_argument(
        "--poll", type=float, default=0.5, metavar="SECS",
        help="--follow poll interval (default 0.5s)",
    )
    ap.add_argument(
        "--blackbox", action="store_true",
        help="decode every flight*.ring under the path into blackbox.json "
        "(the SIGKILL-surviving recorder's pull)",
    )
    ap.add_argument(
        "--alerts", action="store_true",
        help="print the alert firing/resolved timeline (+ stall calls); "
        "exit 1 while any rule is still firing — the CI gate",
    )
    ap.add_argument(
        "--policy", action="store_true",
        help="print the autopilot decision timeline (ops/policy.py: "
        "dry-runs, cooldown/budget suppressions, actions and their "
        "completions); exit 1 while any requested action is still "
        "pending — the chaos-gauntlet gate",
    )
    ap.add_argument(
        "--plan", action="store_true",
        help="print the auto-parallel planning decisions (parallel/"
        "planner.py): chosen layout, every candidate's predicted "
        "step-s/HBM vs the measured seconds of the layout that ran, fit "
        "provenance; exit 1 when an INSTALLED plan's chosen layout "
        "disagrees with the attempt's run_start layout — a silently "
        "ignored plan must fail the stream check",
    )
    ap.add_argument(
        "--parity", action="store_true",
        help="print the eager-parity captures (parity/: bitwise replay "
        "gate + tolerance-gated eager reference gate) with the bisection "
        "trail down to the first divergent (step, stage, leaf, ulp); "
        "exit 1 on any divergence — the parity bench leg's gate",
    )
    ap.add_argument(
        "--serve", action="store_true",
        help="print the per-SLO-class serving attainment table "
        "reconstructed from the serve_route events alone (+ the "
        "installed capacity plan and any dead replicas); exit 1 when "
        "any class with a declared target is below it — the serve "
        "bench leg's self-check",
    )
    ap.add_argument(
        "--trace", action="store_true",
        help="merge kept request-trace spans across the router's and "
        "every replica process's event files (clock skew removed) and "
        "print the per-SLO-class critical-path decomposition — "
        "p50/p95/p99 of admission / queue wait / coalescing / socket "
        "hop / device / reply, widest p95 starred, plus the requeue "
        "trail of any trace that survived a replica death; exit 1 when "
        "a deadlined class shows breaches but zero kept traces",
    )
    ap.add_argument(
        "--export-openmetrics", metavar="OUT", default=None, nargs="?",
        const="-",
        help="render the run's merged metrics/heartbeats/alerts in the "
        "OpenMetrics text format (same exposition as the live "
        "--metrics-port endpoint); OUT is a file path or '-'/omitted "
        "for stdout",
    )
    args = ap.parse_args(argv)

    if args.blackbox:
        rc = 0
        for path in args.paths:
            rc = max(rc, blackbox_report(path))
        return rc

    if args.alerts:
        rc = 0
        for path in args.paths:
            rc = max(rc, alerts_report(path))
        return rc

    if args.policy:
        rc = 0
        for path in args.paths:
            rc = max(rc, policy_report(path))
        return rc

    if args.plan:
        rc = 0
        for path in args.paths:
            rc = max(rc, plan_report(path))
        return rc

    if args.parity:
        rc = 0
        for path in args.paths:
            rc = max(rc, parity_report(path))
        return rc

    if args.serve:
        rc = 0
        for path in args.paths:
            rc = max(rc, serve_report(path))
        return rc

    if args.trace:
        rc = 0
        for path in args.paths:
            rc = max(rc, trace_report(path))
        return rc

    if args.export_openmetrics is not None:
        if len(args.paths) != 1:
            # one exposition renders one run; silently rendering only the
            # first of several roots would pass half a fleet off as whole
            print("--export-openmetrics takes exactly one path", file=sys.stderr)
            return 2
        text = export_openmetrics(args.paths[0], args.export_openmetrics)
        if args.export_openmetrics == "-":
            sys.stdout.write(text)
        return 0

    if args.follow:
        t0: float | None = None
        try:
            for batch in follow_events(args.paths[0], poll_s=args.poll):
                if t0 is None:
                    t0 = batch[0].get("t_wall", 0.0)
                for ev in batch:
                    print(format_event(ev, t0), flush=True)
        except KeyboardInterrupt:
            pass
        except BrokenPipeError:
            # `--follow | head` / `| grep -m1` closing the pipe is a
            # normal way to stop tailing, not an error
            devnull = os.open(os.devnull, os.O_WRONLY)
            os.dup2(devnull, sys.stdout.fileno())
        return 0

    if args.check:
        rc = 0
        for path in args.paths:
            counts: list = []
            problems = check_run(path, counts, require_kinds=args.require_kind)
            if problems:
                rc = 1
                for p in problems:
                    print(f"SCHEMA VIOLATION {p}", file=sys.stderr)
            else:
                print(f"{path}: {sum(counts)} events OK")
        return rc

    if args.compute:
        rc = 0
        for path in args.paths:
            events, _files = load_run(path)
            if not events:
                print(f"{path}: no events found", file=sys.stderr)
                rc = 2
                continue
            print(f"{path}:")
            print(format_compute(compute_summary(events, args.peak_flops)))
        return rc

    if args.diff:
        if len(args.paths) != 2:
            print("--diff needs exactly two paths", file=sys.stderr)
            return 2
        (na, nb) = args.paths
        a, _ = load_run(na)
        b, _ = load_run(nb)
        if not a or not b:
            print("--diff: one of the runs has no events", file=sys.stderr)
            return 2
        print(format_diff(na, summarize(a), nb, summarize(b)))
        return 0

    rc = 0
    for path in args.paths:
        offsets: dict = {}
        events, files = load_run(path, skew_out=offsets)
        if not events:
            print(f"{path}: no events found", file=sys.stderr)
            rc = 2
            continue
        print(format_summary(str(path), summarize(events)))
        skew = {
            key: off
            for key, off in offsets.items()
            if key[1] is not None and abs(off) > 1e-3
        }
        if skew:
            print(
                "  clock skew removed before merge: "
                + ", ".join(
                    f"p{p}@a{att} {off:+.3f}s"
                    for (p, att), off in sorted(skew.items())
                )
            )
        print()
        print(format_timeline(events, args.timeline))
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
