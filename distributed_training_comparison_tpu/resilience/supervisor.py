"""Restart supervisor: run the training command until it exits cleanly.

The shell loop in ``src/tpu_jax/run_elastic.sh`` was the seed of this idea;
the supervisor makes it a programmable primitive: per-attempt command and
environment builders (the elastic tests relaunch with a *different* forced
device count), preemption-aware budgeting (``EXIT_PREEMPTED`` relaunches
immediately — the machine was taken away, the code is fine; any other
nonzero exit consumes the restart budget and backs off exponentially), and
a machine-readable attempt log that feeds goodput accounting.

Recovery composes three existing primitives: every epoch writes a verified
resumable ``last.ckpt`` (``ckpt_io``), ``--auto-resume`` continues the
newest run from its newest *valid* checkpoint (falling back to the rotated
previous one if the newest is torn), and the mesh is rebuilt from whatever
devices the relaunched process actually has (``elastic``).
"""

from __future__ import annotations

import subprocess
import sys
import time
from typing import Callable, Sequence

from .preempt import EXIT_PREEMPTED


def _default_runner(cmd: Sequence[str], env: dict | None) -> int:
    return subprocess.run(list(cmd), env=env).returncode


class PlanRefused(RuntimeError):
    """Raised by ``_plan_attempt`` when no legal next attempt can be
    rendered (the elastic fleet's world-size refusal).  Before the first
    attempt it propagates — a config error belongs at the CLI; mid-run it
    stops the loop ORDERLY, so the completed attempts' summary (and the
    caller's goodput aggregation) survive the refusal."""


class Supervisor:
    """Relaunch a command until success, a budget, or an unretryable exit.

    ``cmd``/``env`` may be static or callables of the attempt index — the
    hook the elastic tests use to change the forced device count between
    attempts, and a real deployment would use to re-render the launch
    command for a resized slice.

    ``progress`` (optional) is a zero-arg probe returning an opaque marker
    of the run's durable progress (typically the newest valid checkpoint's
    path + step).  A crashed attempt whose marker MOVED — e.g. the health
    watchdog rolled back, wrote checkpoints, and only then exhausted its
    budget — made real progress: it does not consume the restart budget and
    resets the exponential crash backoff, so a run that keeps advancing
    through repeated spikes is never starved of restarts, while a run stuck
    at the same checkpoint still exhausts ``max_restarts``.  Preemptions
    keep their PR-2 semantics (immediate relaunch, budget consumed).
    """

    def __init__(
        self,
        cmd: Sequence[str] | Callable[[int], Sequence[str]],
        *,
        env: dict | Callable[[int], dict] | None = None,
        max_restarts: int = 3,
        backoff_base: float = 1.0,
        backoff_max: float = 60.0,
        preempt_exit_code: int = EXIT_PREEMPTED,
        runner: Callable[[Sequence[str], dict | None], int] | None = None,
        sleep: Callable[[float], None] = time.sleep,
        log: Callable[[str], None] | None = None,
        progress: Callable[[], object] | None = None,
        events: Callable[..., object] | None = None,
    ) -> None:
        self._cmd = cmd
        self._env = env
        self.max_restarts = max_restarts
        self.backoff_base = backoff_base
        self.backoff_max = backoff_max
        self.preempt_exit_code = preempt_exit_code
        self._runner = runner or _default_runner
        self._sleep = sleep
        self._log = log or (lambda msg: print(f"[supervisor] {msg}", file=sys.stderr))
        self._progress = progress
        # event hook (obs run-event bus): called as
        # events(kind, **payload) at attempt start/end and backoff, so the
        # restart loop itself shows up on the unified timeline.  Optional —
        # the Supervisor stays importable without the obs package wired.
        self._events = events or (lambda kind, **payload: None)
        # orderly-stop request (the policy engine's abort_with_evidence):
        # once set, the loop ends after the CURRENT attempt instead of
        # relaunching — a run stopped over its own evidence must not be
        # restarted on top of it
        self._stop_reason: str | None = None

    def request_stop(self, reason: str) -> None:
        """Ask the restart loop to stop after the in-flight attempt ends
        (thread-safe: a one-shot str assignment).  First reason wins."""
        if self._stop_reason is None:
            self._stop_reason = str(reason)
            self._log(f"stop requested: {reason}")

    def _resolve(self, attempt: int) -> tuple[list[str], dict | None]:
        cmd = self._cmd(attempt) if callable(self._cmd) else self._cmd
        env = self._env(attempt) if callable(self._env) else self._env
        return list(cmd), env

    # -- subclass seams (the elastic FleetSupervisor re-renders the launch
    # -- set per attempt; the base class runs one static command) --------

    def _plan_attempt(self, attempt: int) -> None:
        """Decide this attempt's launch set BEFORE ``attempt_start`` is
        emitted (the fleet supervisor re-renders world size here and emits
        ``resize`` events)."""

    def _attempt_info(self) -> dict:
        """Extra payload for this attempt's ``attempt_start``/``attempt_end``
        events and its summary record (the fleet supervisor reports
        ``world_size`` and the host set)."""
        return {}

    def _attempt_free(self, rc: int, preempted: bool) -> bool:
        """True when this attempt must not consume the restart budget — a
        DELIBERATE supervisor-initiated drain (re-expansion after a host
        returned) is planned work, not a failure."""
        return False

    def _launch(self, attempt: int) -> int:
        """Run one attempt to completion; returns its exit code."""
        cmd, env = self._resolve(attempt)
        return self._runner(cmd, env)

    def run(self) -> dict:
        """The restart loop.  Returns a summary dict::

            {"final_rc": int, "restarts": int, "preemptions": int,
             "downtime_s": float,   # backoff sleep between attempts
             "attempts": [{"attempt", "returncode", "seconds", "preempted"}]}
        """
        attempts: list[dict] = []
        crashes = 0
        preemptions = 0
        planned_drains = 0
        progress_restarts = 0
        budget_used = 0
        downtime = 0.0
        attempt = 0
        prev_marker = self._progress() if self._progress is not None else None
        while True:
            # the live attempt index, readable by action executors that
            # must stamp decisions with the attempt that made them (the
            # fleet's _plan_attempt re-sets it; this covers the base loop)
            self._attempt = attempt
            try:
                self._plan_attempt(attempt)
            except PlanRefused as e:
                if not attempts:
                    raise  # pre-first-attempt refusal = config error
                self._log(f"stopping after {len(attempts)} attempt(s): {e}")
                break
            info = self._attempt_info()
            self._events("attempt_start", attempt=attempt, **info)
            t0 = time.monotonic()
            rc = self._launch(attempt)
            seconds = time.monotonic() - t0
            preempted = rc == self.preempt_exit_code
            self._events(
                "attempt_end", attempt=attempt, returncode=rc,
                seconds=round(seconds, 3), preempted=preempted, **info,
            )
            attempts.append(
                {
                    "attempt": attempt,
                    "returncode": rc,
                    "seconds": round(seconds, 3),
                    "preempted": preempted,
                    **info,
                }
            )
            if rc == 0:
                break
            if self._stop_reason is not None:
                # requested mid-attempt (policy abort): the attempt's own
                # nonzero rc stands, but no relaunch follows — the stop is
                # the point
                self._log(
                    f"stopping after attempt {attempt} (rc={rc}): "
                    f"{self._stop_reason}"
                )
                self._events(
                    "give_up", attempt=attempt, returncode=rc,
                    reason=self._stop_reason,
                )
                break
            progressed = False
            if self._progress is not None:
                marker = self._progress()
                progressed = marker is not None and marker != prev_marker
                prev_marker = marker
                attempts[-1]["progress"] = progressed
            if preempted:
                if self._attempt_free(rc, True):
                    # a DELIBERATE supervisor-initiated drain (the elastic
                    # re-expand) is planned work: neither a preemption on
                    # the scoreboard nor a draw on the restart budget
                    planned_drains += 1
                else:
                    # counted before the budget check so a final preempted
                    # attempt that exhausts the budget still shows up
                    preemptions += 1
                    budget_used += 1
            elif progressed:
                # the attempt advanced the durable checkpoint (e.g. health
                # rollbacks kept writing progress before the budget ran
                # out): a free restart, and the crash backoff restarts from
                # its base instead of compounding
                progress_restarts += 1
                crashes = 0
            else:
                budget_used += 1
            if budget_used > self.max_restarts:
                self._log(
                    f"giving up after {len(attempts) - 1} restarts (last rc={rc})"
                )
                self._events(
                    "give_up", attempt=attempt, returncode=rc,
                    restarts=len(attempts) - 1,
                )
                break
            if preempted:
                # the machine went away, not the code: relaunch immediately
                self._log(
                    f"attempt {attempt} preempted (rc={rc}); relaunching "
                    f"with --auto-resume ({budget_used}/{self.max_restarts})"
                )
            else:
                crashes += 1
                backoff = min(
                    self.backoff_max, self.backoff_base * 2 ** (crashes - 1)
                )
                note = " (checkpoint progressed: budget spared, backoff reset)" if progressed else ""
                self._log(
                    f"attempt {attempt} failed (rc={rc}); backing off "
                    f"{backoff:.1f}s then restarting "
                    f"({budget_used}/{self.max_restarts}){note}"
                )
                self._events(
                    "backoff", attempt=attempt, seconds=backoff,
                    progressed=progressed,
                )
                self._sleep(backoff)
                downtime += backoff
            attempt += 1
        return {
            "final_rc": attempts[-1]["returncode"],
            "restarts": len(attempts) - 1,
            "preemptions": preemptions,
            "planned_drains": planned_drains,
            "progress_restarts": progress_restarts,
            "downtime_s": round(downtime, 3),
            "attempts": attempts,
        }


def strip_flags(args: Sequence[str], names: Sequence[str]) -> list[str]:
    """Drop ``--flag VALUE`` / ``--flag=VALUE`` occurrences of every named
    flag from an argv — ONE stripping implementation for the restart loop
    (``--resume``) and the fleet's per-rank re-render (``--world-size``/
    ``--rank``/``--dist-url``/the parent-only ``--fleet-*`` flags)."""
    names = tuple(names)
    prefixed = tuple(f"{n}=" for n in names)
    out, skip = [], False
    for a in args:
        if skip:
            skip = False
            continue
        if a in names:
            skip = True
            continue
        if a.startswith(prefixed):
            continue
        out.append(a)
    return out


def strip_resume_flag(args: Sequence[str]) -> list[str]:
    """Drop an explicit ``--resume PATH`` (either flag form) from an argv."""
    return strip_flags(args, ("--resume",))


def run_supervised(hparams, argv: Sequence[str] | None = None) -> dict:
    """``--supervise`` mode of the shared entry point: relaunch this same
    command (minus ``--supervise``, plus ``--auto-resume --resilience``) as
    a child process under the restart policy, then aggregate the attempts'
    goodput records into ``GOODPUT.json``.

    CLI-only by construction: the child command is rebuilt from
    ``sys.argv[0]`` (the backend's ``main.py``), the one invocation shape in
    which "run myself again" is well-defined.
    """
    import os

    from .. import obs
    from .goodput import aggregate_goodput, collect_goodput_records, write_goodput

    if (
        int(getattr(hparams, "fleet_hosts", 0) or 0) > 1
        and not int(getattr(hparams, "fleet_local_devices", 0) or 0)
        and os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
        != "cpu"
    ):
        # The fleet's "hosts" are processes on THIS machine, and with
        # --fleet-local-devices 0 they inherit its accelerators unchanged.
        # One process owns a chip: found on a v5e, the second host dies on
        # libtpu's multi-process lockfile while the first waits in the
        # rendezvous, and the fleet hangs.  This supervisor stays off JAX
        # (its children need the chips), so it judges from the environment,
        # and refuses before spawning.
        raise SystemExit(
            f"--fleet-hosts {hparams.fleet_hosts} starts that many host "
            "processes on this one machine, and they would all open the "
            "same chips (one process owns a chip; hosts are not placed per "
            "chip yet): pass --fleet-local-devices K to emulate the fleet "
            "on K virtual CPU devices per host, or set JAX_PLATFORMS=cpu"
        )
    argv = list(sys.argv[1:] if argv is None else argv)
    child_args = [a for a in argv if a != "--supervise"]
    for extra in ("--auto-resume", "--resilience"):
        if extra not in child_args:
            child_args.append(extra)

    # One run_id for the whole supervised run, generated here (or inherited
    # — a supervisor may itself run under one) and exported into every
    # attempt's environment with its restart index, so all attempts' event
    # and goodput records join on it.  The supervisor's own events (attempt
    # launches, backoffs) land in the ckpt root's events.jsonl — run_report
    # merges them with the per-attempt files in the version dirs.
    run_id = os.environ.get(obs.RUN_ID_ENV) or obs.new_run_id()
    obs_enabled = getattr(hparams, "obs", True)
    bus = obs.configure(run_id=run_id, persist=obs_enabled)
    if obs_enabled:
        bus.bind_dir(hparams.ckpt_path)

    def env_for(attempt: int) -> dict:
        env = dict(os.environ)
        env[obs.RUN_ID_ENV] = run_id
        env[obs.ATTEMPT_ENV] = str(attempt)
        return env

    def cmd_for(attempt: int) -> list[str]:
        # An explicit --resume belongs to attempt 0: it resumes the
        # ORIGINAL checkpoint into a fresh version dir.  Once an attempt
        # has saved progress, restarts must continue from it (--auto-resume
        # discovery of the newest valid last.ckpt) — re-resuming the
        # original file would discard every prior attempt's epochs and
        # re-fire epoch=K fault events forever.  But if NO attempt has
        # saved anything yet (crash before the first last.ckpt), stripping
        # --resume would silently retrain from scratch — keep retrying the
        # original checkpoint until real progress exists.
        args = child_args
        if attempt > 0:
            from ..train.checkpoint import find_valid_resume  # lazy: avoid cycle

            if find_valid_resume(hparams.ckpt_path) is not None:
                args = strip_resume_flag(child_args)
        return [sys.executable, sys.argv[0]] + args

    def progress_probe():
        # durable-progress marker: the newest valid checkpoint's identity
        # (path + manifest checksum/step — manifest-only, so probing a
        # multi-GB state between attempts costs ~KB, not a full read+hash).
        # A crashed attempt that moved it (health rollbacks kept writing
        # last.ckpt before the in-process budget ran out) restarts for free
        # — repeated spikes must not exhaust --max-restarts while epochs
        # still advance.
        from ..train.checkpoint import resume_progress_marker  # lazy: avoid cycle

        return resume_progress_marker(hparams.ckpt_path)

    # --- the live operations plane (obs/): while an attempt runs, a
    # watcher thread tails every host's event file under the ckpt root,
    # classifies lagging hosts slow vs dead off their heartbeats (`stall`
    # events land on the supervisor's own bus — the one place a wedged
    # collective can't take down), and evaluates the --alert rules over
    # the flushed metric events and heartbeat ages.
    # --heartbeat-secs 0 disables heartbeats AND stall detection (with no
    # beats, ordinary work-event gaps would read as the fleet dying); the
    # watcher still runs for the --alert rules.
    heartbeat_s = getattr(hparams, "heartbeat_secs", 10.0)
    tracker = (
        obs.LivenessTracker(heartbeat_s=heartbeat_s)
        if heartbeat_s and heartbeat_s > 0
        else None
    )
    engine = obs.AlertEngine(
        obs.parse_alert_specs(getattr(hparams, "alert", None)),
        bus=bus,
        heartbeats=tracker,
        # the supervisor sees every host's stream, so it is the ONE
        # evaluator of fleet-aggregate rules (sum(...)/max(...) specs);
        # per-process rules evaluate here too, as before
        fleet=True,
    )
    emitted_stragglers: set[tuple] = set()
    # attribution input, accumulated INCREMENTALLY: one persistent tailer
    # plus a metrics-only buffer, so attempt N's pass doesn't re-read and
    # re-parse every prior attempt's whole event history (O(N^2) on long
    # gauntlets).  Separate from the watcher's tailer — that one feeds
    # the live tracker/engine on its own thread.
    straggler_tailer = obs.EventTailer(hparams.ckpt_path)
    metric_events: list[dict] = []

    def on_event(kind: str, **payload):
        bus.emit(kind, **payload)
        if kind == "attempt_start":
            # fresh liveness + fleet-aggregate folds per attempt: the
            # previous attempt's death and the backoff gap must not read
            # as this one's fleet stalling, and its processes' last
            # window values must not hold a sum() rule in breach.  The
            # elastic path re-renders the launch set every attempt, so the
            # tracker is seeded with the EXPECTED ranks — a host that
            # never emits a single event still gets a stall call.
            if tracker is not None:
                world = int(payload.get("world_size") or 0)
                tracker.reset(
                    expect=range(world) if world > 0 else None,
                    attempt=int(payload.get("attempt", 0)),
                )
            engine.reset_fleet()
            if policy_engine is not None:
                # re-grant the per-attempt action budget (idempotent by
                # attempt index — the tailed attempt_start lands too)
                policy_engine.reset_attempt(int(payload.get("attempt", 0)))
        if kind == "attempt_end" and obs_enabled:
            # the black-box pull: decode every host's mmap flight ring
            # under the ckpt root (version dirs included) into ONE
            # blackbox.json — present even when the attempt died by
            # SIGKILL/OOM and no process lived to write its crash dump
            obs.collect_black_box(hparams.ckpt_path)
            # cross-host straggler attribution: merge every host's
            # step-phase sketches and name host + phase for any outlier
            # (one event per NEW finding — re-reading the whole root on a
            # later attempt must not re-emit an earlier one)
            try:
                metric_events.extend(
                    ev for ev in straggler_tailer.poll()
                    if ev.get("kind") == "metrics"
                )
                for f in obs.straggler_findings(metric_events):
                    key = (f["attempt"], f["process_index"], f["phase"])
                    if key not in emitted_stragglers:
                        emitted_stragglers.add(key)
                        bus.emit(obs.STRAGGLER_KIND, **f)
            except Exception:  # attribution must never kill supervising
                pass
            if tracker is not None:
                tracker.reset()

    fleet_hosts = int(getattr(hparams, "fleet_hosts", 0) or 0)
    restart_policy = dict(
        max_restarts=getattr(hparams, "max_restarts", 3),
        backoff_base=getattr(hparams, "restart_backoff", 1.0),
        progress=progress_probe,
        events=on_event,
    )
    if fleet_hosts > 1:
        # the elastic pool: N host processes per attempt, world size
        # re-rendered from the surviving hosts at every boundary
        from .fleet import FleetSupervisor, fleet_env_knobs

        sup = FleetSupervisor(
            cmd_for, env=env_for, ckpt_root=hparams.ckpt_path,
            # --parallel-plan auto: the fleet re-plans the layout at every
            # attempt boundary (resize → fresh plan; children get the
            # rendered flags + --parallel-plan off so they don't re-plan)
            plan_hparams=hparams,
            **fleet_env_knobs(hparams), **restart_policy,
        )
    else:
        sup = Supervisor(cmd_for, env=env_for, **restart_policy)

    # --- the closed-loop autopilot (ops/policy.py): --policy rules bind
    # alert firings to supervisor actions.  The engine is fed by the fleet
    # watcher's tail — ONE delivery path (the alert engine's own emits
    # land in the supervisor's events.jsonl and come back through the
    # tailer one poll later), so an alert can never double-drive an
    # action.  drain_host writes the same host-i.down marker an operator
    # writes; rollback/abort defer through the request channel to the
    # training process; abort additionally stops the restart loop.
    from ..ops import policy as policy_mod
    from . import control as control_mod

    policy_engine = policy_mod.engine_from_hparams(
        hparams, bus=bus, log=sup._log
    )
    if policy_engine is not None:
        policy_engine.bind_actions(
            policy_mod.supervisor_actions(
                hparams.ckpt_path,
                fleet_hosts=fleet_hosts,
                request_stop=sup.request_stop,
                # the replan action exists only where a planner does: an
                # elastic fleet with supervisor-side planning enabled
                request_replan=(
                    sup.request_replan
                    if getattr(sup, "plan_hparams", None) is not None
                    else None
                ),
                # --control-boundary chunk (default) routes deferred
                # actions through the mid-epoch control channel; "epoch"
                # keeps the legacy epoch-boundary request files
                boundary=getattr(hparams, "control_boundary", None)
                or control_mod.DEFAULT_BOUNDARY,
                # drain-class control requests are scoped to the attempt
                # that decided them, so one orphaned across a restart is
                # discarded stale instead of draining every later attempt
                attempt=lambda: int(getattr(sup, "_attempt", 0)),
            )
        )

    watcher = (
        obs.FleetWatcher(
            hparams.ckpt_path, bus, tracker=tracker, engine=engine,
            policy=policy_engine,
            # steady-state cadence; the watcher tightens itself to ~100ms
            # while any host is degraded (obs/heartbeat.py adaptive poll)
            poll_s=getattr(hparams, "fleet_poll_secs", 1.0),
        )
        if obs_enabled
        else None
    )
    t_start = time.time()
    if watcher is not None:
        watcher.start()
    try:
        summary = sup.run()
    finally:
        if watcher is not None:
            watcher.stop()
    if policy_engine is not None:
        # sweep requests no attempt lived to apply (written after the
        # final epoch-boundary poll, or the run ended first): give each
        # id a terminal 'failed' outcome so a completed run's timeline
        # never carries a forever-pending action.  The event is fed back
        # through the engine so its pending ledger (GOODPUT's
        # supervisor.policy) agrees with the stream run_report reads
        for req in policy_mod.PolicyRequestPoller(hparams.ckpt_path).poll():
            if req.get("id") is not None:
                policy_engine.observe_event(
                    policy_mod.emit_completion(
                        bus, req, ok=False,
                        error="run ended before the request was applied",
                    )
                )
        # same sweep for the chunk-boundary control channel: every
        # leftover request is reported 'expired' on the control stream
        # (so the decide→apply trail never just stops), and the
        # trainer-applied verbs additionally get the 'failed' terminal
        # their pending policy id needs
        for req in control_mod.pending_control(hparams.ckpt_path):
            bus.emit(
                control_mod.CONTROL_KIND,
                **control_mod.control_event_payload(
                    req, state="expired", boundary="epoch", step=0,
                ),
            )
            if req.get("id") is not None and req.get("action") in (
                "rollback", "abort_with_evidence",
            ):
                policy_engine.observe_event(
                    policy_mod.emit_completion(
                        bus, req, ok=False,
                        error="run ended before the request was applied",
                    )
                )
        control_mod.clear_control_requests(hparams.ckpt_path)
        # the autopilot's ledger rides the supervisor summary into
        # GOODPUT.json: decisions by state, rules, anything still pending
        summary["policy"] = policy_engine.summary()

    # aggregate the per-attempt goodput records the children appended —
    # across ALL version dirs (an attempt that died pre-first-save leaves
    # its record in one dir while the relaunch progresses in the next),
    # filtered to this run's attempts by record timestamp
    records = collect_goodput_records(hparams.ckpt_path, since=t_start)
    report = aggregate_goodput(
        records,
        downtime_s=summary["downtime_s"],
        restarts=summary["restarts"],
        preemptions=summary["preemptions"],
        resizes=summary.get("resizes"),
    )
    report.setdefault("run_id", run_id)
    # the restart-loop ledger rides into the scoreboard: per-attempt
    # return codes (and, elastic, world sizes/hosts), the planned-drain
    # count, downtime — a GOODPUT.json reader can tell a budget-free
    # re-expand drain from a crash restart without the event stream
    report["supervisor"] = summary
    out_path = getattr(hparams, "goodput_json", None) or "GOODPUT.json"
    write_goodput(out_path, report)
    bus.emit(
        "run_summary",
        final_rc=summary["final_rc"],
        restarts=summary["restarts"],
        preemptions=summary["preemptions"],
        goodput_frac=report["goodput_frac"],
    )
    obs.reset(bus)
    return {
        "supervisor": summary,
        "goodput": report,
        "goodput_json": str(out_path),
        "exit_code": summary["final_rc"],
    }
