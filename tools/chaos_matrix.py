"""The chaos matrix: every named scenario of
``resilience/faults.py CHAOS_SCENARIOS`` run end to end under the fleet
supervisor (or, for ``session: "serve"`` scenarios, the real ``--serve``
entry) with the policy engine active, judged from the event stream alone,
and written as one scoreboard.

    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --out /tmp/chaos.json
    JAX_PLATFORMS=cpu python tools/chaos_matrix.py --out /tmp/chaos.json host_flap

It is a check of recovery, not a measurement of speed: rank 1 of the fleet
is the pid+event-file host emulation of ``tests/fleet_pool_worker.py``, so
it runs only under an explicit ``JAX_PLATFORMS=cpu`` and refuses anywhere
else before it starts a child.  Exit code 1 when a scenario is red.  The
``host_flap`` scenario is the kill -> shrink -> ``host-1.up`` -> re-expand
gauntlet whose goodput the supervisor prices in ``--goodput-json``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def emit_progress(key: str, result: dict) -> None:
    """Per-scenario progress to stderr: a hard crash mid-run still leaves
    the completed scenarios' rows on record (stdout stays reserved for the
    one final JSON line)."""
    print(f"[chaos] {key}: {json.dumps(result)}", file=sys.stderr, flush=True)


def _explicit_cpu() -> bool:
    """True where the environment asks for the CPU backend by name."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def _require_explicit_cpu(mode: str) -> str:
    """Gate for a tool that starts child processes which need a device.

    A chip belongs to one process at a time: a parent that has touched JAX
    holds it, and a child that needs it then fails or hangs.  This parent
    imports the package, and what it records are counts and verdicts taken
    on (virtual) CPU devices — so it runs only where the environment
    explicitly says ``JAX_PLATFORMS=cpu``, and anywhere else it exits
    non-zero with the reason before anything is initialized or spawned: no
    hang, and no CPU record handed back from a chip machine.  Returns the
    platform (``"cpu"``)."""
    if not _explicit_cpu():
        raise SystemExit(
            f"{mode}: refused — its parent holds the device while it starts "
            "children that need one, and its scoreboard is a CPU record; "
            "set JAX_PLATFORMS=cpu explicitly to take it"
        )
    return "cpu"


def events_check_rc(ckpt_root: str, require_kinds=()) -> int:
    """Self-validate a scenario's run: ``tools/run_report.py --check`` over
    every ``events*.jsonl`` it left behind, returncode recorded in the
    scoreboard (0 = every record parses against the versioned obs schema)
    — nobody trusts the verdicts of a run that doesn't.  ``require_kinds``
    additionally fails the check unless the stream carries those kinds, so
    a silently-degraded hook can't pass a run whose trail is missing."""

    cmd = [sys.executable, os.path.join(REPO, "tools", "run_report.py"),
           ckpt_root, "--check"]
    for kind in require_kinds or ():
        cmd += ["--require-kind", kind]
    return subprocess.run(cmd).returncode


def _drive_fleet_gauntlet(
    ckpt_root: str, proc, driver_log: list, readmit,
    timeout: float = 600.0,
) -> None:
    """The external environment's script: SIGKILL host 1 (spot reclaim) once attempt 0 has a
    verified checkpoint, and — with ``readmit`` — signal re-admission
    once the shrunk attempt's ``run_start`` lands: ``True`` writes
    ``host-1.up`` directly (the legacy scheduler interface),
    ``"probe"`` only creates the ``--fleet-probe`` ready file and lets
    the SchedulerProbe write the marker itself.  Never an operator
    action: no ``host-i.down`` is ever written here."""

    from distributed_training_comparison_tpu.resilience import read_manifest

    status_path = os.path.join(ckpt_root, "fleet", "status.json")
    events_path = os.path.join(ckpt_root, "version-0", "events.jsonl")

    def status():
        with open(status_path) as f:
            return json.load(f)

    def wait(cond, what) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                driver_log.append(f"fleet exited before {what}")
                return False
            try:
                if cond():
                    return True
            except (OSError, ValueError, KeyError):
                pass
            time.sleep(0.05)
        driver_log.append(f"timed out waiting for {what}")
        return False

    if not wait(
        lambda: status()["attempt"] == 0
        and read_manifest(
            os.path.join(ckpt_root, "version-0", "last.ckpt")
        ) is not None,
        "attempt 0 checkpoint",
    ):
        return
    os.kill(int(status()["pids"]["1"]), signal.SIGKILL)
    driver_log.append("spot-reclaimed host 1 (SIGKILL)")
    if not readmit:
        return
    if not wait(
        lambda: status()["attempt"] == 1
        and any(
            '"kind": "run_start"' in line and '"attempt": 1' in line
            for line in open(events_path).read().splitlines()
        ),
        "attempt 1 run_start",
    ):
        return
    if readmit == "probe":
        # the residue-closing path: the driver never touches
        # <ckpt>/fleet/ — it creates the PROBE's ready file (a k8s
        # node-ready / GCE guest-attribute stand-in) and --fleet-probe
        # turns that into host-1.up on the supervisor's own cadence
        with open(os.path.join(ckpt_root, "probe-ready-1"), "w"):
            pass
        driver_log.append(
            "scheduler marked host 1 schedulable (probe-ready-1)"
        )
        return
    with open(os.path.join(ckpt_root, "fleet", "host-1.up"), "w"):
        pass
    driver_log.append("scheduler re-admitted host 1 (host-1.up)")


def _run_serve_chaos_scenario(name: str, sc: dict, repo: str, run_report):
    """One ``session: "serve"`` chaos scenario: run the real ``--serve``
    entry (flash crowd onto an unwarmed bucket), judge the storm →
    sentinel alert → ``rewarm_serve`` → p99-recovery chain from the
    event stream alone.  Returns ``(row, problems, events_check_rc)``
    shaped like the fleet scenarios' rows."""

    from distributed_training_comparison_tpu.ops.policy import pending_actions
    from distributed_training_comparison_tpu.resilience import (
        check_chaos_expectations,
    )

    root = tempfile.mkdtemp(prefix=f"chaos-{name}-")
    cmd = [
        sys.executable, os.path.join(repo, "src", "tpu_jax", "main.py"),
        *sc["extra_args"],
        "--ckpt-path", root, "--seed", "7", "--no-progress",
        "--policy-mode", sc["policy_mode"],
    ]
    for spec in sc["alerts"]:
        cmd += ["--alert", spec]
    for spec in sc["policies"]:
        cmd += ["--policy", spec]
    env = dict(os.environ)
    env.update(sc["env"])
    timed_out = False
    proc = subprocess.Popen(
        cmd, cwd=repo, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    # chaos driver "kill_replica": watch the fleet's handshake files
    # until every process replica reports ready, give the load shape a
    # moment to start flowing, then SIGKILL replica 0's worker — rid 0
    # because LIFO scale-down drains the HIGHEST rid, so an autoscaler
    # riding along can never have politely drained our victim first.
    kill_info = {"kills": 0}
    if sc.get("driver") == "kill_replica":

        xargs = list(sc["extra_args"])
        want = (
            int(xargs[xargs.index("--serve-replicas") + 1])
            if "--serve-replicas" in xargs
            else 1
        )

        def _kill_driver():
            fleet = os.path.join(root, "serve-fleet")
            deadline = time.monotonic() + 600
            while time.monotonic() < deadline and proc.poll() is None:
                ready = {}
                for fn in sorted(os.listdir(fleet)) if os.path.isdir(
                    fleet
                ) else []:
                    if (
                        not fn.startswith("replica-")
                        or not fn.endswith(".json")
                        or ".spec." in fn
                    ):
                        continue
                    try:
                        with open(os.path.join(fleet, fn)) as fh:
                            hs = json.load(fh)
                    except (OSError, ValueError):
                        continue  # mid-write handshake; next poll has it
                    if hs.get("state") == "ready" and hs.get("pid"):
                        ready[fn] = int(hs["pid"])
                if len(ready) >= want:
                    time.sleep(2.0)
                    try:
                        os.kill(ready[min(ready)], signal.SIGKILL)
                        kill_info["kills"] += 1
                    except OSError:
                        pass
                    return
                time.sleep(0.25)

        threading.Thread(target=_kill_driver, daemon=True).start()
    try:
        out, err = proc.communicate(timeout=900)
    except subprocess.TimeoutExpired:
        timed_out = True
        proc.kill()
        out, err = proc.communicate()

    events, _files = run_report.load_run(root)
    policy_states: dict[str, int] = {}
    recompiles = 0
    restarts = 0
    failed_requests = None
    phases = None
    for ev in events:
        kind = ev.get("kind")
        p = ev.get("payload") or {}
        if kind == "policy":
            st = p.get("state", "?")
            policy_states[st] = policy_states.get(st, 0) + 1
        elif kind == "compile" and p.get("recompile_after_warmup"):
            recompiles += 1
        elif kind == "replica" and (
            p.get("lifecycle") == "attempt_start" and p.get("attempt")
        ):
            # attempt >= 1 on a replica lifecycle event IS a supervisor
            # restart (attempt 0 is the original launch)
            restarts += 1
        elif kind == "serve":
            if p.get("phases"):
                phases = p["phases"]
            if p.get("failed") is not None:
                failed_requests = p["failed"]
    # recovery is judged against the WORST phase (the storm may land a
    # burst early under Poisson arrivals): the final phase's p99 must sit
    # below the cliff, wherever the cliff was — and the after phase must
    # have actually COMPLETED requests (an empty phase's p99 is 0.0,
    # which would read a total post-flash outage as "recovered")
    p99_recovered = False
    if phases and all(k in phases for k in ("before", "flash", "after")):
        after = phases["after"]["latency_ms"]["p99"]
        worst = max(
            phases[k]["latency_ms"]["p99"] for k in ("before", "flash")
        )
        p99_recovered = bool(
            phases["after"].get("n", 0) > 0
            and after > 0
            and after < worst
        )
    observed = {
        "final_rc": proc.returncode,
        "resizes": 0,
        "rollbacks": 0,
        "alerts_fired": sum(
            1 for ev in events
            if ev.get("kind") == "alert"
            and (ev.get("payload") or {}).get("state") == "firing"
        ),
        "restarts": restarts, "preemptions": 0,
        "kills": kill_info["kills"],
        "failed_requests": failed_requests,
        "policy_requested": policy_states.get("requested", 0),
        "policy_completed": policy_states.get("completed", 0),
        "policy_failed": policy_states.get("failed", 0),
        "policy_dry_run": policy_states.get("dry_run", 0),
        "policy_cooldown": policy_states.get("cooldown", 0),
        "policy_budget": policy_states.get("budget", 0),
        "policy_pending": len(pending_actions(events)),
        "crash_dump_evidence": False,
        "goodput_frac": None,
        "recompiles": recompiles,
        "p99_recovered": p99_recovered,
        "phases": phases,
    }
    problems = check_chaos_expectations(sc["expect"], observed)
    if timed_out:
        problems.append("scenario timed out after 900s (process killed)")
    if observed["policy_pending"]:
        problems.append(
            f"{observed['policy_pending']} policy action(s) still "
            "pending (requested, never completed)"
        )
    check_rc = events_check_rc(root, require_kinds=tuple(sc["require_kinds"]))
    if check_rc != 0:
        problems.append(f"events_check_rc={check_rc}")
    row = {
        "desc": sc["desc"],
        "fault_plan": sc["fault_plan"],
        "alerts": list(sc["alerts"]),
        "policies": list(sc["policies"]),
        "policy_mode": sc["policy_mode"],
        "driver": [sc["driver"]] if sc.get("driver") else [],
        **observed,
        "events_check_rc": check_rc,
        "green": not problems,
        "problems": problems,
        "stderr_tail": (err or "")[-400:] if problems else "",
    }
    return row, problems, check_rc


def chaos_matrix(out_path: str, scenarios=None) -> dict:
    """The chaos gauntlet (ISSUE 13): run every named scenario of
    ``resilience.faults.CHAOS_SCENARIOS`` — preempt x straggler-stall x
    corrupt-shard (nan_grad) x host-flap, alone and composed — end-to-end
    under the fleet supervisor with the closed-loop policy engine active,
    and write the scoreboard to ``out_path``.

    Every scenario must recover via policy/supervisor actions alone: no
    operator marker files (the only marker a driver writes is
    ``host-1.up`` — the SCHEDULER's re-admission interface).  Each run self-validates its event stream
    (``run_report --check`` plus the scenario's required kinds — the
    policy scenarios require ``policy``), its expectations are checked by
    ``check_chaos_expectations`` (a violated scenario fails the leg), and
    no policy action may end the gauntlet still pending
    (``run_report --policy`` semantics).

    CPU emulation caveat: rank 1 is the
    pid+event-file host emulation from ``tests/fleet_pool_worker.py`` —
    the pinned CI jax cannot run multi-process collectives on the CPU
    backend — and the persistent straggler is that rank reporting a
    slowed ``step/dispatch_s`` sketch (``EMU_SLOW_DISPATCH_ENV``), which
    is exactly the interface a genuinely slow host presents to the
    supervisor-side alert engine.
    """

    from distributed_training_comparison_tpu import obs
    from distributed_training_comparison_tpu.resilience import (
        CHAOS_KIND,
        CHAOS_SCENARIOS,
        check_chaos_expectations,
    )
    from distributed_training_comparison_tpu.ops.policy import pending_actions
    from distributed_training_comparison_tpu.resilience.control import (
        unapplied_actions,
    )

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import run_report

    platform = _require_explicit_cpu("tools/chaos_matrix.py")
    repo = REPO
    child = os.path.join(repo, "tests", "fleet_pool_worker.py")
    names = list(scenarios or CHAOS_SCENARIOS)
    rows: dict[str, dict] = {}
    failures: list[str] = []
    worst_rc = 0

    for name in names:
        sc = CHAOS_SCENARIOS[name]
        if sc.get("session") == "serve":
            # the flash-crowd x serve axis: the real --serve entry, not
            # the training fleet worker (see _run_serve_chaos_scenario)
            row, problems, check_rc = _run_serve_chaos_scenario(
                name, sc, repo, run_report
            )
            worst_rc = max(worst_rc, check_rc)
            rows[name] = row
            emit_progress(f"chaos/{name}", {
                "rc": row["final_rc"], "green": row["green"],
                "problems": problems,
                "recompiles": row["recompiles"],
                "p99_recovered": row["p99_recovered"],
            })
            if problems:
                failures.append(
                    f"{name}: {problems} (stderr tail: "
                    f"{row.get('stderr_tail', '')})"
                )
            continue
        root = tempfile.mkdtemp(prefix=f"chaos-{name}-")
        goodput_json = os.path.join(root, "goodput-scenario.json")
        cmd = [
            sys.executable, child, "--supervise",
            "--fleet-hosts", "2", "--fleet-local-devices", "1",
            "--fleet-grace-secs", "3", "--fleet-poll-secs", "0.2",
            "--synthetic-data", "--limit-examples", "256",
            "--batch-size", "32", "--epoch", "10",
            "--no-progress", "--eval-step", "1000",
            "--save-last-min-secs", "0", "--seed", "7",
            "--device-chunk-steps", "2", "--heartbeat-secs", "0.2",
            "--ckpt-path", root, "--goodput-json", goodput_json,
            "--policy-mode", sc["policy_mode"],
        ]
        if sc["fault_plan"]:
            cmd += ["--fault-plan", sc["fault_plan"]]
        for spec in sc["alerts"]:
            cmd += ["--alert", spec]
        for spec in sc["policies"]:
            cmd += ["--policy", spec]
        # {root} in extra_args resolves to the scenario's ckpt root
        # ({host} survives untouched for the SchedulerProbe itself)
        cmd += [a.replace("{root}", root) for a in sc["extra_args"]]
        env = dict(os.environ)
        env.update(sc["env"])

        driver_log: list = []

        def drive(proc, script=sc["driver"]) -> None:
            # the external environment only: spot reclaim (SIGKILL) and
            # the scheduler's re-admission signal — never an operator
            # action (no host-i.down is ever written here; the probe
            # variant writes no marker at all)
            if script is not None:
                _drive_fleet_gauntlet(
                    root, proc, driver_log,
                    readmit=(
                        "probe" if script == "probe_readmit_host1"
                        else script == "kill_and_readmit_host1"
                    ),
                )

        proc = subprocess.Popen(
            cmd, cwd=repo, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            # own process group: a timeout kill must take the supervised
            # fleet's rank children down too, not orphan them onto the
            # next scenario's timings
            start_new_session=True,
        )
        driver = threading.Thread(target=drive, args=(proc,), daemon=True)
        driver.start()
        timed_out = False
        try:
            out, err = proc.communicate(timeout=900)
        except subprocess.TimeoutExpired:
            # a wedged scenario must neither leak its process tree nor
            # abort the gauntlet: kill the whole group, record a red
            # row, move on
            timed_out = True

            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (OSError, ProcessLookupError):
                proc.kill()
            out, err = proc.communicate()
            driver_log.append("scenario timed out after 900s; killed")
        driver.join(timeout=10.0)

        events, _files = run_report.load_run(root)
        by_kind: dict[str, int] = {}
        for ev in events:
            by_kind[ev.get("kind", "?")] = by_kind.get(ev.get("kind", "?"), 0) + 1
        policy_states: dict[str, int] = {}
        for ev in events:
            if ev.get("kind") == "policy":
                st = (ev.get("payload") or {}).get("state", "?")
                policy_states[st] = policy_states.get(st, 0) + 1
        # the decide->apply trail: every control request's end state,
        # split by whether the application landed INSIDE an epoch (the
        # tentpole's chunk boundary) or at the legacy epoch boundary
        controls_applied = control_mid_epoch = controls_superseded = 0
        control_ttms: list[float] = []
        for ev in events:
            if ev.get("kind") != "control":
                continue
            p = ev.get("payload") or {}
            if p.get("state") == "applied":
                controls_applied += 1
                if p.get("mid_epoch"):
                    control_mid_epoch += 1
                if isinstance(p.get("ttm_s"), (int, float)):
                    control_ttms.append(float(p["ttm_s"]))
            elif p.get("state") == "superseded":
                controls_superseded += 1
        try:
            with open(goodput_json) as f:
                gp = json.load(f)
        except (OSError, ValueError):
            gp = {}
        evidence_ok = False
        for dump in sorted(Path(root).glob("version-*/crash_dump*.json")):
            try:
                d = json.loads(dump.read_text())
            except (OSError, ValueError):
                continue
            ev_block = d.get("evidence") or {}
            if ev_block.get("alert_timeline") and ev_block.get("policy_timeline"):
                evidence_ok = True
        observed = {
            "final_rc": proc.returncode,
            "resizes": by_kind.get("resize", 0),
            "rollbacks": by_kind.get("rollback", 0),
            "alerts_fired": sum(
                1 for ev in events
                if ev.get("kind") == "alert"
                and (ev.get("payload") or {}).get("state") == "firing"
            ),
            "restarts": int(gp.get("restarts", 0) or 0),
            "preemptions": int(gp.get("preemptions", 0) or 0),
            "policy_requested": policy_states.get("requested", 0),
            "policy_completed": policy_states.get("completed", 0),
            "policy_failed": policy_states.get("failed", 0),
            "policy_dry_run": policy_states.get("dry_run", 0),
            "policy_cooldown": policy_states.get("cooldown", 0),
            "policy_budget": policy_states.get("budget", 0),
            "policy_pending": len(pending_actions(events)),
            "controls_applied": controls_applied,
            "control_mid_epoch": control_mid_epoch,
            "controls_superseded": controls_superseded,
            "control_ttm_max_s": round(max(control_ttms), 3)
            if control_ttms else None,
            "crash_dump_evidence": evidence_ok,
            "goodput_frac": gp.get("goodput_frac"),
        }
        problems = check_chaos_expectations(sc["expect"], observed)
        if timed_out:
            problems.append("scenario timed out after 900s (process killed)")
        if observed["policy_pending"]:
            problems.append(
                f"{observed['policy_pending']} policy action(s) still "
                "pending (requested, never completed)"
            )
        never_applied = unapplied_actions(events)
        if never_applied:
            problems.append(
                f"{len(never_applied)} acted decision(s) completed with "
                "no 'applied' control event (decide->apply trail broken)"
            )
        check_rc = events_check_rc(
            root, require_kinds=tuple(sc["require_kinds"])
        )
        worst_rc = max(worst_rc, check_rc)
        if check_rc != 0:
            problems.append(f"events_check_rc={check_rc}")
        row = {
            "desc": sc["desc"],
            "fault_plan": sc["fault_plan"],
            "alerts": list(sc["alerts"]),
            "policies": list(sc["policies"]),
            "policy_mode": sc["policy_mode"],
            "driver": driver_log,
            **observed,
            "events_check_rc": check_rc,
            "green": not problems,
            "problems": problems,
        }
        rows[name] = row
        emit_progress(f"chaos/{name}", {
            "rc": proc.returncode, "green": row["green"],
            "problems": problems, "policy": policy_states,
        })
        if problems:
            failures.append(
                f"{name}: {problems} (stderr tail: {(err or '')[-800:]})"
            )
        # one `chaos` event per scenario on a bus bound to the scenario
        # root, so the scoreboard row itself is replayable from the stream
        chaos_bus = obs.EventBus(run_id=obs.new_run_id())
        chaos_bus.bind_dir(root)
        chaos_bus.emit(
            CHAOS_KIND, scenario=name, green=row["green"],
            policy_completed=observed["policy_completed"],
            resizes=observed["resizes"], rollbacks=observed["rollbacks"],
            final_rc=observed["final_rc"],
        )
        chaos_bus.close()

    record = {
        "metric": "chaos_matrix",
        "platform": platform,
        "scenarios": rows,
        "green": not failures,
        "events_check_rc": worst_rc,
        "note": (
            "CPU record: rank 1 is the pid+event-file host emulation "
            "(tests/fleet_pool_worker.py) and the persistent straggler is "
            "its slowed step/dispatch_s sketch; every supervisor/policy "
            "code path (alert evaluation, drain markers, request channel, "
            "world re-render) runs for real. Recovery is policy/supervisor"
            "-driven only — the single driver-written marker is host-1.up, "
            "the scheduler's re-admission interface."
        ),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
        f.write("\n")
    print(json.dumps({
        "metric": "chaos_matrix",
        "green": record["green"],
        "scenarios": {
            n: {
                "green": r["green"], "final_rc": r["final_rc"],
                "policy_completed": r["policy_completed"],
                "resizes": r["resizes"], "rollbacks": r["rollbacks"],
                "goodput_frac": r["goodput_frac"],
            }
            for n, r in rows.items()
        },
        "full_record": out_path,
    }))
    if failures:
        raise RuntimeError(
            "chaos gauntlet red: " + "; ".join(failures)
        )
    return record


def main(argv=None) -> int:
    from distributed_training_comparison_tpu.resilience import CHAOS_SCENARIOS

    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
    )
    ap.add_argument("--out", required=True,
                    help="where the scoreboard (JSON) is written")
    ap.add_argument("scenario", nargs="*",
                    help="scenarios to run (default: all of them): "
                    + ", ".join(CHAOS_SCENARIOS))
    args = ap.parse_args(argv)
    unknown = [n for n in args.scenario if n not in CHAOS_SCENARIOS]
    if unknown:
        ap.error(f"unknown scenario(s): {unknown}")
    try:
        chaos_matrix(args.out, args.scenario or None)
    except RuntimeError as e:
        print(e, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
