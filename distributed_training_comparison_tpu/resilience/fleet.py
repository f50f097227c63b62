"""Elastic fleet supervision: a host pool with re-rendered world size.

The PR-2 :class:`~.supervisor.Supervisor` relaunches ONE command — the same
host set, the same ``--world-size``/``--rank``/``--dist-url`` — so losing a
single host of a preemptible fleet ends the run even though the restore
path has proven N→N/2 device recovery since PR 2 (host-pytree checkpoints,
``elastic.py``).  :class:`FleetSupervisor` closes that gap: it owns N
host-process attempts and a **host pool** (alive / lost / returned), and on
every attempt boundary **re-renders the launch set** from the surviving
hosts — a fresh rendezvous port, ``--world-size W``, one ``--rank`` per
surviving host — so a mid-run host loss degrades the fleet to the widest
*legal* world size (batch divisibility and the tensor-parallel degree can
force W below the surviving count) instead of ending the run.  A returned
host triggers a deliberate drain-checkpoint-and-re-expand cycle back to
full width; that planned drain never consumes the restart budget.

How a host leaves and re-enters the pool:

- a child that dies by a signal the supervisor did not send (spot
  reclamation's SIGKILL, an OOM kill, an operator's ``kill``) marks its
  host **lost**;
- the marker files under ``<ckpt_root>/fleet/`` are the scheduler/operator
  interface: ``host-{i}.down`` marks a host lost (mid-attempt it triggers
  a drain), ``host-{i}.up`` re-admits it (mid-attempt it triggers the
  deliberate drain-and-re-expand).  Markers are consumed when acted on, so
  a host can cycle down/up repeatedly;
- a clean ``EXIT_PREEMPTED`` without either signal keeps the pool intact
  (the whole fleet drained together — e.g. one host's SIGTERM OR-reduced
  across the collective — and the supervisor cannot tell which machine is
  actually going away; the next loss signal will).

Every decision lands on the obs plane: a registered ``resize`` event per
world-size change, ``world_size``/``hosts`` in every ``attempt_start``/
``attempt_end``, per-attempt pids in ``fleet/status.json``, and the resize
list priced into GOODPUT.json by ``run_supervised``.

Restore correctness is the existing elastic path plus the explicit reshard
step (``elastic.validate_reshard``): host-pytree checkpoints re-place onto
whatever mesh the re-rendered world builds, the PRNG trajectory is a
function of the global step (never a device index), and the supervisor
refuses a world size whose mesh/batch split cannot exist — with the actual
numbers — before paying a process start and a compile for it.
"""

from __future__ import annotations

import json
import signal
import socket
import subprocess
import time
from pathlib import Path
from typing import Sequence

from .ckpt_io import atomic_write_bytes
from .faults import SchedulerProbe
from .supervisor import PlanRefused, Supervisor, strip_flags

FLEET_DIR = "fleet"
STATUS_NAME = "status.json"

HOST_ALIVE = "alive"
HOST_LOST = "lost"

# flags the fleet re-renders per attempt/rank; any caller-supplied values
# are stripped from the child argv first
_RENDERED_FLAGS = ("--world-size", "--rank", "--dist-url")
# parent-loop-only flags that must never leak into a child
_PARENT_FLAGS = (
    "--fleet-hosts", "--fleet-min-hosts", "--fleet-local-devices",
    "--fleet-grace-secs", "--fleet-poll-secs", "--fleet-probe",
)
# layout flags the supervisor's auto-parallel plan re-renders per attempt
# (value-taking vs bare, because strip_flags assumes `--flag VALUE` pairs)
_PLAN_VALUE_FLAGS = (
    "--model-parallel", "--pipeline-parallel", "--pipeline-virtual-stages",
    "--pipeline-schedule", "--pipeline-microbatches", "--grad-comms",
    "--parallel-plan",
    # the plan owns the whole layout: a surviving legacy --parallel-style
    # would either parser.error() every child (pipeline-parallel > 1
    # composes with style tensor only) or silently run the legacy
    # single-axis pipeline the cost model never priced; stripping it
    # leaves the child on the default tensor-compose style the candidates
    # were scored as
    "--parallel-style",
)
_PLAN_BARE_FLAGS = ("--shard-optim", "--no-shard-optim")


class FleetPlanError(PlanRefused):
    """No legal world size exists for the surviving hosts (batch
    divisibility / tensor-parallel degree / ``min_hosts`` floor).  The
    message carries the numbers.  Subclasses ``PlanRefused`` so a mid-run
    refusal stops the restart loop orderly (summary + goodput survive)
    while a pre-first-attempt refusal still dies at the CLI."""


def free_rendezvous_port() -> int:
    """A currently-free TCP port for the next attempt's ``--dist-url`` —
    every attempt gets a FRESH rendezvous so a half-dead coordinator from
    the previous attempt can never wedge the relaunch."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def legal_worlds(
    n_hosts: int,
    *,
    batch_size: int = 0,
    local_devices: int = 0,
    model_parallel: int = 1,
    pipeline_parallel: int = 1,
    grad_accum: int = 1,
) -> list[int]:
    """Every world size ``W <= n_hosts`` whose mesh and batch split are
    legal, widest first: ``W * local_devices`` devices must tile the
    model axis, and the global batch must divide the resulting data axis
    x grad_accum.  This is the FEASIBILITY filter — the auto-parallel
    planner scores each legal world and picks the fastest; without a
    planner the widest wins (:func:`widest_legal_world`).

    ``local_devices == 0`` (unknown per-host device count — real TPU
    hosts inheriting their environment) DEGRADES the check rather than
    hardening it: the model-axis tiling cannot be judged without the
    device count (4-chip hosts tile ``model_parallel 4`` at any W, which
    ``local=1`` would wrongly refuse), and host-granularity batch
    divisibility is only a *necessary* condition when the model axis is 1.
    The Trainer's own ``elastic.validate_reshard`` stays the authority at
    restore time."""
    from ..parallel.mesh import elastic_mesh_shape

    local = int(local_devices)
    unit = max(1, grad_accum)
    out: list[int] = []
    for w in range(int(n_hosts), 0, -1):
        if local > 0:
            shape = elastic_mesh_shape(
                w * local, model_parallel, pipeline_parallel
            )
            if shape is None:
                continue
            if batch_size and batch_size % (shape[0] * unit):
                continue
        elif model_parallel == 1 and pipeline_parallel == 1:
            # unknown devices/host, pure data parallel: the data axis is a
            # multiple of W, so batch % W is a necessary condition
            if batch_size and batch_size % (w * unit):
                continue
        # unknown devices/host with a model axis: any W may be legal
        out.append(w)
    return out


def widest_legal_world(
    n_hosts: int,
    *,
    batch_size: int = 0,
    local_devices: int = 0,
    model_parallel: int = 1,
    pipeline_parallel: int = 1,
    grad_accum: int = 1,
) -> int | None:
    """The widest legal world (see :func:`legal_worlds`), or None when no
    W in ``[1, n_hosts]`` is legal."""
    worlds = legal_worlds(
        n_hosts,
        batch_size=batch_size,
        local_devices=local_devices,
        model_parallel=model_parallel,
        pipeline_parallel=pipeline_parallel,
        grad_accum=grad_accum,
    )
    return worlds[0] if worlds else None


class FleetSupervisor(Supervisor):
    """Supervise N host processes as one elastic fleet.

    ``cmd``/``env`` keep the base-class contract (static or callables of
    the attempt index) and describe ONE host's launch; the fleet strips any
    ``--world-size``/``--rank``/``--dist-url`` it finds and re-renders them
    per rank from the live pool.  ``spawn`` is the process seam
    (``subprocess.Popen``-shaped; tests inject fakes).

    The restart policy — budget, exponential crash backoff, immediate
    relaunch on preemption, progress-probe budget sparing — is inherited
    unchanged from :class:`Supervisor`; what changes is *what an attempt
    is*: a set of ranks whose membership is recomputed at every boundary.
    """

    def __init__(
        self,
        cmd,
        *,
        hosts: int,
        ckpt_root: str | Path,
        batch_size: int = 0,
        local_devices: int = 0,
        model_parallel: int = 1,
        pipeline_parallel: int = 1,
        grad_accum: int = 1,
        min_hosts: int = 1,
        grace_s: float = 15.0,
        poll_s: float = 0.5,
        probe: str = "",
        spawn=None,
        coordinator_host: str = "127.0.0.1",
        plan_hparams=None,
        **kw,
    ) -> None:
        super().__init__(cmd, **kw)
        if hosts < 1:
            raise ValueError(f"fleet needs >= 1 host, got {hosts}")
        self.hosts = int(hosts)
        self.ckpt_root = Path(ckpt_root)
        self.batch_size = int(batch_size)
        self.local_devices = int(local_devices)
        self.model_parallel = max(1, int(model_parallel))
        self.pipeline_parallel = max(1, int(pipeline_parallel))
        self.grad_accum = max(1, int(grad_accum))
        self.min_hosts = max(1, int(min_hosts))
        self.grace_s = max(0.0, float(grace_s))
        self.poll_s = max(0.05, float(poll_s))
        # --fleet-probe: the scheduler's re-admission signal, polled for
        # every LOST host on the marker cadence; a schedulable slot is
        # surfaced as the same host-i.up marker an operator would write
        self.probe = SchedulerProbe(probe, log=self._log) if probe else None
        self._spawn = spawn or (
            lambda c, e: subprocess.Popen(list(c), env=e)
        )
        # the rendezvous address handed to every rank.  The loopback
        # default serves the single-machine case (tests, one-box
        # fleets); a multi-machine ``spawn`` implementation must pass the
        # supervisor's REACHABLE address here, or rank>0's --dist-url
        # resolves to its own loopback and the fleet never rendezvouses.
        self.coordinator_host = str(coordinator_host)
        self.pool: dict[int, str] = {i: HOST_ALIVE for i in range(self.hosts)}
        self.resizes: list[dict] = []
        self._world: int | None = None
        self._ranks: list[int] = []  # host ids launched this attempt, rank order
        self._attempt = 0
        self._deliberate: str | None = None  # planned drain reason, one-shot
        self._change: dict[str, list[int]] = {"lost": [], "returned": []}
        # --- auto-parallel planning (--parallel-plan auto under the
        # fleet): the supervisor re-plans at EVERY attempt boundary, so a
        # resize lands on the fastest legal layout rather than the widest
        # (legal_worlds is the feasibility filter; the planner the
        # decision).  Requires a known per-host device count — with
        # local_devices == 0 the supervisor cannot size candidate meshes
        # and planning degrades to the children's own trainer-side plan.
        self.plan_hparams = (
            plan_hparams
            if plan_hparams is not None
            and str(getattr(plan_hparams, "parallel_plan", "off")) == "auto"
            and self.local_devices > 0
            else None
        )
        self.plans: list[dict] = []  # one payload per emitted plan event
        self._plan_flags: list[str] = []  # rendered layout for this attempt
        self._replan_reason: str | None = None  # policy 'replan' request

    # ------------------------------------------------------------- pool

    def _fleet_dir(self) -> Path:
        d = self.ckpt_root / FLEET_DIR
        d.mkdir(parents=True, exist_ok=True)
        return d

    def _marker(self, host: int, kind: str) -> Path:
        return self._fleet_dir() / f"host-{host}.{kind}"

    def active_hosts(self) -> list[int]:
        return [h for h, s in sorted(self.pool.items()) if s == HOST_ALIVE]

    def lost_hosts(self) -> list[int]:
        return [h for h, s in sorted(self.pool.items()) if s == HOST_LOST]

    def mark_lost(self, host: int, why: str = "") -> None:
        if self.pool.get(host) == HOST_LOST:
            return
        self.pool[host] = HOST_LOST
        self._change["lost"].append(host)
        self._log(f"host {host} lost{f' ({why})' if why else ''}")

    def readmit(self, host: int) -> None:
        if self.pool.get(host) != HOST_LOST:
            return
        self.pool[host] = HOST_ALIVE
        self._change["returned"].append(host)
        self._log(f"host {host} returned to the pool")

    def _poll_markers(self) -> tuple[list[int], list[int]]:
        """Consume ``host-*.down`` / ``host-*.up`` marker files; returns
        (hosts newly lost, hosts newly returned) by THIS poll."""
        lost_now: list[int] = []
        returned_now: list[int] = []
        if self.probe is not None:
            # ask the scheduler about every lost slot; a schedulable
            # answer becomes the same up marker an operator would write,
            # consumed by the loop below in this very poll
            for host in self.lost_hosts():
                if self.probe.check(host):
                    up = self._marker(host, "up")
                    if not up.exists():
                        up.write_text(json.dumps(
                            {"by": "probe", "spec": self.probe.spec}
                        ))
        for host in range(self.hosts):
            up = self._marker(host, "up")
            down = self._marker(host, "down")
            if up.exists():
                if self.pool.get(host) == HOST_LOST:
                    self.readmit(host)
                    returned_now.append(host)
                up.unlink(missing_ok=True)
                down.unlink(missing_ok=True)
            elif down.exists():
                if self.pool.get(host) == HOST_ALIVE:
                    self.mark_lost(host, why="down marker")
                    lost_now.append(host)
                down.unlink(missing_ok=True)
        return lost_now, returned_now

    # ------------------------------------------------------------- plan

    def _plan_world(
        self, n_active: int, events: list | None = None
    ) -> tuple[int | None, object | None, list[str]]:
        """Score every legal world size with the auto-parallel planner
        and return ``(world, plan, errors)`` — the fastest predicted
        (W, layout), ties broken toward the WIDER world.

        ``legal_worlds`` in its host-granularity form (``local_devices=0,
        model_parallel=1``: batch % hosts × grad_accum — the condition
        every child hard-enforces via ``host_local_batch_slice`` whatever
        mesh the plan installs) is the feasibility frame; each world's
        per-candidate mesh/batch/HBM gates run inside ``plan_layout``,
        so the refusal strings carry the actual numbers
        (``elastic.divisibility_help``)."""
        from ..parallel import planner as planner_mod

        if events is None:
            events = planner_mod.load_ledger_events(self.ckpt_root)
        # ONE ledger fold for every candidate world (the event history of
        # a long elastic run is large; per-world re-parsing would pay
        # O(hosts x stream) at every boundary).  The supervisor process
        # never touches accelerators — the device kind comes from the
        # children's committed compile events, never from initializing a
        # jax backend in the parent.
        ledger = planner_mod.fit_ledger(events)
        kind = ledger.device_kind or "unknown"
        unit = max(1, self.grad_accum)
        # the host-granularity feasibility frame (see docstring)
        legal = set(
            legal_worlds(
                n_active, batch_size=self.batch_size,
                local_devices=0, model_parallel=1, pipeline_parallel=1,
                grad_accum=self.grad_accum,
            )
        )
        best: tuple | None = None
        errors: list[str] = []
        for w in range(int(n_active), max(1, self.min_hosts) - 1, -1):
            if w not in legal:
                errors.append(
                    f"world {w}: global batch {self.batch_size} not "
                    f"divisible by {w} host(s)"
                    + (f" x grad_accum {unit}" if unit > 1 else "")
                )
                continue
            try:
                p = planner_mod.plan_layout(
                    self.plan_hparams,
                    devices=w * self.local_devices,
                    device_kind=kind,
                    ledger=ledger,
                )
            except planner_mod.PlanError as e:
                errors.append(f"world {w}: {e}")
                continue
            key = (p.predicted_step_s, -w)
            if best is None or key < best[0]:
                best = (key, w, p)
        if best is None:
            return None, None, errors
        return best[1], best[2], errors

    def _plan_attempt(self, attempt: int) -> None:
        self._attempt = attempt
        self._poll_markers()
        if not self.active_hosts():
            # the pool is empty: there is no reduced width left to run at.
            # Re-admit everything and let the relaunch probe whether any
            # machine actually answers — the restart budget still bounds a
            # truly dead fleet.
            self._log(
                "every host is lost; re-admitting the full pool for the "
                "next attempt"
            )
            for host in self.lost_hosts():
                self.readmit(host)
        active = self.active_hosts()
        replan_reason, self._replan_reason = self._replan_reason, None
        plan = None
        plan_errors: list[str] = []
        world = None
        if self.plan_hparams is not None:
            # the planner decides; legal_worlds/widest_legal_world stay
            # the feasibility frame.  A failed plan at every world falls
            # through to the classic widest-legal selection so the
            # refusal path still names the real blocker.
            world, plan, plan_errors = self._plan_world(len(active))
        if world is None:
            world = widest_legal_world(
                len(active),
                batch_size=self.batch_size,
                local_devices=self.local_devices,
                model_parallel=self.model_parallel,
                pipeline_parallel=self.pipeline_parallel,
                grad_accum=self.grad_accum,
            )
        if world is None or world < self.min_hosts:
            from ..parallel.mesh import elastic_mesh_shape
            from .elastic import divisibility_help

            local = max(1, self.local_devices)
            # name the ACTUAL blocker — a floor refusal must not fabricate
            # a batch-divisibility diagnosis for a batch that divides fine
            if world is not None:
                detail = (
                    f"widest legal world {world} is below the "
                    f"--fleet-min-hosts floor {self.min_hosts}"
                )
            else:
                mesh_w = next(
                    (
                        w for w in range(len(active), 0, -1)
                        if elastic_mesh_shape(
                            w * local, self.model_parallel,
                            self.pipeline_parallel,
                        )
                    ),
                    None,
                )
                if mesh_w is None:
                    detail = (
                        f"no surviving device count tiles model_parallel "
                        f"{self.model_parallel} ({len(active)} host(s) x "
                        f"{local} device(s))"
                    )
                else:
                    shape = elastic_mesh_shape(
                        mesh_w * local, self.model_parallel,
                        self.pipeline_parallel,
                    )
                    detail = divisibility_help(
                        self.batch_size, shape[0], self.grad_accum
                    )
            if plan_errors:
                # the planner's per-world refusals carry the same
                # actionable numbers (divisibility_help & friends) —
                # surface the widest world's, not a bare "no plan found"
                detail = f"{detail}; planner: {plan_errors[0]}"
            msg = (
                f"no legal world size for {len(active)} surviving host(s) "
                f"(hosts alive: {active}, {local} device(s)/host, "
                f"model_parallel {self.model_parallel}, floor "
                f"{self.min_hosts}): {detail}"
            )
            self._events("give_up", attempt=attempt, reason=msg)
            raise FleetPlanError(msg)
        prev = self._world
        self._ranks = active[:world]
        self._world = world
        if prev is not None and world != prev:
            if self._change["returned"] and world > prev:
                reason = "host_returned"
            elif self._change["lost"] or world < prev:
                reason = "host_lost"
            else:
                reason = "batch_divisibility"
            record = {
                "attempt": attempt,
                "from_world": prev,
                "to_world": world,
                "reason": reason,
                "hosts": list(self._ranks),
                "lost": list(self._change["lost"]),
                "returned": list(self._change["returned"]),
            }
            self.resizes.append(record)
            self._events("resize", **record)
            self._log(
                f"resize: world {prev} -> {world} ({reason}; "
                f"ranks on hosts {self._ranks})"
            )
        self._change = {"lost": [], "returned": []}
        # one `plan` event per planned attempt, AFTER any resize — a
        # shrink's stream reads resize → plan → run_start, and run_report
        # --plan checks the run_start layout against this payload
        if plan is not None:
            plan_reason = (
                "policy_replan"
                if replan_reason
                else ("resize" if prev is not None and world != prev
                      else "attempt_plan")
            )
            payload = plan.payload(
                installed=True, reason=plan_reason, attempt=attempt
            )
            # the host count this plan sized its devices from: run_report
            # --plan scales the data-axis check by the world the attempt
            # actually joined (the pid-level CPU fleet emulation's rank 0
            # runs its own local world; on a real pod worlds agree and
            # the check is exact)
            payload["world"] = world
            if replan_reason:
                payload["replan_trigger"] = replan_reason
            self.plans.append(
                {
                    "attempt": attempt,
                    "reason": plan_reason,
                    "world": world,
                    "chosen": plan.chosen.key,
                    "predicted_step_s": plan.chosen.predicted_step_s,
                }
            )
            self._events("plan", **payload)
            self._plan_flags = plan.chosen.flags() + ["--parallel-plan", "off"]
            self._log(
                f"plan: attempt {attempt} world {world} -> "
                f"{plan.chosen.key} (predicted step "
                f"{plan.predicted_step_s:.6f}s, {plan_reason})"
            )
        else:
            self._plan_flags = []

    def _attempt_info(self) -> dict:
        return {"world_size": self._world, "hosts": list(self._ranks)}

    def _attempt_free(self, rc: int, preempted: bool) -> bool:
        # the deliberate drain-and-re-expand — and the autopilot's replan
        # drain — are planned work: consuming the restart budget for them
        # would starve real failures of restarts (the policy engine's own
        # cooldown + action budget already bound how often replan fires)
        return self._deliberate in ("host_returned", "replan")

    def request_replan(self, reason: str) -> None:
        """The autopilot's ``replan`` action (ops/policy.py): drain the
        running attempt deliberately and re-plan the layout at the next
        boundary against the freshest ledger — the HBM-breach remediation
        the PR-12 autopilot had no action for.  Thread-safe one-shot (the
        policy engine calls from the watcher thread; the launch poll loop
        reads it); first reason wins until the next plan consumes it."""
        if self.plan_hparams is None:
            raise ValueError(
                "replan needs --parallel-plan auto with a known "
                "--fleet-local-devices (supervisor-side planning is off)"
            )
        if self._replan_reason is None:
            self._replan_reason = str(reason)
            self._log(f"replan requested: {reason}")

    # ----------------------------------------------------------- launch

    def _render_cmd(
        self, base: Sequence[str], world: int, rank: int, port: int
    ) -> list[str]:
        args = strip_flags(base, _RENDERED_FLAGS + _PARENT_FLAGS)
        if self._plan_flags:
            # the supervisor's plan owns the layout: strip any caller
            # layout flags and append the rendered winner (which ends
            # with --parallel-plan off, so the child does not re-plan)
            args = [a for a in args if a not in _PLAN_BARE_FLAGS]
            args = strip_flags(args, _PLAN_VALUE_FLAGS)
            args = args + list(self._plan_flags)
        elif self.plan_hparams is not None:
            # supervisor-side planning is on but this attempt fell back
            # to the classic widest-legal selection (every world's plan
            # refused): the caller's hand layout flags survive untouched,
            # but the children must not re-plan — their own planner would
            # re-raise the same refusal at Trainer construction and the
            # fleet would burn its restart budget relaunching a crash
            args = strip_flags(args, ("--parallel-plan",))
            args = args + ["--parallel-plan", "off"]
        return args + [
            "--world-size", str(world),
            "--rank", str(rank),
            "--dist-url", f"{self.coordinator_host}:{port}",
        ]

    def _render_env(self, base: dict | None, host: int) -> dict | None:
        if self.local_devices > 0:
            from .elastic import forced_host_device_env

            # the CPU-emulation knob (tests, chaos matrix): force each child's
            # virtual device count; a real TPU fleet inherits its env
            return forced_host_device_env(self.local_devices, base=base)
        return dict(base) if base is not None else None

    def _write_status(self, pids: dict[int, int], port: int) -> None:
        try:
            # atomic (tmp+rename): ops tooling polls this file, and a read
            # landing mid-rewrite must never observe torn JSON
            atomic_write_bytes(
                self._fleet_dir() / STATUS_NAME,
                json.dumps(
                    {
                        "attempt": self._attempt,
                        "world_size": self._world,
                        "hosts": list(self._ranks),
                        "pids": {str(h): p for h, p in pids.items()},
                        "dist_url": f"{self.coordinator_host}:{port}",
                        "t_wall": time.time(),
                    },
                    indent=1,
                ).encode(),
                durable=False,  # advisory: rename-atomicity, no fsync stall
            )
        except OSError:
            pass  # status is advisory; losing it must not kill the fleet

    def _terminate(self, procs: dict[int, object], signaled: set[int]) -> None:
        """SIGTERM the running children (the in-process preemption handler
        drains a checkpoint and exits ``EXIT_PREEMPTED``), then SIGKILL
        whatever is still alive past the grace window — a host wedged in a
        collective whose peer died can never reach its drain poll.  Every
        host WE signal lands in ``signaled``: a signal death the supervisor
        caused (including a SIGTERM that beat the handler install) must
        never read as the host itself going away."""
        for host, p in procs.items():
            if p.poll() is None:
                signaled.add(host)
                try:
                    p.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + self.grace_s
        while time.monotonic() < deadline and any(
            p.poll() is None for p in procs.values()
        ):
            self._sleep(min(0.1, self.poll_s))
        for host, p in procs.items():
            if p.poll() is None:
                signaled.add(host)
                try:
                    p.kill()
                except OSError:
                    pass

    def _launch(self, attempt: int) -> int:
        base_cmd, base_env = self._resolve(attempt)
        port = free_rendezvous_port()
        world = len(self._ranks)
        self._deliberate = None
        procs: dict[int, object] = {}
        pids: dict[int, int] = {}
        for rank, host in enumerate(self._ranks):
            cmd = self._render_cmd(base_cmd, world, rank, port)
            env = self._render_env(base_env, host)
            p = self._spawn(cmd, env)
            procs[host] = p
            pids[host] = int(getattr(p, "pid", 0) or 0)
            try:
                self._marker(host, "pid").write_text(str(pids[host]))
            except OSError:
                pass
        # stale pidfiles of hosts NOT in this launch set would point ops at
        # processes that no longer exist
        for host in range(self.hosts):
            if host not in procs:
                self._marker(host, "pid").unlink(missing_ok=True)
        self._write_status(pids, port)

        signaled_by_us: set[int] = set()
        rcs: dict[int, int] = {}
        ending = False
        while len(rcs) < len(procs):
            for host, p in procs.items():
                if host in rcs:
                    continue
                rc = p.poll()
                if rc is None:
                    continue
                rcs[host] = int(rc)
                if rc != 0 and not ending:
                    # one bad exit ends the attempt: the rest either drain
                    # (SIGTERM) or are killed past the grace window.  A
                    # clean rc 0 lets the others finish normally.
                    ending = True
                    self._terminate(
                        {h: q for h, q in procs.items() if h not in rcs},
                        signaled_by_us,
                    )
            if len(rcs) == len(procs):
                break
            if not ending:
                lost_now, returned_now = self._poll_markers()
                if set(lost_now) & set(self._ranks):
                    # only a RUNNING rank's loss ends the attempt; a spare
                    # host leaving the pool changes membership, not work
                    self._deliberate = "host_lost"
                elif returned_now and (
                    widest_legal_world(
                        len(self.active_hosts()),
                        batch_size=self.batch_size,
                        local_devices=self.local_devices,
                        model_parallel=self.model_parallel,
                        grad_accum=self.grad_accum,
                    ) or 0
                ) > world:
                    # drain only when the return actually WIDENS the legal
                    # world — a spare coming back that batch divisibility
                    # still excludes must not burn a drain-relaunch cycle
                    self._deliberate = "host_returned"
                elif self._replan_reason is not None:
                    # the autopilot asked for a replan (an HBM-ledger
                    # alert fired): drain deliberately; _plan_attempt
                    # consumes the reason and re-plans with the ledger
                    # that now carries the breach
                    self._deliberate = "replan"
                if self._deliberate is not None:
                    self._log(
                        f"draining attempt {attempt} ({self._deliberate}): "
                        "checkpoint, then re-render the launch set"
                    )
                    ending = True
                    self._terminate(
                        {h: q for h, q in procs.items() if h not in rcs},
                        signaled_by_us,
                    )
            self._sleep(self.poll_s)

        # a child killed by a signal the supervisor did not send is a host
        # that went away under us — out of the pool until it returns
        external_death = False
        for host, rc in rcs.items():
            if rc < 0 and host not in signaled_by_us:
                external_death = True
                try:
                    name = signal.Signals(-rc).name
                except ValueError:
                    name = str(-rc)
                self.mark_lost(host, why=f"killed by signal {name}")
        self._log(
            f"attempt {attempt} rank exits: "
            + ", ".join(f"host {h}: rc={rcs[h]}" for h in sorted(rcs))
        )
        if all(rc == 0 for rc in rcs.values()):
            return 0
        if external_death:
            # a machine went away: relaunch immediately with a re-rendered
            # world (preemption semantics), whatever else happened
            return self.preempt_exit_code
        crashes = [
            rc for rc in rcs.values()
            if rc > 0 and rc != self.preempt_exit_code
        ]
        if crashes:
            # a real crash keeps crash semantics (backoff + budget) even
            # when it surfaced DURING a deliberate drain or next to drained
            # peers — their clean 75s are a consequence, and a planned
            # drain must never mask a crash as budget-free
            self._deliberate = None
            return crashes[0]
        return self.preempt_exit_code

    def run(self) -> dict:
        summary = super().run()
        summary["resizes"] = list(self.resizes)
        summary["hosts"] = {str(h): s for h, s in sorted(self.pool.items())}
        if self.plan_hparams is not None:
            # the compact plan ledger (full payloads live on the bus as
            # `plan` events): one row per planned attempt
            summary["plans"] = list(self.plans)
        return summary


def fleet_env_knobs(hparams) -> dict:
    """The FleetSupervisor constructor kwargs derived from hparams — one
    place, for ``run_supervised``."""
    return {
        "hosts": int(getattr(hparams, "fleet_hosts", 0) or 0),
        "batch_size": int(getattr(hparams, "batch_size", 0) or 0),
        "local_devices": int(getattr(hparams, "fleet_local_devices", 0) or 0),
        "model_parallel": int(getattr(hparams, "model_parallel", 1) or 1),
        "pipeline_parallel": int(getattr(hparams, "pipeline_parallel", 1) or 1),
        "grad_accum": int(getattr(hparams, "grad_accum", 1) or 1),
        "min_hosts": int(getattr(hparams, "fleet_min_hosts", 1) or 1),
        "grace_s": float(getattr(hparams, "fleet_grace_secs", 15.0)),
        "poll_s": float(getattr(hparams, "fleet_poll_secs", 1.0)) / 2.0,
        "probe": str(getattr(hparams, "fleet_probe", "") or ""),
    }
