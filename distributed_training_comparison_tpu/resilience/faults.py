"""Deterministic, seeded fault-injection harness.

A production run meets preemptions, torn checkpoint writes, and slow-downs;
CI never does unless they are injected on purpose.  A ``FaultPlan`` parses a
``--fault-plan`` spec and fires the configured faults at configured points
of the epoch loop, deterministically — the same (spec, seed, trajectory)
always produces the same failures, so a recovery bug reproduces.

Spec syntax (``;``- or ``,``-separated events)::

    preempt@epoch=2            # injected preemption at the END of epoch 2
    preempt@epoch=2:step=40    # MID-epoch preemption once 40 steps are done
                               # (host data mode polls chunk boundaries;
                               # device mode fires at the epoch boundary)
    ckpt_fail@epoch=1          # epoch 1's last.ckpt write raises OSError
    torn_write@epoch=1         # epoch 1's last.ckpt is torn AFTER landing
    stall@epoch=0:secs=0.5     # 0.5 s step-time stall after epoch 0
    preempt@prob=0.1           # seeded per-epoch Bernoulli alternative

Training-health faults (the watchdog's test harness, ``health/``)::

    nan_grad@epoch=1                      # NaN loss+grads on steps [0, 3)
    nan_grad@epoch=1:step=4:steps=2       # ... on steps [4, 6)
    loss_spike@epoch=2                    # 64x loss/grad spike, 3 steps
                                          # starting mid-epoch
    loss_spike@epoch=2:scale=100:steps=5  # tunable magnitude/width
    bad_batch@epoch=1                     # ONE Inf step (a corrupt batch):
                                          # skipped by the compiled guard,
                                          # absorbed without rollback
    desync@epoch=1                        # simulated replica drift in the
                                          # param-fingerprint check

Step faults inject through the compiled step's ``fault_scale`` seam
(``train/step.py``): the loss metric and the gradients of the targeted
steps are multiplied by ``scale`` (NaN/Inf scales exercise the non-finite
guard, large finite scales the spike detector).  They are **one-shot per
process by consumption**: ``step_fault``/``desync_due`` mark the event
consumed when fetched, so a watchdog rollback replays the offending epoch
*clean* — modeling transient corruption (a flaky data server read) rather
than a persistent one, which the rollback budget bounds instead.

``epoch=K`` events whose effect lands AFTER epoch K's checkpoint
(``preempt``, ``torn_write``, ``stall``) are one-shot across restarts *by
construction*: the supervisor relaunches with ``--auto-resume``, training
resumes past epoch K, the trigger condition is never true again, and the
run completes — no need to strip the fault plan from the restart command.
A mid-epoch ``preempt`` (``step=S``) is one-shot the same way: the drain
records the steps already done, the relaunch fast-forwards past them, and
``preempt_step_due`` only fires for steps trained in THIS attempt.
``ckpt_fail@epoch=K`` is the deliberate exception: it blocks epoch K's
save, so a restart resumes at-or-before K and the fault re-fires — the
persistent-write-failure scenario (a genuinely dying disk), which the
supervisor's restart budget must bound rather than outrun.  ``prob=p``
events draw from a counter-free RNG keyed on ``(seed, kind, epoch)`` so a
restart replays identical decisions for identical epochs.
"""

from __future__ import annotations

import random
import subprocess
from dataclasses import dataclass, field
from pathlib import Path

KINDS = (
    "preempt", "ckpt_fail", "torn_write", "stall",
    "nan_grad", "bad_batch", "loss_spike", "desync",
)
# faults injected through the compiled step's fault_scale seam
STEP_KINDS = ("nan_grad", "bad_batch", "loss_spike")

_SCALE_DEFAULTS = {
    "nan_grad": float("nan"),
    "loss_spike": 64.0,
    "bad_batch": float("inf"),
}
_STEPS_DEFAULTS = {"nan_grad": 3, "loss_spike": 3, "bad_batch": 1}


class FaultSpecError(ValueError):
    """Malformed ``--fault-plan`` spec."""


def _reject_conflicts(events: list) -> None:
    """Refuse duplicate / overlapping specs of the same kind+window.

    Composed chaos scenarios stack many kinds in one plan; what they must
    NOT stack is two events of the same kind aimed at the same window —
    today those silently double-fire, and the second firing lands on the
    rollback REPLAY that is contractually clean (``step_fault`` consumes
    one event per epoch pass), corrupting the chaos scoreboard's
    fault→alert→action attribution.  Rules (``prob=`` draws are exempt —
    their windows are not knowable at parse time):

    - step faults (``nan_grad``/``bad_batch``/``loss_spike``) and
      ``desync``: two events of the same kind due at the same epoch
      conflict, whatever their step offsets — only the first fires on the
      first pass, so the second can ONLY fire on a replay;
    - ``preempt``/``ckpt_fail``/``torn_write``/``stall``: same kind,
      same epoch, same step offset is a duplicate (distinct mid-epoch
      preempt steps in one epoch are a legitimate composition — each
      relaunch resumes past the previous one).
    """
    seen: dict[tuple, "FaultEvent"] = {}
    for e in events:
        if e.epoch is None:
            continue
        if e.kind in STEP_KINDS or e.kind == "desync":
            key = (e.kind, e.epoch)
        else:
            key = (e.kind, e.epoch, e.step)
        other = seen.get(key)
        if other is not None:
            raise FaultSpecError(
                f"fault plan: {other.spec!r} and {e.spec!r} target the "
                f"same kind+window (kind {e.kind!r}, epoch {e.epoch}"
                + ("" if len(key) == 2 else f", step {e.step}")
                + ") — they would silently double-fire (the second on the "
                "rollback replay that must run clean); merge them into "
                "one event or move one to a different window"
            )
        seen[key] = e


@dataclass
class FaultEvent:
    kind: str
    epoch: int | None = None   # fire at the end of exactly this epoch
    prob: float | None = None  # or: per-epoch Bernoulli at this rate
    secs: float = 0.0          # stall duration
    step: int | None = None    # within-epoch step offset (step faults /
                               # mid-epoch preempt)
    steps: int | None = None   # step-fault width (defaults per kind)
    scale: float | None = None # step-fault multiplier (defaults per kind)
    consumed: bool = field(default=False, compare=False)
    spec: str = field(default="", compare=False)  # original item text,
                               # for conflict errors that name both specs

    def due(self, epoch: int, seed: int) -> bool:
        if self.epoch is not None:
            return epoch == self.epoch
        if self.prob is not None:
            # keyed, counter-free draw: deterministic per (seed, kind, epoch)
            # regardless of how many other events fired before — restarts
            # replay the same decisions for the same epochs
            return random.Random(f"{seed}:{self.kind}:{epoch}").random() < self.prob
        return False


@dataclass
class FaultPlan:
    """A parsed fault plan; the Trainer polls it at epoch (and, for
    step-granular events, chunk) boundaries."""

    events: list[FaultEvent] = field(default_factory=list)
    seed: int = 0

    @classmethod
    def parse(cls, spec: str | None, seed: int = 0) -> "FaultPlan | None":
        """Parse a ``--fault-plan`` spec; None/empty spec → no plan."""
        if not spec or not spec.strip():
            return None
        events = []
        for item in spec.replace(",", ";").split(";"):
            item = item.strip()
            if not item:
                continue
            kind, _, argstr = item.partition("@")
            kind = kind.strip()
            if kind not in KINDS:
                raise FaultSpecError(
                    f"unknown fault kind {kind!r} in {item!r} (known: {KINDS})"
                )
            kwargs: dict = {}
            for pair in argstr.split(":"):
                if not pair.strip():
                    continue
                key, _, val = pair.partition("=")
                key, val = key.strip(), val.strip()
                try:
                    if key == "epoch":
                        kwargs["epoch"] = int(val)
                    elif key == "prob":
                        kwargs["prob"] = float(val)
                    elif key == "secs":
                        kwargs["secs"] = float(val)
                    elif key == "step":
                        kwargs["step"] = int(val)
                    elif key == "steps":
                        kwargs["steps"] = int(val)
                    elif key == "scale":
                        kwargs["scale"] = float(val)
                    else:
                        raise FaultSpecError(
                            f"unknown fault arg {key!r} in {item!r} "
                            "(known: epoch, prob, secs, step, steps, scale)"
                        )
                except ValueError as e:
                    if isinstance(e, FaultSpecError):
                        raise
                    raise FaultSpecError(
                        f"bad value {val!r} for {key!r} in {item!r}"
                    ) from None
            if kwargs.get("epoch") is None and kwargs.get("prob") is None:
                raise FaultSpecError(
                    f"fault {item!r} needs an epoch=K or prob=P trigger"
                )
            events.append(FaultEvent(kind=kind, spec=item, **kwargs))
        _reject_conflicts(events)
        return cls(events=events, seed=seed)

    def _due(self, kind: str, epoch: int) -> list[FaultEvent]:
        return [e for e in self.events if e.kind == kind and e.due(epoch, self.seed)]

    def preempt_due(self, epoch: int, include_step_events: bool = True) -> bool:
        """Injected preemption fires at the end of ``epoch``.

        ``include_step_events=False`` excludes ``step=S`` events — the host
        data mode handles those mid-epoch via ``preempt_step_due`` and must
        not double-fire them at the boundary; device mode (where the epoch
        is one device program) keeps them, firing at the boundary instead.
        """
        return any(
            include_step_events or e.step is None
            for e in self._due("preempt", epoch)
        )

    def preempt_step_due(
        self, epoch: int, done: int, start_offset: int = 0, cap: int | None = None
    ) -> bool:
        """A mid-epoch (``step=S``) preemption is pending once ``done`` steps
        of ``epoch`` have completed.  ``start_offset`` is the step this
        attempt resumed at: an event only fires if its step was actually
        trained in THIS attempt (``start_offset < S <= done``), which makes
        mid-epoch preempts one-shot across restarts — the relaunch resumes
        at-or-past S and never re-fires it.  ``cap`` (the epoch's step
        count) clamps an out-of-range S so it fires at the epoch boundary
        instead of silently never."""
        for e in self._due("preempt", epoch):
            if e.step is None:
                continue
            step = min(e.step, cap) if cap is not None else e.step
            # step=0 means "as soon as possible": clamp to 1 so the window
            # test can ever pass (0 < 0 never fires)
            if start_offset < max(step, 1) <= done:
                return True
        return False

    def stall_secs(self, epoch: int) -> float:
        """Total injected step-time stall after ``epoch`` (0.0 = none)."""
        return sum(e.secs for e in self._due("stall", epoch))

    def has_step_faults(self) -> bool:
        """Any ``nan_grad``/``bad_batch``/``loss_spike`` events in the plan?
        The Trainer builds the fault-injection runner variant only then."""
        return any(e.kind in STEP_KINDS for e in self.events)

    def step_fault(self, epoch: int, steps_per_epoch: int) -> tuple[float, int, int]:
        """The ``(scale, start, stop)`` step-fault window for ``epoch``, or
        the benign ``(1.0, 0, 0)``.  Consumes the first due unconsumed event
        (one-shot per process): a watchdog rollback re-running this epoch
        gets a clean pass.  Defaults: ``nan_grad`` poisons the first 3
        steps; ``loss_spike``/``bad_batch`` start mid-epoch (so the spike
        detector has a baseline window) with 3 / 1 step(s) at 64x / Inf.
        """
        for e in self.events:
            if e.kind not in STEP_KINDS or e.consumed or not e.due(epoch, self.seed):
                continue
            e.consumed = True
            if e.step is not None:
                start = e.step
            elif e.kind == "nan_grad":
                start = 0
            else:
                start = steps_per_epoch // 2
            count = e.steps if e.steps else _STEPS_DEFAULTS[e.kind]
            scale = e.scale if e.scale is not None else _SCALE_DEFAULTS[e.kind]
            return (scale, start, min(start + count, steps_per_epoch))
        return (1.0, 0, 0)

    def desync_due(self, epoch: int) -> bool:
        """An injected replica-desync fires after ``epoch`` (one-shot by
        consumption, so the rollback replay's re-check passes)."""
        for e in self.events:
            if e.kind == "desync" and not e.consumed and e.due(epoch, self.seed):
                e.consumed = True
                return True
        return False

    def ckpt_hook(self, epoch: int):
        """A write-fault hook for this epoch's resumable save, or None.

        The hook is called by ``save_resume_state`` as ``hook(stage, path)``:
        ``"pre"`` before any bytes land (``ckpt_fail`` raises here — the
        write never happens, and the failure must surface through the async
        writer's ``wait()``), ``"post"`` after payload+manifest are durable
        (``torn_write`` corrupts the payload here, bypassing the atomic
        machinery the way a dying disk would — the manifest then no longer
        matches, which is exactly what verify-on-restore must catch).
        """
        fail = bool(self._due("ckpt_fail", epoch))
        tear = bool(self._due("torn_write", epoch))
        if not (fail or tear):
            return None

        def hook(stage: str, path: Path) -> None:
            if stage == "pre" and fail:
                raise OSError(
                    f"injected checkpoint write failure (fault plan, epoch {epoch})"
                )
            if stage == "post" and tear:
                tear_file(path)

        return hook


def tear_file(path: str | Path) -> None:
    """Simulate a torn write: truncate the file to half its bytes, in place,
    without touching its manifest (a real torn write updates neither)."""
    path = Path(path)
    data = path.read_bytes()
    path.write_bytes(data[: max(1, len(data) // 2)])


# ------------------------------------------------- scheduler re-admission

PROBE_TIMEOUT_S = 5.0


class SchedulerProbe:
    """The scheduler's re-admission interface, automated.

    A drained host's ``host-i.up`` marker used to be written by hand (or
    by a chaos driver standing in for the scheduler).  ``--fleet-probe``
    binds that marker to a real schedulability signal the supervisor
    polls for every LOST host on its marker cadence:

    - ``file:PATH``  — the slot is schedulable when PATH exists
      (``{host}`` in PATH is substituted with the host index — the
      shape a k8s node-ready touch-file or GCE guest-attribute mirror
      takes on shared storage);
    - ``exec:CMD``   — run CMD through the shell; exit 0 means
      schedulable (``{host}`` substituted, else the index is appended
      as an argv tail).  A nonzero exit is "not yet", not a failure.

    When the probe itself breaks — malformed spec, command not found,
    timeout, unreadable path — it degrades PERMANENTLY to the manual
    marker path with exactly one warning: a flapping probe must not spam
    the supervisor log or, worse, flap the world size.  Operators can
    still write ``host-i.up`` by hand; the probe only automates it.
    """

    def __init__(self, spec: str, *, log=None) -> None:
        self.spec = spec
        self._log = log or (lambda msg: None)
        self._failed = False
        kind, _, arg = spec.partition(":")
        self.kind, self.arg = kind, arg
        if kind not in ("exec", "file") or not arg:
            self._degrade(f"malformed --fleet-probe spec {spec!r} "
                          "(want exec:CMD or file:PATH)")

    def _degrade(self, why: str) -> None:
        if not self._failed:
            self._failed = True
            self._log(f"[fleet] probe failed ({why}); degrading to the "
                      f"manual host-i.up marker path")

    def check(self, host: int) -> bool:
        """True when the scheduler says host ``host``'s slot is
        schedulable again.  Never raises; infrastructure failures
        degrade the probe (once) and read as "not schedulable"."""
        if self._failed:
            return False
        if self.kind == "file":
            try:
                return Path(self.arg.replace("{host}", str(host))).exists()
            except OSError as e:
                self._degrade(f"file probe: {e}")
                return False
        cmd = self.arg
        cmd = (cmd.replace("{host}", str(host)) if "{host}" in cmd
               else f"{cmd} {host}")
        try:
            res = subprocess.run(
                cmd, shell=True, timeout=PROBE_TIMEOUT_S,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            )
        except (OSError, subprocess.SubprocessError) as e:
            self._degrade(f"exec probe: {e}")
            return False
        return res.returncode == 0


# ------------------------------------------------------- chaos matrix

CHAOS_KIND = "chaos"

# The emulated-rank injection knob (tests/fleet_pool_worker.py): a rank>0
# host reading this env var reports a persistently slowed step/dispatch_s
# sketch of that many seconds — the persistent straggler a policy rule
# must drain.  Emission waits for rank 0's first verified checkpoint, so
# the drain always lands on a resumable run.
EMU_SLOW_DISPATCH_ENV = "DTC_EMU_SLOW_DISPATCH_S"

# The shared sensing/acting vocabulary of the gauntlet: one alert + one
# policy rule per failure mode, reused verbatim across scenarios so the
# scoreboard's columns compare like with like.
_STRAGGLER_ALERT = "step/dispatch_s:p95>30:for=2"
_STRAGGLER_POLICY = f"{_STRAGGLER_ALERT} -> drain_host:cooldown=120"
# the window the straggler chain is given: host 1 reports its slowed sketch
# from rank 0's first checkpoint on, and two flushed windows, a supervisor
# poll and the drain take about a second — the ten epochs of a matrix run
# take less, so rank 0 holds at epoch 1's boundary (behind its checkpoint:
# the stall never fires again on resume)
_STRAGGLER_WINDOW = "stall@epoch=1:secs=15"
_SPIKE_ALERT = "train/loss:p95>50:for=1"
_SPIKE_POLICY = f"{_SPIKE_ALERT} -> rollback:cooldown=300"
_SKIP_ALERT = "train/skipped_steps:n>0:for=1"
_ABORT_ALERT = "train/loss:p95>-1:for=1"  # always-breaching tripwire
_ABORT_POLICY = f"{_ABORT_ALERT} -> abort_with_evidence:cooldown=600"
_SENTINEL_ALERT = "compile/recompiles_after_warmup:n>0:for=1"
_REWARM_POLICY = f"{_SENTINEL_ALERT} -> rewarm_serve:cooldown=5"

# Named scenarios composing preempt x straggler-stall x corrupt-shard
# (nan_grad) x host-flap x mid-epoch control, each run end-to-end under
# the fleet supervisor with the policy engine active
# (``tools/chaos_matrix.py``).  Every scenario recovers via policy/supervisor actions
# alone — no scenario writes an operator marker file.  Re-admission of a
# killed host goes through the SCHEDULER's interface: either the legacy
# driver writing ``host-1.up`` directly (``kill_and_readmit_host1``) or,
# in ``probe_readmission``, a :class:`SchedulerProbe` ready-file the
# driver creates and ``--fleet-probe`` turns into the marker.
#
# Field contract (consumed by ``tools/chaos_matrix.py``, linted by tests):
#   fault_plan   --fault-plan spec for the training child (or None)
#   alerts       --alert specs handed to the supervisor
#   policies     --policy specs binding those alerts to actions
#   policy_mode  off | dry-run | act
#   driver       None | "kill_host1" | "kill_and_readmit_host1" — the
#                external-environment script (spot reclaim / scheduler)
#   env          extra child environment (emulated-rank injection knobs)
#   extra_args   extra child CLI flags
#   expect       scoreboard expectations, checked by
#                ``check_chaos_expectations``:  key / key__min / key__max
#   require_kinds  event kinds the scenario's stream must carry
#   session      (optional) "serve" runs the real --serve entry instead
#                of the training fleet worker — the flash-crowd x serve
#                axis; its extra_args ARE the whole serve CLI
CHAOS_SCENARIOS: dict[str, dict] = {
    "straggler_drain": {
        "desc": "persistent straggler on host 1 -> dispatch alert -> "
                "policy drain_host -> world shrinks -> run completes",
        "fault_plan": _STRAGGLER_WINDOW,
        "alerts": (_STRAGGLER_ALERT,),
        "policies": (_STRAGGLER_POLICY,),
        "policy_mode": "act",
        "driver": None,
        "env": {EMU_SLOW_DISPATCH_ENV: "60"},
        "extra_args": (),
        "expect": {
            "final_rc": 0, "policy_completed__min": 1,
            "resizes__min": 1, "alerts_fired__min": 1,
            "policy_dry_run": 0,
        },
        "require_kinds": ("policy", "resize"),
    },
    "straggler_dryrun": {
        "desc": "same straggler, --policy-mode dry-run: the decision is "
                "logged, NO drain happens, the world never shrinks",
        "fault_plan": _STRAGGLER_WINDOW,
        "alerts": (_STRAGGLER_ALERT,),
        "policies": (_STRAGGLER_POLICY,),
        "policy_mode": "dry-run",
        "driver": None,
        "env": {EMU_SLOW_DISPATCH_ENV: "60"},
        "extra_args": (),
        "expect": {
            "final_rc": 0, "policy_dry_run__min": 1,
            "policy_completed": 0, "policy_requested": 0,
            "resizes": 0, "restarts": 0,
        },
        "require_kinds": ("policy",),
    },
    "preempt_resume": {
        "desc": "injected preemption mid-run -> supervisor relaunch "
                "resumes from the verified checkpoint",
        "fault_plan": "preempt@epoch=2",
        "alerts": (_STRAGGLER_ALERT,),
        "policies": (_STRAGGLER_POLICY,),
        "policy_mode": "act",
        "driver": None,
        "env": {},
        "extra_args": (),
        "expect": {
            "final_rc": 0, "preemptions__min": 1, "restarts__min": 1,
            "policy_completed": 0,
        },
        "require_kinds": ("preempt",),
    },
    "nan_rollback": {
        "desc": "corrupt shard (nan_grad) -> compiled guard skips, "
                "watchdog rolls back, skipped-steps alert fires",
        "fault_plan": "nan_grad@epoch=1",
        "alerts": (_SKIP_ALERT, _STRAGGLER_ALERT),
        "policies": (_STRAGGLER_POLICY,),
        "policy_mode": "act",
        "driver": None,
        "env": {},
        "extra_args": (),
        "expect": {
            "final_rc": 0, "rollbacks__min": 1, "alerts_fired__min": 1,
        },
        "require_kinds": ("rollback", "alert"),
    },
    "policy_rollback": {
        "desc": "sustained loss breach the (deliberately blinded) spike "
                "detector ignores -> loss alert -> policy rollback "
                "request -> trainer rolls back and replays clean",
        # the stall after epoch 6 is the insurance window: the alert ->
        # policy -> request chain (one watcher poll each way) must land
        # before the short CI run's last epoch boundary
        "fault_plan": "loss_spike@epoch=5:scale=64:steps=3;"
                      "stall@epoch=6:secs=4",
        "alerts": (_SPIKE_ALERT,),
        "policies": (_SPIKE_POLICY,),
        "policy_mode": "act",
        "driver": None,
        "env": {},
        # spike detection blinded so the POLICY path (not the watchdog)
        # performs the recovery; sparse saves keep the spiked trajectory
        # out of last.ckpt while the request is in flight
        "extra_args": (
            "--health-spike-mads", "1e9", "--save-last-every", "5",
        ),
        "expect": {
            "final_rc": 0, "policy_completed__min": 1,
            "rollbacks__min": 1, "alerts_fired__min": 1,
        },
        "require_kinds": ("policy", "rollback"),
    },
    "host_flap": {
        "desc": "host 1 SIGKILLed (spot reclaim) -> shrink -> scheduler "
                "re-admits it (host-1.up) -> deliberate re-expand",
        "fault_plan": "stall@epoch=7:secs=6",  # insurance window so the
        # re-admission lands mid-run even on a fast box
        "alerts": (_STRAGGLER_ALERT,),
        "policies": (_STRAGGLER_POLICY,),
        "policy_mode": "act",
        "driver": "kill_and_readmit_host1",
        "env": {},
        "extra_args": (),
        "expect": {
            "final_rc": 0, "resizes__min": 2, "policy_completed": 0,
        },
        "require_kinds": ("resize",),
    },
    "composed": {
        "desc": "nan_grad + mid-run preempt + persistent straggler at "
                "once: rollback, relaunch, and policy drain in one run",
        "fault_plan": "nan_grad@epoch=1;preempt@epoch=3",
        "alerts": (_SKIP_ALERT, _STRAGGLER_ALERT),
        "policies": (_STRAGGLER_POLICY,),
        "policy_mode": "act",
        "driver": None,
        "env": {EMU_SLOW_DISPATCH_ENV: "60"},
        "extra_args": (),
        "expect": {
            "final_rc": 0, "rollbacks__min": 1, "restarts__min": 1,
            "policy_completed__min": 1, "resizes__min": 1,
        },
        "require_kinds": ("policy", "resize", "rollback"),
    },
    "abort_evidence": {
        "desc": "sustained regression tripwire -> policy "
                "abort_with_evidence: orderly abort, evidence attached "
                "to crash_dump.json, restart loop stops (no relaunch)",
        "fault_plan": None,
        "alerts": (_ABORT_ALERT,),
        "policies": (_ABORT_POLICY,),
        "policy_mode": "act",
        "driver": None,
        "env": {},
        "extra_args": (),
        "expect": {
            "final_rc_nonzero": True, "policy_completed__min": 1,
            "restarts": 0, "crash_dump_evidence": True,
        },
        "require_kinds": ("policy", "abort"),
    },
    "control_rollback": {
        "desc": "sustained loss breach (spike detector blinded) -> loss "
                "alert -> policy rollback lands on the mid-epoch CONTROL "
                "channel -> the trainer applies it at a CHUNK boundary "
                "inside the epoch and replays clean",
        # the policy_rollback recipe with LONGER epochs (512 examples =
        # 16 steps, chunk 2 -> 8 poll boundaries per epoch): the
        # control-rollback.req lands mid-epoch with a whole epoch of
        # chunk boundaries to catch it, and the post-spike stall is the
        # same insurance window the legacy scenario uses.  The applied
        # `control` event must say boundary=chunk — time-to-mitigation
        # bounded by ONE CHUNK, not one epoch (the tentpole's claim).
        "fault_plan": "loss_spike@epoch=5:scale=64:steps=3;"
                      "stall@epoch=6:secs=4",
        "alerts": (_SPIKE_ALERT,),
        "policies": (_SPIKE_POLICY,),
        "policy_mode": "act",
        "driver": None,
        "env": {},
        "extra_args": (
            "--health-spike-mads", "1e9", "--save-last-every", "5",
            "--limit-examples", "512", "--epoch", "8",
        ),
        "expect": {
            "final_rc": 0, "policy_completed__min": 1,
            "rollbacks__min": 1, "alerts_fired__min": 1,
            "controls_applied__min": 1, "control_mid_epoch__min": 1,
            "policy_dry_run": 0,
        },
        "require_kinds": ("policy", "rollback", "control"),
    },
    "probe_readmission": {
        "desc": "host 1 SIGKILLed (spot reclaim) -> shrink -> the "
                "--fleet-probe scheduler probe sees the slot schedulable "
                "(ready file) and writes host-1.up ITSELF -> deliberate "
                "re-expand, zero operator/driver marker files",
        # the host_flap scenario with the residue closed: the driver
        # never touches <ckpt>/fleet/ — it only creates the probe's
        # ready file (a k8s node-ready / GCE guest-attribute stand-in),
        # and the SchedulerProbe turns that into the up marker on the
        # supervisor's own poll cadence
        "fault_plan": "stall@epoch=7:secs=6",  # same insurance window
        # as host_flap: the re-admission must land mid-run on a fast box
        "alerts": (_STRAGGLER_ALERT,),
        "policies": (_STRAGGLER_POLICY,),
        "policy_mode": "act",
        "driver": "probe_readmit_host1",
        "env": {},
        # {root} is substituted by the matrix with the scenario's ckpt
        # root; {host} survives for the probe's own substitution
        "extra_args": ("--fleet-probe", "file:{root}/probe-ready-{host}"),
        "expect": {
            "final_rc": 0, "resizes__min": 2, "policy_completed": 0,
        },
        "require_kinds": ("resize",),
    },
    "serve_flash_rewarm": {
        "desc": "flash crowd lands on an unwarmed serve bucket -> "
                "recompile storm trips the sentinel alert -> policy "
                "rewarm_serve re-warms the replica fleet -> p99 recovers "
                "after the flash",
        # the serve session (session: "serve"): the matrix runs the
        # real --serve entry instead of the training fleet worker.  Warm
        # buckets 1,2 only; the flash's queue depth reaches bucket 8 —
        # a mid-serving compile cliff, exactly the storm rewarm_serve
        # exists for.  The AOT persistence is OFF here on purpose: a
        # persisted-cache hit is a millisecond load that deliberately
        # does NOT page the sentinel, and this scenario proves the page.
        "session": "serve",
        "fault_plan": None,
        "alerts": (_SENTINEL_ALERT,),
        "policies": (_REWARM_POLICY,),
        "policy_mode": "act",
        "driver": None,
        "env": {},
        "extra_args": (
            "--serve", "--serve-shape", "flash", "--serve-rate", "6",
            "--serve-flash-mult", "8", "--serve-requests", "180",
            "--serve-buckets", "1,2,8", "--serve-warm-buckets", "1,2",
            "--serve-mode", "continuous", "--serve-aot-cache", "off",
            "--queue-limit", "512",
        ),
        "expect": {
            "final_rc": 0, "alerts_fired__min": 1,
            "policy_completed__min": 1, "recompiles__min": 1,
            "p99_recovered": True, "policy_dry_run": 0,
        },
        "require_kinds": ("serve", "serve_route", "policy", "compile"),
    },
    "serve_replica_kill_flash": {
        "desc": "SIGKILL a process replica mid-load -> in-flight batch "
                "requeues (zero failed requests), the supervisor "
                "relaunches the worker inside its restart budget, and "
                "the post-flash p99 recovers on the survivor + the "
                "warm-started incarnation",
        # process transport (serve/fleet/): each replica is a real OS
        # process behind the socket transport, so the kill is a true
        # worker death — the chaos driver (the matrix) watches the
        # handshake files and SIGKILLs replica 0 once the fleet is
        # ready and load is flowing.  The autoscaler rides along
        # (--serve-scale-target) so the scenario also proves scaling
        # decisions keep flowing through a replica death.
        "session": "serve",
        "fault_plan": None,
        "alerts": (),
        "policies": (),
        "policy_mode": "act",
        "driver": "kill_replica",
        "env": {},
        "extra_args": (
            "--serve", "--serve-transport", "process",
            "--serve-replicas", "2", "--serve-shape", "flash",
            "--serve-rate", "6", "--serve-flash-mult", "6",
            "--serve-requests", "220", "--serve-buckets", "1,4",
            "--serve-mode", "continuous", "--queue-limit", "512",
            "--serve-scale-target", "p99=2000",
            "--serve-max-replicas", "2",
        ),
        "expect": {
            "final_rc": 0, "kills__min": 1, "restarts__min": 1,
            "failed_requests": 0, "p99_recovered": True,
        },
        "require_kinds": ("serve", "serve_route", "replica"),
    },
}


def check_chaos_expectations(expect: dict, observed: dict) -> list[str]:
    """Compare a scenario's scoreboard row against its ``expect`` block;
    returns the violations (empty = scenario green).  Keys: ``name`` for
    exact equality, ``name__min`` / ``name__max`` for bounds, and
    ``final_rc_nonzero`` / ``crash_dump_evidence`` as boolean checks."""
    problems: list[str] = []
    for key, want in expect.items():
        if key == "final_rc_nonzero":
            if bool(observed.get("final_rc", 0) != 0) is not bool(want):
                problems.append(
                    f"final_rc={observed.get('final_rc')} (wanted "
                    f"{'nonzero' if want else 'zero'})"
                )
            continue
        if key == "crash_dump_evidence":
            if bool(observed.get("crash_dump_evidence")) is not bool(want):
                problems.append(
                    f"crash_dump_evidence={observed.get('crash_dump_evidence')}"
                    f" (wanted {want})"
                )
            continue
        if key.endswith("__min"):
            name, cmp = key[: -len("__min")], ">="
        elif key.endswith("__max"):
            name, cmp = key[: -len("__max")], "<="
        else:
            name, cmp = key, "=="
        got = observed.get(name)
        if got is None:
            problems.append(f"{name} missing from the scoreboard row")
            continue
        ok = (
            got >= want if cmp == ">=" else
            got <= want if cmp == "<=" else got == want
        )
        if not ok:
            problems.append(f"{name}={got} (wanted {cmp} {want})")
    return problems
