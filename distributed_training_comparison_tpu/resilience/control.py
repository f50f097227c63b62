"""Mid-epoch control plane: chunk-boundary application of policy actions.

PR 12's autopilot could *decide* the moment an alert fired, but every
supervisor-side decision still *applied* at the next epoch boundary — on
a long epoch the blast radius of a detected fault was the whole epoch,
even though mid-epoch preemption already proved the trainer can drain at
a chunk boundary, checkpoint, and resume exactly.  This module
generalizes that one-shot preemption drain into a **control barrier**:

- decisions land here as durable request files under ``<ckpt>/fleet/``
  (``control-{action}.req`` — the same crash-safe rename-atomic marker
  idiom as ``host-i.down`` and the legacy epoch-boundary
  ``policy-{action}.req`` channel);
- the trainer polls the channel at EVERY chunk boundary (the same poll
  site as ``_preempt_due``) and applies the action inside the epoch:
  ``rollback`` re-enters the epoch loop through the verified-restore
  path, ``abort_with_evidence`` dumps its evidence and raises, and a
  ``drain`` request (written for ``drain_host`` and ``replan``) rides
  the proven mid-epoch preemption drain — partial-epoch checkpoint,
  ``EXIT_PREEMPTED``, fast-forward resume;
- every application emits one registered ``control`` event carrying the
  decide→apply timestamps (``t_decide``/``t_apply``/``ttm_s``) and the
  step distance, so ``run_report --policy`` can render
  time-to-mitigation per decision.

One-shot across restarts: a ``drain`` request asks for *an attempt
boundary* — if the supervisor restarted the run before the trainer
consumed it (the SIGTERM won the race), that boundary already happened,
and applying the stale file would drain every subsequent attempt into a
restart loop.  Requests therefore carry the attempt that decided them,
and :func:`is_stale` discards drain-class requests from earlier attempts
(the trainer reports them ``superseded``) — the request-file twin of
``FaultPlan.preempt_step_due``'s fire-once window.  ``rollback`` and
``abort_with_evidence`` deliberately survive restarts: the state they
revoke is restored by the relaunch, and the decision still stands.
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path

CONTROL_KIND = "control"

# actions the trainer consumes from the control channel.  "drain" is the
# file both drain_host and replan write (payload ``verb`` records which):
# either way the trainer-side application is the same clean mid-epoch
# drain; what differs is what the SUPERVISOR does at the attempt
# boundary (re-render the world minus a host vs re-run the planner).
CONTROL_ACTIONS = ("rollback", "abort_with_evidence", "drain")

# actions that are attempt-scoped (their application IS an attempt
# boundary) and therefore go stale once that boundary has passed
ATTEMPT_SCOPED_ACTIONS = ("drain",)

CONTROL_DIRNAME = "fleet"  # shared with host markers + policy-*.req

BOUNDARIES = ("chunk", "epoch")
DEFAULT_BOUNDARY = "chunk"

# control event end-states: "applied" (the action ran at this boundary),
# "superseded" (stale attempt-scoped request discarded — its boundary
# already happened), "expired" (the run ended with the request still
# queued; swept by the supervisor so nothing dangles silently)
CONTROL_STATES = ("applied", "superseded", "expired")

ATTEMPT_ENV = "DTC_ATTEMPT"


class MidEpochRollback(Exception):
    """Control flow for a chunk-boundary rollback: the chunk loop holds
    iterators/prefetchers the verified-restore path must not run under,
    so the barrier unwinds to ``fit()`` (closing them on the way — the
    same unwind a mid-epoch preemption drain takes) which applies the
    rollback and re-enters the epoch loop at the restored epoch."""

    def __init__(self, *, epoch: int, steps_done: int, requests) -> None:
        self.epoch = int(epoch)
        self.steps_done = int(steps_done)
        self.requests = list(requests)
        super().__init__(
            f"mid-epoch policy rollback at epoch {epoch} "
            f"(step {steps_done})"
        )


def control_filename(action: str) -> str:
    return f"control-{action}.req"


def write_control_request(
    root, action: str, payload: dict, *, attempt: int | None = None,
) -> Path | None:
    """Persist a chunk-boundary control request under ``<root>/fleet/``.

    Rename-atomic (the polling trainer never reads a torn request) and
    one file per action with an UNCONSUMED file winning — overwriting a
    pending request would orphan its decision id, exactly like the
    legacy channel.  Returns None when an earlier request is still
    queued (the caller reports the new decision coalesced into it).

    The payload is stamped with ``t_decide`` (wall clock at write — the
    start of the time-to-mitigation measurement) and ``attempt`` (the
    staleness scope for drain-class requests) unless the caller already
    set them.
    """
    if action not in CONTROL_ACTIONS:
        raise ValueError(
            f"{action!r} is not a control-channel action ({CONTROL_ACTIONS})"
        )
    d = Path(root) / CONTROL_DIRNAME
    d.mkdir(parents=True, exist_ok=True)
    path = d / control_filename(action)
    if path.exists():
        return None
    body = dict(payload, action=action)
    body.setdefault("t_decide", time.time())
    if attempt is not None:
        body.setdefault("attempt", int(attempt))
    tmp = path.with_suffix(".req.tmp")
    tmp.write_text(json.dumps(body))
    tmp.replace(path)
    return path


class ControlPoller:
    """The trainer side of the control channel: consume (read + unlink)
    any pending ``control-*.req`` files.  Cost when idle: one ``stat``
    per control action per chunk boundary.  Only process 0 polls; under
    multi-host the fold is allgather-OR'd by the caller so every process
    enters the drain/rollback collectives together (the ``_preempt_due``
    idiom)."""

    def __init__(self, root) -> None:
        self.dir = Path(root) / CONTROL_DIRNAME

    def poll(self) -> list[dict]:
        out: list[dict] = []
        for action in CONTROL_ACTIONS:
            path = self.dir / control_filename(action)
            try:
                text = path.read_text()
            except OSError:
                continue
            path.unlink(missing_ok=True)
            try:
                req = json.loads(text)
            except ValueError:
                req = {}
            if not isinstance(req, dict):
                req = {}
            req.setdefault("action", action)
            out.append(req)
        return out


def pending_control(root) -> list[dict]:
    """Non-consuming read of the queued control requests (the
    supervisor's end-of-run sweep: report what was decided but never
    reached a boundary, without racing a trainer that might still be
    draining)."""
    d = Path(root) / CONTROL_DIRNAME
    out: list[dict] = []
    for action in CONTROL_ACTIONS:
        try:
            text = (d / control_filename(action)).read_text()
        except OSError:
            continue
        try:
            req = json.loads(text)
        except ValueError:
            req = {}
        if not isinstance(req, dict):
            req = {}
        req.setdefault("action", action)
        out.append(req)
    return out


def clear_control_requests(root) -> int:
    """Drop every queued control file (the sweep's second half, after
    each has been reported ``expired``)."""
    d = Path(root) / CONTROL_DIRNAME
    n = 0
    for action in CONTROL_ACTIONS:
        path = d / control_filename(action)
        try:
            path.unlink()
            n += 1
        except OSError:
            pass
    return n


def is_stale(req: dict, current_attempt: int) -> bool:
    """Attempt-scoped (drain-class) requests from an earlier attempt are
    stale: the attempt boundary they asked for already happened (the
    supervisor restarted before the trainer consumed the file), so
    applying them now would drain a healthy attempt.  Requests that
    carry no attempt stamp are never aged out — a hand-written control
    file must keep working like a hand-written marker does."""
    if req.get("action") not in ATTEMPT_SCOPED_ACTIONS:
        return False
    attempt = req.get("attempt")
    if not isinstance(attempt, (int, float)):
        return False
    return int(attempt) < int(current_attempt)


def current_attempt() -> int:
    """The attempt index of this process (the supervisor exports it)."""
    try:
        return int(os.environ.get(ATTEMPT_ENV, "0") or 0)
    except ValueError:
        return 0


def control_event_payload(
    req: dict, *, state: str, boundary: str, step: int,
    t_apply: float | None = None, step_at_decide: int | None = None,
    **extra,
) -> dict:
    """The ``control`` event body for one request reaching ``state`` at
    a boundary: the decision's identity (action/verb/id/rule) plus the
    decide→apply measurement — ``ttm_s`` in seconds and, when the caller
    can date the decision on its step axis, ``steps_since_decide``."""
    t_apply = time.time() if t_apply is None else t_apply
    payload = {
        "action": req.get("action"),
        "id": req.get("id"),
        "rule": req.get("rule"),
        "state": state,
        "boundary": boundary,
        "mid_epoch": boundary == "chunk",
        "t_apply": round(t_apply, 6),
        **extra,
    }
    if req.get("verb") is not None:
        payload["verb"] = req["verb"]
    t_decide = req.get("t_decide")
    if isinstance(t_decide, (int, float)):
        payload["t_decide"] = round(float(t_decide), 6)
        payload["ttm_s"] = round(max(0.0, t_apply - float(t_decide)), 6)
    if step_at_decide is not None:
        payload["steps_since_decide"] = max(0, int(step) - int(step_at_decide))
    return payload


# ------------------------------------------------- offline (run_report)


def control_timeline(events) -> list[dict]:
    """The ``control`` events of a merged stream, in order."""
    return [
        ev for ev in events
        if isinstance(ev, dict) and ev.get("kind") == CONTROL_KIND
    ]


def controls_by_id(events) -> dict:
    """decision id -> its control event payloads (most decisions have
    exactly one; a drain superseded in attempt N+1 keeps both)."""
    out: dict = {}
    for ev in control_timeline(events):
        p = ev.get("payload") or {}
        if p.get("id") is not None:
            out.setdefault(p["id"], []).append(p)
    return out


def unapplied_actions(events) -> list[dict]:
    """Acted policy decisions that never reached an ``applied`` (or
    ``superseded``) control event: the decision completed but no
    boundary ever recorded applying it — the applying process died
    between consuming the request and acting, or the control event was
    lost.  Scope: act-mode ``completed`` decisions for the trainer-side
    control actions (``rollback``/``abort_with_evidence``); drain-class
    decisions complete supervisor-side (the marker/replan IS the fleet
    mitigation) and are gated by the chaos matrix's expectations
    (``tools/chaos_matrix.py``) instead.
    """
    gated = {"rollback", "abort_with_evidence"}
    completed: dict = {}
    for ev in events:
        if not isinstance(ev, dict) or ev.get("kind") != "policy":
            continue
        p = ev.get("payload") or {}
        if (
            p.get("state") == "completed"
            and p.get("action") in gated
            and p.get("id") is not None
            and not p.get("dry_run")
        ):
            completed[p["id"]] = p
    seen = controls_by_id(events)
    out = []
    for pid, p in completed.items():
        states = {c.get("state") for c in seen.get(pid, ())}
        if not states & {"applied", "superseded"}:
            out.append(p)
    return out
