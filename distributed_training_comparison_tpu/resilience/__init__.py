"""Resilience: fault injection, preemption handling, crash-safe checkpoint
I/O, elastic restore, restart supervision, and goodput accounting.

The reference repo saves only model weights — a killed run cannot resume
(SURVEY.md §5), and nothing in it ever *exercises* a failure.  A production
system spends real wall-clock in preemptions and restarts, so this package
makes failure a first-class, testable code path:

- ``faults``     — deterministic, seeded fault-injection harness (preemption
                   signals, checkpoint-write failures, torn writes, stalls)
                   driven by a ``--fault-plan`` spec;
- ``preempt``    — SIGTERM/injected-preemption handler: drain the async
                   checkpointer, write a final ``last.ckpt``, exit with a
                   distinct code the supervisor recognizes as transient;
- ``control``    — the mid-epoch control plane: durable request files
                   that land supervisor/policy decisions (rollback,
                   abort, drain, replan) at the trainer's next CHUNK
                   boundary through the same drain machinery as
                   mid-epoch preemption, with per-decision
                   time-to-mitigation ``control`` events;
- ``ckpt_io``    — atomic tmp+fsync+rename writes, a sidecar integrity
                   manifest (payload checksum, step, mesh shape), and
                   verify-on-restore with previous-good rotation;
- ``supervisor`` — restart loop with exponential backoff + max-restart
                   budget, resuming from the newest *valid* checkpoint;
- ``fleet``      — elastic fleet supervision: a host pool whose world size
                   is re-rendered per attempt (``--world-size``/``--rank``/
                   fresh ``--dist-url``), shrinking on host loss and
                   re-expanding — via a deliberate drain — when a host
                   returns; ``resize`` events price every change;
- ``elastic``    — restoring onto a different device count / mesh shape
                   than the state was saved under, with an explicit reshard
                   validation step (``validate_reshard``) that refuses with
                   actionable numbers when no legal mesh exists;
- ``goodput``    — productive step time vs. checkpoint / restart / recovery
                   time, aggregated across restarts into ``GOODPUT.json``.
"""

from .ckpt_io import (
    atomic_write_bytes,
    atomic_write_chunks,
    manifest_path,
    previous_path,
    read_and_hash,
    read_manifest,
    rotate_previous,
    verify_checkpoint,
    write_manifest,
)
from .elastic import (
    ReshardError,
    describe_restore,
    divisibility_help,
    forced_host_device_env,
    topology,
    validate_reshard,
)
from .control import (
    CONTROL_KIND,
    ControlPoller,
    MidEpochRollback,
    pending_control,
    write_control_request,
)
from .faults import (
    CHAOS_KIND,
    CHAOS_SCENARIOS,
    FaultEvent,
    FaultPlan,
    FaultSpecError,
    SchedulerProbe,
    check_chaos_expectations,
)
from .fleet import FleetPlanError, FleetSupervisor, widest_legal_world
from .goodput import GoodputMeter, aggregate_goodput, load_goodput_records
from .preempt import EXIT_PREEMPTED, Preempted, PreemptionHandler
from .supervisor import Supervisor

__all__ = [
    "atomic_write_bytes",
    "atomic_write_chunks",
    "manifest_path",
    "previous_path",
    "read_and_hash",
    "read_manifest",
    "rotate_previous",
    "verify_checkpoint",
    "write_manifest",
    "describe_restore",
    "divisibility_help",
    "forced_host_device_env",
    "topology",
    "validate_reshard",
    "ReshardError",
    "FleetPlanError",
    "FleetSupervisor",
    "widest_legal_world",
    "CHAOS_KIND",
    "CHAOS_SCENARIOS",
    "CONTROL_KIND",
    "ControlPoller",
    "MidEpochRollback",
    "SchedulerProbe",
    "check_chaos_expectations",
    "pending_control",
    "write_control_request",
    "FaultEvent",
    "FaultPlan",
    "FaultSpecError",
    "GoodputMeter",
    "aggregate_goodput",
    "load_goodput_records",
    "EXIT_PREEMPTED",
    "Preempted",
    "PreemptionHandler",
    "Supervisor",
]
