"""Elastic-pool e2e child: one rendered rank of a supervised CPU fleet.

Launched by ``tests/test_fleet_pool.py`` through the real CLI
(``--supervise --fleet-hosts 2``), which routes ``run_supervised`` to the
:class:`FleetSupervisor`.  The fleet re-renders ``--world-size``/
``--rank``/``--dist-url`` per attempt and spawns this same script once per
rank:

- **rank 0** runs a real ``Trainer`` attempt (TinyNet, device data mode) —
  checkpoints, preemption drain on SIGTERM, the full product path;
- **rank > 0** is an **emulated host** (the ``tests/fleet_worker.py``
  pattern): a real process with a real pid whose interface to the
  supervisor is exactly a real host's — per-process event files with
  heartbeats in the shared version dir, ``EXIT_PREEMPTED`` on SIGTERM
  (the drain), death by whatever signal the test sends.  It exits 0 on
  its own when rank 0's ``run_end`` lands, so a clean attempt completes
  without supervisor intervention.

Why emulated: the pinned CI jax cannot run multi-process collectives on
the CPU backend (``Multiprocess computations aren't implemented``, see
tests/test_multihost.py — slow-marked for real TPU pods), so rank 0
deliberately skips ``init_distributed`` here.  Every SUPERVISOR-side code
path — spawn set, pidfiles, kill detection, pool transitions, world
re-render, deliberate drain, resize events, watcher host set — consumes
processes and files, never collectives, and is exercised for real.  The
production entry (``src/tpu_jax/main.py``) does call ``init_distributed``
with the rendered flags.
"""

import os
import signal
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax

jax.config.update("jax_platforms", "cpu")  # CPU-only worker, also when run by hand

import flax.linen as lnn
import jax.numpy as jnp


class TinyNet(lnn.Module):
    """Conv+BN+dense classifier sharing the zoo interface (duplicated from
    tests/test_train.py so the worker is standalone)."""

    num_classes: int = 100
    dtype: jnp.dtype = jnp.float32

    @lnn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        x = lnn.Conv(8, (3, 3), strides=2, use_bias=False, dtype=self.dtype)(x)
        x = lnn.BatchNorm(use_running_average=not train, dtype=self.dtype)(x)
        x = lnn.relu(x)
        x = jnp.mean(x, axis=(1, 2))
        return lnn.Dense(self.num_classes, dtype=self.dtype)(x).astype(jnp.float32)


def emulate_host(hp, rank: int) -> int:
    """A non-zero rank at the file level: bind a per-process event bus into
    the run's version dir, heartbeat on the configured cadence, exit 0 when
    rank 0 finishes (its ``run_end``), 75 on SIGTERM (the drain a real host
    would run), or by whatever signal kills the process.

    Chaos injection (resilience/faults.py ``EMU_SLOW_DISPATCH_ENV``): when
    the env var is set, this host reports a persistently slowed
    ``step/dispatch_s`` sketch — the straggler a ``--policy`` drain rule
    must remove.  Emission waits for rank 0's first verified checkpoint so
    the policy-driven drain always lands on a resumable run."""
    from distributed_training_comparison_tpu import obs
    from distributed_training_comparison_tpu.resilience import (
        EXIT_PREEMPTED,
        read_manifest,
    )
    from distributed_training_comparison_tpu.resilience.faults import (
        EMU_SLOW_DISPATCH_ENV,
    )

    drained = {"flag": False}
    signal.signal(signal.SIGTERM, lambda *_: drained.__setitem__("flag", True))

    root = Path(hp.ckpt_path)
    deadline = time.monotonic() + 300.0
    vdir = None
    while vdir is None and time.monotonic() < deadline:
        if drained["flag"]:
            return EXIT_PREEMPTED
        dirs = sorted(root.glob("version-*"))
        if dirs:
            vdir = dirs[-1]
        else:
            time.sleep(0.05)
    if vdir is None:
        return 1
    bus = obs.EventBus(
        run_id=os.environ.get(obs.RUN_ID_ENV) or obs.new_run_id(),
        attempt=int(os.environ.get(obs.ATTEMPT_ENV, "0") or 0),
        process_index=rank,
    )
    bus.bind_dir(vdir)
    hb = obs.HeartbeatEmitter(bus, every_s=getattr(hp, "heartbeat_secs", 0.2))
    slow_dispatch_s = float(os.environ.get(EMU_SLOW_DISPATCH_ENV, "0") or 0)
    reg = obs.MetricRegistry(flush_steps=1) if slow_dispatch_s > 0 else None
    straggling = False
    last_straggle = 0.0
    step = 0
    events = vdir / "events.jsonl"  # rank 0's file: run_end says we're done
    try:
        # start at the current tail: a previous attempt's run_end/abort in
        # the same (auto-resumed) version dir is not OUR attempt's verdict
        offset = events.stat().st_size
    except OSError:
        offset = 0
    rc = 1  # timeout without a verdict is a failure
    while time.monotonic() < deadline:
        if drained["flag"]:
            rc = EXIT_PREEMPTED
            break
        hb.beat(epoch=0, step=step)
        step += 1
        if reg is not None:
            if not straggling:
                # hold the injection until rank 0 has a resumable state
                straggling = (
                    read_manifest(vdir / "last.ckpt") is not None
                )
            if straggling and time.monotonic() - last_straggle > 0.3:
                last_straggle = time.monotonic()
                # one flushed window of pathologically slow dispatch: the
                # per-process p95 alert on this source fires after for=N
                # windows, and the policy names THIS host for the drain
                reg.histogram("step/dispatch_s").record_many(
                    [slow_dispatch_s] * 4
                )
                reg.note_steps(4)
                reg.flush(bus, epoch=0, step=step)
        try:
            with open(events, "rb") as f:
                f.seek(offset)
                chunk = f.read().decode("utf-8", "replace")
                offset += len(chunk.encode("utf-8"))
        except OSError:
            chunk = ""
        if '"kind": "run_end"' in chunk:
            rc = 0
            break
        if '"kind": "abort"' in chunk:
            rc = 1
            break
        time.sleep(0.05)
    bus.close()
    print(f"RESULT emulated host rank={rank} rc={rc}", flush=True)
    return rc


def main(argv) -> int:
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.resilience import (
        EXIT_PREEMPTED,
        Preempted,
    )
    from distributed_training_comparison_tpu.utils import (
        enable_persistent_compilation_cache,
    )

    hp = load_config("tpu", argv)
    if getattr(hp, "supervise", False):
        from distributed_training_comparison_tpu.resilience.supervisor import (
            run_supervised,
        )

        return int(run_supervised(hp, argv)["exit_code"])

    if hp.rank > 0:
        return emulate_host(hp, hp.rank)

    enable_persistent_compilation_cache()
    from distributed_training_comparison_tpu.train import Trainer

    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    try:
        version = trainer.fit()
    except Preempted as e:
        print(
            f"RESULT preempted=1 rank=0 epoch={e.epoch} "
            f"rendered_world={hp.world_size}",
            flush=True,
        )
        return EXIT_PREEMPTED
    finally:
        trainer.close()
    print(
        f"RESULT preempted=0 rank=0 rendered_world={hp.world_size} "
        f"version={version}",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
