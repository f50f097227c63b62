"""Fleet-operations e2e child: one real training attempt plus an emulated
second host on the same checkpoint root.

Launched by ``tests/test_fleet.py`` two ways (mirroring resil_worker.py):

- with ``--supervise``: runs the real ``run_supervised`` path — restart
  loop, fleet watcher tailing every host's event files, liveness/stall
  classification, ``--alert`` evaluation, post-attempt straggler
  attribution — whose child is this same script in train mode;
- train mode: a real ``Trainer`` attempt (process 0: genuine events,
  heartbeats, metric flushes) followed by an **emulated host 1** — a
  second ``EventBus`` with ``process_index=1`` writing into the same
  version dir, which is exactly the interface a real second host presents
  (per-process event files on the shared checkpoint root).  Host 1
  reports a slowed ``step/dispatch_s`` sketch (the injected per-host
  slowdown straggler attribution must name), then goes silent long
  enough for the supervisor to call it dead, then beats again (the
  recovery that resolves a heartbeat-age alert).

The CI container has one host; emulating the second at the file level
exercises every supervisor-side code path a real one would (the watcher,
tracker, alert engine, and attribution all consume the files, never
process handles).
"""

import os
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


# The slowed phase host 1 reports.  It must dominate host 0's dispatch
# p95 INCLUDING the first chunk's compile (the donated runners never come
# from the persistent cache, so host 0's first dispatch sample carries a
# multi-second compile on CPU) — 60s is far above any TinyNet compile
# while 0.5s would not be, so attribution flags (process 1, dispatch) and
# nothing else.
SLOW_DISPATCH_S = 60.0
SLOW_SAMPLES = 12


def emulate_host1(version_dir: Path) -> None:
    """Host 1 at the file level: heartbeats + a slowed dispatch sketch +
    a dead-then-recovered silence window, in the same version dir.  No
    ``run_start`` anchor is emitted — a fabricated one would feed the
    clock-skew estimator a bogus offset for this 'host'."""
    from distributed_training_comparison_tpu import obs

    bus = obs.EventBus(
        run_id=os.environ.get(obs.RUN_ID_ENV) or obs.new_run_id(),
        attempt=int(os.environ.get(obs.ATTEMPT_ENV, "0") or 0),
        process_index=1,
    )
    bus.bind_dir(version_dir)
    reg = obs.MetricRegistry(flush_steps=1)
    bus.emit("heartbeat", epoch=0, step=0, flush_seq=0)
    reg.histogram("step/dispatch_s").record_many(
        [SLOW_DISPATCH_S] * SLOW_SAMPLES
    )
    reg.note_steps(SLOW_SAMPLES)
    reg.flush(bus, epoch=0, step=SLOW_SAMPLES)
    bus.emit("heartbeat", epoch=0, step=SLOW_SAMPLES, flush_seq=1)
    # silence: the watcher (1s poll, --heartbeat-secs 0.2 → slow at 0.6s,
    # dead at 2s) must classify this host slow, then dead
    time.sleep(4.0)
    # recovery: the next beat flips the state back and resolves the
    # heartbeat-age alert for this host
    bus.emit("heartbeat", epoch=0, step=SLOW_SAMPLES, flush_seq=1)
    time.sleep(1.5)  # one more watcher poll must see the recovery
    bus.close()


def main(argv) -> int:
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.resilience import (
        EXIT_PREEMPTED,
        Preempted,
    )
    from distributed_training_comparison_tpu.utils import (
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()
    hp = load_config("tpu", argv)
    if getattr(hp, "supervise", False):
        from distributed_training_comparison_tpu.resilience.supervisor import (
            run_supervised,
        )

        return int(run_supervised(hp, argv)["exit_code"])

    from distributed_training_comparison_tpu.train import Trainer

    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    version_dir = trainer.version_dir
    try:
        trainer.fit()
    except Preempted:
        return EXIT_PREEMPTED
    finally:
        trainer.close()
    emulate_host1(Path(version_dir))
    print("RESULT fleet worker done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
