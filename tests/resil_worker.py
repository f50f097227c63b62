"""Resilience supervisor child: one training attempt on forced CPU devices.

Launched by ``tests/test_resilience.py`` (and usable standalone) under a
per-attempt ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the
supervisor varies N between attempts, so a resumed attempt restores the
preempted attempt's checkpoint onto a DIFFERENT device count (the elastic
path).  Runs the real product path — ``load_config`` flags, ``Trainer``
with fault plan + preemption handler, checkpoint drain, distinct exit code
— with a TinyNet model (the zoo ResNets are too heavy for the single-core
CI host; the net is defined inline so the worker has no pytest imports).

Exit codes mirror the backend ``main.py`` contract: 0 = completed,
``EXIT_PREEMPTED`` = drained preemption (supervisor relaunches
immediately), anything else = crash (supervisor backs off).
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax

jax.config.update("jax_platforms", "cpu")  # CPU-only worker, also when run by hand

import flax.linen as lnn
import jax.numpy as jnp


class TinyNet(lnn.Module):
    """Conv+BN+dense classifier sharing the zoo interface (see
    tests/test_train.py — duplicated here so the worker is standalone)."""

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


def main(argv) -> int:
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.resilience import (
        EXIT_PREEMPTED,
        Preempted,
    )
    from distributed_training_comparison_tpu.train import Trainer
    from distributed_training_comparison_tpu.utils import (
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()
    hp = load_config("tpu", argv)
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    try:
        version = trainer.fit()
    except Preempted as e:
        print(
            f"RESULT preempted=1 epoch={e.epoch} "
            f"start_epoch={trainer.start_epoch} devices={jax.device_count()}",
            flush=True,
        )
        return EXIT_PREEMPTED
    finally:
        trainer.close()
    print(
        f"RESULT preempted=0 start_epoch={trainer.start_epoch} "
        f"devices={jax.device_count()} version={version}",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
