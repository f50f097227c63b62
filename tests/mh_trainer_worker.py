"""Multi-host Trainer worker: one JAX process of a 2-process CPU 'cluster'
running the FULL product path — ``Trainer.fit()`` → checkpoints →
``test()`` — with tensor parallelism spanning the two processes.

Launched by tests/test_multihost.py (4 virtual CPU devices per process →
an 8-device (4 data × 2 model) mesh).  This drives exactly the branches a
process-0-only or worker-thread collective would deadlock on:

- the symmetric cross-host fetch of TP-partitioned state before the
  process-0 checkpoint writer serializes (trainer.fit),
- the found-flag + zero-placeholder best-checkpoint broadcast in
  ``test()``,
- per-epoch validation/eval runners over a multi-process mesh.

The model is the real zoo ``ResNet`` truncated to one block each in stages
3 and 4 (the TP-sharded stages) so the tensor-parallel layout genuinely
partitions parameters across processes while staying CPU-compilable.
"""

import sys

import jax

jax.config.update("jax_platforms", "cpu")  # CPU-only worker, also when run by hand


def main(rank: int, port: int, ckpt_dir: str) -> None:
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.models.resnet import BasicBlock, ResNet
    from distributed_training_comparison_tpu.parallel import init_distributed
    from distributed_training_comparison_tpu.parallel.sharding import (
        needs_collective_fetch,
    )
    from distributed_training_comparison_tpu.train import Trainer

    hp = load_config(
        "ddp",
        argv=[
            "--synthetic-data",
            "--limit-examples", "128",
            "--batch-size", "32",
            "--epoch", "1",
            "--eval-step", "2",
            "--lr", "0.05",
            "--ckpt-path", ckpt_dir,
            "--model-parallel", "2",
            "--world-size", "2",
            "--rank", str(rank),
            "--dist-url", f"127.0.0.1:{port}",
        ],
    )
    init_distributed(hp)
    assert jax.process_count() == 2

    model = ResNet(block=BasicBlock, num_blocks=(0, 0, 1, 1), num_classes=100)
    trainer = Trainer(hp, model=model)
    # TP must actually partition params across the processes — otherwise
    # this test would silently stop covering the symmetric-fetch path
    assert needs_collective_fetch(trainer.state.params)

    version = trainer.fit()
    results = trainer.test()
    trainer.close()
    print(
        f"RESULT rank={rank} version={version} "
        f"top1={results['test_top1']:.4f} loss={results['test_loss']:.6f}",
        flush=True,
    )


if __name__ == "__main__":
    main(int(sys.argv[1]), int(sys.argv[2]), sys.argv[3])
