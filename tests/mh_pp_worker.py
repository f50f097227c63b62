"""Multi-host pipeline-parallel worker: one JAX process of a 2-process CPU
'cluster' training a ViT with ``--parallel-style pipeline`` where the two
pipeline stages live on DIFFERENT processes — every per-tick ``ppermute``
activation handoff crosses the process boundary (the CPU stand-in for a
cross-host DCN hop), and the stage-sharded stacked parameters are
partitioned across processes (exercising the symmetric checkpoint fetch).

Launched by tests/test_multihost.py (4 virtual CPU devices per process →
an 8-device (4 data × 2 model) mesh, ViT depth 2 → 1 layer per stage).
"""

import sys

import jax

jax.config.update("jax_platforms", "cpu")  # CPU-only worker, also when run by hand


def main(rank: int, port: int, ckpt_dir: str) -> None:
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.models import ViT
    from distributed_training_comparison_tpu.parallel import init_distributed
    from distributed_training_comparison_tpu.parallel.sharding import (
        needs_collective_fetch,
    )
    from distributed_training_comparison_tpu.train import Trainer

    hp = load_config(
        "tpu",
        argv=[
            "--synthetic-data",
            "--limit-examples", "128",
            "--batch-size", "32",
            "--epoch", "1",
            "--eval-step", "2",
            "--lr", "0.01",
            "--ckpt-path", ckpt_dir,
            "--model", "vit_tiny",  # name only; tiny stand-in passed below
            "--model-parallel", "2",
            "--parallel-style", "pipeline",
            "--pipeline-microbatches", "2",
            "--world-size", "2",
            "--rank", str(rank),
            "--dist-url", f"127.0.0.1:{port}",
        ],
    )
    init_distributed(hp)
    assert jax.process_count() == 2

    trainer = Trainer(hp, model=ViT(depth=2, dim=32, heads=2, patch=8))
    # the stacked trunk must genuinely partition across the processes
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
