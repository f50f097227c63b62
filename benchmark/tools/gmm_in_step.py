#!/usr/bin/env python3
"""Time the grouped expert matmul's implementations inside the train step.

    python3 benchmark/tools/gmm_in_step.py --config <config> --impl <name>

``Trainer.fit()`` for a few epochs under the configuration's own argv, on a
model built with ``moe_gmm=<impl>`` (the launcher has no flag for it: the
model's ``auto`` takes the implementation this table decided); one process
an implementation; prints one JSON line with each epoch's train seconds,
steps and routed rows a step (host clock around the epoch's dispatch and
fetch, validation and saves outside it) and the train program's
``temp_bytes``.  Standalone timings of a grouped matmul say
little about it between the gathers around it; this is the step it runs in.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path[:0] = [str(ROOT), str(HERE)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--impl", required=True)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax

    import jax.numpy as jnp

    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.models import get_model
    from distributed_training_comparison_tpu.train import Trainer
    from distributed_training_comparison_tpu.utils import (
        enable_persistent_compilation_cache,
    )

    config = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    work = ROOT / ".benchmark_work" / "gmm_in_step"
    shutil.rmtree(work, ignore_errors=True)
    argv = ["--synthetic-data", "--no-progress", "--seed", str(args.seed),
            "--ckpt-path", str(work), "--num-devices", "1",
            "--epoch", str(args.epochs), *config["argv"]]
    if args.rehearse:
        argv += config.get("rehearse_argv", [])
    enable_persistent_compilation_cache()
    hparams = load_config("tpu", argv)
    model = get_model(
        hparams.model, model_cut=hparams.model_cut, remat=hparams.remat,
        dtype=jnp.bfloat16 if hparams.precision == "bf16" else jnp.float32,
        moe_gmm=args.impl,
    )
    trainer = Trainer(hparams, model=model)
    events = []
    trainer.bus.subscribe(events.append)
    try:
        trainer.fit()
    finally:
        trainer.close()
    ends = [e["payload"] for e in events if e.get("kind") == "epoch_end"]
    compiles = [e["payload"] for e in events if e.get("kind") == "compile"]
    rows = [e["payload"].get("metrics", {}).get("moe/rows", {}).get("n")
            for e in events if e.get("kind") == "metrics"]
    print(json.dumps({
        "config": args.config, "impl": args.impl,
        "platform": jax.devices()[0].platform,
        "steps_per_epoch": trainer.steps_per_epoch,
        "epoch_train_s": [e["secs"] for e in ends],
        "ms_per_step": [1e3 * e["secs"] / trainer.steps_per_epoch for e in ends],
        "train_loss": [e["train_loss"] for e in ends],
        "moe_rows_per_step": [n / trainer.steps_per_epoch
                              for n in rows[:len(ends)] if n is not None],
        "kernel_paths": {k: v for c in compiles
                         for k, v in (c.get("kernel_paths") or {}).items()},
        "temp_bytes": max((c.get("temp_bytes") or 0) for c in compiles),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
