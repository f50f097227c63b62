#!/usr/bin/env python3
"""What computing a configuration in a lower precision would cost, read
through the comparison that decides ``correct``.

    python3 benchmark/tools/precision_below.py --config <config> \
        --seed <n> [--dtype float8_e4m3fn|bfloat16]

A tolerance a configuration brings lies between two readings: the largest
error its program shows over seeds, and the error of the plain reference
computed in the nearest precision below the configuration's own (for a
bf16 configuration, 8-bit floats) — which has to come out as not
``correct``.  This prints the second: ``harness.compare.first_step`` with,
as "the program's end", the configuration's reference whose matrix-product
operands are rounded to ``--dtype`` (its ``operand`` hook) against the same
reference in float32.  It builds the ``Trainer`` as ``run.py`` does, for
its initial parameters; a reference without the hook is an error.  One
JSON line; exit 0.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path[:0] = [str(ROOT), str(HERE)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", default="float8_e4m3fn")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.train import Trainer
    from harness import compare, load_module

    config = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    argv = ["--synthetic-data", "--no-progress", "--seed", str(args.seed),
            "--ckpt-path", str(ROOT / ".benchmark_work" / "precision_below"),
            "--num-devices", "1", *config["argv"]]
    if args.rehearse:
        argv += config.get("rehearse_argv", [])
        config["compare"].update(config.get("rehearse_compare", {}))
    trainer = Trainer(load_config("tpu", argv))
    reference_path = HERE / config["reference"]
    low = load_module(reference_path)
    if not hasattr(low, "operand"):
        raise SystemExit(f"{reference_path} has no operand hook")
    dtype = jnp.dtype(args.dtype)
    # straight through: the product sees the rounded operand, the gradient
    # passes unrounded (8-bit floats would flush a cotangent of 1e-5 to zero,
    # which no such training does unscaled)
    low.operand = lambda x: x + jax.lax.stop_gradient(
        x.astype(dtype).astype(jnp.float32) - x
    )

    def rounded(trainer, inputs, labels, seed):
        recipe = dict(config["compare"]["recipe"])
        params = compare._to_host(trainer.state.params)
        stats = compare._to_host(trainer.state.batch_stats)
        with jax.default_matmul_precision("highest"):
            out = jax.jit(
                lambda p, s, x, y: low.step(p, s, x, y, recipe)
            )(params, stats, inputs, labels)
        return {
            "loss": float(out["loss"]), "grad_norm": float(out["grad_norm"]),
            "params": compare._to_host(out["params"]),
            "batch_stats": compare._to_host(out["batch_stats"]),
        }

    try:
        out = compare.first_step(
            trainer, config, args.seed, reference_path, step=rounded
        )
    finally:
        trainer.close()
    print(json.dumps({
        "config": args.config, "operands_rounded_to": args.dtype,
        "seed": args.seed, "platform": jax.devices()[0].platform,
        "errors": out["errors"], "tolerance": out["tolerance"],
        "would_be_correct": bool(out["ok"]),
        "broken_limits": [k for k, v in out["errors"].items()
                          if not (np.isfinite(v) and v <= out["tolerance"][k])],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
