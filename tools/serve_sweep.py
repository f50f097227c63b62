"""Bucket-ladder × arrival-rate sweep for the serving engine.

One-factor-at-a-time evidence for the README's serving analysis.  Each cell builds an engine
with one bucket ladder, drives it open-loop at one Poisson rate, and
prints a JSON line — so the latency-vs-load curve and the effect of
bucket granularity (fine ladders pad less but compile more programs and
coalesce smaller batches) are measured, not guessed.

Usage::

    python tools/serve_sweep.py            # resnet18 matrix
    python tools/serve_sweep.py vit        # vit_tiny matrix
    python tools/serve_sweep.py --requests 512 --rates 100,400,1600

Prints one JSON line per (buckets, rate) cell to stdout.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

import jax  # noqa: E402

from distributed_training_comparison_tpu.serve import (  # noqa: E402
    MicroBatcher,
    ServeEngine,
    open_loop,
    request_pool,
)
from distributed_training_comparison_tpu.utils import (  # noqa: E402
    enable_persistent_compilation_cache,
)

# bucket ladders: coarse (one big program), standard, fine-grained
LADDERS = {
    "single_64": (64,),
    "pow2_to_64": (1, 4, 16, 64),
    "fine_to_64": (1, 2, 4, 8, 16, 32, 64),
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("model", nargs="?", default="resnet18")
    ap.add_argument("--requests", type=int, default=0, help="0 = auto by platform")
    ap.add_argument("--rates", type=str, default="", help="req/s list, comma-separated")
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    args = ap.parse_args()
    model = "vit_tiny" if args.model == "vit" else args.model

    enable_persistent_compilation_cache()
    on_tpu = jax.devices()[0].platform == "tpu"
    requests = args.requests or (2048 if on_tpu else 64)
    rates = (
        tuple(float(r) for r in args.rates.split(",") if r)
        or ((500.0, 2000.0, 8000.0) if on_tpu else (32.0, 128.0))
    )

    images = request_pool(256, image_size=32, seed=0)
    for ladder_key, buckets in LADDERS.items():
        try:
            engine = ServeEngine(
                model_name=model, buckets=buckets, precision="bf16"
            )
            engine.warmup()
        except Exception as e:  # keep sweeping; a failed cell is a datum
            print(
                json.dumps({"key": ladder_key, "error": str(e)[:200]}),
                flush=True,
            )
            continue
        for rate in rates:
            with MicroBatcher(
                engine, max_wait_ms=args.max_wait_ms, queue_limit=4 * int(max(buckets))
            ) as batcher:
                rep = open_loop(
                    batcher, images, rate_rps=rate,
                    num_requests=requests, seed=0,
                )
            print(
                json.dumps(
                    {
                        "key": f"{ladder_key}_r{int(rate)}",
                        "model": model,
                        "buckets": list(buckets),
                        "offered_rps": rate,
                        "throughput_rps": rep["throughput_rps"],
                        "latency_ms": rep["latency_ms"],
                        "shed": rep["shed"],
                        "compiles": engine.stats()["compiles"],
                    }
                ),
                flush=True,
            )


if __name__ == "__main__":
    main()
