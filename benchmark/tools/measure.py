#!/usr/bin/env python3
"""Run cells several times, as the driver's check does, and print each
metric's median and spread (distance between the quartiles / median).

    python3 benchmark/tools/measure.py --label set1 --runs 6 --seed0 100 \
        [--trace 0|1] [--seconds S] [--keep-trace] <cell> [<cell> ...]

Each run is a new process with another ``--seed``; this parent never touches
JAX, so the children get the chip one after another.  Every run's last line
is appended to ``chiprun_out/measure_<label>.jsonl`` (with the cell, the
seed, the exit code and the wall seconds), and the end of the errors of a
failed run to ``chiprun_out/measure_<label>.err``.  Stops at the first
failed run of a cell and goes on with the next cell.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
OUT = ROOT / "chiprun_out"


def spread(values: list[float]) -> float:
    """Interquartile distance over the median (inclusive quartiles)."""
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4, method="inclusive")
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("nan")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--label", default="set")
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or bench["run_seconds"]
    OUT.mkdir(exist_ok=True)
    log = OUT / f"measure_{args.label}.jsonl"
    rc_all = 0
    for cell in args.cells:
        lines = []
        for i in range(args.runs):
            seed = args.seed0 + i
            cmd = [*bench["command"], "--workload", cell, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(args.trace)]
            if args.keep_trace:
                cmd += ["--keep-trace", str(OUT / f"trace_{cell}.json")]
            t0 = time.time()
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            wall = time.time() - t0
            last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
            try:
                line = json.loads(last)
            except ValueError:
                line = {}
            line.update(cell=cell, seed=seed, rc=p.returncode, wall_s=wall)
            with open(log, "a") as f:
                f.write(json.dumps(line) + "\n")
            if p.returncode != 0 or "metrics" not in line:
                with open(OUT / f"measure_{args.label}.err", "a") as f:
                    f.write(f"== {cell} seed {seed} rc {p.returncode}\n")
                    f.write(p.stdout[-3000:] + "\n--\n" + p.stderr[-6000:] + "\n")
                print(f"{cell} seed {seed}: FAILED rc={p.returncode}")
                print(p.stderr[-1500:])
                rc_all = 1
                break
            lines.append(line)
            print(f"{cell} seed {seed}: correct={line['correct']} "
                  f"wall={wall:.1f}s " + " ".join(
                      f"{k}={v['value']:.5g}" for k, v in line["metrics"].items()),
                  flush=True)
        names = sorted({k for ln in lines for k in ln["metrics"]})
        for k in names:
            vals = [ln["metrics"][k]["value"] for ln in lines if k in ln["metrics"]]
            print(f"  {cell:28s} {k:24s} n={len(vals)} "
                  f"median={statistics.median(vals):.6g} "
                  f"spread={100 * spread(vals):.3f}%  "
                  f"min={min(vals):.6g} max={max(vals):.6g}")
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
