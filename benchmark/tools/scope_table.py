#!/usr/bin/env python3
"""Where a step's device time goes, by the names the trace carries.

    python3 benchmark/tools/scope_table.py <trace.json | .xplane.pb> [--depth n]

One row per scope path of the train program's ops (``harness/scopes.py``
``scope_path``: from the model down, ``--depth`` components below it —
``ResNet/stage1_block0``, at 2 ``ResNet/stage1_block0/BatchNorm_0``, at 4
``ViT/ViT.trunk/blocks/attn/attention``), with forward, backward and other
milliseconds of op self-time a step and the share of the train program,
sorted by time; above it the four phases.  A fusion is booked whole to the
scope of its root instruction.

The input is a profiler trace (``.xplane.pb``, as ``benchmark/run.py
--trace 1`` leaves under ``.benchmark_work/<cell>/trace/``) or a recorded
cut of one (``harness.scopes.to_json``).  The train program is the one the
device spent most time in, and the trace's steps are the number of times
most of its instructions ran.
"""

from __future__ import annotations

import argparse
import collections
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import scopes  # noqa: E402

WHOLE = float("-inf"), float("inf")


def longest_program(scoped) -> str:
    spent = collections.Counter()
    for name, _, duration in scoped.modules:
        spent[scopes.program_of(name)] += duration
    return spent.most_common(1)[0][0]


def steps_in(scoped, program: str) -> int:
    runs = scopes.executions(scoped, program, *WHOLE)
    counts = collections.Counter(
        name for name, start, _, _ in scoped.ops
        if any(lo <= start < hi for lo, hi in runs)
    )
    return collections.Counter(counts.values()).most_common(1)[0][0]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--depth", type=int, default=1)
    args = ap.parse_args()

    load = scopes.from_json if args.trace.endswith(".json") else scopes.load_xplane
    scoped = load(args.trace)
    if scoped is None or not scoped.modules:
        print(f"no device program in {args.trace}", file=sys.stderr)
        return 1
    program = longest_program(scoped)
    steps = steps_in(scoped, program)
    spent = scopes.by_phase(scoped, *WHOLE, program)
    whole = sum(spent.values())
    print(f"{program}: {steps} steps, {whole / 1e6 / steps:.3f} ms of op "
          f"self-time a step")
    for phase, ns in spent.items():
        print(f"  {phase:<9}{ns / 1e6 / steps:>9.3f} ms {100 * ns / whole:>6.2f} %")
    print(f"\n{'scope':<56}{'fwd ms':>9}{'bwd ms':>9}{'other':>9}{'share %':>9}")
    for path, *row in scopes.table(scoped, *WHOLE, program, args.depth):
        fwd, bwd, rest = (1e3 * s / steps for s in row)
        share = 100 * sum(row) * 1e9 / whole
        print(f"{path:<56}{fwd:>9.3f}{bwd:>9.3f}{rest:>9.3f}{share:>9.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
