#!/usr/bin/env python3
"""Where the device's idle time goes, by the span the host was in.

    python3 benchmark/tools/boundary_table.py <.xplane.pb>

One row per span name of the program's span tree on the trainer's thread
(``harness/host_spans.py``: ``epoch`` with ``dispatch`` and ``compute``, its
sibling ``boundary`` and what the host does inside it), indented by depth,
in the order the names first open: how often it opened, the host's
milliseconds in it an epoch, and the milliseconds an epoch the first device
sat idle while it was the innermost span open.  Below the table the sums by
group (``host_spans.GROUPS``), what no group takes, the whole idle time,
which the rows add up to, and how far the device's clock and the host's
disagree in this trace — as ``scope_table.py`` is for the device's scopes.

The input is a profiler trace: the one ``benchmark/run.py --trace 1`` leaves
under ``.benchmark_work/<cell>/trace/`` (the span runs from its first
``bench/epoch_start`` mark to its last) or an operator's ``--profile-dir``
capture (no marks: from the first program span to the end of the last).
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import host_spans, trace as trace_mod  # noqa: E402


def span_of(trace, spans) -> tuple:
    starts = [t for kind, _, t in trace.marks if kind == "epoch_start"]
    if len(starts) >= 2:
        return starts[0], starts[-1]
    return min(s for _, s, _, _ in spans), max(e for _, _, e, _ in spans)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    args = ap.parse_args()

    host = host_spans.load_xplane(args.trace)
    spans = host_spans.program_spans(host) if host is not None else []
    if not spans:
        print(f"no program span in {args.trace}", file=sys.stderr)
        return 1
    trace = trace_mod.load(args.trace)
    lo, hi = span_of(trace, spans)
    epochs = max(1, len(host_spans.boundaries(host, lo, hi)))
    idle = host_spans.idle_by_span(trace, host, lo, hi)
    rows: dict = {}  # name -> [depth, count, host ns]
    for name, start, end, depth in spans:
        if start >= lo and end <= hi:
            row = rows.setdefault(name, [depth, 0, 0])
            row[1] += 1
            row[2] += end - start

    print(f"{host.thread}: {(hi - lo) / 1e9:.3f} s, {epochs} epoch(s), "
          f"{len(trace.devices)} device(s)")
    print(f"\n{'span':<28}{'group':<14}{'count':>7}{'host ms':>11}{'idle ms':>11}")
    for name, (depth, count, ns) in rows.items():
        group = host_spans.GROUPS.get(name, "-")
        print(f"{'  ' * depth + name:<28}{group:<14}{count:>7}"
              f"{ns / 1e6 / epochs:>11.3f}{idle.get(name, 0) / 1e6 / epochs:>11.3f}")
    for name in sorted(set(idle) - set(rows)):  # NO_SPAN, a span cut by the edge
        print(f"{name:<28}{'-':<14}{'':>7}{'':>11}{idle[name] / 1e6 / epochs:>11.3f}")
    print()
    groups = host_spans.by_group(idle)
    for group, ns in groups.items():
        print(f"{group:<28}{ns / 1e6 / epochs:>11.3f} ms an epoch")
    whole = sum(groups.values())
    print(f"{'all idle':<28}{whole / 1e6 / epochs:>11.3f} ms an epoch, "
          f"{100.0 * whole / (hi - lo):.2f} % of the span")
    lead = host_spans.clock_lead_ns(trace, host, lo, hi)
    if lead:
        print(f"the device's timeline runs up to {lead / 1e6:.3f} ms ahead of the "
              f"host's (a train execution starts before its dispatch opens): "
              f"neighbouring rows may have traded that much idle time")
    return 0


if __name__ == "__main__":
    sys.exit(main())
