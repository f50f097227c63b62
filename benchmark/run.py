#!/usr/bin/env python3
"""Run one cell of the benchmark once and print the contract's last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which owns the cell's chips.  The cell goes through what a user
runs — ``Trainer(load_config("tpu", argv))`` and ``fit()`` exactly as
``entry.run`` does — under the launcher's flags, on ``--synthetic-data``
made from ``--seed``.  Everything that belongs to one cell, configuration,
traffic mix or metric is data found by its name in ``BENCHMARK.json``:

    workloads/<cell>.json          the cell: what it expects of the program
    configs/<config>.json          the model and recipe: argv, FLOP sizes,
    reference/<config>.py            the comparison batch; its plain reference
    traffic/<mix>.json             the mix: argv, how the window is cut
    end_to_end/<metric>.py         one reader each: ``read(run) -> value``
    layer_metrics/<metric>.py        (None: nothing to read, metric left out)

This file names none of them.  ``harness/`` holds the yardstick: the window
clock, the first-step comparison, the trace reduction, FLOP counts, peaks.

Without a TPU, or with fewer devices than the cell's ``chips``, nothing is
printed and the exit code is 2.  ``--rehearse`` (honoured only under an
explicit ``JAX_PLATFORMS=cpu``) walks the same control flow at the tiny sizes
the files give, prints its values under ``rehearsal`` and never under
``metrics``, and exits 1: a CPU run is never a result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
WORK = ROOT / ".benchmark_work"  # checkpoints, events and traces of runs
BIG_EPOCH = 1_000_000  # fit() is stopped by the window clock, not by this
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def die(message: str, code: int = 2):
    print(f"benchmark: {message}", file=sys.stderr)
    sys.exit(code)


def load_json(path: Path) -> dict:
    if not path.is_file():
        die(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_reader(kind_dir: str, name: str):
    from harness import load_module

    path = HERE / kind_dir / f"{name}.py"
    if not path.is_file():
        die(f"metric {name!r} has no reader at {path.relative_to(ROOT)}")
    return load_module(path).read


def by_name(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    die(f"no {what} named {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, group: str, cell: str) -> list[dict]:
    return [
        m for m in bench[group]
        if "workloads" not in m or cell in m["workloads"]
    ]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", default=None, metavar="FILE",
                    help="also write the loaded trace as JSON (to cut a "
                    "sample for tests/data)")
    args = ap.parse_args()

    bench = load_json(ROOT / "BENCHMARK.json")
    entry = by_name(bench["workloads"], args.workload, "workload")
    config_entry = by_name(bench["configs"], entry["config"], "configuration")
    cell = load_json(HERE / "workloads" / f"{entry['name']}.json")
    config = load_json(ROOT / config_entry["file"])
    mix = load_json(HERE / "traffic" / f"{entry['traffic']}.json")
    chips = int(entry["chips"])
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    if not (ROOT / "distributed_training_comparison_tpu").is_dir():
        die("the program is not in this checkout: nothing to measure", 3)

    if args.rehearse and os.environ.get("JAX_PLATFORMS") != "cpu":
        die("--rehearse is honoured only under an explicit JAX_PLATFORMS=cpu")
    sys.path[:0] = [str(ROOT), str(HERE)]
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and platform != "tpu":
        die(f"no TPU: JAX's default backend is {platform!r}")
    if len(devices) < chips:
        die(f"the cell needs {chips} device(s), JAX finds {len(devices)}")

    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.parallel import init_distributed
    from distributed_training_comparison_tpu.train import Trainer
    from distributed_training_comparison_tpu.utils import (
        enable_persistent_compilation_cache,
    )
    from harness import compare, flops, peaks, trace as trace_mod
    from harness.window import WindowClock

    work = WORK / entry["name"]
    shutil.rmtree(work, ignore_errors=True)  # a run never resumes another
    work.mkdir(parents=True)
    argv = [
        "--synthetic-data", "--no-progress", "--seed", str(args.seed),
        "--ckpt-path", str(work / "ckpt"), "--num-devices", str(chips),
        "--epoch", str(BIG_EPOCH),
        *config["argv"], *mix["argv"],
    ]
    if args.rehearse:  # the same flags again at the files' tiny sizes
        argv += config.get("rehearse_argv", []) + mix.get("rehearse_argv", [])
        config = {**config, "compare": {**config["compare"],
                                        **config.get("rehearse_compare", {})}}

    compiles: list[tuple[float, float]] = []  # (perf_counter, seconds)
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append((time.perf_counter(), secs))
        if event == COMPILE_EVENT else None
    )

    # ---- exactly entry.run's sequence, with the clock on the bus
    t_imports = time.perf_counter()
    hparams = load_config("tpu", argv)
    enable_persistent_compilation_cache()
    init_distributed(hparams)
    trainer = Trainer(hparams)
    t_trainer = time.perf_counter()
    clock = WindowClock(
        trainer, seconds, mix["window"]["warmup_epochs"],
        trace_dir=str(work / "trace") if args.trace else None,
        trace_epochs=mix["window"]["trace_epochs"],
    )
    trainer.bus.subscribe(clock)
    try:
        first = compare.first_step(
            trainer, config, args.seed, HERE / config["reference"]
        )
        t_compare = time.perf_counter()
        trainer.fit()
    finally:
        trainer.bus.unsubscribe(clock)
        trainer.close()
    if clock.error is not None:
        raise clock.error
    if clock.closed is None:
        die("fit() returned before the window closed", 1)

    # ---- the run, as the readers see it
    window = clock.window()
    mesh_devices = list(trainer.mesh.devices.flat)
    stats = [d.memory_stats() or {} for d in mesh_devices]
    kind = mesh_devices[0].device_kind
    events = clock.events
    setup_compiles = [
        e["payload"] for e in events
        if e["kind"] == "compile" and e["t"] < clock.opened
    ]
    # On this runtime the allocator's statistics count live buffers only
    # (PERF.md, Findings, PR 22: 0.51 GiB read beside a program with 6.2 GiB
    # of temporaries), so the peak is the allocator's plus the largest
    # temporary allocation of a program that ran, from XLA's memory
    # analysis on its compile event.  Arguments and outputs are live
    # buffers and already in the allocator's figure.
    allocator_peak = max(
        (s.get("peak_bytes_in_use", 0) for s in stats), default=0
    )
    largest_temp = max(
        (e["payload"].get("temp_bytes") or 0 for e in events
         if e["kind"] == "compile"), default=0
    )
    epoch_loss = {
        e["epoch"]: e["payload"].get("train_loss")
        for e in events if e["kind"] == "epoch_end"
    }
    run = types.SimpleNamespace(
        cell=cell, config=config, mix=mix, chips=chips, device_kind=kind,
        peaks=None if args.rehearse else peaks.peaks_for(kind),
        window=window, events=events, clock=clock,
        goodput=clock.goodput_window(),
        seconds_to_window=clock.opened - T_START,
        setup_compiles=setup_compiles,
        memory_peak_bytes=(
            allocator_peak + largest_temp if allocator_peak else 0
        ),
        train_flops_per_image=flops.train_flops_per_image(config["flops"]),
        traced_steps=clock.trace_epochs * window["steps_per_epoch"],
        trace=None, trace_span=None, trace_mod=trace_mod,
    )
    if args.trace:
        found = sorted((work / "trace").rglob("*.xplane.pb"))
        if found:
            run.trace = trace_mod.load(found[-1])
            run.trace_span = trace_mod.span(
                run.trace, clock.first_epoch, clock.trace_epochs
            )
            if args.keep_trace:
                Path(args.keep_trace).parent.mkdir(parents=True, exist_ok=True)
                Path(args.keep_trace).write_text(trace_mod.to_json(run.trace))

    # ---- correct: (a) first step, (b) losses, (c) no compile, (d) path
    starts = [e for e in clock.in_window("epoch_start") if e["t"] < clock.closed]
    ends = clock.in_window("epoch_end")
    skipped = sum(
        (e["payload"].get("metrics", {}).get("train/skipped_steps") or {})
        .get("n", 0)
        for e in clock.in_window("metrics")
    )
    window_losses = [e["payload"].get("train_loss") for e in ends]
    late = [c for c in compiles if clock.opened <= c[0] <= clock.closed]
    train_compiles = [
        c for c in setup_compiles
        if str(c.get("name", "")).startswith(mix["train_program"])
    ]
    kernel_paths = {
        k: v for c in train_compiles
        for k, v in (c.get("kernel_paths") or {}).items()
    }
    last_epoch = clock.close_epoch - 1
    checks = {
        "first_step_matches_reference": bool(first["ok"]),
        "losses_finite": skipped == 0 and all(
            v is not None and math.isfinite(v) for v in window_losses
        ),
        "loss_below_epoch_0": (
            epoch_loss.get(last_epoch) is not None
            and epoch_loss.get(0) is not None
            and epoch_loss[last_epoch] < epoch_loss[0]
        ),
        "every_epoch_ended": len(starts) == len(ends) == window["epochs"],
        "no_compile_in_window": not late and not clock.in_window("compile"),
        "on_tpu": platform == "tpu",
        "device_count": len(mesh_devices) == chips,
        "kernel_paths_as_expected": bool(train_compiles)
        and kernel_paths == cell["expect"]["kernel_paths"]
        and sum(c.get("tpu_custom_calls") or 0 for c in train_compiles)
        == cell["expect"]["tpu_custom_calls"],
    }
    failed_epochs = max(0, len(starts) - len(ends))
    failed = int(skipped + failed_epochs * window["steps_per_epoch"])

    # ---- metrics: --trace 0 the end-to-end ones, --trace 1 the per-layer
    group, readers = (
        ("per_layer", "layer_metrics") if args.trace
        else ("end_to_end", "end_to_end")
    )
    values = {}
    for m in metrics_of(bench, group, entry["name"]):
        value = load_reader(readers, m["name"])(run)
        if value is not None:
            values[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {
        "platform": platform, "kind": kind, "count": len(mesh_devices),
        "memory_peak_bytes": int(run.memory_peak_bytes),
    }
    line = {
        "correct": all(checks.values()),
        "attempted": int(window["steps"]),
        "failed": failed,
        "metrics": values,
        "device": device,
    }
    if args.trace and run.trace_span is not None and run.trace.devices:
        lo, hi = run.trace_span
        device["busy_s"] = trace_mod.busy_seconds(run.trace, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        line["breakdown"] = {
            "device_ops": trace_mod.top_ops(run.trace, lo, hi),
            "idle_gaps": trace_mod.idle_by_host_phase(run.trace, lo, hi),
        }
    line.update(
        checks=checks,
        first_step=first,
        window={k: window[k] for k in
                ("seconds", "epochs", "steps", "images", "steps_per_epoch",
                 "batch_size")},
        losses={"epoch_0": epoch_loss.get(0), "last": epoch_loss.get(last_epoch)},
        setup={
            "imports_s": t_imports - T_START,
            "trainer_init_s": t_trainer - t_imports,
            "first_step_compare_s": t_compare - t_trainer,
            "epoch_0_and_boundary_s": clock.opened - t_compare,
            "compiles": [
                [c.get("name"), c.get("compile_s"), c.get("cache"),
                 c.get("temp_bytes")]
                for c in setup_compiles
            ],
            "kernel_paths": kernel_paths,
        },
        goodput_window_s=run.goodput,
        memory={"allocator_peak_bytes": allocator_peak,
                "largest_program_temp_bytes": largest_temp,
                "allocator": stats[0]},
        argv=argv,
        cache_dir=jax.config.jax_compilation_cache_dir,
        total_s=time.perf_counter() - T_START,
    )
    if args.trace and run.trace is not None:
        line["trace"] = trace_mod.describe(run.trace)
    if args.rehearse:
        line["rehearsal"] = line.pop("metrics")
        line["metrics"] = {}
        line["correct"] = False
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 1 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
