#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, which owns the chip, drives the product's main path once
through the entry point a user calls (``entry.run("tpu", argv)``, what
``src/tpu_jax/main.py`` and the ``run_*.sh`` launchers run), at the full
width of the models the repo supports, with random weights made from
``--seed`` and runs cut only in length:

- ``train``   the ``run_tpu.sh`` recipe (ResNet-18, CIFAR-100 shapes, global
  batch 256, bf16, ``--contain-test``) on ``--synthetic-data``: a few dozen
  steps, one validation, one checkpoint save, the test on the restored best
  checkpoint;
- ``vit_tiny_p2`` / ``vit_moe`` / ``vit_long``   a few steps each of the
  kernel-carrying models through the same Trainer; each must show its
  Pallas kernel compiled into the train step (``kernel_paths`` and
  ``tpu_custom_calls`` on the ``compile`` event, obs/compilation.py) — not
  interpreted, not composed;
- ``serve``   ``--serve`` restores the checkpoint ``train`` just wrote, warms
  its buckets and answers a few dozen requests over the thread transport;
  every reply is compared, at bf16 tolerance, with a direct ``model.apply``
  on the same images with the restored variables.

``--chips 4`` runs the path across chips and what it is compared with, and
no other phase: the same seed, model and global batch for a few steps on a
one-device mesh, on ``data=4`` and on ``data=2 x model=2``, with the loss
trajectories held to the tolerances tests/test_tp.py uses and the shards
shown to sit on distinct devices.

Each phase prints one JSON line (seconds to compile and per step, losses,
which kernel path ran, the compile-cache directory and its hit/miss counts).
The last line of stdout is the verdict::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Exit code 0 only with ``"ok": true``.  Without a TPU nothing runs: the
script prints no result and exits 2.  ``--rehearse`` (honoured only under an
explicit ``JAX_PLATFORMS=cpu``) walks every phase at a tiny size on the CPU
to check the control flow, and always ends ``"ok": false`` / exit 1 — a CPU
run is never a pass.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import shutil
import struct
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
WORKDIR = REPO / ".chip_smoke"  # checkpoints + event files of this run

# src/tpu_jax/run_tpu.sh, minus its length (EPOCH=50 on the full split) and
# its batch size, which each phase states
RECIPE = [
    "--lr", "0.1", "--lr-decay-step-size", "25", "--lr-decay-gamma", "0.1",
    "--weight-decay", "1e-4", "--amp", "--epoch", "1",
]
QUIET = ["--synthetic-data", "--no-progress", "--log-every-step",
         "--save-last-min-secs", "0"]


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def step_losses(version_dir: Path) -> list[float]:
    """The per-step train losses a ``--log-every-step`` run wrote to its
    TensorBoard file (``loss/step``, utils/tensorboard.py), in step order,
    exact fp32.  Records are ``len:u64 | crc:u32 | payload | crc:u32``; a
    scalar event's payload ends in its tag, ``0x15`` and the float32."""
    losses = []
    for f in sorted((version_dir / "tb").glob("events.out.tfevents.*")):
        buf = f.read_bytes()
        pos = 0
        while pos + 12 <= len(buf):
            (n,) = struct.unpack_from("<Q", buf, pos)
            payload = buf[pos + 12: pos + 12 + n]
            pos += 12 + n + 4
            if payload[-15:-4] == b"\x09loss/step\x15":  # len-prefixed tag
                losses.append(struct.unpack("<f", payload[-4:])[0])
    return losses


def run_events(directory: Path) -> tuple[list[dict], list[dict]]:
    """(the ``compile`` payloads, every event) a run wrote under a dir."""
    from distributed_training_comparison_tpu.obs import load_events

    events = []
    for f in sorted(directory.glob("events*.jsonl")):
        events.extend(load_events(f))
    return [e["payload"] for e in events if e.get("kind") == "compile"], events


def compile_summary(compiles: list[dict]) -> dict:
    """Seconds and persistent-cache outcome of every compile of a phase,
    and the cache directory in use."""
    import jax

    return {
        "compiles": [
            [c["name"], round(c["compile_s"], 2), c["cache"]] for c in compiles
        ],
        "cache": {
            "dir": jax.config.jax_compilation_cache_dir,
            "hits": sum(c["cache"] == "hit" for c in compiles),
            "misses": sum(c["cache"] == "miss" for c in compiles),
        },
    }


def finish(rec: dict, checks: dict) -> dict:
    """Close a phase record: its checks, its verdict, its one JSON line."""
    rec["checks"] = checks
    rec["ok"] = all(checks.values())
    emit(rec)
    return rec


def guarded(name: str, phase, *args, **kw) -> dict:
    """Run one phase; a phase that raises is a failed phase with its
    traceback on stderr, and the phases after it still run."""
    try:
        return phase(name, *args, **kw)
    except Exception as e:
        import traceback

        traceback.print_exc()
        return finish(
            {"phase": name, "error": f"{type(e).__name__}: {e}"[:500]},
            {"ran": False},
        )


def train_phase(
    name: str, argv: list[str], *, kernel: str | None = None,
    must_fall: bool = False,
) -> dict:
    """One Trainer run through ``entry.run``; everything reported is read
    back from what the run itself wrote (events, TensorBoard, results).

    ``kernel`` names the gate (``note_kernel_path``) whose Pallas kernel
    must be compiled into the train step.  ``must_fall`` holds the loss to
    a fall from the first third of the steps to the last — asked of the
    main train phase, whose few dozen steps are enough to show one."""
    from distributed_training_comparison_tpu.entry import run

    ckpt = WORKDIR / name
    argv = [*argv, *QUIET, "--ckpt-path", str(ckpt)]
    t0 = time.perf_counter()
    results = run("tpu", argv)
    seconds = time.perf_counter() - t0
    vdir = ckpt / f"version-{results['version']}"
    compiles, events = run_events(vdir)
    losses = step_losses(vdir)
    train_exec = [
        c for c in compiles if c["name"].startswith("device_chunk_runner")
    ]
    epoch_end = [e["payload"] for e in events if e.get("kind") == "epoch_end"]
    epoch_secs = sum(e["secs"] for e in epoch_end)
    third = max(1, len(losses) // 3)
    rec = {
        "phase": name,
        "argv": argv,
        "seconds": round(seconds, 2),
        **compile_summary(compiles),
        # epoch wall time less the train step's own compiles, per step
        "step_s": round(
            (epoch_secs - sum(c["compile_s"] for c in train_exec))
            / max(1, len(losses)), 5,
        ),
        "losses": losses,
        "loss_first_third": float(np.mean(losses[:third])) if losses else None,
        "loss_last_third": float(np.mean(losses[-third:])) if losses else None,
        "val": [{k: e[k] for k in ("val_loss", "val_acc")} for e in epoch_end],
        "results": {k: v for k, v in results.items() if k != "version"},
        "platform": sorted({c["platform"] for c in compiles}),
        "kernel_paths": {
            k: v for c in train_exec
            for k, v in (c.get("kernel_paths") or {}).items()
        },
        "tpu_custom_calls": sum(
            c.get("tpu_custom_calls") or 0 for c in train_exec
        ),
    }
    checks = {
        "ran_steps": len(losses) > 0 and len(train_exec) > 0,
        "finite": bool(losses) and bool(np.isfinite(losses).all()),
    }
    if must_fall:
        checks["loss_falls"] = (
            bool(losses) and rec["loss_last_third"] < rec["loss_first_third"]
        )
    if "test_loss" in results:
        checks["test_finite"] = bool(np.isfinite(results["test_loss"]))
    if kernel is not None:
        # the gate noted a path (so it judged the kernel applicable), `auto`
        # took the kernel, and Mosaic compiled it into the program
        checks["kernel_compiled"] = (
            rec["kernel_paths"].get(kernel) == "pallas"
            and rec["tpu_custom_calls"] > 0
        )
    return finish(rec, checks)


def serve_phase(
    name: str, train_ckpt: Path, model: str, seed: int, extra: list[str]
) -> dict:
    """``--serve`` through ``entry.run`` over the thread transport, with
    every reply recorded at the router's door and checked against a direct
    ``model.apply`` on the restored variables."""
    import jax
    import jax.numpy as jnp

    from distributed_training_comparison_tpu.data.augment import normalize_images
    from distributed_training_comparison_tpu.data.cifar100 import (
        CIFAR100_MEAN,
        CIFAR100_STD,
    )
    from distributed_training_comparison_tpu.entry import run
    from distributed_training_comparison_tpu.models import get_model
    from distributed_training_comparison_tpu.serve.router import ServeRouter
    from distributed_training_comparison_tpu.train.checkpoint import (
        find_serving_checkpoint,
        load_eval_variables,
    )

    argv = [
        "--serve", "--model", model, "--amp", "--seed", str(seed),
        "--serve-transport", "thread", "--serve-requests", "48",
        "--serve-concurrency", "8", "--ckpt-path", str(train_ckpt), *extra,
    ]
    sent: list = []  # (image, future) for every request the router took
    submit = ServeRouter.submit

    def recording_submit(self, image, *a, **kw):
        fut = submit(self, image, *a, **kw)
        sent.append((np.asarray(image), fut))
        return fut

    ServeRouter.submit = recording_submit
    t0 = time.perf_counter()
    try:
        report = run("tpu", argv)
    finally:
        ServeRouter.submit = submit
    seconds = time.perf_counter() - t0

    # the reference: no engine, no buckets, no batcher
    ckpt_file = find_serving_checkpoint(train_ckpt)
    net = get_model(model, dtype=jnp.bfloat16)
    template = net.init(
        jax.random.key(0), jnp.zeros((1, 32, 32, 3), jnp.float32), train=False
    )
    variables, meta = load_eval_variables(
        ckpt_file,
        {"params": template["params"],
         "batch_stats": template.get("batch_stats", {})},
    )
    images = np.stack([img for img, _ in sent])
    replies = np.stack([np.asarray(fut.result(timeout=0)) for _, fut in sent])
    want = np.asarray(
        jax.jit(
            lambda v, x: net.apply(
                v,
                normalize_images(x, CIFAR100_MEAN, CIFAR100_STD, dtype=jnp.bfloat16),
                train=False,
            ).astype(jnp.float32)
        )(variables, images)
    )
    scale = float(np.abs(want).max())
    err = float(np.abs(replies - want).max())
    compiles, _ = run_events(train_ckpt)
    engine = report.get("engine", {})
    rec = {
        "phase": name,
        "argv": argv,
        "seconds": round(seconds, 2),
        "checkpoint": {"file": ckpt_file.name, **meta},
        **compile_summary(compiles),
        "report": {
            k: report.get(k)
            for k in ("offered", "completed", "shed", "expired", "failed",
                      "throughput_rps", "latency_ms")
        },
        "engine": engine,
        "replies": {
            "checked": len(sent), "max_abs_err": err, "logit_scale": scale,
            "argmax_agree": int((replies.argmax(1) == want.argmax(1)).sum()),
        },
    }
    checks = {
        "all_answered": report.get("completed") == report.get("offered") == len(sent)
        and not (report.get("failed") or report.get("shed") or report.get("expired")),
        "buckets_warmed": engine.get("compiles", 0) + engine.get("persisted_hits", 0)
        == len(engine.get("buckets", ())) > 0,
        "finite": bool(np.isfinite(replies).all()),
        # bf16 carries 8 bits of mantissa; a bucket of 8 and a batch of 48
        # tile differently, so allow a few ulps of the largest logit
        "replies_match": err <= 2e-2 * max(scale, 1.0),
    }
    return finish(rec, checks)


def one_chip(seed: int, on_tpu: bool) -> list[dict]:
    seed_args = ["--seed", str(seed)]
    if on_tpu:
        # 9216 train examples = 36 steps in three scanned dispatches of 12
        train = ["--model", "resnet18", "--batch-size", "256",
                 "--limit-examples", "10240", "--device-chunk-steps", "12"]
        kernel_models = {
            "vit_tiny_p2": (["--model", "vit_tiny", "--patch-size", "2",
                             "--batch-size", "256", "--limit-examples", "2560"],
                            "vit_block"),
            "vit_moe": (["--model", "vit_moe", "--batch-size", "256",
                         "--limit-examples", "2560"], "moe_ffn"),
            "vit_long": (["--model", "vit_long", "--image-size", "256",
                          "--batch-size", "8", "--limit-examples", "80"],
                         "attention"),
        }
        serve_model, serve_extra = "resnet18", []
    else:
        # rehearsal: the same phases through the same flags at sizes a CPU
        # compiles in seconds (the zoo's smallest model stands in for
        # ResNet-18, and `auto` composes off the TPU, so no kernel check)
        small = ["--batch-size", "8", "--limit-examples", "40"]
        train = ["--model", "vit_tiny", *small, "--device-chunk-steps", "2"]
        kernel_models = {
            "vit_tiny_p2": (["--model", "vit_tiny", "--patch-size", "2", *small],
                            None),
            "vit_moe": (["--model", "vit_moe", *small], None),
            "vit_long": (["--model", "vit_long", "--image-size", "32", *small],
                         None),
        }
        serve_model, serve_extra = "vit_tiny", ["--serve-buckets", "1,8"]
    phases = [
        guarded("train", train_phase,
                [*train, *RECIPE, *seed_args, "--contain-test"],
                must_fall=on_tpu),
    ]
    for name, (flags, kernel) in kernel_models.items():
        phases.append(
            guarded(name, train_phase, [*flags, *RECIPE, *seed_args],
                    kernel=kernel)
        )
    phases.append(
        guarded("serve", serve_phase, WORKDIR / "train", serve_model, seed,
                serve_extra)
    )
    return phases


def across_chips(seed: int, on_tpu: bool) -> list[dict]:
    """The same seed, model and global batch on a one-device mesh, on
    ``data=4`` and on ``data=2 x model=2``: trajectories compared at
    tests/test_tp.py's tolerances, shards shown to sit on distinct devices."""
    import jax

    from distributed_training_comparison_tpu import parallel
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.train import Trainer
    from distributed_training_comparison_tpu.utils import (
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()
    # The tolerances below are fp32 figures, so fp32 has to mean fp32: at the
    # TPU's default matmul precision an fp32 conv is a few bf16 passes whose
    # rounding depends on the per-device shapes — measured on a v5e, the
    # one-device and data=4 losses then differ by 7e-5 at step 0 with
    # identical parameters, which says nothing about the sharding.
    jax.config.update("jax_default_matmul_precision", "highest")
    # what parallel.make_mesh says about the branch that built each array
    mesh_lines: list[str] = []
    mesh_handler = logging.Handler(logging.INFO)
    mesh_handler.emit = lambda record: mesh_lines.append(record.getMessage())
    mesh_log = logging.getLogger("dtc_tpu.mesh")
    mesh_log.setLevel(logging.INFO)
    mesh_log.addHandler(mesh_handler)
    # fp32 like the CPU-mesh test whose tolerances these are; 4 steps
    model = "resnet18" if on_tpu else "vit_tiny"
    batch = "256" if on_tpu else "16"
    limit = "1152" if on_tpu else "72"
    base = [
        "--model", model, "--batch-size", batch, "--limit-examples", limit,
        *[a for a in RECIPE if a != "--amp"], "--seed", str(seed), *QUIET,
    ]
    legs = {
        "one_device": ["--num-devices", "1"],
        "data4": [],
        "data2_model2": ["--model-parallel", "2"],
    }
    out = []
    for name, extra in legs.items():
        hp = load_config("tpu", [*base, *extra, "--ckpt-path", str(WORKDIR / name)])
        mesh_lines.clear()
        t0 = time.perf_counter()
        trainer = Trainer(hp)  # what entry.run builds; kept to inspect it
        try:
            mesh = trainer.mesh
            leaves = jax.tree_util.tree_leaves(trainer.state.params)
            state_devices = set().union(*(x.sharding.device_set for x in leaves))
            split = [x for x in leaves if not x.sharding.is_fully_replicated]
            bx = parallel.shard_batch(
                np.zeros((hp.batch_size, 32, 32, 3), np.uint8), mesh
            )
            facts = {
                "mesh": dict(mesh.shape),
                "mesh_built_by": list(mesh_lines),
                "init_device": str(jax.local_devices()[0]),
                "state_devices": len(state_devices),
                "batch_shards": len({s.index for s in bx.addressable_shards}),
                "batch_devices": len({s.device for s in bx.addressable_shards}),
                "split_leaves": len(split),
                "shards_per_split_leaf": sorted(
                    {len({s.index for s in x.addressable_shards}) for x in split}
                ),
            }
            version = trainer.fit()
        finally:
            trainer.close()
        vdir = WORKDIR / name / f"version-{version}"
        compiles, _ = run_events(vdir)
        losses = step_losses(vdir)
        n = mesh.shape["data"] * mesh.shape["model"]
        checks = {
            "finite": bool(losses) and bool(np.isfinite(losses).all()),
            # the state was initialized on one device, then placed on all
            "state_on_every_mesh_device": facts["state_devices"] == n,
            "batch_on_distinct_devices": facts["batch_devices"] == n
            and facts["batch_shards"] == mesh.shape["data"],
            "tensor_parallel_leaves_split": (
                facts["split_leaves"] > 0
                and facts["shards_per_split_leaf"] == [mesh.shape["model"]]
            ) if mesh.shape["model"] > 1 else facts["split_leaves"] == 0,
        }
        out.append(finish(
            {"phase": name, "seconds": round(time.perf_counter() - t0, 2),
             **compile_summary(compiles), "losses": losses, **facts},
            checks,
        ))
    mesh_log.removeHandler(mesh_handler)

    ref, *others = (np.asarray(r["losses"]) for r in out)
    cmp = {"phase": "compare", "reference": out[0]["phase"],
           "matmul_precision": "highest"}
    checks = {}
    for rec, got in zip(out[1:], others):
        same_len = got.shape == ref.shape and ref.size > 0
        rel = (np.abs(got - ref) / np.abs(ref)).tolist() if same_len else None
        cmp[rec["phase"]] = {"rel_diff_per_step": rel}
        # tests/test_tp.py: step 0 to fp32 ulp, later steps within 2% (lr=0.1
        # SGD amplifies partitioned-reduction ordering differences)
        checks[rec["phase"]] = bool(
            same_len and rel[0] <= 1e-5 and max(rel) <= 2e-2
        )
    return [*out, finish(cmp, checks)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the path across chips and its comparison")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU walk-through of every phase (needs an "
                    "explicit JAX_PLATFORMS=cpu); always ends ok=false")
    args = ap.parse_args(argv)

    explicit_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if args.rehearse and not explicit_cpu:
        print("chip_smoke: --rehearse is honoured only under an explicit "
              "JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO))
    # fails here, before any output, where the script stands without the program
    import distributed_training_comparison_tpu  # noqa: F401
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU found (jax reports {device}); nothing ran",
              file=sys.stderr)
        return 2
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs exactly that many "
              f"devices, jax reports {device}", file=sys.stderr)
        return 2

    shutil.rmtree(WORKDIR, ignore_errors=True)
    try:
        phases = (
            one_chip(args.seed, on_tpu) if args.chips == 1
            else across_chips(args.seed, on_tpu)
        )
    finally:
        shutil.rmtree(WORKDIR, ignore_errors=True)
    ok = on_tpu and all(p["ok"] for p in phases)
    failed = [p["phase"] for p in phases if not p["ok"]]
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
