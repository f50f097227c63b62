"""50-epoch torch-vs-flax convergence agreement on identical data.

The ≥71% CIFAR-100 north star (``/root/reference/README.md:47-51``) cannot
run offline (no dataset, no egress).  This script is the strongest
available stand-in (VERDICT r3 item 5): it trains the
reference-architecture torch net under the reference recipe
(``/root/reference/src/single/trainer.py:78-94``: SGD momentum 0.9
nesterov, wd 1e-4, StepLR(25, 0.1), pad-4 crop + hflip) and this
framework's flax zoo through the real ``Trainer`` — on byte-identical
synthetic splits — for the full 50-epoch horizon, then compares final
best-checkpoint test metrics.  Agreement to noise de-risks exactly the
pieces the blocked real-data run would have proven: optimizer/scheduler
semantics, BN running-statistics behavior, and the augment/normalize
pipeline, all at the 50-epoch scale SURVEY §7 flags.

The torch net/recipe mirror the reference spec but the data is synthetic
(class-anchor images, ``data/synthetic.py``) — raise ``--noise`` so final
accuracy lands mid-range; a saturated 100%-vs-100% comparison proves
nothing.

Usage (full run, flax on the ambient backend, torch on CPU):
    python tools/convergence_parity.py --epochs 50 --limit-examples 10000 \
        --noise 0.45 --out /tmp/convergence_parity.json
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from distributed_training_comparison_tpu.config import load_config  # noqa: E402
from distributed_training_comparison_tpu.data.cifar100 import (  # noqa: E402
    CIFAR100_MEAN,
    CIFAR100_STD,
)
from distributed_training_comparison_tpu.data.loader import get_datasets  # noqa: E402


def _hparams(args, ckpt_path: str):
    return load_config(
        "tpu",
        argv=[
            "--synthetic-data",
            "--synthetic-noise", str(args.noise),
            "--limit-examples", str(args.limit_examples),
            "--epoch", str(args.epochs),
            "--batch-size", str(args.batch_size),
            "--model", args.model,
            "--seed", str(args.seed),
            # the reference's published recipe (run_single.sh) — NOT the
            # flag defaults: decay at 25 epochs is what pulls a 50-epoch
            # run out of the chaotic lr-0.1 regime so final metrics are
            # comparable to noise at all
            "--lr", "0.1",
            "--lr-decay-step-size", "25",
            "--lr-decay-gamma", "0.1",
            "--weight-decay", "0.0001",
            "--ckpt-path", ckpt_path,
        ],
    )


def run_flax(args, workdir: str) -> dict:
    """The product path: real Trainer fit() + best-checkpoint test()."""
    from distributed_training_comparison_tpu.train import Trainer
    from distributed_training_comparison_tpu.utils import (
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()
    hp = _hparams(args, workdir)
    trainer = Trainer(hp)
    t0 = time.perf_counter()
    trainer.fit()
    out = trainer.test()  # loads the best-val-acc checkpoint, like the ref
    out = {k: float(v) for k, v in out.items()}
    out["train_seconds"] = round(time.perf_counter() - t0, 1)
    trainer.close()
    return out


# ----------------------------------------------------------------- torch side


def _torch_ref_module():
    """The reference-architecture torch net lives with the parity tests
    (state_dict naming IS the parity surface); load it from there rather
    than duplicating 70 lines of reference-mirroring code."""
    spec = importlib.util.spec_from_file_location(
        "torch_parity_fixture", REPO / "tests" / "test_torch_parity.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _normalize_np(images_u8: np.ndarray) -> np.ndarray:
    """uint8 NHWC → normalized fp32 NCHW (torchvision ToTensor+Normalize)."""
    mean = np.asarray(CIFAR100_MEAN, np.float32) * 255.0
    std = np.asarray(CIFAR100_STD, np.float32) * 255.0
    x = (images_u8.astype(np.float32) - mean) / std
    return np.transpose(x, (0, 3, 1, 2)).copy()


def _augment_np(images_u8: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """Pad-4 zero crop + hflip, the reference's torchvision train transform
    (``src/single/dataset.py:55-62``) in vectorized numpy."""
    n, h, w, _ = images_u8.shape
    pad = 4
    padded = np.pad(
        images_u8, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="constant"
    )
    out = np.empty_like(images_u8)
    offs = rng.integers(0, 2 * pad + 1, size=(n, 2))
    flips = rng.random(n) < 0.5
    for i in range(n):  # host loop, torch side only (the ref augments per
        r, c = offs[i]  # sample on the host too)
        crop = padded[i, r : r + h, c : c + w]
        out[i] = crop[:, ::-1] if flips[i] else crop
    return out


def _torch_eval(tmodel, images_u8, labels, batch_size: int) -> dict:
    import torch
    import torch.nn.functional as F

    tmodel.eval()
    loss_sum = top1 = top5 = 0
    with torch.no_grad():
        for s in range(0, len(images_u8), batch_size):
            x = torch.from_numpy(_normalize_np(images_u8[s : s + batch_size]))
            y = torch.from_numpy(labels[s : s + batch_size].astype(np.int64))
            logits = tmodel(x)
            loss_sum += float(
                F.cross_entropy(logits, y, reduction="sum").detach()
            )
            top = logits.topk(5, dim=1).indices
            top1 += int((top[:, 0] == y).sum())
            top5 += int((top == y[:, None]).any(dim=1).sum())
    n = len(images_u8)
    return {
        "test_loss": loss_sum / n,
        "test_top1": 100.0 * top1 / n,
        "test_top5": 100.0 * top5 / n,
    }


def run_flax_torch_init(args) -> dict:
    """Flax training started from the torch net's NATIVE init (ported via
    ``models/torch_port.py``): the controlled experiment isolating the
    initialization scheme.  Measured at the committed config: this lands
    within noise of the torch run (38.05% vs 37.93% top-1), while flax's
    own variance-scaling init lands ~9 points higher — i.e. the
    cross-framework gap is the init, not the training math."""
    import jax
    import jax.numpy as jnp
    import torch

    from distributed_training_comparison_tpu import models, parallel
    from distributed_training_comparison_tpu.models.torch_port import (
        from_torch_resnet,
    )
    from distributed_training_comparison_tpu.train import (
        configure_optimizers,
        create_train_state,
        make_device_chunk_runner,
        make_eval_runner,
    )
    from distributed_training_comparison_tpu.utils import (
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()
    mod = _torch_ref_module()
    hp = _hparams(args, ckpt_path="/tmp/unused")
    train, _val, test = get_datasets(hp)

    torch.manual_seed(args.seed)
    block, depths = mod._TORCH_ZOO[args.model]
    tnet = mod._TorchCifarResNet(block, depths, num_classes=100)
    sd = {k: v.detach().cpu().numpy() for k, v in tnet.state_dict().items()}
    fmodel = models.get_model(args.model)
    variables = fmodel.init(
        jax.random.key(0), jnp.zeros((1, 32, 32, 3), jnp.float32), train=False
    )
    ported = from_torch_resnet(sd, variables)

    mesh = parallel.make_mesh(backend="tpu")
    tx, _ = configure_optimizers(hp, steps_per_epoch=len(train) // hp.batch_size)
    state = create_train_state(fmodel, jax.random.key(0), tx)
    state = state.replace(
        params=jax.tree_util.tree_map(jnp.asarray, ported["params"]),
        batch_stats=jax.tree_util.tree_map(jnp.asarray, ported["batch_stats"]),
    )
    repl = parallel.replicated_sharding(mesh)
    state = jax.device_put(state, repl)
    di = jax.device_put(jnp.asarray(train.images), repl)
    dl = jax.device_put(jnp.asarray(train.labels), repl)
    # one dispatch an epoch, as Trainer's device mode runs by default
    runner = make_device_chunk_runner(
        mesh, hp.batch_size, len(train) // hp.batch_size,
        precision="fp32", augment=True,
    )
    key = jax.random.key(hp.seed)
    t0 = time.perf_counter()
    for e in range(args.epochs):
        state, stacked = runner(state, di, dl, key, jnp.asarray(e), jnp.asarray(0))
    float(stacked["loss"][-1])  # sync

    ev = make_eval_runner(mesh, hp.batch_size, precision="fp32")
    n = len(test)
    t = ev(
        state,
        jax.device_put(jnp.asarray(test.images), repl),
        jax.device_put(jnp.asarray(test.labels), repl),
        jax.device_put(jnp.ones((n,), jnp.float32), repl),
    )
    cnt = float(t["count"])
    return {
        "test_loss": float(t["loss_sum"]) / cnt,
        "test_top1": 100.0 * float(t["top1_count"]) / cnt,
        "test_top5": 100.0 * float(t["top5_count"]) / cnt,
        "train_seconds": round(time.perf_counter() - t0, 1),
        "note": "final-epoch model (no best-val selection); torch-native init",
    }


def run_torch(args, log=print) -> dict:
    """Reference net + reference recipe on the SAME splits the Trainer saw
    (the loader derives every split deterministically from the seed)."""
    import torch
    import torch.nn.functional as F

    mod = _torch_ref_module()
    hp = _hparams(args, ckpt_path="/tmp/unused")
    train, val, test = get_datasets(hp)

    torch.manual_seed(args.seed)
    block, depths = mod._TORCH_ZOO[args.model]
    tmodel = mod._TorchCifarResNet(block, depths, num_classes=100)
    opt = torch.optim.SGD(
        tmodel.parameters(), lr=hp.lr, momentum=0.9, nesterov=True,
        weight_decay=hp.weight_decay,
    )
    sched = torch.optim.lr_scheduler.StepLR(
        opt, step_size=hp.lr_decay_step_size, gamma=hp.lr_decay_gamma
    )

    rng = np.random.default_rng(args.seed)
    bs = args.batch_size
    steps = len(train) // bs
    best_acc, best_sd = -1.0, None
    t0 = time.perf_counter()
    for epoch in range(args.epochs):
        tmodel.train()
        perm = rng.permutation(len(train))
        aug = _augment_np(train.images[perm], rng)
        lab = train.labels[perm]
        run_loss = 0.0
        for s in range(steps):
            x = torch.from_numpy(_normalize_np(aug[s * bs : (s + 1) * bs]))
            y = torch.from_numpy(
                lab[s * bs : (s + 1) * bs].astype(np.int64)
            )
            opt.zero_grad()
            loss = F.cross_entropy(tmodel(x), y)
            loss.backward()
            opt.step()
            run_loss += float(loss.detach())
        sched.step()
        val_metrics = _torch_eval(tmodel, val.images, val.labels, bs)
        if val_metrics["test_top1"] > best_acc:  # best-val ckpt, like
            best_acc = val_metrics["test_top1"]  # the reference's save rule
            best_sd = {
                k: v.detach().clone() for k, v in tmodel.state_dict().items()
            }
        log(
            f"[torch] epoch {epoch}: train loss {run_loss / steps:.4f}, "
            f"val acc {val_metrics['test_top1']:.2f}%, "
            f"lr {opt.param_groups[0]['lr']:.4f}",
            file=sys.stderr,
        )
    tmodel.load_state_dict(best_sd)
    out = _torch_eval(tmodel, test.images, test.labels, bs)
    out["best_val_acc"] = best_acc
    out["train_seconds"] = round(time.perf_counter() - t0, 1)
    return out


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="resnet18")
    p.add_argument("--epochs", type=int, default=50)
    p.add_argument("--limit-examples", type=int, default=10_000)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--noise", type=float, default=0.45)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--skip-torch", action="store_true")
    p.add_argument("--skip-flax", action="store_true")
    p.add_argument(
        "--flax-torch-init", action="store_true",
        help="also train flax FROM the torch net's native init (isolates "
        "the init scheme; see run_flax_torch_init)",
    )
    p.add_argument("--workdir", default="/tmp/convergence_parity_ckpt")
    p.add_argument("--out", default="")
    args = p.parse_args(argv)

    result: dict = {
        "config": {
            "model": args.model, "epochs": args.epochs,
            "train_examples": args.limit_examples, "batch_size": args.batch_size,
            "noise": args.noise, "seed": args.seed,
        }
    }
    if not args.skip_flax:
        result["flax"] = run_flax(args, args.workdir)
        print(f"[flax] {result['flax']}", file=sys.stderr)
    if args.flax_torch_init:
        result["flax_torch_init"] = run_flax_torch_init(args)
        print(f"[flax_torch_init] {result['flax_torch_init']}", file=sys.stderr)
    if not args.skip_torch:
        result["torch"] = run_torch(args)
        print(f"[torch] {result['torch']}", file=sys.stderr)
    if "flax" in result and "torch" in result:
        result["delta"] = {
            k: round(result["flax"][k] - result["torch"][k], 4)
            for k in ("test_loss", "test_top1", "test_top5")
        }
    print(json.dumps(result))
    if args.out:
        Path(args.out).write_text(json.dumps(result, indent=2))
    return result


if __name__ == "__main__":
    main()
