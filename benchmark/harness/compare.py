"""The comparison that decides part (a) of ``correct``: one SGD step of the
step function the Trainer dispatches against the configuration's plain
reference, on the chip, at the cell's full width, before the window.

The step is ``train/step.py``'s ``make_train_step`` built from the
Trainer's own mesh, precision, shardings and initial state — the same
``_make_step_core`` that the scanned runners of ``fit()`` trace — with
augmentation off so that both sides see the same pixels.  The batch is
``compare.batch`` seeded images (a sub-batch: the float32 reference of a
whole 4,096-image batch does not fit beside the program, and its peak would
be read as the program's), sharded over the mesh like a training batch.

No step function of the program returns logits, so the logits are compared
through what they determine: the loss, the gradient's global norm, every
parameter after the update and every normalisation statistic.

The update of a randomly initialised BatchNorm ResNet is badly conditioned:
float32 rounding alone moves it by 0.1% (the reference in float32 against
itself in float64) and bf16 by a quarter, in a random direction, while the
loss moves by 2e-4.  So the update is held to two numbers: its length along
the reference's (a wrong learning rate, momentum or decay shows there, and
rounding does not), and its relative distance (loose in bf16).
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from . import load_module


def _dot(a, b) -> float:
    """<a, b> over two trees of arrays, in float64."""
    import jax

    return sum(
        float(np.vdot(np.asarray(x, np.float64), np.asarray(y, np.float64)))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


def rel_l2(a, b) -> float:
    """||a - b|| / ||b|| over two trees of arrays (b the reference)."""
    import jax

    diff = jax.tree_util.tree_map(
        lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64), a, b
    )
    den = _dot(b, b)
    return float(np.sqrt(_dot(diff, diff) / den)) if den else float("inf")


def projection(a, b) -> float:
    """<a, b> / <b, b>: the length of ``a`` along ``b`` in units of ``b``."""
    den = _dot(b, b)
    return _dot(a, b) / den if den else float("nan")


def first_step(trainer, config: dict, seed: int, reference_path: Path) -> dict:
    """Run both sides on one seeded batch; returns the measured errors."""
    import jax

    from distributed_training_comparison_tpu.train.step import make_train_step

    from reference.common import reference_step

    spec = config["compare"]
    n = int(spec["batch"])
    size = int(config.get("image_size", 32))
    rng = np.random.default_rng(seed)
    images = rng.integers(0, 256, (n, size, size, 3), dtype=np.uint8)
    labels = rng.integers(0, int(config["num_classes"]), (n,), dtype=np.int32)

    state = trainer.state
    to_host = lambda tree: jax.tree_util.tree_map(np.asarray, jax.device_get(tree))  # noqa: E731
    params0, stats0 = to_host(state.params), to_host(state.batch_stats)

    step = make_train_step(
        trainer.mesh, precision=trainer.precision, augment=False,
        state_sharding=trainer.state_sharding, grad_accum=trainer.grad_accum,
        fwd_bwd=trainer.train_fwd_bwd, comms=trainer.comms,
        monitor=trainer.compile_monitor,
    )
    new_state, metrics = step(state, images, labels, jax.random.key(seed))
    got = {
        "loss": float(metrics["loss"]),
        "grad_norm": float(metrics["grad_norm"]),
        "params": to_host(new_state.params),
        "batch_stats": to_host(new_state.batch_stats),
    }
    del new_state

    module = load_module(reference_path)
    recipe = dict(spec["recipe"])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(
            lambda p, s, x, y: reference_step(module.forward, p, s, x, y, recipe)
        )(params0, stats0, images, labels)
    want = to_host({k: v for k, v in want.items() if k != "logits"})

    delta = lambda new: jax.tree_util.tree_map(lambda a, b: a - b, new, params0)  # noqa: E731
    moved, should_move = delta(got["params"]), delta(want["params"])
    errors = {
        "loss_rel": abs(got["loss"] - float(want["loss"])) / abs(float(want["loss"])),
        "grad_norm_rel": abs(got["grad_norm"] - float(want["grad_norm"]))
        / abs(float(want["grad_norm"])),
        # the update, not the parameters: p1 - p0 is 1e-3 of p0, and an
        # error in it would vanish in a comparison of p1 itself.  Its
        # length along the reference's update catches a wrong factor
        # (learning rate, momentum, decay) that rounding noise would hide
        "update_scale_err": abs(projection(moved, should_move) - 1.0),
        "update_rel_l2": rel_l2(moved, should_move),
    }
    if jax.tree_util.tree_leaves(stats0):
        errors["stats_rel_l2"] = rel_l2(got["batch_stats"], want["batch_stats"])
    tolerance = spec["tolerance"][trainer.precision]
    return {
        "batch": n,
        "precision": trainer.precision,
        "loss": got["loss"],
        "reference_loss": float(want["loss"]),
        "errors": errors,
        "tolerance": tolerance,
        "ok": all(
            np.isfinite(v) and v <= tolerance[k] for k, v in errors.items()
        ),
    }
