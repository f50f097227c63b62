"""Optimizer parity tests: optax chain vs torch SGD semantics.

The SURVEY.md §7 risk list calls out exact torch SGD(nesterov, wd-coupled)
+ StepLR parity as accuracy-critical; these tests verify it numerically
against torch (CPU build available in the image) rather than by reading
formulas.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
import torch

from distributed_training_comparison_tpu.train.optim import (
    configure_optimizers,
    step_lr_schedule,
)


class HP:
    lr = 0.1
    weight_decay = 1e-4
    lr_decay_step_size = 2
    lr_decay_gamma = 0.1


def test_step_lr_staircase():
    sched = step_lr_schedule(0.1, step_size_epochs=25, gamma=0.1, steps_per_epoch=100)
    assert float(sched(0)) == pytest.approx(0.1)
    assert float(sched(2499)) == pytest.approx(0.1)
    assert float(sched(2500)) == pytest.approx(0.01)
    assert float(sched(4999)) == pytest.approx(0.01)
    assert float(sched(5000)) == pytest.approx(0.001)


def test_sgd_matches_torch_nesterov_wd():
    """Run 7 identical steps in torch and optax from the same init/grads and
    compare parameters (covers momentum warmup + an LR decay boundary)."""
    rng = np.random.default_rng(0)
    w0 = rng.normal(size=(5, 3)).astype(np.float32)
    grads = [rng.normal(size=(5, 3)).astype(np.float32) for _ in range(7)]

    # torch: StepLR steps per epoch; emulate 1 epoch == 2 optimizer steps
    tw = torch.nn.Parameter(torch.tensor(w0.copy()))
    opt = torch.optim.SGD(
        [tw], lr=HP.lr, momentum=0.9, nesterov=True, weight_decay=HP.weight_decay
    )
    sched = torch.optim.lr_scheduler.StepLR(
        opt, step_size=HP.lr_decay_step_size, gamma=HP.lr_decay_gamma
    )
    steps_per_epoch = 2
    for i, g in enumerate(grads):
        opt.zero_grad()
        tw.grad = torch.tensor(g.copy())
        opt.step()
        if (i + 1) % steps_per_epoch == 0:
            sched.step()

    # ours: schedule over global steps with the same steps_per_epoch
    tx, _ = configure_optimizers(HP, steps_per_epoch=steps_per_epoch)
    params = {"w": jnp.asarray(w0)}
    opt_state = tx.init(params)
    for g in grads:
        updates, opt_state = tx.update({"w": jnp.asarray(g)}, opt_state, params)
        params = optax.apply_updates(params, updates)

    np.testing.assert_allclose(
        np.asarray(params["w"]), tw.detach().numpy(), rtol=1e-5, atol=1e-6
    )


def test_weight_decay_applies_to_all_params():
    """torch SGD decays every param incl. BN scale/bias; the chain must not
    mask anything."""
    tx, _ = configure_optimizers(HP, steps_per_epoch=1)
    params = {"conv": jnp.ones((2, 2)), "bn_scale": jnp.ones((4,))}
    zero_grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    updates, _ = tx.update(zero_grads, tx.init(params), params)
    # with zero grads, first-step nesterov update = -lr * (1+m) * wd * param
    for leaf in jax.tree_util.tree_leaves(updates):
        np.testing.assert_allclose(
            np.asarray(leaf), -HP.lr * 1.9 * HP.weight_decay, rtol=1e-5
        )


def test_adamw_step_matches_optax_and_the_plain_reference():
    """``--optimizer adamw``: one step from a fresh state against
    ``optax.adamw`` spelled out and against the plain reference's own
    formula (``benchmark/reference/lfm2_24b_a2b_ep8.py step``'s): beta 0.9
    / 0.95, eps 1e-8, decoupled decay on matrices only — a norm's scale is
    not decayed — under a constant learning rate."""
    from distributed_training_comparison_tpu.train import optim

    class AdamHP(HP):
        optimizer = "adamw"
        lr = 3e-4
        weight_decay = 0.1
        lr_decay_gamma = 1.0

    params = {
        "kernel": jax.random.normal(jax.random.key(0), (8, 4)),
        "experts": jax.random.normal(jax.random.key(1), (2, 8, 4)),
        "scale": jnp.ones((4,)) * 1.5,
    }
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.key(2), p.shape) * 1e-3, params
    )
    tx, schedule = configure_optimizers(AdamHP, steps_per_epoch=10)
    assert float(schedule(0)) == float(schedule(1000)) == pytest.approx(3e-4)
    updates, _ = tx.update(grads, tx.init(params), params)
    got = optax.apply_updates(params, updates)

    plain = optax.adamw(
        3e-4, b1=optim.ADAMW_B1, b2=optim.ADAMW_B2, eps=optim.ADAMW_EPS,
        weight_decay=0.1,
        mask={"kernel": True, "experts": True, "scale": False},
    )
    updates, _ = plain.update(grads, plain.init(params), params)
    want = optax.apply_updates(params, updates)

    def by_hand(p, g):  # the first step: m_hat = g, v_hat = g^2
        decay = 0.1 * p if p.ndim >= 2 else 0.0
        return p - 3e-4 * (g / (jnp.sqrt(g * g) + 1e-8) + decay)

    for name in params:
        np.testing.assert_allclose(got[name], want[name], rtol=1e-6)
        np.testing.assert_allclose(
            got[name], by_hand(params[name], grads[name]), rtol=1e-5
        )
    # the scale moved by the Adam step alone: lr, whatever its size
    np.testing.assert_allclose(
        np.abs(np.asarray(got["scale"] - params["scale"])), 3e-4, rtol=1e-3
    )


def test_sgd_is_still_the_default_optimizer():
    tx, _ = configure_optimizers(HP, steps_per_epoch=10)
    params = {"w": jnp.ones((3,))}
    updates, _ = tx.update({"w": jnp.ones((3,))}, tx.init(params), params)
    # torch SGD, nesterov, coupled decay: -(lr) * (d + 0.9 d), d = g + wd p
    np.testing.assert_allclose(updates["w"], -0.1 * 1.9 * (1 + 1e-4), rtol=1e-6)
