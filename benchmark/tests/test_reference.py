"""Each plain reference against the program's own model, at a tiny size on
the CPU: same parameters in, the loss, the gradient norm, the updated
parameters and statistics out, through ``harness.compare`` — the function
that decides part (a) of ``correct`` on the chip.

In float32 the two must agree to rounding; in bf16 (what every cell states)
within the configuration's bf16 tolerance, and *not* within its float32
tolerance — the tolerance is tight enough that a float32-stated step
computed in bf16 would fail.
"""

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import compare

BENCH = Path(__file__).resolve().parents[1]


def fake_trainer(model_name, model_kw, precision, lr):
    """What ``compare.first_step`` reads from a Trainer, without building
    one: mesh, precision, state and its shardings."""
    from distributed_training_comparison_tpu.models import get_model
    from distributed_training_comparison_tpu.parallel import (
        make_mesh,
        state_shardings,
    )
    from distributed_training_comparison_tpu.train.optim import (
        configure_optimizers,
    )
    from distributed_training_comparison_tpu.train.state import (
        create_train_state,
    )

    dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
    model = get_model(model_name, dtype=dtype, norm_dtype=jnp.float32, **model_kw)
    hp = argparse.Namespace(
        lr=lr, lr_decay_step_size=25, lr_decay_gamma=0.1, weight_decay=1e-4
    )
    tx, _ = configure_optimizers(hp, 10)
    state = create_train_state(model, jax.random.key(0), tx)
    mesh = make_mesh(1)
    return argparse.Namespace(
        mesh=mesh, precision=precision, state=state,
        state_sharding=state_shardings(mesh, state), grad_accum=1,
        train_fwd_bwd=None, comms=None, compile_monitor=None,
    )


CASES = [
    ("resnet18_cifar100", "resnet18", {}),
    ("vit_small_cifar100_p2", "vit_small", {"patch": 2, "image_size": 32}),
]


@pytest.mark.parametrize("config_name,model_name,model_kw", CASES)
@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_reference_matches_program(config_name, model_name, model_kw, precision):
    config = json.loads((BENCH / "configs" / f"{config_name}.json").read_text())
    config["compare"]["batch"] = 8
    trainer = fake_trainer(
        model_name, model_kw, precision, config["compare"]["recipe"]["lr"]
    )
    with jax.default_matmul_precision("highest"):
        out = compare.first_step(trainer, config, 0, BENCH / config["reference"])
    assert out["ok"], out
    if precision == "bf16":
        fp32 = config["compare"]["tolerance"]["fp32"]
        assert any(out["errors"][k] > fp32[k] for k in out["errors"]), (
            "a bf16 step passes the float32 tolerance: it is too loose", out
        )


def test_parameter_counts_are_the_published_ones():
    for config_name, model_name, model_kw in CASES:
        config = json.loads((BENCH / "configs" / f"{config_name}.json").read_text())
        from distributed_training_comparison_tpu.models import get_model

        model = get_model(model_name, **model_kw)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)), train=False)
        )
        n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes["params"]))
        assert n == config["parameters"]
