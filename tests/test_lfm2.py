"""LFM2-MoE against its plain reference (``benchmark/reference/
lfm2_24b_a2b_ep8.py``) at test widths on the CPU: each mixer, the whole
model's loss and every gradient, the benchmark's first-step comparison in
float32 and bf16, and a ``Trainer.fit()`` on tokens that saves and resumes.
The expert layer's own tests are in ``test_moe.py``, AdamW's in
``test_optim.py``."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.models import get_model, lfm2
from distributed_training_comparison_tpu.train import Trainer

from lfm2_reference import BENCH, reference

CUT = "layers=5,dense=1,experts=4,first_expert=4,vocab=256"
ARCH = {"first_expert": 4}
CONFIG_FILE = BENCH / "configs" / "lfm2_24b_a2b_ep8.json"


def plain(tree):
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


@pytest.fixture(scope="module")
def tiny():
    model = get_model("lfm2_tiny", model_cut=CUT)
    tokens = jax.random.randint(jax.random.key(1), (2, 48), 0, 256)
    variables = model.init(jax.random.key(0), tokens)
    return model, variables, tokens


def test_published_config_is_the_catalog_row_and_the_cut_keeps_a_period():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():  # the driver's catalog, where it is installed
        row = next(
            json.loads(line) for line in catalog.read_text().splitlines()
            if json.loads(line)["name"] == "LFM2-24B-A2B"
        )
        assert row["config"] == lfm2.LFM2_24B_A2B
    cut = lfm2.cut_config(lfm2.LFM2_24B_A2B, lfm2.parse_cut(
        "layers=5,dense=1,experts=8,first_expert=0,vocab=8192"
    ))
    assert cut["layer_types"] == ["conv", "full_attention", "conv", "conv", "conv"]
    assert (cut["num_dense_layers"], cut["num_experts_held"]) == (1, 8)
    assert cut["num_experts"] == 64 and cut["hidden_size"] == 2048  # no width
    with pytest.raises(ValueError):
        lfm2.parse_cut("hidden=64")
    with pytest.raises(ValueError):
        lfm2.cut_config(lfm2.LFM2_24B_A2B, {"experts": 8, "first_expert": 60})


def test_short_conv_matches_reference_and_is_causal():
    layer = lfm2.ShortConv(dim=32, kernel=3)
    h = jax.random.normal(jax.random.key(2), (2, 20, 32))
    variables = layer.init(jax.random.key(3), h)
    got = layer.apply(variables, h)
    np.testing.assert_allclose(
        got, reference.short_conv(h, variables["params"]), rtol=1e-5, atol=1e-7
    )
    later = h.at[:, 10:].add(1.0)  # the past does not see the future
    np.testing.assert_allclose(
        layer.apply(variables, later)[:, :10], got[:, :10], rtol=1e-6
    )


def test_grouped_query_attention_matches_reference():
    layer = lfm2.GQAttention(dim=64, heads=4, kv_heads=2, eps=1e-5, theta=1e6)
    h = jax.random.normal(jax.random.key(4), (2, 24, 64))
    variables = layer.init(jax.random.key(5), h)
    # norms away from their initial ones, so the test sees them
    variables = jax.tree_util.tree_map(
        lambda a: a * 1.3 if a.ndim == 1 else a, variables
    )
    want = reference.attention(h, variables["params"], reference.ARCH)
    np.testing.assert_allclose(layer.apply(variables, h), want, rtol=2e-4, atol=2e-6)


def test_whole_model_loss_and_every_gradient_match_reference(tiny):
    model, variables, tokens = tiny
    labels = jnp.roll(tokens, -1, axis=1)
    stats = variables["batch_stats"]

    def program(p):
        logits = model.apply({"params": p, "batch_stats": stats}, tokens)
        return reference.next_token_loss(logits, labels)

    def plain_reference(p):
        logits, _ = reference.forward(p, stats, tokens, ARCH)
        return reference.next_token_loss(logits, labels)

    got, got_grads = jax.value_and_grad(program)(variables["params"])
    want, want_grads = jax.value_and_grad(plain_reference)(variables["params"])
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, g in jax.tree_util.tree_leaves_with_path(got_grads):
        r = flat_want[path]
        assert float(jnp.abs(r).max()) > 0, path  # every leaf is reached
        np.testing.assert_allclose(
            g, r, rtol=2e-3, atol=2e-5 * float(jnp.abs(r).max()),
            err_msg=jax.tree_util.keystr(path),
        )


def test_remat_changes_no_value(tiny):
    model, variables, tokens = tiny
    again = get_model("lfm2_tiny", model_cut=CUT, remat=True)
    np.testing.assert_allclose(
        again.apply(variables, tokens), model.apply(variables, tokens), rtol=1e-6
    )


# ---------------------------------------------------------------- trainer

ARGV = [
    "--synthetic-data", "--no-progress", "--num-devices", "1",
    "--model", "lfm2_tiny", "--model-cut", CUT, "--seq-len", "32",
    "--batch-size", "4", "--limit-examples", "80", "--optimizer", "adamw",
    "--lr", "3e-3", "--weight-decay", "0.1", "--lr-decay-gamma", "1.0",
]


def _tiny_compare_config():
    """The cell's own ``compare`` group (its recipe and tolerances) at test
    sizes; the learning rate is the test run's."""
    held = json.loads(CONFIG_FILE.read_text())
    compare = held["compare"]
    return {"compare": {
        **compare, "batch": 2, "tokens": 32, "vocab": 256,
        "recipe": {**compare["recipe"], "lr": 3e-3, "arch": ARCH},
    }}


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_first_step_comparison_holds_each_precision_to_its_own(tmp_path, precision):
    """``harness/compare.py first_step`` on the program's own train step:
    float32 passes the float32 tolerance; bf16 passes its own and fails
    float32's (a bf16-for-float32 swap is not ``correct``)."""
    from harness import compare

    hp = load_config("tpu", [
        *ARGV, "--ckpt-path", str(tmp_path), "--precision", precision,
    ])
    trainer = Trainer(hp)
    try:
        config = _tiny_compare_config()
        out = compare.first_step(
            trainer, config, 2**31 + 27, BENCH / "reference" / "lfm2_24b_a2b_ep8.py"
        )
    finally:
        trainer.close()
    assert out["ok"], out
    assert set(out["errors"]) == set(out["tolerance"])
    strict = config["compare"]["tolerance"]["fp32"]
    fails_float32 = any(out["errors"][k] > strict[k] for k in strict)
    assert fails_float32 == (precision == "bf16"), out["errors"]


def test_trainer_fits_tokens_saves_and_resumes(tmp_path):
    events = []
    hp = load_config("tpu", [*ARGV, "--ckpt-path", str(tmp_path), "--epoch", "2"])
    trainer = Trainer(hp)
    trainer.bus.subscribe(events.append)
    version = trainer.fit()
    results = trainer.test()
    trainer.close()
    ends = [e["payload"] for e in events if e.get("kind") == "epoch_end"]
    assert len(ends) == 2 and ends[1]["train_loss"] < ends[0]["train_loss"]
    assert ends[1]["val_loss"] < np.log(256)  # below an untrained model's
    assert 0.0 < ends[1]["val_acc"] <= 100.0  # next-token top-1, per token
    assert 0.0 <= results["test_top1"] <= results["test_top5"] <= 100.0
    compiled = {
        k: v for e in events if e.get("kind") == "compile"
        for k, v in (e["payload"].get("kernel_paths") or {}).items()
    }
    assert compiled == {"attention": "composed", "moe_gmm": "ragged_dot"}
    counted = [
        e["payload"]["metrics"] for e in events if e.get("kind") == "metrics"
        and "moe/rows" in e["payload"]["metrics"]
    ]
    # 18 steps x 4 layers x 128 tokens x 4 selections, a quarter held if even
    assert counted[0]["moe/rows"]["n"] > 18 * 4 * 128 * 4 / 8
    assert counted[0]["moe/load_max_over_mean"]["value"] >= 1.0
    # the share of layer calls that overflowed the held prefix (here the
    # router sends the four held experts over twice their even share in
    # about half of them, so both sizes train)
    assert 0.0 <= counted[0]["moe/full_buffer_share"]["value"] <= 1.0
    vdir = tmp_path / f"version-{version}"
    assert (vdir / "last.ckpt").exists() and list(vdir.glob("best_model_*.ckpt"))

    hp = load_config("tpu", [
        *ARGV, "--ckpt-path", str(tmp_path / "again"), "--epoch", "3",
        "--resume", str(vdir / "last.ckpt"),
    ])
    resumed = Trainer(hp)
    assert resumed.start_epoch == 2
    assert int(resumed.state.step) == int(trainer.state.step) == 36
    np.testing.assert_array_equal(
        plain(resumed.state.batch_stats)["layers_1"]["moe"]["expert_bias"],
        plain(trainer.state.batch_stats)["layers_1"]["moe"]["expert_bias"],
    )
    resumed.fit()
    resumed.close()
