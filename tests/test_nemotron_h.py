"""Nemotron-H (one mixer a layer: Mamba-2, squared-ReLU experts beside a
shared expert, attention without positions) against its plain reference
(``benchmark/reference/nemotron3_nano_30b_a3b_ep16.py``) at test widths on
the CPU: each mixer, the whole model's loss and every gradient, the
benchmark's first-step comparison in float32 and bf16, the sixteen shares of
an expert layer, a ``Trainer.fit()`` with its gauge and kernel paths, and
the configuration's files against the published config.  The scan's own
tests are in ``test_ops.py``, the expert pass's in ``test_moe.py``, the
lowered step program's scopes in ``test_scopes.py``."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.models import get_model, nemotron_h
from distributed_training_comparison_tpu.models.moe import TopKMoE
from distributed_training_comparison_tpu.models.token_parts import cut_config, parse_cut
from distributed_training_comparison_tpu.train import Trainer

from lfm2_reference import BENCH, ROOT, load

REFERENCE_FILE = BENCH / "reference" / "nemotron3_nano_30b_a3b_ep16.py"
reference = load(REFERENCE_FILE)

CUT = "layers=7,experts=4,first_expert=4,vocab=256"
TINY = nemotron_h.NEMOTRON_H_TINY
ARCH = {
    "first_expert": 4, "num_experts_per_tok": TINY["num_experts_per_tok"],
    "n_groups": TINY["n_groups"], "head_dim": TINY["head_dim"],
    "query_block": 8, "scan_block": 16,
}
CONFIG_FILE = BENCH / "configs" / "nemotron3_nano_30b_a3b_ep16.json"
CELL_CUT = "layers=7,experts=8,first_expert=0,vocab=16384"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
HELD = ["mamba", "moe", "mamba", "moe", "mamba", "attention", "moe"]


def _away_from_init(params):
    """Norm scales, ``D`` and the convolution's bias away from their initial
    values and decays a test sequence can see (``A`` between 0.05 and 4 a
    head), so that every leaf has a gradient worth comparing."""

    def move(path, a):
        name = jax.tree_util.keystr(path)
        if "A_log" in name:
            return jnp.log(jnp.linspace(0.05, 4.0, a.size))
        if a.ndim == 1:
            return a + 0.3 * jnp.sin(jnp.arange(a.size, dtype=a.dtype))
        return a

    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def tiny():
    model = get_model("nemotron_h_tiny", model_cut=CUT)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 256)
    variables = jax.jit(model.init)(jax.random.key(0), tokens)
    stats = jax.tree_util.tree_map(  # a bias that decides some selections
        lambda b: 0.05 * jnp.cos(jnp.arange(b.size, dtype=b.dtype)),
        variables["batch_stats"],
    )
    return model, {
        "params": _away_from_init(variables["params"]), "batch_stats": stats,
    }, tokens


def test_published_config_is_the_catalog_row_and_the_cut_is_the_patterns_unit():
    if CATALOG.exists():  # the driver's catalog, where it is installed
        row = next(
            json.loads(line) for line in CATALOG.read_text().splitlines()
            if json.loads(line)["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
        )
        assert row["config"] == nemotron_h.NEMOTRON_3_NANO_30B_A3B
    whole = nemotron_h.derived(nemotron_h.NEMOTRON_3_NANO_30B_A3B)
    kinds = whole["layer_types"]
    assert [kinds.count(k) for k in ("mamba", "moe", "attention")] == [23, 23, 6]
    assert kinds[:35] == HELD * 5  # the unit the pattern repeats before its tail
    assert (whole["num_dense_layers"], whole["num_experts"]) == (0, 128)
    cut = cut_config(whole, parse_cut(CELL_CUT))
    assert cut["layer_types"] == HELD and cut["num_experts_held"] == 8
    assert cut["published_num_hidden_layers"] == 52 and cut["num_hidden_layers"] == 7
    # no width: the inner width is heads x head size, not expand x hidden
    assert cut["mamba_num_heads"] * cut["mamba_head_dim"] == 4096 != 2 * cut["hidden_size"]
    assert (cut["moe_intermediate_size"], cut["moe_shared_expert_intermediate_size"]) == (1856, 3712)
    assert TINY["mamba_num_heads"] // TINY["n_groups"] > 1 < TINY["n_groups"]
    with pytest.raises(ValueError, match="dense MLP"):
        nemotron_h.derived({**TINY, "hybrid_override_pattern": "ME-EM*EMEMEM*E"})
    with pytest.raises(ValueError, match="group-limited"):
        nemotron_h.derived({**TINY, "n_group": 2})


def test_mamba2_mixer_matches_reference_and_is_causal():
    layer = nemotron_h.Mamba2Mixer(
        dim=64, heads=8, head_dim=8, groups=2, state=16, conv_kernel=4,
        chunk=16, eps=1e-5, dt_limits=(1e-3, 0.1, 1e-4), out_std=0.02,
    )
    h = jax.random.normal(jax.random.key(4), (2, 40, 64))
    params = layer.init(jax.random.key(5), h)["params"]
    # the initialisers: decays in (e^-16 dt, e^-dt), steps inside their limits
    assert np.all((np.exp(params["A_log"]) >= 1) & (np.exp(params["A_log"]) <= 16))
    step = np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert np.all((step >= 1e-3 * 0.999) & (step <= 0.1 * 1.001))
    assert np.all(np.asarray(params["D"]) == 1) and np.abs(params["conv_bias"]).max() <= 0.5
    variables = {"params": _away_from_init(params)}
    arch = {**reference.ARCH, **ARCH, "scan_block": 8}
    want = reference.mamba2(h, variables["params"], arch)
    got = layer.apply(variables, h)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    # causal, and the convolution reaches three tokens back and no further
    later = layer.apply(variables, h.at[:, 20].add(3.0))
    np.testing.assert_allclose(later[:, :20], got[:, :20], rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(later[:, 20:] - want[:, 20:]).max()) > 1e-3
    # 40 tokens are two and a half chunks of 16: the padding changes nothing
    whole = layer.clone(chunk=8)
    np.testing.assert_allclose(whole.apply(variables, h), got, rtol=2e-4, atol=2e-6)


def test_attention_has_no_position_signal():
    layer = nemotron_h.Attention(dim=64, heads=4, kv_heads=2, head_dim=32, out_std=0.02)
    h = jax.random.normal(jax.random.key(6), (2, 24, 64))
    variables = layer.init(jax.random.key(7), h)
    assert set(variables["params"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    arch = {**reference.ARCH, **ARCH}
    got = layer.apply(variables, h)
    np.testing.assert_allclose(
        got, reference.attention(h, variables["params"], arch), rtol=2e-4, atol=2e-6
    )
    # the last token sees the same set of keys whatever their order
    order = jnp.concatenate([jnp.arange(23)[::-1], jnp.array([23])])
    np.testing.assert_allclose(
        layer.apply(variables, h[:, order])[:, -1], got[:, -1], rtol=1e-4, atol=1e-6
    )


def test_whole_model_loss_and_every_gradient_match_reference(tiny):
    model, variables, tokens = tiny
    labels = jnp.roll(tokens, -1, axis=1)
    stats = variables["batch_stats"]

    def program(p):
        logits = model.apply({"params": p, "batch_stats": stats}, tokens)
        return reference.next_token_loss(logits, labels), logits

    def plain_reference(p):
        logits, _ = reference.forward(p, stats, tokens, ARCH)
        return reference.next_token_loss(logits, labels), logits

    (got, logits), got_grads = jax.jit(
        jax.value_and_grad(program, has_aux=True)
    )(variables["params"])
    (want, want_logits), want_grads = jax.jit(
        jax.value_and_grad(plain_reference, has_aux=True)
    )(variables["params"])
    np.testing.assert_allclose(logits, want_logits, rtol=2e-4, atol=2e-5)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, g in jax.tree_util.tree_leaves_with_path(got_grads):
        r = flat_want[path]
        assert float(jnp.abs(r).max()) > 0, path  # every leaf is reached
        np.testing.assert_allclose(
            g, r, rtol=2e-3, atol=2e-5 * float(jnp.abs(r).max()),
            err_msg=jax.tree_util.keystr(path),
        )


def test_remat_changes_no_value_and_training_moves_the_bias_and_sows_the_decay(tiny):
    model, variables, tokens = tiny
    again = get_model("nemotron_h_tiny", model_cut=CUT, remat=True)
    out, new = jax.jit(lambda v: model.apply(
        v, tokens, train=True, mutable=["moe_metrics", "batch_stats"]
    ))(variables)
    np.testing.assert_allclose(
        jax.jit(lambda v: again.apply(v, tokens))(variables), out, rtol=1e-5, atol=1e-6
    )
    sown = new["moe_metrics"]
    assert [k for k in sown if "mamba" in sown[k]] == ["layers_0", "layers_2", "layers_4"]
    for i in (0, 2, 4):  # mean exp(dt A), A < 0 < dt: inside (0, 1)
        (decay,) = sown[f"layers_{i}"]["mamba"]["ssm_decay_mean"]
        assert 0.0 < float(decay) < 1.0
    # one layer, one mixer, one norm; the bias is a buffer the step moves
    assert all(set(variables["params"][f"layers_{i}"]) == {"norm", kind_key}
               for i, kind_key in enumerate(
                   ["mamba", "moe", "mamba", "moe", "mamba", "attn", "moe"]))
    assert list(new["batch_stats"]) == ["layers_1", "layers_3", "layers_6"]
    _, want = jax.jit(lambda v: reference.trunk(
        v["params"], v["batch_stats"], tokens, {**reference.ARCH, **ARCH}
    ))(variables)
    for name in new["batch_stats"]:
        moved = new["batch_stats"][name]["moe"]["expert_bias"]
        np.testing.assert_allclose(moved, want[name]["moe"]["expert_bias"], atol=1e-7)
        before = variables["batch_stats"][name]["moe"]["expert_bias"]
        assert float(jnp.abs(moved - before).max()) == pytest.approx(
            2 * nemotron_h.BIAS_UPDATE_RATE, rel=0.5
        )


def test_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_reference_layer():
    """Every rank of the sixteen-chip job holds ``experts / 16`` experts and
    computes the shared expert alike: the shares' routed parts and the
    shared expert once are what the plain reference gives for the layer
    whole (relu2 experts, sigmoid scores, a selection bias, scale 2.5)."""
    d, f, shared, experts, k, ranks = 32, 24, 40, 32, 6, 16
    held = experts // ranks
    x = jax.random.normal(jax.random.key(3), (2, 24, d))
    whole = TopKMoE(d, f, experts, k, scale=2.5, shared_hidden=shared, mlp="relu2")
    variables = whole.init(jax.random.key(4), x)
    p = variables["params"]
    assert set(p) == {"router", "w1", "w2", "shared_expert"}  # no gate, no w3
    assert set(p["shared_expert"]) == {"w1", "w2"}
    bias = variables["batch_stats"]["expert_bias"]
    arch = {**reference.ARCH, "num_experts_per_tok": k, "first_expert": 0}
    want, _ = reference.moe(x, p, bias, arch)
    np.testing.assert_allclose(whole.apply(variables, x), want, rtol=2e-4, atol=2e-6)
    alike = reference.relu2(
        x, p["shared_expert"]["w1"]["kernel"], p["shared_expert"]["w2"]["kernel"]
    )
    total = alike
    for rank in range(ranks):
        first = rank * held
        share = TopKMoE(
            d, f, experts, k, held, first, scale=2.5, shared_hidden=shared, mlp="relu2"
        )
        mine = {**p, "w1": p["w1"][first:first + held], "w2": p["w2"][first:first + held]}
        out = share.apply({"params": mine, "batch_stats": variables["batch_stats"]}, x)
        total = total + (out - alike)
    np.testing.assert_allclose(total, want, rtol=5e-4, atol=5e-6)


# ---------------------------------------------------------------- trainer

ARGV = [
    "--synthetic-data", "--no-progress", "--num-devices", "1",
    "--model", "nemotron_h_tiny", "--model-cut", CUT, "--seq-len", "32",
    "--batch-size", "4", "--limit-examples", "80", "--optimizer", "adamw",
    "--lr", "3e-3", "--weight-decay", "0.1", "--lr-decay-gamma", "1.0",
]


def _tiny_compare_config():
    """The cell's own ``compare`` group (its recipe and tolerances) at test
    sizes; the learning rate is the test run's."""
    compare = json.loads(CONFIG_FILE.read_text())["compare"]
    return {"compare": {
        **compare, "batch": 2, "tokens": 32, "vocab": 256,
        "recipe": {**compare["recipe"], "lr": 3e-3, "arch": ARCH},
    }}


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_first_step_comparison_holds_each_precision_to_its_own(tmp_path, precision):
    """``harness/compare.py first_step`` on the program's own train step —
    loss, gradient norm, one AdamW step, the moved bias: float32 passes the
    float32 tolerance; bf16 (the control) fails it."""
    from harness import compare

    hp = load_config("tpu", [
        *ARGV, "--ckpt-path", str(tmp_path), "--precision", precision,
    ])
    trainer = Trainer(hp)
    try:
        config = _tiny_compare_config()
        out = compare.first_step(trainer, config, 2**31 + 40, REFERENCE_FILE)
    finally:
        trainer.close()
    assert set(out["errors"]) == set(out["tolerance"]) and "stats_rel_l2" in out["errors"]
    strict = config["compare"]["tolerance"]["fp32"]
    fails_float32 = any(out["errors"][k] > strict[k] for k in strict)
    assert fails_float32 == (precision == "bf16"), out["errors"]
    # the cell's bf16 limit on the gradient norm is the chip's at 8,192
    # tokens of width 2,688; a test width's norm averages less rounding away
    roomy = {**out["tolerance"], "grad_norm_rel": 0.02}
    assert all(out["errors"][k] <= roomy[k] for k in roomy), out
    assert out["ok"] or precision == "bf16", out


def test_trainer_fits_tokens_with_its_gauge_and_kernel_paths(tmp_path):
    events = []
    hp = load_config("tpu", [*ARGV, "--ckpt-path", str(tmp_path), "--epoch", "2"])
    trainer = Trainer(hp)
    trainer.bus.subscribe(events.append)
    trainer.fit()
    trainer.close()
    ends = [e["payload"] for e in events if e.get("kind") == "epoch_end"]
    assert len(ends) == 2 and ends[1]["train_loss"] < ends[0]["train_loss"]
    compiled = {
        k: v for e in events if e.get("kind") == "compile"
        for k, v in (e["payload"].get("kernel_paths") or {}).items()
    }
    assert compiled == {
        "attention": "composed", "moe_gmm": "ragged_dot", "ssd": "composed",
    }
    counted = [
        e["payload"]["metrics"] for e in events if e.get("kind") == "metrics"
        and "moe/rows" in e["payload"]["metrics"]
    ]
    assert counted[0]["moe/rows"]["n"] > 0
    assert all(0.0 < m["ssm/decay_mean"]["value"] < 1.0 for m in counted)
    assert counted[-1]["moe/bias_spread"]["value"] > 0  # the rule moves it
    assert "gdn/decay_mean" not in counted[0]


# ----------------------------------------------------- the cell's own files


def test_configuration_holds_the_published_config_and_names_its_cut():
    """Every key of the catalog's ``config`` is in the file unchanged; what
    this chip holds is beside it, each held value under ``reduced`` with
    its arithmetic.  ``parameters_held`` is ``jax.eval_shape``'s count at
    the published widths and the sum ``reduced`` writes out."""
    body = json.loads(CONFIG_FILE.read_text())
    published = nemotron_h.NEMOTRON_3_NANO_30B_A3B
    assert not [k for k, v in published.items() if body.get(k) != v]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == body["name"])
    assert entry["source"] == body["source"]
    assert body["argv"][body["argv"].index("--model-cut") + 1] == CELL_CUT
    cut = cut_config(nemotron_h.derived(published), parse_cut(CELL_CUT))
    run_as = {
        "num_layers_held": cut["num_hidden_layers"],
        "num_experts_held": cut["num_experts_held"],
        "vocab_rows_held": cut["vocab_size"],
    }
    assert {k: body[k] for k in run_as} == run_as
    assert body["layer_types_held"] == cut["layer_types"] == body["flops"]["layer_types"]
    assert body["layers_held_of_published"] == list(range(7))
    assert body["first_expert_held"] == cut["first_expert"] == reference.ARCH["first_expert"]
    assert set(body["reduced"]) == set(run_as) == set(entry["reduced"])
    assert all("->" in reason for reason in body["reduced"].values())
    assert "sixteen chips share each expert layer's experts" in body["deployment"]
    assert "eight share the vocabulary" in body["deployment"]
    # the floors: 8 experts, an eighth of the vocabulary, every kind of layer
    assert cut["num_experts_held"] >= 8 and cut["vocab_size"] * 8 >= published["vocab_size"]
    assert set(cut["layer_types"]) == {"mamba", "moe", "attention"}
    # the reference's constants are the published config's
    for key in ("layer_norm_epsilon", "n_groups", "head_dim", "num_experts_per_tok",
                "routed_scaling_factor"):
        assert reference.ARCH[key] == published[key], key
    assert reference.ARCH["bias_update_rate"] == nemotron_h.BIAS_UPDATE_RATE
    model = get_model("nemotron_h", model_cut=CELL_CUT)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )
    sizes = {
        jax.tree_util.keystr(p): int(np.prod(a.shape))
        for p, a in jax.tree_util.tree_leaves_with_path(shapes["params"])
    }
    of = lambda part: sum(v for k, v in sizes.items() if part in k)  # noqa: E731
    assert sum(sizes.values()) == body["parameters_held"] == 528_092_736
    assert of("['layers_0']") == 38_744_896 and of("['layers_0']['mamba']") == 38_742_208
    assert of("['layers_5']") == 23_399_040 and of("['layers_1']") == 100_125_312
    assert of("['layers_1']['moe']") - 8 * 9_977_856 == 20_299_776
    assert of("embedding") + of("lm_head") == 88_080_384
    assert "3 x 38,744,896 + 23,399,040 + 3 x 100,125,312 + 88,080,384 + 2,688 = 528,092,736" \
        in body["reduced"]["num_layers_held"]
    assert 3 * 38_744_896 + 23_399_040 + 3 * 100_125_312 + 88_080_384 + 2_688 == 528_092_736
