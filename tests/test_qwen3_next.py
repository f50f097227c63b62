"""Qwen3-Next (Gated DeltaNet and gated attention 3:1, softmax-routed
experts beside a gated shared expert) against its plain reference
(``benchmark/reference/qwen3_next_80b_a3b_ep32.py``) at test widths on the
CPU: each mixer, the whole model's loss and every gradient, the benchmark's
first-step comparison in float32 and bf16, a ``Trainer.fit()`` that saves
and restores the scan's own parameters, and the configuration's files
against the published config.  The scan's own tests are in ``test_ops.py``,
the expert layer's (softmax routing, the gated shared expert, shares) in
``test_moe.py``, the lowered step program's scopes in ``test_scopes.py``."""

import json
import re
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.models import get_model, qwen3_next
from distributed_training_comparison_tpu.models.token_parts import (
    RMSNorm,
    cut_config,
    parse_cut,
    rope,
)
from distributed_training_comparison_tpu.train import Trainer

from lfm2_reference import BENCH, ROOT, load

from harness import flops, scopes  # noqa: E402  (lfm2_reference puts benchmark/ on the path)

REFERENCE_FILE = BENCH / "reference" / "qwen3_next_80b_a3b_ep32.py"
reference = load(REFERENCE_FILE)

CUT = "layers=4,experts=4,first_expert=4,vocab=256"
TINY = qwen3_next.QWEN3_NEXT_TINY
ARCH = {
    "first_expert": 4, "num_experts_per_tok": TINY["num_experts_per_tok"],
    "linear_key_head_dim": TINY["linear_key_head_dim"],
    "rotary": int(TINY["head_dim"] * TINY["partial_rotary_factor"]),
    "query_block": 8, "scan_block": 16,
}
CONFIG_FILE = BENCH / "configs" / "qwen3_next_80b_a3b_ep32.json"
CELL = "qwen3next_ep32_seq8k_job"
CELL_CUT = "layers=4,experts=16,first_expert=0,vocab=18992"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def plain(tree):
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


def _away_from_init(params):
    """Norm scales away from their initial zeros and ones and decays a test
    sequence can see (``A`` between 0.05 and 4 a head), so that every leaf
    has a gradient worth comparing."""

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
    model = get_model("qwen3_next_tiny", model_cut=CUT)
    tokens = jax.random.randint(jax.random.key(1), (2, 48), 0, 256)
    params = model.init(jax.random.key(0), tokens)["params"]
    return model, {"params": _away_from_init(params)}, tokens


def test_published_config_is_the_catalog_row_and_the_cut_keeps_a_period():
    if CATALOG.exists():  # the driver's catalog, where it is installed
        row = next(
            json.loads(line) for line in CATALOG.read_text().splitlines()
            if json.loads(line)["name"] == "Qwen3-Next-80B-A3B-Instruct"
        )
        assert row["config"] == qwen3_next.QWEN3_NEXT_80B_A3B
    whole = qwen3_next.derived(qwen3_next.QWEN3_NEXT_80B_A3B)
    assert whole["layer_types"] == (
        ["linear_attention"] * 3 + ["full_attention"]
    ) * 12 and whole["num_dense_layers"] == 0
    cut = cut_config(whole, parse_cut(CELL_CUT))
    assert cut["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]
    assert (cut["num_dense_layers"], cut["num_experts_held"]) == (0, 16)
    # ``dense=0`` says what leaving it out says; a dense layer there is none of
    assert cut_config(whole, parse_cut(CELL_CUT + ",dense=0")) == cut
    with pytest.raises(ValueError, match="does not fit"):
        cut_config(whole, parse_cut(CELL_CUT + ",dense=1"))
    # no width: the head size stays apart from hidden / heads
    assert (cut["num_experts"], cut["hidden_size"], cut["head_dim"]) == (512, 2048, 256)
    assert cut["hidden_size"] // cut["num_attention_heads"] != cut["head_dim"]
    assert TINY["hidden_size"] // TINY["num_attention_heads"] != TINY["head_dim"]
    assert TINY["linear_num_value_heads"] == 2 * TINY["linear_num_key_heads"]
    with pytest.raises(ValueError, match="dense MLP"):
        qwen3_next.derived({**TINY, "mlp_only_layers": [0]})


def test_zero_centred_norm_starts_as_the_identity_scale():
    x = jax.random.normal(jax.random.key(2), (3, 5, 16)) * 4.0
    norm = RMSNorm(1e-6, zero_centred=True)
    variables = norm.init(jax.random.key(0), x)
    assert float(jnp.abs(variables["params"]["scale"]).max()) == 0
    unit = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(norm.apply(variables, x), unit, rtol=1e-6)
    # and it is the plain norm's function at its own start (scale one)
    plain_norm = RMSNorm(1e-6)
    np.testing.assert_array_equal(
        norm.apply(variables, x),
        plain_norm.apply(plain_norm.init(jax.random.key(0), x), x),
    )
    moved = {"params": {"scale": jnp.full((16,), 0.5)}}
    np.testing.assert_allclose(norm.apply(moved, x), 1.5 * unit, rtol=1e-6)


def test_rotary_touches_the_first_quarter_of_the_head_and_nothing_else():
    x = jax.random.normal(jax.random.key(3), (2, 12, 3, 32))
    turned = rope(x, 1e7, 8)
    np.testing.assert_array_equal(turned[..., 8:], x[..., 8:])
    # the first eight turn as a head of eight would, frequencies over eight
    np.testing.assert_array_equal(turned[..., :8], rope(x[..., :8], 1e7))
    assert float(jnp.abs(turned[:, 1:, :, :8] - x[:, 1:, :, :8]).max()) > 0.1
    np.testing.assert_array_equal(turned[:, 0], x[:, 0])  # position 0: no turn
    # the whole head, asked for or left out, is the accepted decoders' call
    np.testing.assert_array_equal(rope(x, 1e4, 32), rope(x, 1e4))
    want = reference.rope(x, 1e7, 8)
    np.testing.assert_allclose(turned, want, rtol=1e-5, atol=1e-6)


def test_gated_delta_net_layer_matches_reference():
    layer = qwen3_next.GatedDeltaNet(
        dim=64, key_heads=2, value_heads=4, key_dim=16, value_dim=24,
        conv_kernel=4, eps=1e-6, chunk=16,
    )
    h = jax.random.normal(jax.random.key(4), (2, 40, 64))
    variables = {"params": _away_from_init(layer.init(jax.random.key(5), h)["params"])}
    arch = {**reference.ARCH, **ARCH, "scan_block": 8}
    want = reference.gated_delta_net(h, variables["params"], arch)
    np.testing.assert_allclose(layer.apply(variables, h), want, rtol=2e-4, atol=2e-6)
    # causal, and the convolution reaches three tokens back and no further
    later = h.at[:, 20].add(3.0)
    np.testing.assert_allclose(
        layer.apply(variables, later)[:, :20], layer.apply(variables, h)[:, :20],
        rtol=1e-5, atol=1e-6,
    )
    assert float(jnp.abs(layer.apply(variables, later)[:, 20:] - want[:, 20:]).max()) > 1e-3
    # 40 tokens are two and a half chunks of 16: the padding changes nothing
    whole = qwen3_next.GatedDeltaNet(
        dim=64, key_heads=2, value_heads=4, key_dim=16, value_dim=24,
        conv_kernel=4, eps=1e-6, chunk=8,
    )
    np.testing.assert_allclose(
        whole.apply(variables, h), layer.apply(variables, h), rtol=2e-4, atol=2e-6
    )


def test_gated_attention_matches_reference():
    layer = qwen3_next.GatedAttention(
        dim=64, heads=4, kv_heads=2, head_dim=32, rotary=8, eps=1e-6, theta=1e7,
    )
    h = jax.random.normal(jax.random.key(6), (2, 40, 64))
    variables = {"params": _away_from_init(layer.init(jax.random.key(7), h)["params"])}
    assert variables["params"]["q_proj"]["kernel"].shape == (64, 4 * 2 * 32)
    arch = {**reference.ARCH, **ARCH}
    want = reference.attention(h, variables["params"], arch)
    np.testing.assert_allclose(layer.apply(variables, h), want, rtol=2e-4, atol=2e-6)


def test_whole_model_loss_and_every_gradient_match_reference(tiny):
    model, variables, tokens = tiny
    labels = jnp.roll(tokens, -1, axis=1)

    def program(p):
        logits = model.apply({"params": p}, tokens)
        return reference.next_token_loss(logits, labels), logits

    def plain_reference(p):
        logits, _ = reference.forward(p, {}, tokens, ARCH)
        return reference.next_token_loss(logits, labels), logits

    (got, logits), got_grads = jax.value_and_grad(program, has_aux=True)(variables["params"])
    (want, want_logits), want_grads = jax.value_and_grad(
        plain_reference, has_aux=True
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


def test_remat_changes_no_value_and_training_sows_the_decay(tiny):
    model, variables, tokens = tiny
    again = get_model("qwen3_next_tiny", model_cut=CUT, remat=True)
    out, sown = model.apply(variables, tokens, train=True, mutable=["moe_metrics"])
    np.testing.assert_allclose(again.apply(variables, tokens), out, rtol=1e-5, atol=1e-6)
    layers = sown["moe_metrics"]
    assert [k for k in layers if "gdn" in layers[k]] == ["layers_0", "layers_1", "layers_2"]
    # mean exp(g) with g = -A softplus(alpha + dt_bias): inside (0, 1)
    for i in range(3):
        (decay,) = layers[f"layers_{i}"]["gdn"]["gdn_decay_mean"]
        assert 0.0 < float(decay) < 1.0
    assert "batch_stats" not in model.init(jax.random.key(0), tokens)  # no bias


SCOPES = ["embed", "gdn", "gdn_conv", "gdn_scan", "gdn_gate_norm", "attn",
          "attn_gate", "attention", "moe", "moe_gmm", "shared_expert", "lm_head"]


@pytest.fixture(scope="module")
def grad_op_names(tiny):
    model, variables, tokens = tiny
    # a loss that is not linear in the logits keeps the head's forward alive
    grad = jax.jit(jax.grad(
        lambda p: jnp.square(model.apply({"params": p}, tokens)).sum()
    ))
    text = grad.lower(variables["params"]).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("scope", SCOPES)
def test_scopes_the_readers_look_for_are_path_components(grad_op_names, scope):
    """Forward and backward ops of a differentiated call carry the scope
    (``harness/scopes.py under`` decides, as the benchmark's readers do)."""
    found = {scopes.phase_of(n) for n in grad_op_names if scopes.under(n, scope)}
    assert {"forward", "backward"} <= found, found
    inside = {"gdn_conv": "gdn", "gdn_scan": "gdn", "gdn_gate_norm": "gdn",
              "attn_gate": "attn", "attention": "attn", "moe_gmm": "moe",
              "shared_expert": "moe"}
    if scope in inside:
        mine = [n for n in grad_op_names if scopes.under(n, scope)]
        assert all(scopes.under(n, inside[scope]) for n in mine)
    if scope == "gdn":  # ``attention_ms_per_step`` reads that name
        mine = [n for n in grad_op_names if scopes.under(n, scope)]
        assert not any(scopes.under(n, "attention") for n in mine)
    if scope == "shared_expert":  # the gate's projection and multiply too
        mine = [n for n in grad_op_names if scopes.under(n, scope)]
        assert any("moe/shared_expert/dot_general" in n for n in mine)


# ---------------------------------------------------------------- trainer

ARGV = [
    "--synthetic-data", "--no-progress", "--num-devices", "1",
    "--model", "qwen3_next_tiny", "--model-cut", CUT, "--seq-len", "32",
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
    """``harness/compare.py first_step`` on the program's own train step:
    float32 passes the float32 tolerance; bf16 passes its own and fails
    float32's."""
    from harness import compare

    hp = load_config("tpu", [
        *ARGV, "--ckpt-path", str(tmp_path), "--precision", precision,
    ])
    trainer = Trainer(hp)
    try:
        config = _tiny_compare_config()
        out = compare.first_step(trainer, config, 2**31 + 35, REFERENCE_FILE)
    finally:
        trainer.close()
    assert set(out["errors"]) == set(out["tolerance"])  # no statistics here
    strict = config["compare"]["tolerance"]["fp32"]
    fails_float32 = any(out["errors"][k] > strict[k] for k in strict)
    assert fails_float32 == (precision == "bf16"), out["errors"]
    # the cell's bf16 limit on the gradient norm (8e-4, between the chip's
    # 3.5e-4 and the 8-bit control's 1.7e-3 at 8,192 tokens of width 2,048)
    # is not a test width's: a norm over 10^5 elements averages less
    # rounding away than one over 4 x 10^8, and reads 6e-3 here
    roomy = {**out["tolerance"], "grad_norm_rel": 0.02}
    assert all(out["errors"][k] <= roomy[k] for k in roomy), out
    assert out["ok"] or precision == "bf16", out


def test_trainer_fits_tokens_saves_and_restores_the_scans_parameters(tmp_path):
    events = []
    hp = load_config("tpu", [*ARGV, "--ckpt-path", str(tmp_path), "--epoch", "2"])
    trainer = Trainer(hp)
    trainer.bus.subscribe(events.append)
    start = plain(trainer.state.params)["layers_0"]["gdn"]
    version = trainer.fit()
    trainer.close()
    ends = [e["payload"] for e in events if e.get("kind") == "epoch_end"]
    assert len(ends) == 2 and ends[1]["train_loss"] < ends[0]["train_loss"]
    compiled = {
        k: v for e in events if e.get("kind") == "compile"
        for k, v in (e["payload"].get("kernel_paths") or {}).items()
    }
    assert compiled == {
        "attention": "composed", "moe_gmm": "ragged_dot", "gated_delta": "composed",
    }
    # the tiny head sizes (16, 24) are no lane tile: every mixer call site of
    # the train program is counted under the composed form, a key of its own
    sites = [
        e["payload"]["gdn_pointwise"] for e in events if e.get("kind") == "compile"
        and e["payload"]["name"].startswith("device_chunk_runner")
    ]
    assert sites and all(set(s) == {"composed"} and s["composed"] % 3 == 0 for s in sites)
    counted = [
        e["payload"]["metrics"] for e in events if e.get("kind") == "metrics"
        and "moe/rows" in e["payload"]["metrics"]
    ]
    assert counted[0]["moe/rows"]["n"] > 0
    assert all(0.0 < m["gdn/decay_mean"]["value"] < 1.0 for m in counted)
    assert "moe/bias_spread" not in counted[0]  # no selection bias here
    moved = plain(trainer.state.params)["layers_0"]["gdn"]
    for name in ("A_log", "dt_bias", "conv_kernel"):
        assert np.abs(moved[name] - start[name]).max() > 0, name
    assert start["dt_bias"].tolist() == [1.0] * TINY["linear_num_value_heads"]
    assert np.all(np.exp(start["A_log"]) < 16) and np.abs(start["conv_kernel"]).max() <= 0.5
    # AdamW decays matrices only: the taps are one, the per-head vectors not
    vdir = tmp_path / f"version-{version}"
    assert (vdir / "last.ckpt").exists()

    hp = load_config("tpu", [
        *ARGV, "--ckpt-path", str(tmp_path / "again"), "--epoch", "3",
        "--resume", str(vdir / "last.ckpt"),
    ])
    resumed = Trainer(hp)
    assert resumed.start_epoch == 2
    back = plain(resumed.state.params)["layers_0"]["gdn"]
    for name in ("A_log", "dt_bias", "conv_kernel", "norm_scale"):
        np.testing.assert_array_equal(back[name], moved[name])
    resumed.close()


# ----------------------------------------------------- the cell's own files


def test_configuration_holds_the_published_config_and_names_its_cut():
    """Every key of the catalog's ``config`` is in the file unchanged; what
    this chip holds is beside it, each held value under ``reduced`` with
    its arithmetic, and what the config's keys do not carry under
    ``assumed``.  ``parameters_held`` is ``jax.eval_shape``'s count."""
    body = json.loads(CONFIG_FILE.read_text())
    published = qwen3_next.QWEN3_NEXT_80B_A3B
    differs = [k for k, v in published.items() if body.get(k) != v]
    assert not differs, differs
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == body["name"])
    assert entry["source"] == body["source"]
    assert body["argv"][body["argv"].index("--model-cut") + 1] == CELL_CUT
    cut = cut_config(qwen3_next.derived(published), parse_cut(CELL_CUT))
    run_as = {
        "num_layers_held": cut["num_hidden_layers"],
        "num_experts_held": cut["num_experts_held"],
        "vocab_rows_held": cut["vocab_size"],
    }
    assert {k: body[k] for k in run_as} == run_as
    assert body["layer_types_held"] == cut["layer_types"]
    assert list(reference.ARCH["layer_types"]) == cut["layer_types"]
    assert body["first_expert_held"] == cut["first_expert"] == reference.ARCH["first_expert"]
    assert set(body["reduced"]) == set(run_as) == set(entry["reduced"])
    for reason in body["reduced"].values():
        assert "->" in reason
    assert "32 chips share each layer's experts" in body["deployment"]
    assert "eight share the vocabulary" in body["deployment"]
    # the floors: a whole period of four layers, 8 experts, an eighth of
    # the vocabulary
    assert len(cut["layer_types"]) >= 4
    assert cut["layer_types"] == qwen3_next.derived(published)["layer_types"][:4]
    assert cut["num_experts_held"] >= 8
    assert cut["vocab_size"] * 8 >= published["vocab_size"]
    widths = {
        k: v for k, v in cut.items()
        if k.endswith("_size") and k != "vocab_size" or "head" in k or "_dim" in k
        or k in ("num_experts", "num_experts_per_tok", "partial_rotary_factor")
    }
    assert widths == {k: published[k] for k in widths} and len(widths) >= 14
    text = " ".join(body["assumed"])
    for said in ("full_attention_interval", "zero-centred", "12,288", "layout",
                 "convolution", "L2-normalised", "softplus", "Qwen3NextRMSNormGated",
                 "partial_rotary_factor", "softmax over all 512", "shared_expert_gate",
                 "multi-token-prediction", "initialiser", "U(0, 16)", "AdamW",
                 "Markov", "8,192", "one document a sequence"):
        assert said in text, said
    model = get_model("qwen3_next", model_cut=CELL_CUT)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )
    sizes = {
        jax.tree_util.keystr(p): int(np.prod(a.shape))
        for p, a in jax.tree_util.tree_leaves_with_path(shapes["params"])
    }
    assert sum(sizes.values()) == body["parameters_held"] == 424_340_544
    of = lambda part: sum(v for k, v in sizes.items() if part in k)  # noqa: E731
    assert of("['layers_0']['gdn']") == 33_718_464
    assert of("['layers_3']['attn']") == 27_263_488
    moe = of("['layers_0']['moe']")
    assert moe - 16 * 3_145_728 == 4_196_352
    assert of("embedding") + of("lm_head") == 77_791_232
    # the FLOP group, the reference and the comparison batch run the same cut
    group = body["flops"]
    assert group["layer_types"] == cut["layer_types"] and group["num_dense_layers"] == 0
    assert (group["num_experts_held"], group["vocab_rows"]) == (16, 18992)
    assert (group["head_dim"], group["gdn_chunk"]) == (256, qwen3_next.GDN_CHUNK)
    assert reference.ARCH["rotary"] == published["head_dim"] * published["partial_rotary_factor"]
    assert reference.ARCH["linear_key_head_dim"] == published["linear_key_head_dim"]
    assert body["compare"]["vocab"] == cut["vocab_size"]
    assert body["compare"]["tokens"] == body["example"]["tokens"] == group["tokens"]


def test_flop_counts_by_hand():
    """230 M multiply-accumulates a token forward (ISSUE 35's arithmetic):
    the three DeltaNet mixers 110 M (projections 101, the scan 8.7 at chunk
    64), the attention layer 61 (projections 27, scores and values 34), the
    head 39, shared experts, their gates and routers 17, routed experts 4."""
    group = json.loads(CONFIG_FILE.read_text())["flops"]
    family = load(BENCH / "flops" / "qwen3_next.py")
    t, d = 8192, 2048
    gdn_projections = 3 * (d * (12288 + 64) + 4096 * d)
    scan = 3 * 32 * (64 * (3 * 128 + 2 * 128) + 3 * 128 * 128)
    attn_projections = d * (8192 + 512 + 512) + 4096 * d
    scores = 2 * 4096 * (t + 1) / 2
    experts = 4 * (d * 512 + d + 3 * d * 512 + (10 * 16 / 512) * 3 * d * 512)
    head = d * 18992
    per_token = flops.train_flops_per_image(group) / 3 / t
    assert per_token == pytest.approx(
        2 * (gdn_projections + scan + attn_projections + scores + experts + head)
    )
    assert per_token == pytest.approx(2 * 230.14e6, rel=1e-4)
    assert gdn_projections == pytest.approx(101.1e6, rel=1e-3)
    assert scan == 8_650_752 == 3 * family.gdn_scan_macs_per_token(group)
    assert attn_projections == pytest.approx(27.3e6, rel=1e-2)
    assert scores == pytest.approx(33.6e6, rel=1e-2)
    # the kernels' counts
    assert family.gdn_scan_flops(2, group) == 3 * 2 * 2 * t * scan
    per_token_bytes = 2 * (6 * 2048 + 5 * 4096) + 6 * 32 * 4
    assert family.gdn_scan_bytes(2, group) == 3 * 2 * t * per_token_bytes
    assert family.attention_flops(1, group) == pytest.approx(3 * 2 * scores * t)
    assert family.attention_bytes(1, group) == 2 * t * 6 * (4096 + 512)
    assert family.moe_gmm_flops(2560, group) == 3 * 2 * 2560 * 3 * d * 512
    # flops bound the scan's least time on a v5e, by a hair: 2.16 against 1.99 ms
    assert family.gdn_scan_flops(1, group) / 197e12 == pytest.approx(2.158e-3, rel=1e-3)
    assert family.gdn_scan_bytes(1, group) / 819e9 == pytest.approx(1.990e-3, rel=1e-3)


def test_cell_runs_the_recipe_its_issue_names():
    """AdamW at a constant 3e-4 under the launcher's default save cadence,
    one 8,192-token sequence a step, 32 steps an epoch, 4 validation
    sequences; the comparison's reference takes the same optimizer numbers
    as the argv; the cell reports the new readers and the accepted ones."""
    from distributed_training_comparison_tpu.data.sampler import train_val_split

    body = json.loads(CONFIG_FILE.read_text())
    hp = load_config("tpu", ["--synthetic-data", *body["argv"]])
    default = load_config("tpu", ["--synthetic-data"])
    assert (hp.model, hp.optimizer, hp.lr, hp.lr_decay_gamma) == (
        "qwen3_next", "adamw", 3e-4, 1.0
    )
    assert hp.save_last_min_secs == default.save_last_min_secs
    assert (hp.batch_size, hp.seq_len, hp.remat, hp.amp) == (1, 8192, True, True)
    train, valid = train_val_split(
        hp.limit_examples, valid_size=0.1, seed=0, valid_count=hp.valid_examples
    )
    assert (len(train), len(valid)) == (32, 4)
    recipe = body["compare"]["recipe"]
    assert (recipe["lr"], recipe["weight_decay"]) == (hp.lr, hp.weight_decay)
    assert set(body["compare"]["tolerance"]["bf16"]) == {
        "loss_rel", "grad_norm_rel", "update_scale_err", "update_rel_l2"
    }
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    assert cell["expect"]["kernel_paths"] == {
        "attention": ["composed", "pallas"], "moe_gmm": ["ragged_dot", "megablox"],
        "gated_delta": ["composed", "pallas"],
    }
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        body["name"], "recipe_device", 1
    )
    reported = {
        m["name"] for m in spec["per_layer"]
        if CELL in m.get("workloads", [CELL])
    }
    assert {"gdn_ms_per_step", "gdn_scan_ms_per_step", "gdn_scan_roofline_pct",
            "gdn_decay_mean", "attention_ms_per_step", "attention_roofline_pct",
            "moe_ms_per_step", "moe_full_buffer_pct", "shared_expert_ms_per_step",
            "flash_bwd_fused_pct", "step_mfu_pct"} <= reported
    # ``moe_gmm_roofline_pct`` divides the rows that were counted by the
    # time under ``moe_gmm``; here most layer calls overflow the held prefix
    # and their rows are worked outside that scope, so it would read over
    # 100 (100.3 in the first traced run, PERF.md §6): not this cell's
    assert not {"window_attention_ms_per_step", "short_conv_ms_per_step",
                "moe_gmm_roofline_pct"} & reported
    new = [m for m in spec["per_layer"] if m["name"].startswith("gdn_")]
    assert [m["workloads"] for m in new] == [[CELL]] * 5
    assert {m["layer"] for m in new} == {"Models", "Kernels"}


def test_the_scan_readers_divide_the_work_by_the_scopes_time(monkeypatch):
    """The new readers on a stub of a traced run: the roofline share is the
    family's count over the time under ``gdn_scan`` whatever path ran, and
    a program without the scope (the parent's) or a family without the
    functions reads nothing and raises nothing."""
    body = json.loads(CONFIG_FILE.read_text())
    run = SimpleNamespace(
        setup_compiles=[{"name": "device_chunk_runner@k32",
                         "kernel_paths": {"gated_delta": "composed"}}],
        mix={"train_program": "device_chunk_runner"}, config=body,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        traced_steps=64, window={"batch_size": 1},
    )
    ms = {"gdn_scan": 40.0, "gdn": 90.0}
    monkeypatch.setattr(
        scopes, "train_ms_per_step",
        lambda run, pick: next(
            (v for k, v in ms.items() if pick(f"jit(f)/gdn/{k}/dot")), None
        ),
    )
    reader = lambda name: load(BENCH / "layer_metrics" / f"{name}.py").read  # noqa: E731
    family = load(BENCH / "flops" / "qwen3_next.py")
    want = 100 * family.gdn_scan_flops(64, body["flops"]) / 197e12 / (0.040 * 64)
    assert reader("gdn_scan_roofline_pct")(run) == pytest.approx(want)
    assert 0 < want < 100
    assert reader("gdn_scan_ms_per_step")(run) == 40.0
    assert reader("gdn_ms_per_step")(run) == 40.0  # the stub's first match
    ms.clear()
    for name in ("gdn_scan_roofline_pct", "gdn_scan_ms_per_step", "gdn_ms_per_step"):
        assert reader(name)(run) is None
    ms["gdn_scan"] = 40.0
    trinity = json.loads((BENCH / "configs" / "trinity_mini_ep16.json").read_text())
    run.config = trinity  # a family without gdn_scan_flops
    assert reader("gdn_scan_roofline_pct")(run) is None
    # the gauge: the mean over the window's metrics events, or nothing
    events = [{"payload": {"metrics": {"gdn/decay_mean": {"value": v}}}} for v in (0.1, 0.3)]
    run.clock = SimpleNamespace(in_window=lambda kind: events)
    assert reader("gdn_decay_mean")(run) == pytest.approx(0.2)
    run.clock = SimpleNamespace(in_window=lambda kind: [{"payload": {"metrics": {}}}])
    assert reader("gdn_decay_mean")(run) is None


def test_the_pointwise_counters_reader_sums_the_train_programs_call_sites():
    """``gdn_pointwise_fused_pct`` on a stub of a run's set-up: the parent's
    event has no ``gdn_pointwise`` and reads nothing; the train program's
    events are summed, another program's are not its call sites."""
    read = load(BENCH / "layer_metrics" / "gdn_pointwise_fused_pct.py").read
    run = SimpleNamespace(
        setup_compiles=[{"name": "device_chunk_runner@k32",
                         "kernel_paths": {"gated_delta": "pallas"}}],
        mix={"train_program": "device_chunk_runner"},
    )
    assert read(run) is None
    run.setup_compiles[0]["gdn_pointwise"] = {"fused": 3}
    assert read(run) == 100.0
    run.setup_compiles.append({"name": "eval_runner", "gdn_pointwise": {"composed": 3}})
    assert read(run) == 100.0
    run.setup_compiles.append(
        {"name": "device_chunk_runner@k4", "gdn_pointwise": {"composed": 1}}
    )
    assert read(run) == pytest.approx(75.0)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in spec["per_layer"] if m["name"] == "gdn_pointwise_fused_pct"]
    assert entry == {
        "name": "gdn_pointwise_fused_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Models",
        "moves": "images_per_s_per_chip", "workloads": [CELL],
    }
