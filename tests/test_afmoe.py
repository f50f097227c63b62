"""AFMoE (Trinity-Mini's family) against its plain reference
(``benchmark/reference/trinity_mini_ep16.py``) at test widths on the CPU:
the gated attention of both kinds of layer, the whole model's loss and every
gradient, the selection bias after three steps, the benchmark's first-step
comparison in float32 and bf16, a ``Trainer.fit()`` that saves and resumes
the bias, and the configuration's files against the published config.  The
window's own tests are in ``test_ops.py``, the expert layer's (shared expert,
bias rule, shares) in ``test_moe.py``."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.models import afmoe, get_model
from distributed_training_comparison_tpu.models.token_parts import frozen_config
from distributed_training_comparison_tpu.train import Trainer

from lfm2_reference import BENCH, ROOT, load

from harness import flops, scopes  # noqa: E402  (lfm2_reference puts benchmark/ on the path)

reference = load(BENCH / "reference" / "trinity_mini_ep16.py")

CUT = "layers=5,dense=1,experts=4,first_expert=4,vocab=256"
TINY = afmoe.AFMOE_TINY
ARCH = {
    "first_expert": 4, "sliding_window": TINY["sliding_window"],
    "num_experts_per_tok": TINY["num_experts_per_tok"], "query_block": 8,
}
CONFIG_FILE = BENCH / "configs" / "trinity_mini_ep16.json"
CELL_CUT = "layers=5,dense=1,experts=8,first_expert=0,vocab=25024"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def plain(tree):
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


@pytest.fixture(scope="module")
def tiny():
    model = get_model("afmoe_tiny", model_cut=CUT)
    tokens = jax.random.randint(jax.random.key(1), (2, 48), 0, 256)
    variables = model.init(jax.random.key(0), tokens)
    # norms away from their initial ones, so the tests see all four a layer
    params = jax.tree_util.tree_map(
        lambda a: a * (1.0 + 0.3 * jnp.sin(jnp.arange(a.size, dtype=a.dtype)))
        if a.ndim == 1 else a, variables["params"],
    )
    return model, {"params": params, "batch_stats": variables["batch_stats"]}, tokens


def test_published_config_is_the_catalog_row_and_the_cut_keeps_a_period():
    if CATALOG.exists():  # the driver's catalog, where it is installed
        row = next(
            json.loads(line) for line in CATALOG.read_text().splitlines()
            if json.loads(line)["name"] == "Trinity-Mini"
        )
        assert row["config"] == afmoe.TRINITY_MINI
    cut = afmoe.cut_config(afmoe.TRINITY_MINI, afmoe.parse_cut(CELL_CUT))
    assert cut["layer_types"] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention",
    ]
    assert (cut["num_dense_layers"], cut["num_experts_held"]) == (1, 8)
    # no width: the head size stays apart from hidden / heads
    assert (cut["num_experts"], cut["hidden_size"], cut["head_dim"]) == (128, 2048, 128)
    assert cut["hidden_size"] // cut["num_attention_heads"] != cut["head_dim"]
    assert TINY["hidden_size"] // TINY["num_attention_heads"] != TINY["head_dim"]


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_gated_attention_matches_reference(kind):
    sliding = kind == "sliding_attention"
    layer = afmoe.GatedAttention(
        dim=64, heads=4, kv_heads=2, head_dim=32, eps=1e-5,
        theta=1e4 if sliding else None, window=16 if sliding else None,
    )
    h = jax.random.normal(jax.random.key(4), (2, 40, 64))
    variables = layer.init(jax.random.key(5), h)
    variables = jax.tree_util.tree_map(
        lambda a: a * 1.3 if a.ndim == 1 else a, variables
    )
    arch = {**reference.ARCH, **ARCH}
    want = reference.attention(h, variables["params"], arch, sliding)
    np.testing.assert_allclose(layer.apply(variables, h), want, rtol=2e-4, atol=2e-6)
    if sliding:  # the window is there: a key 16 back moves nothing
        far = h.at[:, 0].add(3.0)
        np.testing.assert_allclose(
            layer.apply(variables, far)[:, 16:], layer.apply(variables, h)[:, 16:],
            rtol=1e-5, atol=1e-6,
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


def test_the_bias_after_three_training_calls_and_eval_leaves_it_alone(tiny):
    model, variables, tokens = tiny
    labels = jnp.roll(tokens, -1, axis=1)
    recipe = {"lr": 0.0, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
              "weight_decay": 0.0, "arch": ARCH}
    stats = want = variables["batch_stats"]
    assert all(float(jnp.abs(b).max()) == 0 for b in jax.tree_util.tree_leaves(stats))
    for _ in range(3):
        _, mutated = model.apply(
            {"params": variables["params"], "batch_stats": stats}, tokens,
            train=True, mutable=["batch_stats", "moe_metrics"],
        )
        stats = mutated["batch_stats"]
        want = reference.step(variables["params"], want, tokens, labels, recipe)["batch_stats"]
    assert jax.tree_util.tree_structure(stats) == jax.tree_util.tree_structure(want)
    for got, ref in zip(jax.tree_util.tree_leaves(stats), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-8)
        assert float(jnp.abs(ref).max()) >= 0.001  # the rule moved it
    # an evaluation may not write, and does not
    model.apply({"params": variables["params"], "batch_stats": stats}, tokens)
    _, mutated = model.apply(
        {"params": variables["params"], "batch_stats": stats}, tokens,
        train=False, mutable=["batch_stats"],
    )
    for got, ref in zip(
        jax.tree_util.tree_leaves(mutated["batch_stats"]),
        jax.tree_util.tree_leaves(stats),
    ):
        assert bool(jnp.all(got == ref))


def test_remat_changes_no_value_and_moves_the_bias_alike(tiny):
    model, variables, tokens = tiny
    again = get_model("afmoe_tiny", model_cut=CUT, remat=True)
    train = dict(train=True, mutable=["batch_stats"])
    out, moved = model.apply(variables, tokens, **train)
    out_again, moved_again = again.apply(variables, tokens, **train)
    np.testing.assert_allclose(out_again, out, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(moved), jax.tree_util.tree_leaves(moved_again)):
        assert bool(jnp.all(a == b))


def test_a_window_as_long_as_the_sequence_is_causal_attention(tiny):
    """``window >= S``: a sliding layer is then a causal one with RoPE."""
    _, variables, tokens = tiny
    wide = afmoe.Afmoe(frozen_config(afmoe.cut_config(
        {**TINY, "sliding_window": 48}, afmoe.parse_cut(CUT)
    )))
    wider = afmoe.Afmoe(frozen_config(afmoe.cut_config(
        {**TINY, "sliding_window": 4096}, afmoe.parse_cut(CUT)
    )))
    assert bool(jnp.all(wide.apply(variables, tokens) == wider.apply(variables, tokens)))
    want, _ = reference.forward(
        variables["params"], variables["batch_stats"], tokens,
        {**ARCH, "sliding_window": 10**6},
    )
    np.testing.assert_allclose(wide.apply(variables, tokens), want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize(
    "scope", ["attention", "attention_window", "attn_gate", "shared_expert", "moe_gmm"]
)
def test_scopes_the_readers_look_for_are_path_components(tiny, scope):
    """Forward and backward ops of a differentiated call carry the scope
    (``harness/scopes.py under`` decides, as the benchmark's readers do)."""
    model, variables, tokens = tiny
    grad = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p, "batch_stats": variables["batch_stats"]}, tokens
    ).sum()))
    text = grad.lower(variables["params"]).compile().as_text()
    import re

    names = set(re.findall(r'op_name="([^"]*)"', text))
    found = {scopes.phase_of(n) for n in names if scopes.under(n, scope)}
    assert {"forward", "backward"} <= found, found
    if scope == "attention_window":  # inside ``attention``, and not every call
        inside = [n for n in names if scopes.under(n, scope)]
        assert all(scopes.under(n, "attention") for n in inside)
        assert any(
            scopes.under(n, "attention") and not scopes.under(n, scope)
            for n in names
        )


# ---------------------------------------------------------------- trainer

ARGV = [
    "--synthetic-data", "--no-progress", "--num-devices", "1",
    "--model", "afmoe_tiny", "--model-cut", CUT, "--seq-len", "32",
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
    float32 passes the float32 tolerance, the moved bias included; bf16
    passes its own and fails float32's."""
    from harness import compare

    hp = load_config("tpu", [
        *ARGV, "--ckpt-path", str(tmp_path), "--precision", precision,
    ])
    trainer = Trainer(hp)
    try:
        config = _tiny_compare_config()
        out = compare.first_step(
            trainer, config, 2**31 + 31, BENCH / "reference" / "trinity_mini_ep16.py"
        )
    finally:
        trainer.close()
    assert out["ok"], out
    assert set(out["errors"]) == set(out["tolerance"])
    strict = config["compare"]["tolerance"]["fp32"]
    fails_float32 = any(out["errors"][k] > strict[k] for k in strict)
    assert fails_float32 == (precision == "bf16"), out["errors"]


def test_trainer_fits_tokens_saves_and_resumes_the_bias(tmp_path):
    events = []
    hp = load_config("tpu", [*ARGV, "--ckpt-path", str(tmp_path), "--epoch", "2"])
    trainer = Trainer(hp)
    trainer.bus.subscribe(events.append)
    start = plain(trainer.state.batch_stats)
    version = trainer.fit()
    trainer.close()
    ends = [e["payload"] for e in events if e.get("kind") == "epoch_end"]
    assert len(ends) == 2 and ends[1]["train_loss"] < ends[0]["train_loss"]
    compiled = {
        k: v for e in events if e.get("kind") == "compile"
        for k, v in (e["payload"].get("kernel_paths") or {}).items()
    }
    assert compiled == {"attention": "composed", "moe_gmm": "ragged_dot"}
    counted = [
        e["payload"]["metrics"] for e in events if e.get("kind") == "metrics"
        and "moe/rows" in e["payload"]["metrics"]
    ]
    assert counted[0]["moe/rows"]["n"] > 0
    # max - min of a bias that 18 steps moved by 0.001 each, from zero
    assert 0.001 <= counted[0]["moe/bias_spread"]["value"] <= 2 * 18 * 0.001
    assert counted[1]["moe/bias_spread"]["value"] > counted[0]["moe/bias_spread"]["value"]
    moved = plain(trainer.state.batch_stats)["layers_1"]["moe"]["expert_bias"]
    assert np.abs(start["layers_1"]["moe"]["expert_bias"]).max() == 0
    assert 0.001 <= np.abs(moved).max() <= 36 * 0.002
    # it never met the optimizer: no moment has its shape's path
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(trainer.state.opt_state)]
    assert not [p for p in paths if "expert_bias" in p]
    vdir = tmp_path / f"version-{version}"
    assert (vdir / "last.ckpt").exists()

    hp = load_config("tpu", [
        *ARGV, "--ckpt-path", str(tmp_path / "again"), "--epoch", "3",
        "--resume", str(vdir / "last.ckpt"),
    ])
    resumed = Trainer(hp)
    assert resumed.start_epoch == 2
    np.testing.assert_array_equal(
        plain(resumed.state.batch_stats)["layers_1"]["moe"]["expert_bias"], moved
    )
    resumed.fit()
    after = plain(resumed.state.batch_stats)["layers_1"]["moe"]["expert_bias"]
    resumed.close()
    assert np.abs(after - moved).max() > 0  # and it moves on


# ----------------------------------------------------- the cell's own files


def test_configuration_holds_the_published_config_and_names_its_cut():
    """Every key of the catalog's ``config`` is in the file unchanged; what
    this chip holds is beside it, each held value under ``reduced`` with
    its arithmetic, and what the config's keys do not carry under
    ``assumed`` with its source.  ``parameters_held`` is ``jax.eval_shape``'s
    count."""
    body = json.loads(CONFIG_FILE.read_text())
    published = afmoe.TRINITY_MINI
    differs = [k for k, v in published.items() if body.get(k) != v]
    assert not differs, differs
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == body["name"])
    assert entry["source"] == body["source"]
    assert body["argv"][body["argv"].index("--model-cut") + 1] == CELL_CUT
    cut = afmoe.cut_config(published, afmoe.parse_cut(CELL_CUT))
    run_as = {
        "num_layers_held": cut["num_hidden_layers"],
        "num_dense_layers_held": cut["num_dense_layers"],
        "num_experts_held": cut["num_experts_held"],
        "vocab_rows_held": cut["vocab_size"],
    }
    assert {k: body[k] for k in run_as} == run_as
    assert body["layer_types_held"] == cut["layer_types"]
    assert list(reference.ARCH["layer_types"]) == cut["layer_types"]
    assert body["first_expert_held"] == cut["first_expert"]
    assert set(body["reduced"]) == set(run_as) == set(entry["reduced"])
    for reason in body["reduced"].values():
        assert "->" in reason
    assert "sixteen chips share each layer's experts" in body["deployment"]
    assert "eight share the vocabulary" in body["deployment"]
    # the floors: a whole period and four layers after the dense one, 8
    # experts, an eighth of the vocabulary
    after_dense = cut["layer_types"][cut["num_dense_layers"]:]
    assert len(after_dense) >= 4 and set(after_dense) == set(published["layer_types"])
    assert cut["num_experts_held"] >= 8
    assert cut["vocab_size"] * 8 >= published["vocab_size"]
    widths = {
        k: v for k, v in cut.items()
        if k.endswith("_size") and k != "vocab_size" or "head" in k
        or k in ("num_experts", "num_experts_per_tok", "sliding_window")
    }
    assert widths == {k: published[k] for k in widths}
    text = " ".join(body["assumed"])
    for said in ("gate", "q_norm", "no position encoding", "four norms",
                 "sqrt(hidden_size)", "starts at zero", "initialiser", "AdamW",
                 "Markov", "8,192", "one document a sequence", "1e-20"):
        assert said in text, said
    model = get_model("trinity_mini", model_cut=CELL_CUT)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )
    count = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes["params"]))
    assert count == body["parameters_held"] == 504_147_200
    # the FLOP group and the comparison batch run the same cut
    group = body["flops"]
    assert group["layer_types"] == cut["layer_types"]
    assert (group["num_experts_held"], group["vocab_rows"]) == (8, 25024)
    assert (group["head_dim"], group["sliding_window"]) == (128, 2048)
    assert body["compare"]["vocab"] == cut["vocab_size"]
    assert body["compare"]["tokens"] == body["example"]["tokens"] == group["tokens"]


def test_flop_counts_by_hand():
    """356 M multiply-accumulates a token forward (ISSUE 31's arithmetic):
    attention 228 M (projections with the gate 136, scores and values 92, of
    which the four windowed layers 59), the dense layer 38, the shared
    experts 25, the routed experts 13, the head 51."""
    group = json.loads(CONFIG_FILE.read_text())["flops"]
    family = load(BENCH / "flops" / "afmoe.py")
    t, w, d, wide = 8192, 2048, 2048, 4096
    seen_sliding = sum(min(i + 1, w) for i in range(t))
    assert family.visible_keys(group, "sliding_attention") == seen_sliding
    assert family.visible_keys(group, "full_attention") == t * (t + 1) / 2
    projections = 5 * (3 * d * wide + 2 * d * 512)
    scores = 2 * wide * (4 * seen_sliding + t * (t + 1) / 2) / t
    rest = 3 * d * 6144 + 4 * (d * 128 + (1 + 8 * 8 / 128) * 3 * d * 1024) + d * 25024
    per_token = flops.train_flops_per_image(group) / 3 / t
    assert per_token == pytest.approx(2 * (projections + scores + rest))
    assert per_token == pytest.approx(2 * 356.39e6, rel=1e-4)
    assert projections == pytest.approx(136.3e6, rel=1e-3)
    assert scores == pytest.approx(92.3e6, rel=1e-3)
    # the kernels' counts: both kinds of layer, and the sliding ones alone
    both, sliding = family.attention_flops(1, group), family.window_attention_flops(1, group)
    assert both == pytest.approx(3 * 2 * scores * t)
    assert sliding == pytest.approx(3 * 2 * 2 * wide * 4 * seen_sliding)
    assert family.window_attention_bytes(1, group) == pytest.approx(
        0.8 * family.attention_bytes(1, group)
    )
    assert family.moe_gmm_flops(4096, group) == 3 * 2 * 4096 * 3 * d * 1024


def test_cell_runs_the_recipe_its_issue_names():
    """AdamW at a constant 3e-4 under the launcher's default save cadence,
    one 8,192-token sequence a step, 32 steps an epoch, 4 validation
    sequences; the comparison's reference takes the same optimizer numbers
    as the argv."""
    from distributed_training_comparison_tpu.data.sampler import train_val_split

    body = json.loads(CONFIG_FILE.read_text())
    hp = load_config("tpu", ["--synthetic-data", *body["argv"]])
    default = load_config("tpu", ["--synthetic-data"])
    assert (hp.optimizer, hp.lr, hp.lr_decay_gamma) == ("adamw", 3e-4, 1.0)
    assert hp.save_last_min_secs == default.save_last_min_secs
    assert (hp.batch_size, hp.seq_len, hp.remat, hp.amp) == (1, 8192, True, True)
    train, valid = train_val_split(
        hp.limit_examples, valid_size=0.1, seed=0, valid_count=hp.valid_examples
    )
    assert (len(train), len(valid)) == (32, 4)
    recipe = body["compare"]["recipe"]
    assert (recipe["lr"], recipe["weight_decay"]) == (hp.lr, hp.weight_decay)
    cell = json.loads((BENCH / "workloads" / "trinity_ep16_seq8k_job.json").read_text())
    assert cell["expect"]["kernel_paths"] == {
        "attention": ["composed", "pallas"], "moe_gmm": ["ragged_dot", "megablox"],
    }
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    reported = {
        m["name"] for m in spec["per_layer"]
        if cell["name"] in m.get("workloads", [cell["name"]])
    }
    assert {"window_attention_ms_per_step", "window_attention_roofline_pct",
            "shared_expert_ms_per_step", "attention_roofline_pct",
            "moe_gmm_roofline_pct", "step_mfu_pct"} <= reported


def test_the_window_readers_share_is_the_accepted_readers_arithmetic(monkeypatch):
    """``harness/roofline.py share`` on a stub of a traced run: the new
    reader gives the band's count over the scope's time, the accepted
    ``attention_roofline_pct`` gives what the helper gives for its own
    scope and functions, and a composed path, a program without the scope
    or a parent without the family's functions reads nothing."""
    from types import SimpleNamespace

    from harness import roofline

    body = json.loads(CONFIG_FILE.read_text())
    run = SimpleNamespace(
        setup_compiles=[{"name": "device_chunk_runner@k32",
                         "kernel_paths": {"attention": "pallas"}}],
        mix={"train_program": "device_chunk_runner"}, config=body,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        traced_steps=64, window={"batch_size": 1},
    )
    ms = {"attention_window": 68.53, "attention": 96.33}
    monkeypatch.setattr(
        scopes, "train_ms_per_step",
        lambda run, pick: next(
            (v for k, v in ms.items() if pick(f"jit(f)/{k}/dot")), None
        ),
    )
    family = load(BENCH / "flops" / "afmoe.py")
    window = load(BENCH / "layer_metrics" / "window_attention_roofline_pct.py").read
    accepted = load(BENCH / "layer_metrics" / "attention_roofline_pct.py").read
    want = 100 * family.window_attention_flops(64, body["flops"]) / 197e12 / (0.06853 * 64)
    assert window(run) == pytest.approx(want) and 0 < want < 100
    ms.pop("attention_window")
    assert accepted(run) == pytest.approx(roofline.share(
        run, "attention", "attention", "attention_flops", "attention_bytes"
    ))
    assert window(run) is None  # no op under the scope: the parent's program
    ms["attention_window"] = 68.53
    run.setup_compiles[0]["kernel_paths"]["attention"] = "composed"
    assert window(run) is None
    run.setup_compiles[0]["kernel_paths"]["attention"] = "pallas"
    assert roofline.share(run, "attention", "attention_window", "no_such", "nor_this") is None
    # the counter's reader on the same stub: the parent's event has no
    # ``flash_backward`` and reads nothing; the train program's events are
    # summed, another program's are not its call sites
    fused_pct = load(BENCH / "layer_metrics" / "flash_bwd_fused_pct.py").read
    assert fused_pct(run) is None
    run.setup_compiles[0]["flash_backward"] = {"fused": 4, "tiled": 1}
    run.setup_compiles.append({"name": "train_step", "flash_backward": {"tiled": 5}})
    assert fused_pct(run) == pytest.approx(80.0)
    run.setup_compiles.append(
        {"name": "device_chunk_runner@k4", "flash_backward": {"fused": 5}}
    )
    assert fused_pct(run) == pytest.approx(90.0)
