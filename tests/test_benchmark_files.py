"""``BENCHMARK.json`` finds its files, and the token configuration states
its cut.  ``benchmark/tests/test_contract.py`` checks the contract's form;
tier-1 does not collect ``benchmark/tests/``, so the part a program PR can
break — a name without its file, a FLOP family that does not resolve, a
reference without its three functions, a configuration that drifts from
the published one unsaid — is checked here too."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from lfm2_reference import BENCH, ROOT, load

from harness import flops  # noqa: E402  (lfm2_reference puts benchmark/ on the path)

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in SPEC["configs"]}
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW = "lfm2_24b_a2b_ep8"


def held(config_name):
    return json.loads((ROOT / CONFIGS[config_name]["file"]).read_text())


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files(cell):
    body = json.loads((BENCH / "workloads" / f"{cell['name']}.json").read_text())
    assert (body["config"], body["traffic"], body["chips"]) == (
        cell["config"], cell["traffic"], cell["chips"]
    )
    assert cell["config"] in CONFIGS
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    assert isinstance(body["expect"]["kernel_paths"], dict)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_configuration_finds_its_reference_and_flop_family(name):
    body = held(name)
    assert set(CONFIGS[name]["reduced"]) == set(body["reduced"])
    reference = load(BENCH / body["reference"])
    for function in ("forward", "make_batch", "step"):
        assert callable(getattr(reference, function, None)), function
    assert flops.train_flops_per_image(body["flops"]) > 0
    assert {"batch", "recipe", "tolerance"} <= set(body["compare"])
    assert body["argv"] and isinstance(body.get("assumed"), list)


@pytest.mark.parametrize(
    "metric", SPEC["end_to_end"] + SPEC["per_layer"], ids=lambda m: m["name"]
)
def test_every_metric_finds_its_reader_and_its_cells(metric):
    folder = "end_to_end" if "bound" in metric else "layer_metrics"
    assert callable(load(BENCH / folder / f"{metric['name']}.py").read)
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_cifar_resnet18_flop_count_matches_the_published_macs():
    # 0.557 GMACs for CIFAR ResNet-18 at 32x32 -> 1.111 GFLOP forward
    group = held("resnet18_cifar100")["flops"]
    assert flops.resnet_forward_flops(group) == pytest.approx(1.111e9, rel=0.01)


def test_train_is_three_forwards():
    group = held("resnet18_cifar100")["flops"]
    assert flops.train_flops_per_image(group) == pytest.approx(
        3 * flops.resnet_forward_flops(group), rel=1e-9
    )


@pytest.mark.parametrize(
    "cell", [w["name"] for w in SPEC["workloads"]] + ["no_such_cell"]
)
def test_benchmark_refuses_without_a_chip(cell, tmp_path):
    """A measurement path that finds no chip fails: under an explicit
    ``JAX_PLATFORMS=cpu`` the instrument exits 2 with the reason on stderr
    and not one byte on stdout (the driver parses stdout), in every cell
    and for a cell that does not exist."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout == ""
    assert "benchmark: " in proc.stderr


def test_token_configuration_holds_the_published_config_and_names_its_cut():
    """Every key of the catalog's ``config`` is in the file unchanged; what
    this chip holds is beside it, each held value under ``reduced`` with
    the deployment, and each size the catalog does not give under
    ``assumed``.  No width is among the reduced keys."""
    from distributed_training_comparison_tpu.models import lfm2

    body = held(NEW)
    published = lfm2.LFM2_24B_A2B
    if CATALOG.exists():
        row = next(
            json.loads(line) for line in CATALOG.read_text().splitlines()
            if json.loads(line)["source_url"] == body["source"]
        )
        assert row["config"] == published
    differs = [k for k, v in published.items() if body.get(k) != v]
    assert not differs, differs
    cut = lfm2.cut_config(published, lfm2.parse_cut(
        body["argv"][body["argv"].index("--model-cut") + 1]
    ))
    run_as = {
        "num_layers_held": cut["num_hidden_layers"],
        "num_dense_layers_held": cut["num_dense_layers"],
        "num_experts_held": cut["num_experts_held"],
        "vocab_rows_held": cut["vocab_size"],
    }
    assert {k: body[k] for k in run_as} == run_as
    assert body["layer_types_held"] == cut["layer_types"]
    assert body["first_expert_held"] == cut["first_expert"]
    assert set(body["reduced"]) == set(run_as)  # each held value, with its reason
    for reason in body["reduced"].values():
        assert "->" in reason
    assert "eight chips share each layer" in body["deployment"]
    # the floors: a whole period and four layers after the dense one, 8
    # experts, an eighth of the vocabulary
    after_dense = cut["layer_types"][cut["num_dense_layers"]:]
    assert len(after_dense) >= 4 and "full_attention" in after_dense
    assert cut["num_experts_held"] >= 8
    assert cut["vocab_size"] * 8 >= published["vocab_size"]
    widths = {
        k: v for k, v in cut.items()
        if k.endswith("_size") and k != "vocab_size" or "heads" in k
        or k in ("num_experts", "num_experts_per_tok", "conv_L_cache")
    }
    assert widths == {k: published[k] for k in widths}
    text = " ".join(body["assumed"])
    for said in ("tied", "initialiser", "AdamW", "selection bias",
                 "4,096", "one document a sequence"):
        assert said in text, said
    # the FLOP group and the comparison batch run the same cut
    group = body["flops"]
    assert group["layer_types"] == cut["layer_types"]
    assert (group["num_experts_held"], group["vocab_rows"]) == (8, 8192)
    assert body["compare"]["vocab"] == cut["vocab_size"]
    assert body["compare"]["tokens"] == body["example"]["tokens"] == group["tokens"]


def test_flop_count_of_the_token_configuration_by_hand():
    """389 MFLOP a token forward: dense layer 178, attention layer 48,
    three convolution layers 43 each, head 34 (ISSUE 27's arithmetic)."""
    group = held(NEW)["flops"]
    per_token = flops.train_flops_per_image(group) / 3 / group["tokens"]
    d, f, m, v, t = 2048, 11776, 1536, 8192, 4096
    conv = d * 3 * d + 3 * d + d * d
    attn = 2 * d * d + 2 * d * 512 + d * (t + 1)
    experts = d * 64 + 4 * 8 / 64 * 3 * d * m
    macs = (conv + 3 * d * f) + (attn + experts) + 3 * (conv + experts) + d * v
    assert per_token == pytest.approx(2 * macs) == pytest.approx(389.07e6, rel=1e-4)


def test_token_cell_runs_the_recipe_its_issue_names():
    """AdamW at a constant 3e-4 under the launcher's default save cadence,
    4 sequences a step, 16-32 steps an epoch; the first-step comparison's
    reference takes the same optimizer numbers as the argv."""
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.data.sampler import train_val_split

    body = held(NEW)
    hp = load_config("tpu", ["--synthetic-data", *body["argv"]])
    default = load_config("tpu", ["--synthetic-data"])
    assert (hp.optimizer, hp.lr, hp.lr_decay_gamma) == ("adamw", 3e-4, 1.0)
    assert hp.save_last_min_secs == default.save_last_min_secs
    assert (hp.batch_size, hp.seq_len, hp.remat) == (4, 4096, True)
    train, _ = train_val_split(hp.limit_examples, valid_size=0.1, seed=0)
    assert 16 <= len(train) // hp.batch_size <= 32
    recipe = body["compare"]["recipe"]
    assert (recipe["lr"], recipe["weight_decay"]) == (hp.lr, hp.weight_decay)
