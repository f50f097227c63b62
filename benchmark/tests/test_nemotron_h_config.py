"""``nemotron3_nano_30b_a3b_ep16``, the benchmark's side: the published keys
against the catalog's row, the FLOP family by hand (the scan's count and the
expert layers' share of the grouped matmul's bytes included), the new
readers' arithmetic, and the plain reference against the program at the
configuration's own rehearsal sizes through ``harness.compare`` — float32
agrees to rounding, bf16 within the configuration's bf16 tolerance and not
within its float32 one.  (The program's own tests, ``tests/test_nemotron_h.py``,
compare every gradient.)
"""

import json
import types
from pathlib import Path

import pytest

from harness import compare, flops, load_module

BENCH = Path(__file__).resolve().parents[1]
CONFIG = json.loads(
    (BENCH / "configs" / "nemotron3_nano_30b_a3b_ep16.json").read_text()
)
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CELL = "nemotron3nano_ep16_seq8k_job"


def test_published_keys_are_the_catalog_rows():
    if not CATALOG.exists():
        pytest.skip("the driver's catalog is not installed here")
    row = next(
        json.loads(line) for line in CATALOG.read_text().splitlines()
        if json.loads(line)["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
    )
    assert row["source_url"] == CONFIG["source"]
    assert {k: CONFIG.get(k, "missing") for k in row["config"]} == row["config"]
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == list(CONFIG["reduced"])
    assert not any("hidden" in key for key in entry["reduced"])


def test_flop_count_by_hand():
    p = CONFIG["flops"]
    family = load_module(BENCH / "flops" / "nemotron_h.py")
    t, d = 8192, 2688
    # a token forward, multiply-accumulates: three Mamba-2 mixers (the
    # projection to z | xBC | dt, the scan at chunk 128, the output
    # projection), one attention layer (q, k, v, o; scores and values over
    # half the keys), three expert layers (router, shared expert, 6 x 8 /
    # 128 of a routed expert, two products each), the head
    scan = 8 * 128 * 128 + 64 * 128 * 64 + 2 * 64 * 128 * 64
    mamba = d * 10304 + scan + 4096 * d
    attn = d * (4096 + 256 + 256) + 4096 * d + 2 * 4096 * (t + 1) / 2
    expert_layer = d * 128 + 2 * d * 3712 + 0.375 * 2 * d * 1856
    per_token = 3 * mamba + attn + 3 * expert_layer + d * 16384
    assert flops.train_flops_per_image(p) == pytest.approx(3 * 2 * per_token * t)
    assert per_token == pytest.approx(294.35e6, rel=1e-4)
    assert 3 * 2 * per_token * t == pytest.approx(14.468e12, rel=1e-4)
    assert family.ssd_scan_macs_per_token(p) == scan == 1_703_936
    assert family.ssd_scan_flops(1, p) == 3 * 2 * 3 * t * scan
    # x, B, C in bf16 and dt in float32 in, y out; those and dO in, four out
    forward = 2 * (2 * 4096 + 2048) + 64 * 4
    backward = 2 * (2 * (4096 + 2048) + 4096) + 2 * 64 * 4
    assert family.ssd_scan_bytes(1, p) == 3 * t * (forward + backward)
    # a whole step's scan at the v5e's peaks: 1.28 ms of products, 1.62 of bytes
    assert family.ssd_scan_flops(1, p) / 197e12 == pytest.approx(1.275e-3, rel=1e-3)
    assert family.ssd_scan_bytes(1, p) / 819e9 == pytest.approx(1.621e-3, rel=1e-3)
    assert family.attention_flops(1, p) == 3 * 2 * 2 * 4096 * t * (t + 1) / 2
    assert family.attention_bytes(1, p) == 2 * t * 6 * (4096 + 256)


def test_grouped_matmul_bytes_count_the_expert_layers_only():
    """``moe_gmm_roofline_pct`` hands every family ``traced steps x (layers -
    leading dense ones)`` as its layer calls, seven a step here; three of
    the seven are expert layers, and the weights stream once a call of
    those."""
    p = CONFIG["flops"]
    family = load_module(BENCH / "flops" / "nemotron_h.py")
    steps = 64
    handed = steps * (len(p["layer_types"]) - p["num_dense_layers"])
    assert handed == 7 * steps and p["layer_types"].count("moe") == 3
    weights = 2 * 3 * 8 * 2 * 2688 * 1856  # bf16; read, read again, written
    assert family.moe_gmm_bytes(0, handed, p) == 3 * steps * weights
    rows = 3 * steps * 3072
    per_row = 2 * 2 * 2 * (2688 + 2 * 1856)  # bf16, both directions, in + out
    assert family.moe_gmm_bytes(rows, handed, p) == 3 * steps * weights + rows * per_row
    assert family.moe_gmm_flops(rows, p) == 3 * 2 * rows * 2 * 2688 * 1856


def test_the_new_readers_divide_what_they_say():
    """``ssd_scan_roofline_pct``, ``ssd_scan_ms_per_step``, ``mamba_ms_per_step``
    and ``ssm_decay_mean`` on a hand-made run: the scope's time, the
    family's counts, the gauge's mean over the window's epochs; ``None``
    where the program has nothing to read (the parent's side)."""
    from harness import scopes

    read = lambda name: load_module(BENCH / "layer_metrics" / f"{name}.py").read  # noqa: E731
    events = [
        {"kind": "metrics", "payload": {"metrics": {"ssm/decay_mean": {"value": v}}}}
        for v in (0.8, 0.9)
    ]
    run = types.SimpleNamespace(
        config=CONFIG, traced_steps=64, window={"batch_size": 1},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        clock=types.SimpleNamespace(in_window=lambda kind: events),
    )
    ms = {"ssd_scan": 20.0, "mamba": 90.0}
    real = scopes.train_ms_per_step
    scopes.train_ms_per_step = lambda run, keep: next(
        (v for k, v in ms.items() if keep(f"jit(f)/jvp(NemotronH)/layers_0/mamba/{k}/mul")
         and not keep("jit(f)/jvp(NemotronH)/layers_1/moe/mul")), 0.0
    )
    try:
        assert read("ssd_scan_ms_per_step")(run) == 20.0
        assert read("mamba_ms_per_step")(run) == 20.0  # the first scope under mamba
        # bytes bind: 1.6209 ms a step of the 20 measured
        assert read("ssd_scan_roofline_pct")(run) == pytest.approx(8.104, rel=1e-3)
        assert read("ssm_decay_mean")(run) == pytest.approx(0.85)
        ms.clear()  # a program without the scopes: nothing, and no error
        assert read("ssd_scan_roofline_pct")(run) is None
        assert read("ssd_scan_ms_per_step")(run) is None
        assert read("mamba_ms_per_step")(run) is None
        events.clear()
        assert read("ssm_decay_mean")(run) is None
    finally:
        scopes.train_ms_per_step = real
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    mine = {m["name"]: m for m in spec["per_layer"] if m.get("workloads") == [CELL]}
    assert set(mine) == {
        "mamba_ms_per_step", "ssd_scan_ms_per_step", "ssd_scan_roofline_pct",
        "ssm_decay_mean",
    }
    assert all(m["moves"] == "images_per_s_per_chip" for m in mine.values())


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_reference_matches_program_at_the_rehearsal_sizes(tmp_path, precision):
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.train import Trainer

    argv = [
        "--synthetic-data", "--no-progress", "--num-devices", "1",
        "--ckpt-path", str(tmp_path), *CONFIG["argv"], *CONFIG["rehearse_argv"],
        "--precision", precision,
    ]
    config = {"compare": {**CONFIG["compare"], **CONFIG["rehearse_compare"]}}
    trainer = Trainer(load_config("tpu", argv))
    try:
        out = compare.first_step(trainer, config, 40, BENCH / CONFIG["reference"])
    finally:
        trainer.close()
    assert out["precision"] == precision
    strict = config["compare"]["tolerance"]["fp32"]
    assert any(out["errors"][k] > strict[k] for k in strict) == (precision == "bf16")
    # the bf16 limit on the gradient norm is the chip's at 8,192 tokens of
    # width 2,688; a test width's norm averages less rounding away
    roomy = {**out["tolerance"], "grad_norm_rel": 0.02}
    assert all(out["errors"][k] <= roomy[k] for k in roomy), out
    assert out["ok"] or precision == "bf16", out
