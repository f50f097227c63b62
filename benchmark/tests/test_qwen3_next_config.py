"""``qwen3_next_80b_a3b_ep32``, the benchmark's side: the FLOP family by
hand (the scan's count included), the new readers' arithmetic, and the plain
reference against the program at the configuration's own rehearsal sizes
through ``harness.compare`` — float32 agrees to rounding, bf16 within the
configuration's bf16 tolerance and not within its float32 one.  (The
program's own tests, ``tests/test_qwen3_next.py``, compare every gradient.)
"""

import json
from pathlib import Path

import pytest

from harness import compare, flops, load_module

BENCH = Path(__file__).resolve().parents[1]
CONFIG = json.loads((BENCH / "configs" / "qwen3_next_80b_a3b_ep32.json").read_text())


def test_flop_count_by_hand():
    p = CONFIG["flops"]
    family = load_module(BENCH / "flops" / "qwen3_next.py")
    t, d = 8192, 2048
    # a token forward, multiply-accumulates: three DeltaNet mixers
    # (projections to q | k | v | z and b | alpha, the scan at chunk 64, the
    # output projection), one attention layer (q with its gate, k, v, o;
    # scores and values over half the keys), four expert layers (router,
    # gated shared expert, 10 x 16 / 512 of a routed expert), the head
    scan = 32 * (64 * (3 * 128 + 2 * 128) + 3 * 128 * 128)
    gdn = d * (12288 + 64) + scan + 4096 * d
    attn = d * (8192 + 512 + 512) + 4096 * d + 2 * 4096 * (t + 1) / 2
    expert_layer = d * 512 + d + 3 * d * 512 + 0.3125 * 3 * d * 512
    per_token = 3 * gdn + attn + 4 * expert_layer + d * 18992
    assert flops.train_flops_per_image(p) == pytest.approx(3 * 2 * per_token * t)
    assert per_token == pytest.approx(230.14e6, rel=1e-4)
    assert family.gdn_scan_macs_per_token(p) == scan == 2_883_584
    assert family.gdn_scan_flops(1, p) == 3 * 2 * 3 * t * scan
    assert family.gdn_scan_bytes(1, p) == 3 * t * (2 * (6 * 2048 + 5 * 4096) + 6 * 32 * 4)
    # a whole step's scan at the v5e's peaks: 2.16 ms of products, 1.99 of bytes
    assert family.gdn_scan_flops(1, p) / 197e12 == pytest.approx(2.158e-3, rel=1e-3)
    assert family.gdn_scan_bytes(1, p) / 819e9 == pytest.approx(1.990e-3, rel=1e-3)


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
        out = compare.first_step(trainer, config, 35, BENCH / CONFIG["reference"])
    finally:
        trainer.close()
    assert out["precision"] == precision
    strict = config["compare"]["tolerance"]["fp32"]
    assert any(out["errors"][k] > strict[k] for k in strict) == (precision == "bf16")
    # the bf16 limit on the gradient norm is the chip's at 8,192 tokens of
    # width 2,048 (8e-4); a test width's norm averages less rounding away
    roomy = {**out["tolerance"], "grad_norm_rel": 0.02}
    assert all(out["errors"][k] <= roomy[k] for k in roomy), out
    assert out["ok"] or precision == "bf16", out
