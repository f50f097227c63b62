"""The FLOP functions against counts made by hand, layer by layer."""

import json
from pathlib import Path

import pytest

from harness import flops, peaks

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["flops"]


def test_resnet18_cifar_by_hand():
    # multiply-accumulates per image, 32x32 input
    stem = 9 * 3 * 64 * 32 * 32
    stage1 = 4 * (9 * 64 * 64 * 32 * 32)  # two blocks of two 3x3 convs
    stage2 = (9 * 64 * 128 + 9 * 128 * 128 + 64 * 128) * 16 * 16 \
        + 2 * 9 * 128 * 128 * 16 * 16  # strided block with projection, then one plain
    stage3 = (9 * 128 * 256 + 9 * 256 * 256 + 128 * 256) * 8 * 8 \
        + 2 * 9 * 256 * 256 * 8 * 8
    stage4 = (9 * 256 * 512 + 9 * 512 * 512 + 256 * 512) * 4 * 4 \
        + 2 * 9 * 512 * 512 * 4 * 4
    head = 512 * 100
    macs = stem + stage1 + stage2 + stage3 + stage4 + head
    assert macs == 555_468_800  # the usual 0.56 GMAC of a CIFAR ResNet-18
    assert flops.resnet_forward_flops(cfg("resnet18_cifar100")) == 2.0 * macs
    assert flops.train_flops_per_image(cfg("resnet18_cifar100")) == 6.0 * macs


def test_deit_small_p2_by_hand():
    s, d, m = 256, 384, 1536
    per_token_block = 4 * d * d + 2 * d * m + 2 * s * d  # qkv+proj, MLP, scores+values
    macs = s * (12 * per_token_block + 2 * 2 * 3 * d) + d * 100
    assert per_token_block == 589_824 + 1_179_648 + 196_608
    assert flops.vit_forward_flops(cfg("vit_small_cifar100_p2")) == 2.0 * macs
    assert flops.train_flops_per_image(cfg("vit_small_cifar100_p2")) == pytest.approx(
        36.246e9, rel=1e-4
    )


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
