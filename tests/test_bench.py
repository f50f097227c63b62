"""Bench-harness unit tests: the analytic FLOP model.

The throughput/MFU numbers the driver records are only as honest as this
formula; pin it against published reference points (torchvision MAC counts
× 2) so architecture edits that break the accounting fail loudly.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent.parent))

from bench import forward_flops_per_image, train_flops_per_image  # noqa: E402


def test_cifar_resnet18_flops():
    # 0.557 GMACs for CIFAR ResNet-18 at 32×32 → 1.11 GFLOPs forward
    assert forward_flops_per_image("resnet18") == pytest.approx(1.111e9, rel=0.01)


def test_imagenet_resnet50_flops_match_published():
    # torchvision resnet50 @224: 4.09 GMACs → 8.18 GFLOPs forward
    f = forward_flops_per_image("resnet50", 1000, 224, "imagenet")
    assert f == pytest.approx(8.18e9, rel=0.01)


def test_imagenet_resnet18_flops_match_published():
    # torchvision resnet18 @224: 1.81 GMACs → 3.63 GFLOPs forward
    f = forward_flops_per_image("resnet18", 1000, 224, "imagenet")
    assert f == pytest.approx(3.63e9, rel=0.01)


def test_train_is_three_forwards():
    assert train_flops_per_image("resnet50", 224, "imagenet") == pytest.approx(
        3 * forward_flops_per_image("resnet50", image_size=224, stem="imagenet"),
        rel=1e-9,
    )


def test_run_legs_isolates_leg_failures(monkeypatch):
    """One leg blowing up (the round-3 failure mode: a compile OOM) must
    record an error for that leg only — every other leg's numbers survive."""
    import bench

    def fake_bench_native(mesh, images, labels, model_name, *a, **kw):
        if model_name == "resnet50":
            raise RuntimeError("Mosaic scoped vmem OOM (simulated)")
        return 1000.0

    monkeypatch.setattr(bench, "bench_native", fake_bench_native)
    configs = [
        ("leg_ok", "resnet18", "bf16", 64, 32, "cifar", 128, 1, {}),
        ("leg_boom", "resnet50", "bf16", 64, 32, "cifar", 128, 1, {}),
        ("leg_vit", "vit_tiny", "bf16", 64, 32, "cifar", 128, 1, {}),
    ]
    per_config, data_cache = bench.run_legs(None, configs, 1, 197e12)
    assert per_config["leg_ok"]["images_per_sec_per_chip"] == 1000.0
    assert "vmem OOM" in per_config["leg_boom"]["error"]
    # tokens/s derived for transformer legs (64 tokens at 32px / patch 4)
    assert per_config["leg_vit"]["tokens_per_sec_per_chip"] == 64_000
    # the caller resolves the baseline leg's data from this cache by the
    # headline config's (n, image_size)
    assert (128, 32) in data_cache


def _full_record(n_legs: int = 12, n_flash: int = 10) -> dict:
    """A record shaped like a real full-size TPU run: every leg populated,
    long float values, one errored leg."""
    configs = {
        f"resnet50_bf16_bs128_224px_leg{i}": {
            "images_per_sec_per_chip": 34710.4,
            "train_flops_per_image": 24.524,
            "achieved_tflops": 123.67,
            "mfu": 0.6278,
            "tokens_per_sec_per_chip": 1529234,
        }
        for i in range(n_legs)
    }
    configs["leg_boom"] = {"error": "XlaRuntimeError: " + "x" * 480}
    return {
        "metric": "cifar100_resnet18_train_throughput",
        "value": 34710.4,
        "unit": "images/sec/chip",
        "vs_baseline": 20.878,
        "detail": {
            "platform": "tpu",
            "device_kind": "TPU v5 lite",
            "chips": 1,
            "chip_peak_bf16_tflops": 197.0,
            "headline_key": "resnet50_bf16_bs128_224px_leg0",
            "configs": configs,
            "flash_attention": {
                "head_dim": 128,
                "heads": 8,
                "configs": {
                    f"s{2 ** (11 + i // 2)}"
                    + ("_causal" if i % 2 else ""): {
                        "fwd_tflops": 105.7,
                        "fwd_bwd_tflops": 99.6,
                    }
                    for i in range(n_flash)
                },
                "reference_impl_tflops": 13.0,
                "speedup": 6.8,
            },
            "reference_style_images_per_sec": 1662.5,
            "baseline_definition": "same chip, reference loop shape",
        },
    }


def test_compact_line_fits_driver_budget():
    """The driver parses the final stdout JSON line out of a bounded tail
    capture; r4's full-detail line overflowed it (BENCH_r04 parsed=null).
    The compact line must stay within budget at full-run size AND survive
    a simulated tail capture."""
    import json

    import bench

    line = bench.compact_line(_full_record())
    assert len(line) <= 1500
    parsed = json.loads(line)
    assert parsed["metric"] == "cifar100_resnet18_train_throughput"
    assert parsed["value"] == 34710.4
    assert parsed["vs_baseline"] == 20.878
    # per-leg numbers survive compaction
    assert parsed["detail"]["ips"]["resnet50_bf16_bs128_224px_leg0"] == 34710.4
    assert parsed["detail"]["ips"]["leg_boom"] == "err"
    assert parsed["detail"]["flash_fwd_bwd_tflops"]["s2048"] == 99.6
    # simulate the driver: keep only the tail of a stdout stream whose
    # last line is the record, then parse the final line
    stream = "some earlier stdout noise\n" * 50 + line + "\n"
    tail = stream[-2000:]
    final_line = tail.strip().rsplit("\n", 1)[-1]
    assert json.loads(final_line) == parsed


def test_main_emits_one_budgeted_line_and_detail_file(monkeypatch, tmp_path, capsys):
    """bench.main() end-to-end with the measurement fns stubbed: stdout
    must be exactly ONE parseable JSON line within the driver budget, the
    full record must land in BENCH_DETAIL.json, and the baseline leg must
    replay the headline config's workload (batch/data resolved by
    headline_key, not list position — ADVICE r4)."""
    import json
    import os

    import bench

    seen = {}

    def fake_native(mesh, images, labels, model_name, precision, batch, *a, **kw):
        return 1000.0 * batch

    def fake_ref_style(mesh, images, labels, batch, steps):
        seen["baseline_batch"] = batch
        seen["baseline_n"] = len(images)
        return 500.0

    monkeypatch.setattr(bench, "bench_native", fake_native)
    monkeypatch.setattr(bench, "bench_reference_style", fake_ref_style)
    monkeypatch.setattr(
        bench, "bench_flash_attention", lambda *a, **kw: {"configs": {}}
    )
    monkeypatch.chdir(tmp_path)
    # the rehearsal sizing is honoured only under an EXPLICIT cpu platform
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    bench.main()
    out = capsys.readouterr().out.strip()
    assert "\n" not in out  # ONE line
    assert len(out) <= 1500
    parsed = json.loads(out)
    # cpu config: bs64 → fake 64k img/s over the 8-device CPU mesh
    assert parsed["value"] == 8_000.0
    assert parsed["vs_baseline"] == 128.0  # 8000 * 8 chips / 500
    assert seen["baseline_batch"] == 64
    full = json.load(open("BENCH_DETAIL.json"))
    assert full["value"] == parsed["value"]
    assert full["detail"]["headline_key"] == parsed["detail"]["headline_key"]
    assert set(parsed["detail"]["ips"]) == set(full["detail"]["configs"])


def test_compact_line_degrades_instead_of_overflowing():
    """Pathologically many legs: the compact line drops verbose sections
    (mfu first) rather than exceed the budget — headline fields are never
    sacrificed."""
    import json

    import bench

    line = bench.compact_line(_full_record(n_legs=40, n_flash=20))
    assert len(line) <= 1500
    parsed = json.loads(line)
    assert parsed["value"] == 34710.4
    assert "mfu" not in parsed["detail"]  # dropped to fit


def _stub_measurements(monkeypatch, bench, native):
    monkeypatch.setattr(bench, "bench_native", native)
    monkeypatch.setattr(
        bench, "bench_reference_style", lambda *a, **kw: 500.0
    )
    monkeypatch.setattr(
        bench, "bench_flash_attention", lambda *a, **kw: {"configs": {}}
    )


def test_main_refuses_without_a_tpu_or_an_explicit_cpu(monkeypatch, tmp_path, capsys):
    """No chip and no explicit JAX_PLATFORMS=cpu: the default mode must
    refuse (non-zero, reason on stderr, NO result line) instead of silently
    sizing down to whatever backend answered."""
    import bench

    _stub_measurements(monkeypatch, bench, lambda *a, **kw: 1000.0)
    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code not in (0, None)
    assert "no TPU found" in str(ei.value.code)
    assert capsys.readouterr().out.strip() == ""
    assert not (tmp_path / "BENCH_DETAIL.json").exists()


def test_main_exits_nonzero_when_a_leg_errored(monkeypatch, tmp_path, capsys):
    """A failed leg still leaves the record (evidence over abort) — but the
    exit code must say the run was not whole."""
    import json

    import bench

    def boom(*a, **kw):
        raise RuntimeError("Mosaic scoped vmem OOM (simulated)")

    _stub_measurements(monkeypatch, bench, boom)
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code not in (0, None)
    assert "leg(s) failed" in str(ei.value.code)
    parsed = json.loads(capsys.readouterr().out.strip())
    assert parsed["value"] is None
    assert set(parsed["detail"]["ips"].values()) == {"err"}


@pytest.mark.parametrize(
    "mode",
    [
        "bench_serve", "bench_serve_fleet", "bench_trace", "bench_resilience",
        "bench_chaos", "bench_control", "bench_comms", "bench_parity",
        "bench_relayout", "bench_plan", "bench_pipeline",
    ],
)
def test_child_spawning_modes_refuse_without_explicit_cpu(monkeypatch, tmp_path, mode):
    """A chip belongs to one process: every mode whose parent touches JAX
    and then starts children that need a device must exit non-zero with a
    reason BEFORE it spawns anything, unless the environment explicitly
    asks for the CPU (where these CPU captures belong)."""
    import subprocess

    import bench

    def no_spawn(*a, **kw):
        raise AssertionError(f"{mode} spawned a child before refusing")

    monkeypatch.setattr(subprocess, "run", no_spawn)
    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as ei:
        getattr(bench, mode)()
    assert "refused" in str(ei.value.code)
    assert list(tmp_path.iterdir()) == []  # no capture written
