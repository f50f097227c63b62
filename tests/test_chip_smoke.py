"""CPU rehearsal of ``chip_smoke.py``: the control flow of every phase, and
the refusal to pass anywhere but on a TPU.

The smoke's real run needs the chip (the builder's tool runs it there).
What a CPU can pin is that the script walks every phase through the real
entry points at a tiny size, reads its own artifacts back correctly, and
still ends ``"ok": false`` / non-zero for want of a TPU *after* the phases
passed — and that without ``--rehearse`` it runs nothing and prints no
result at all.

The two full rehearsals are ``slow``-marked (a minute or more each: every
phase pays a Trainer start-up and its compiles, and the tier-1 command's
time limit has little room); the refusals and the artifact reader stay in
the fast gate.  Run them after any change to ``chip_smoke.py`` or to what
it drives: ``pytest tests/test_chip_smoke.py``.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

from distributed_training_comparison_tpu.resilience.elastic import (  # noqa: E402
    forced_host_device_env,
)
from distributed_training_comparison_tpu.utils.tensorboard import (  # noqa: E402
    SummaryWriter,
)


def _smoke(args, n_devices=1, env=None, timeout=600):
    env = forced_host_device_env(n_devices) if env is None else env
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=timeout,
    )
    records = [
        json.loads(line) for line in proc.stdout.splitlines()
        if line.startswith('{"')
    ]
    return proc, records


@pytest.mark.slow  # ~60-85 s: five Trainer/serve start-ups, too much for tier-1's limit
def test_rehearsal_walks_every_phase_and_never_passes():
    proc, records = _smoke(["--rehearse"])
    assert proc.returncode == 1, proc.stderr[-2000:]
    *phases, verdict = records
    assert [p["phase"] for p in phases] == [
        "train", "vit_tiny_p2", "vit_moe", "vit_long", "serve",
    ]
    # every phase passed its own checks on the CPU ...
    assert {p["phase"]: p["ok"] for p in phases} == {
        p["phase"]: True for p in phases
    }, proc.stderr[-2000:]
    train = phases[0]
    assert len(train["losses"]) == 4 and "test_loss" in train["results"]
    assert any(name.startswith("device_chunk_runner") for name, _, _ in train["compiles"])
    assert phases[1]["kernel_paths"]["vit_block"] == "composed"  # off the TPU
    assert phases[-1]["replies"]["checked"] == 48
    # ... and the verdict is still a failure, as the LAST line of stdout
    assert verdict == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert json.loads(proc.stdout.splitlines()[-1]) == verdict
    assert not (REPO / ".chip_smoke").exists()  # cleans up after itself


@pytest.mark.slow
def test_rehearsal_across_four_virtual_devices():
    proc, records = _smoke(["--rehearse", "--chips", "4"], n_devices=4)
    assert proc.returncode == 1, proc.stderr[-2000:]
    *phases, verdict = records
    assert [p["phase"] for p in phases] == [
        "one_device", "data4", "data2_model2", "compare",
    ]
    assert all(p["ok"] for p in phases), proc.stdout[-3000:]
    assert phases[1]["batch_devices"] == 4 and phases[2]["shards_per_split_leaf"] == [2]
    assert verdict == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }


def test_without_a_tpu_nothing_runs_and_no_result_is_printed():
    proc, records = _smoke([])
    assert proc.returncode == 2
    assert proc.stdout == "" and records == []
    assert "no TPU found" in proc.stderr


def test_alone_without_the_program_it_fails_and_prints_no_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo
    the script must fail before it prints anything."""
    (tmp_path / "chip_smoke.py").write_bytes((REPO / "chip_smoke.py").read_bytes())
    env = forced_host_device_env(1)
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse"], cwd=str(tmp_path),
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and proc.stdout == ""
    assert "distributed_training_comparison_tpu" in proc.stderr


def test_rehearse_is_honoured_only_under_an_explicit_cpu():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc, records = _smoke(["--rehearse"], env=env)
    assert proc.returncode == 2
    assert proc.stdout == "" and "JAX_PLATFORMS=cpu" in proc.stderr


def test_chips_option_must_match_the_devices_found():
    proc, _ = _smoke(["--rehearse", "--chips", "4"], n_devices=1)
    assert proc.returncode == 2
    assert proc.stdout == "" and "--chips 4" in proc.stderr


def test_step_losses_reads_back_exactly_what_the_trainer_logged(tmp_path):
    """The smoke reads per-step losses from the run's own TensorBoard file:
    exact float32, in step order, other tags ignored."""
    losses = np.asarray([4.6051702, 3.25, 1e-7, 123456.789], np.float32)
    with SummaryWriter(tmp_path / "tb") as w:
        w.add_scalar("lr", 0.1, 0)
        for step, loss in enumerate(losses):
            w.add_scalar("loss/step", float(loss), step + 300)  # 2-byte varint
            w.add_scalar("loss/step/other", 9.0, step)
        w.add_scalar("loss/epoch/train", 2.0, 0)
    got = chip_smoke.step_losses(tmp_path)
    assert np.asarray(got, np.float32).tobytes() == losses.tobytes()
