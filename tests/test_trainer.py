"""End-to-end Trainer tests on the 8-device CPU mesh: fit → artifacts →
test → resume, with a tiny model standing in for the (CPU-prohibitive)
ResNet flagship.  This is the 'src/single slice end-to-end' of SURVEY.md §7
step 4, exercised hermetically."""

import numpy as np
import pytest

from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.train import Trainer

from test_train import TinyNet


def _hparams(tmp_path, extra=()):
    return load_config(
        "ddp",
        argv=[
            "--synthetic-data",
            "--limit-examples", "256",
            "--batch-size", "64",
            "--epoch", "2",
            "--eval-step", "2",
            "--lr", "0.05",
            "--ckpt-path", str(tmp_path),
            *extra,
        ],
    )


@pytest.fixture(scope="module")
def run_dir(tmp_path_factory):
    """One fit() shared by the artifact assertions below."""
    tmp_path = tmp_path_factory.mktemp("run")
    hp = _hparams(tmp_path)
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    version = trainer.fit()
    results = trainer.test()
    trainer.close()
    return tmp_path, version, results, trainer


def test_fit_returns_version_and_artifacts(run_dir):
    tmp_path, version, results, _ = run_dir
    vdir = tmp_path / f"version-{version}"
    assert version == 0
    assert (vdir / "experiment.log").exists()
    assert (vdir / "hparams.yaml").exists()
    assert (vdir / "last.ckpt").exists()
    assert list(vdir.glob("best_model_*.ckpt"))
    assert list((vdir / "tb").glob("events.out.tfevents.*"))
    log = (vdir / "experiment.log").read_text()
    assert "start training" in log and "val acc" in log


def test_hparams_yaml_roundtrip(run_dir):
    yaml = pytest.importorskip("yaml")
    tmp_path, version, _, _ = run_dir
    loaded = yaml.safe_load((tmp_path / f"version-{version}" / "hparams.yaml").read_text())
    assert loaded["batch_size"] == 64 and loaded["backend"] == "ddp"


def test_test_metrics_shape(run_dir):
    _, _, results, _ = run_dir
    assert set(results) == {"test_loss", "test_top1", "test_top5"}
    assert 0.0 <= results["test_top1"] <= results["test_top5"] <= 100.0
    assert results["test_loss"] > 0


def test_auto_resume_continues_latest_run(run_dir):
    """--auto-resume must reuse the newest version dir + last.ckpt instead
    of starting a fresh version."""
    src_tmp, version, _, trainer = run_dir
    hp = _hparams(src_tmp, extra=["--auto-resume", "--epoch", "3"])
    t2 = Trainer(hp, model=TinyNet(num_classes=100))
    assert t2.start_epoch == 2
    assert t2.version == version  # same run continued, not a new version dir
    assert int(np.asarray(t2.state.step)) == int(np.asarray(trainer.state.step))
    t2.close()


def test_auto_resume_skips_newest_run_without_last_ckpt(run_dir, tmp_path):
    """If the newest version crashed before its first save, auto-resume must
    start fresh — not silently resume an older (completed) run in place."""
    import shutil

    src_tmp, version, _, _ = run_dir
    shutil.copytree(src_tmp / f"version-{version}", tmp_path / f"version-{version}")
    (tmp_path / f"version-{version + 1}").mkdir()  # crashed, no last.ckpt
    hp = _hparams(tmp_path, extra=["--auto-resume", "--epoch", "1"])
    t = Trainer(hp, model=TinyNet(num_classes=100))
    assert t.start_epoch == 0
    assert t.version == version + 2  # a fresh version dir
    t.close()


def test_explicit_resume_with_auto_flag_uses_fresh_version_dir(run_dir, tmp_path):
    """--resume PATH (even alongside --auto-resume) must write into a new
    version under --ckpt-path, never into the source run's directory."""
    src_tmp, version, _, _ = run_dir
    last = src_tmp / f"version-{version}" / "last.ckpt"
    hp = _hparams(tmp_path, extra=["--auto-resume", "--resume", str(last), "--epoch", "3"])
    t = Trainer(hp, model=TinyNet(num_classes=100))
    assert t.start_epoch == 2  # state restored from the source checkpoint
    assert t.version_dir.parent == tmp_path  # but artifacts go to a new dir
    t.close()


def test_auto_resume_without_checkpoint_starts_fresh(tmp_path):
    hp = _hparams(tmp_path, extra=["--auto-resume", "--epoch", "1"])
    t = Trainer(hp, model=TinyNet(num_classes=100))
    assert t.start_epoch == 0 and t.version == 0
    t.close()


def test_nan_loss_aborts_run(tmp_path):
    """Failure detection: a diverged epoch must abort with a pointer to the
    last saved state instead of training on."""
    hp = _hparams(tmp_path, extra=["--lr", "1e8"])  # guaranteed divergence
    t = Trainer(hp, model=TinyNet(num_classes=100))
    with pytest.raises(FloatingPointError, match="non-finite train loss"):
        t.fit()
    t.close()


def test_host_mode_chunk_invariance(tmp_path):
    """The chunked host-streaming path must produce a bit-identical loss
    trajectory for any --host-chunk-steps (keys fold from the global step
    index inside the scan), and its state must advance like the device
    path's."""
    losses = {}
    for chunk in (1, 4):
        hp = _hparams(
            tmp_path / f"c{chunk}",
            extra=["--data-mode", "host", "--host-chunk-steps", str(chunk)],
        )
        t = Trainer(hp, model=TinyNet(num_classes=100))
        ls, top1 = t._train_epoch_host(0)
        losses[chunk] = (ls, top1, int(np.asarray(t.state.step)))
        t.close()
    l1, t1, s1 = losses[1]
    l4, t4, s4 = losses[4]
    assert s1 == s4 == len(l1) == len(l4)
    assert t1 == t4
    np.testing.assert_array_equal(l1, l4)


def test_device_mode_chunk_invariance(tmp_path):
    """The chunked device path must produce a bit-identical loss trajectory
    and state for any --device-chunk-steps (the chunk recomputes the epoch
    permutation + key split the monolithic program derives — same contract
    the host chunk runner documents), including a remainder-sized chunk."""
    losses = {}
    for chunk in (0, 2, 3):  # 0 = whole epoch; 3 leaves a remainder of 1
        hp = _hparams(
            tmp_path / f"c{chunk}",
            extra=["--device-chunk-steps", str(chunk)],
        )
        t = Trainer(hp, model=TinyNet(num_classes=100))
        ls, top1 = t._train_epoch_device(0)
        losses[chunk] = (ls, top1, int(np.asarray(t.state.step)))
        t.close()
    l0, t0, s0 = losses[0]
    for chunk in (2, 3):
        lc, tc, sc = losses[chunk]
        assert s0 == sc == len(l0) == len(lc)
        assert t0 == tc
        np.testing.assert_array_equal(l0, lc)


def test_goodput_record_carries_step_breakdown(run_dir):
    """The h2d-wait / dispatch / compute breakdown must ride the attempt's
    goodput record (how overlap health reaches GOODPUT.json)."""
    import json

    tmp_path, version, _, _ = run_dir
    record = json.loads(
        (tmp_path / f"version-{version}" / "goodput.jsonl")
        .read_text().splitlines()[0]
    )
    breakdown = record["step_breakdown"]
    assert set(breakdown) == {"h2d_wait_s", "dispatch_s", "compute_s", "chunks"}
    assert breakdown["chunks"] >= 2  # one per epoch at the default chunk
    assert breakdown["dispatch_s"] >= 0.0


def test_resume_continues(run_dir, tmp_path):
    src_tmp, version, _, trainer = run_dir
    last = src_tmp / f"version-{version}" / "last.ckpt"
    hp = _hparams(tmp_path, extra=["--resume", str(last), "--epoch", "3"])
    t2 = Trainer(hp, model=TinyNet(num_classes=100))
    assert t2.start_epoch == 2  # resumes after the 2 completed epochs
    assert int(np.asarray(t2.state.step)) == int(np.asarray(trainer.state.step))
    t2.fit()  # one more epoch runs without error
    t2.close()


def test_batch_not_divisible_raises(tmp_path):
    hp = _hparams(tmp_path)
    hp.batch_size = 60  # not divisible by 8-device data axis
    with pytest.raises(ValueError, match="not divisible"):
        Trainer(hp, model=TinyNet(num_classes=100))


def test_best_only_snapshot_copies_only_what_the_save_writes(run_dir):
    """A best-only save writes params and statistics, so its device
    snapshot holds no optimizer state; a whole one holds everything; both
    are copies, never the live (donated) buffers."""
    import jax

    _, _, _, trainer = run_dir
    state = trainer.state
    best = trainer._snapshot_state(state, whole=False)
    whole = trainer._snapshot_state(state)
    assert best.opt_state is None and best.step is None
    assert jax.tree_util.tree_structure(whole) == jax.tree_util.tree_structure(state)
    for snap in (best, whole):
        for a, b in zip(jax.tree_util.tree_leaves(snap.params),
                        jax.tree_util.tree_leaves(state.params)):
            assert a is not b
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(whole.opt_state),
                    jax.tree_util.tree_leaves(state.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------- the seam the benchmark's clock uses


@pytest.mark.parametrize("data_mode", ["device", "host"])
def test_lowering_epoch_at_an_epoch_start_makes_it_the_last(tmp_path, data_mode):
    """``benchmark/harness/window.py`` ends a run by lowering
    ``trainer.hparams.epoch`` from a subscriber on ``trainer.bus`` at an
    ``epoch_start``: the epoch that has just started must then run as the
    job's last — its ``epoch_end``, the final save, ``run_end`` and
    ``close()`` all happen, and no further epoch starts."""
    from flax import serialization

    hp = _hparams(tmp_path, (
        "--epoch", "1000", "--data-mode", data_mode,
        "--save-last-min-secs", "0", "--no-progress",
    ))
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    seen = []

    def on_event(ev):
        seen.append((ev["kind"], ev.get("epoch")))
        if ev["kind"] == "epoch_start" and ev["epoch"] == 2:
            trainer.hparams.epoch = 3

    trainer.bus.subscribe(on_event)
    try:
        version = trainer.fit()
    finally:
        trainer.close()
    assert [e for k, e in seen if k == "epoch_start"] == [0, 1, 2]
    assert [e for k, e in seen if k == "epoch_end"] == [0, 1, 2]
    kinds = [k for k, _ in seen]
    assert kinds.index("run_end") > max(
        i for i, k in enumerate(kinds) if k == "epoch_end"
    )
    last = serialization.msgpack_restore(
        (tmp_path / f"version-{version}" / "last.ckpt").read_bytes()
    )
    assert last["epoch"] == 2  # the final save is the lowered epoch's
    # close() joined the checkpoint writer: nothing is left in flight
    writer = trainer.ckpt_writer
    assert writer is None or not writer._thread.is_alive()
