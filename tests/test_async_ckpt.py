"""AsyncCheckpointer unit tests + host-streaming data-mode end-to-end."""

import threading
import time

import numpy as np
import pytest

from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.train import AsyncCheckpointer, Trainer

from test_train import TinyNet


def test_async_jobs_run_in_order_and_wait_drains(tmp_path):
    w = AsyncCheckpointer()
    order = []
    gate = threading.Event()

    def slow():
        gate.wait(5)
        order.append("slow")

    w.submit(slow, key="a")
    w.submit(lambda: order.append("fast"), key="b")
    assert order == []  # nothing ran yet — the first job is gated
    gate.set()
    w.wait()
    assert order == ["slow", "fast"]  # single worker => strict FIFO
    w.close()


def test_async_same_key_coalesces():
    """Queued-but-unstarted snapshots for the same target are superseded —
    only the newest hits disk."""
    w = AsyncCheckpointer()
    ran = []
    gate = threading.Event()
    w.submit(lambda: gate.wait(5), key="other")  # block the worker
    for i in range(5):
        w.submit(lambda i=i: ran.append(i), key="best")
    gate.set()
    w.wait()
    assert ran == [4]
    w.close()


def test_async_error_surfaces_on_wait():
    w = AsyncCheckpointer()

    def boom():
        raise OSError("disk full")

    w.submit(boom)
    with pytest.raises(RuntimeError, match="disk full"):
        w.wait()
    w.close()


def test_async_error_surfaces_on_close_too():
    """A failed background save must also surface when the only drain point
    is close() (e.g. a run that never calls wait() again after fit)."""
    w = AsyncCheckpointer()
    w.submit(lambda: (_ for _ in ()).throw(OSError("quota exceeded")))
    with pytest.raises(RuntimeError, match="quota exceeded"):
        w.close()
    w.close()  # error list cleared by the raise; close stays idempotent


def test_async_multiple_errors_report_count():
    w = AsyncCheckpointer()
    gate = threading.Event()
    w.submit(lambda: gate.wait(5), key="gate")
    for i in range(2):
        w.submit(
            lambda i=i: (_ for _ in ()).throw(OSError(f"boom{i}")), key=f"k{i}"
        )
    gate.set()
    with pytest.raises(RuntimeError, match=r"boom0.*\+1 more"):
        w.wait()
    w.close()


def test_held_worker_starts_nothing_until_release():
    """Between hold() and release() the worker starts no job, and the
    newest snapshot of a key still wins."""
    w = AsyncCheckpointer()
    ran = []
    w.hold()
    w.submit(lambda: ran.append("old"), key="last")
    w.submit(lambda: ran.append("new"), key="last")
    time.sleep(0.2)
    assert ran == [] and w.stats()["queue_depth"] == 2
    w.release()
    w.wait()
    assert ran == ["new"]
    w.close()


@pytest.mark.parametrize("drain", ["wait", "close"])
def test_drain_releases_a_held_worker(drain):
    w = AsyncCheckpointer()
    ran = []
    w.hold()
    w.submit(lambda: ran.append(1))
    getattr(w, drain)()  # would never return if the hold outlived it
    assert ran == [1]
    w.close()


def test_full_queue_releases_a_held_worker():
    """A held worker frees no slot, so a submit that finds the queue full
    gives up the pacing and never blocks on it."""
    w = AsyncCheckpointer(max_pending=2)
    ran = []
    w.hold()
    for i in range(4):
        w.submit(lambda i=i: ran.append(i), key=f"k{i}")
    w.wait()
    assert ran == [0, 1, 2, 3]
    w.close()


def test_close_idempotent():
    w = AsyncCheckpointer()
    w.close()
    w.close()


@pytest.mark.slow
def test_host_data_mode_end_to_end(tmp_path):
    """--data-mode host: streaming loader feeds the per-step compiled path;
    artifacts and metrics match the device-resident contract."""
    hp = load_config(
        "ddp",
        argv=[
            "--synthetic-data",
            "--limit-examples", "256",
            "--batch-size", "64",
            "--epoch", "2",
            "--lr", "0.05",
            "--data-mode", "host",
            "--save-last-every", "2",
            "--ckpt-path", str(tmp_path),
        ],
    )
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    assert trainer.train_loader is not None and trainer.chunk_runner is not None
    assert not trainer._device_runners  # host mode builds no device-epoch program
    version = trainer.fit()
    results = trainer.test()
    trainer.close()
    vdir = tmp_path / f"version-{version}"
    assert (vdir / "last.ckpt").exists()  # epoch 1 hits save-last-every=2
    assert list(vdir.glob("best_model_*.ckpt"))
    assert results["test_loss"] > 0


def test_streamed_serialisation_is_flax_msgpack_byte_for_byte(tmp_path):
    """``checkpoint.msgpack_chunks`` yields exactly the bytes
    ``flax.serialization.msgpack_serialize`` builds — large arrays as views
    of their own memory — and ``atomic_write_chunks`` hashes what it writes."""
    import hashlib

    import jax.numpy as jnp
    import numpy as np
    from flax import serialization

    from distributed_training_comparison_tpu.resilience import atomic_write_chunks
    from distributed_training_comparison_tpu.train.checkpoint import msgpack_chunks

    rng = np.random.default_rng(0)
    tree = {
        "fmt": 3, "epoch": 7, "best_acc": 43.78, "name": "last",
        "state": {
            "step": np.asarray(36, np.int32),
            "params": {
                "big": rng.standard_normal((300, 1024)).astype(np.float32),
                "half": rng.standard_normal((1024, 600)).astype(jnp.bfloat16),
                "strided": rng.standard_normal((2048, 512)).astype(np.float32).T,
                "small": rng.standard_normal((64,)).astype(np.float32),
            },
            "opt_state": {"0": {"count": np.asarray(36, np.int32), "mu": {}}},
            "batch_stats": {},
        },
    }
    want = serialization.msgpack_serialize(tree)
    pieces = list(msgpack_chunks(tree))
    assert b"".join(bytes(p) for p in pieces) == want
    assert sum(isinstance(p, memoryview) for p in pieces) == 3  # no copies
    path, digest, size = atomic_write_chunks(tmp_path / "x.ckpt", msgpack_chunks(tree))
    assert path.read_bytes() == want and size == len(want)
    assert digest == hashlib.sha256(want).hexdigest()
    back = serialization.msgpack_restore(path.read_bytes())
    np.testing.assert_array_equal(back["state"]["params"]["half"], tree["state"]["params"]["half"])


def test_paced_write_stops_between_pieces(tmp_path, monkeypatch):
    """``atomic_write_chunks(..., pace=)`` calls ``pace`` before every piece
    it writes — a large buffer goes out in pieces — and a held
    ``AsyncCheckpointer`` stops a job there; the bytes and their hash are
    those of the unpaced write."""
    import hashlib

    from distributed_training_comparison_tpu.resilience import (
        atomic_write_chunks, ckpt_io,
    )

    monkeypatch.setattr(ckpt_io, "_PIECE_BYTES", 1000)
    big = np.arange(2500, dtype=np.uint8)
    chunks = [b"head", memoryview(big), np.arange(6, dtype=np.float32)]
    want = b"head" + big.tobytes() + chunks[2].tobytes()
    calls = []
    path, digest, size = atomic_write_chunks(
        tmp_path / "a", chunks, pace=lambda: calls.append(1)
    )
    assert len(calls) == 1 + 3 + 1  # 4 | 1000 + 1000 + 500 | 24 bytes
    assert path.read_bytes() == want and size == len(want)
    assert digest == hashlib.sha256(want).hexdigest()

    w = AsyncCheckpointer()
    w.submit(lambda: atomic_write_chunks(tmp_path / "b", chunks, pace=w.pace))
    w.wait()
    w.hold()
    w.submit(lambda: atomic_write_chunks(tmp_path / "c", chunks, pace=w.pace))
    time.sleep(0.2)
    assert (tmp_path / "b").read_bytes() == want
    assert not (tmp_path / "c").exists()  # held before it started
    w.release()
    w.wait()
    assert (tmp_path / "c").read_bytes() == want
    w.close()
