"""Overlapped-execution tests: DevicePrefetcher semantics (order, depth
bound, cancellation, exception propagation), donated runners, the chunked
device-mode runner's bit-identity contract, the step-time meter, and the
pipelined checkpoint read+hash.

The perf-marked tests are the overlap microbenchmarks: they measure the
mechanism (staging latency hidden behind consumer work) with deterministic
sleep-based stages, device-free — slow-marked so tier-1 skips them.
"""

import hashlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_comparison_tpu.data import (
    DeviceDataset,
    DevicePrefetcher,
    HostLoader,
    PrefetchLoader,
    chunked_batches,
    synthetic_dataset,
)
from distributed_training_comparison_tpu.parallel import (
    batch_sharding,
    make_mesh,
    replicated_sharding,
)
from distributed_training_comparison_tpu.resilience import (
    atomic_write_bytes,
    read_and_hash,
    verify_checkpoint,
    write_manifest,
)
from distributed_training_comparison_tpu.train import (
    configure_optimizers,
    create_train_state,
    make_chunk_runner,
    make_device_chunk_runner,
)
from distributed_training_comparison_tpu.utils import StepTimeMeter

from test_train import HP, TinyNet


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(backend="ddp")


@pytest.fixture(scope="module")
def tiny_data():
    x, y = synthetic_dataset(256, num_classes=10, seed=0)
    return jnp.asarray(x), jnp.asarray(y)


def _fresh_state(mesh):
    tx, _ = configure_optimizers(HP, steps_per_epoch=4)
    state = create_train_state(TinyNet(), jax.random.key(0), tx)
    return jax.device_put(state, replicated_sharding(mesh))


# -------------------------------------------- device-mode chunked runner


def _run_chunked(mesh, data, chunk, epochs, fault=None):
    """``epochs`` epochs in dispatches of ``chunk`` steps (and one of the
    remainder): stacked losses and the final params."""
    x, y = data
    bs = 32
    steps = len(x) // bs  # 8
    key = jax.random.key(7)
    kw = {"fault_injection": fault is not None}
    extra = () if fault is None else (fault,)
    runners = {
        k: make_device_chunk_runner(mesh, bs, k, **kw)
        for k in {chunk, steps % chunk} - {0}
    }
    state = _fresh_state(mesh)
    losses = []
    for e in range(epochs):
        for start in range(0, steps, chunk):
            take = min(chunk, steps - start)
            state, stacked = runners[take](
                state, x, y, key, jnp.asarray(e), jnp.asarray(start), *extra
            )
            losses.append(np.asarray(stacked["loss"]))
    return np.concatenate(losses), jax.device_get(state.params)


@pytest.fixture(scope="module")
def whole_epoch(mesh, tiny_data):
    """Two epochs at ``K = steps``: one dispatch an epoch, what ``Trainer``
    runs by default."""
    return _run_chunked(mesh, tiny_data, 8, epochs=2)


FAULT = (64.0, 2, 5)  # (scale, start, stop): steps [2, 5) of the epoch


@pytest.fixture(scope="module")
def whole_epoch_faulted(mesh, tiny_data):
    return _run_chunked(mesh, tiny_data, 8, epochs=1, fault=FAULT)[0]


@pytest.mark.parametrize("chunk", [1, 3, 5])
def test_any_chunking_gives_the_whole_epoch_trajectory(
    mesh, tiny_data, whole_epoch, chunk
):
    """Any chunk size gives the trajectory of ``K = steps``, bit for bit:
    every chunk derives the epoch's whole permutation and key table and
    slices its rows, never re-derives them from its own position."""
    ref_losses, ref_params = whole_epoch
    assert len(ref_losses) == 16
    losses, params = _run_chunked(mesh, tiny_data, chunk, epochs=2)
    np.testing.assert_array_equal(losses, ref_losses)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), params, ref_params
    )


@pytest.mark.parametrize("chunk", [1, 3, 4])
def test_fault_window_hits_the_same_batches_under_any_chunking(
    mesh, tiny_data, whole_epoch, whole_epoch_faulted, chunk
):
    """The traced step-fault window indexes steps WITHIN the epoch — a
    fault on steps [2, 5) must hit the same batches however the epoch is
    chunked (inside one chunk, across two, one step a dispatch)."""
    # the window is felt: its first step's loss is the plain one scaled
    assert whole_epoch_faulted[2] == pytest.approx(
        FAULT[0] * whole_epoch[0][2], rel=1e-4
    )
    losses, _ = _run_chunked(mesh, tiny_data, chunk, epochs=1, fault=FAULT)
    np.testing.assert_array_equal(losses, whole_epoch_faulted)


def test_donated_runner_consumes_input_state(mesh, tiny_data):
    """Donation must actually take effect: the input state's buffers are
    consumed by the dispatch (this is what eliminates the per-dispatch HBM
    copy), while donate=False preserves them — the contract the trainer's
    writer-snapshot logic is built on."""
    x, y = tiny_data
    key = jax.random.key(3)
    cx = jnp.stack([x[:16], x[16:32]])  # (K=2, B=16, ...)
    cy = jnp.stack([y[:16], y[16:32]])

    donating = make_chunk_runner(mesh, augment=False)  # donate default True
    state = _fresh_state(mesh)
    leaf_before = jax.tree_util.tree_leaves(state.params)[0]
    new_state, _ = donating(state, cx, cy, key, jnp.asarray(0))
    jax.block_until_ready(new_state)
    assert leaf_before.is_deleted()

    keeping = make_chunk_runner(mesh, augment=False, donate=False)
    state = _fresh_state(mesh)
    leaf_before = jax.tree_util.tree_leaves(state.params)[0]
    new_state, _ = keeping(
        state, jnp.stack([x[:16], x[16:32]]), jnp.stack([y[:16], y[16:32]]),
        key, jnp.asarray(0),
    )
    jax.block_until_ready(new_state)
    assert not leaf_before.is_deleted()


def test_donated_cache_write_bar_blocks_only_barred_compiles(mesh):
    """Donated executables compiled for the CPU must never land in the
    persistent compile cache: on this jax's CPU backend a warm process
    deserializing one segfaults or silently corrupts the scanned carry (the
    bug _compat.donated_cache_write_barred / step._donated_jit exist for).
    Normal programs keep caching — the guard must not disable the cache
    wholesale.  (The bar is down for a TPU mesh: test_write_bar_is_keyed_
    on_the_compiled_platform.)

    Observes the LIVE cache dir (conftest's — the cache singleton latches
    its directory at first use, so redirecting the config mid-process is a
    no-op: exactly why the fix had to bar the WRITE, not move the dir) and
    identifies its own entries by uniquely-named probe functions, so a
    concurrent test process sharing the cache cannot race the assertion.
    """
    from pathlib import Path

    from distributed_training_comparison_tpu.train.step import _donated_jit

    cache_dir = Path(jax.config.jax_compilation_cache_dir)
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs

    def named_entries(token):
        if not cache_dir.exists():
            return set()
        return {p for p in cache_dir.rglob("*") if token in p.name}

    def overlap_cache_probe_barred(s, xs):
        return jax.lax.scan(lambda c, x: (c + x.sum(), x.mean()), s, xs)

    def overlap_cache_probe_open(s, xs):
        return jax.lax.scan(lambda c, x: (c + x.max(), x.min()), s, xs)

    try:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        barred = _donated_jit(
            overlap_cache_probe_barred, mesh, donate_argnums=(0,)
        )
        out = barred(jnp.ones((32, 32)), jnp.ones((4, 16)))
        jax.block_until_ready(out)
        assert named_entries("overlap_cache_probe_barred") == set()

        open_jit = jax.jit(overlap_cache_probe_open)
        out = open_jit(jnp.ones((32, 32)), jnp.ones((4, 16)))
        jax.block_until_ready(out)
        assert named_entries("overlap_cache_probe_open")  # cache still works
    finally:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", min_secs
        )


def test_write_bar_is_keyed_on_the_compiled_platform():
    """The bar stays where its fault was reproduced (executables compiled
    for the CPU backend) and is down for the TPU, where the donated train
    programs — the costliest compiles of a run — must reach the cache."""
    from distributed_training_comparison_tpu._compat import (
        donated_cache_write_barred,
    )

    base = jax.config.jax_persistent_cache_min_compile_time_secs
    with donated_cache_write_barred("cpu"):
        assert jax.config.jax_persistent_cache_min_compile_time_secs > 1e9
    with donated_cache_write_barred("tpu"):
        assert jax.config.jax_persistent_cache_min_compile_time_secs == base
    assert jax.config.jax_persistent_cache_min_compile_time_secs == base


# ------------------------------------------------------- chunked_batches


def test_chunked_batches_chunks_and_remainder():
    src = iter([(np.full(2, i), np.full(2, i)) for i in range(7)])
    out = list(chunked_batches(src, 7, 3))
    assert [(s, k) for s, k, _ in out] == [(0, 3), (3, 3), (6, 1)]
    np.testing.assert_array_equal(out[0][2]["x"][1], np.full(2, 1))


def test_chunked_batches_tolerates_short_source():
    """A source that runs dry mid-epoch must yield its partial chunk and
    stop — never explode with the PEP-479 RuntimeError."""
    src = iter([(np.zeros(1), np.zeros(1))] * 5)
    out = list(chunked_batches(src, 12, 4))
    assert [(s, k) for s, k, _ in out] == [(0, 4), (4, 1)]


# ------------------------------------------------------ DevicePrefetcher


def _counted_source(n, counter, item_shape=4):
    for i in range(n):
        counter[0] += 1
        yield np.full(item_shape, i, np.float32), np.full(item_shape, i, np.int32)


def test_device_prefetcher_preserves_sequence():
    """The prefetcher must deliver exactly the synchronous chunker's
    sequence — same starts, same takes, same stacked contents."""
    a, b = [0], [0]
    sync = list(chunked_batches(_counted_source(10, a), 10, 3))
    pf = DevicePrefetcher(
        _counted_source(10, b), 10, 3, place=lambda x: x, depth=2
    )
    staged = list(pf)
    assert [(s, k) for s, k, _ in staged] == [(s, k) for s, k, _ in sync]
    for (_, _, sb), (_, _, pb) in zip(sync, staged):
        np.testing.assert_array_equal(sb["x"], pb["x"])
        np.testing.assert_array_equal(sb["y"], pb["y"])


def test_device_prefetcher_depth_bounds_runahead():
    """The producer must not run ahead unboundedly: at depth D and chunk K,
    at most (delivered + D + 1 in-assembly) chunks' worth of source batches
    may be consumed — this is the HBM cap."""
    counter = [0]
    pf = DevicePrefetcher(
        _counted_source(100, counter), 100, 2, place=lambda x: x, depth=2
    )
    try:
        next(pf)
        deadline = time.monotonic() + 2.0
        while counter[0] < 6 and time.monotonic() < deadline:
            time.sleep(0.01)  # let the producer fill the queue
        time.sleep(0.2)  # then prove it stops there
        # delivered 1 chunk + 2 staged + 1 in assembly = at most 4 chunks = 8
        assert counter[0] <= 8
        assert counter[0] >= 6  # and it DID stage ahead of the consumer
    finally:
        pf.close()


def test_device_prefetcher_close_joins_producer():
    counter = [0]
    pf = DevicePrefetcher(
        _counted_source(1000, counter), 1000, 2, place=lambda x: x, depth=2
    )
    next(pf)
    pf.close()
    assert not pf._thread.is_alive()
    pf.close()  # idempotent


def test_device_prefetcher_propagates_source_errors():
    def bad():
        yield np.zeros(2), np.zeros(2)
        yield np.zeros(2), np.zeros(2)
        raise RuntimeError("loader failed")

    pf = DevicePrefetcher(bad(), 10, 2, place=lambda x: x, depth=2)
    next(pf)  # first chunk (2 batches) is fine
    with pytest.raises(RuntimeError, match="loader failed"):
        next(pf)
    assert not pf._thread.is_alive()  # the error path also joined


def test_device_prefetcher_propagates_place_errors():
    """A failing device_put (the H2D analogue of an OOM) surfaces at the
    consuming next(), not as a hung iterator."""

    def explode(_):
        raise ValueError("device_put failed")

    pf = DevicePrefetcher(
        _counted_source(10, [0]), 10, 2, place=explode, depth=2
    )
    with pytest.raises(ValueError, match="device_put failed"):
        next(pf)


def test_prefetch_loader_close_joins_producer():
    x, y = synthetic_dataset(128, num_classes=10, seed=4)
    ds = DeviceDataset(x, y, num_classes=10)
    pre = PrefetchLoader(HostLoader(ds, 32, shuffle=False, seed=1), depth=2)
    it = iter(pre)
    next(it)
    pre.close()  # explicit abort API: signal + drain + JOIN
    assert pre._thread is None
    # a fresh epoch after close works
    assert len(list(pre)) == len(pre)


# ---------------------------------------------------------- StepTimeMeter


def test_step_time_meter_phases_and_merge():
    m = StepTimeMeter()
    with m.phase("h2d_wait"):
        time.sleep(0.01)
    m.add("dispatch", 0.5)
    m.note_chunk()
    s = m.summary()
    assert s["h2d_wait_s"] >= 0.009 and s["dispatch_s"] == 0.5
    assert s["compute_s"] == 0.0 and s["chunks"] == 1

    total = StepTimeMeter()
    total.merge(m)
    total.merge(m)
    assert total.summary()["dispatch_s"] == 1.0
    assert total.chunks == 2
    m.reset()
    assert m.summary()["dispatch_s"] == 0.0


# ------------------------------------------------- pipelined read + hash


def test_read_and_hash_matches_single_pass(tmp_path):
    data = np.random.default_rng(0).bytes(100_000)
    path = tmp_path / "blob.ckpt"
    path.write_bytes(data)
    # the small-file fast path (plain read-then-hash)
    got, digest = read_and_hash(path)
    assert got == data and digest == hashlib.sha256(data).hexdigest()
    # the pipelined path, forced through many small chunks
    got, digest = read_and_hash(path, chunk_bytes=4096, pipeline_min_bytes=0)
    assert got == data
    assert digest == hashlib.sha256(data).hexdigest()
    # ragged tail: size not a chunk multiple
    got, digest = read_and_hash(path, chunk_bytes=4097, pipeline_min_bytes=0)
    assert got == data and digest == hashlib.sha256(data).hexdigest()
    # empty file edge (pipelined)
    (tmp_path / "empty").write_bytes(b"")
    got, digest = read_and_hash(tmp_path / "empty", pipeline_min_bytes=0)
    assert got == b"" and digest == hashlib.sha256(b"").hexdigest()


def test_read_and_hash_raises_reader_errors(tmp_path, monkeypatch):
    with pytest.raises(OSError):
        read_and_hash(tmp_path / "missing.ckpt")
    # pipelined reader: a file that shrinks below its stat size mid-read
    # must raise at the consumer, never hand back silently-short bytes
    import pathlib
    import types

    import distributed_training_comparison_tpu.resilience.ckpt_io as cio

    path = tmp_path / "shrinking.ckpt"
    path.write_bytes(b"x" * 10_000)

    class LyingPath(pathlib.PosixPath):
        """stat() overstates the size, as if the file shrank after stat."""

        def stat(self, **kw):
            real = super().stat(**kw)
            return types.SimpleNamespace(st_size=real.st_size * 2)

    monkeypatch.setattr(cio, "Path", LyingPath)
    with pytest.raises(OSError, match="truncated"):
        read_and_hash(path, chunk_bytes=4096, pipeline_min_bytes=0)


def test_verify_checkpoint_precomputed_digest(tmp_path):
    data = b"payload" * 1000
    path = tmp_path / "blob.ckpt"
    atomic_write_bytes(path, data)
    write_manifest(path, data, meta={"step": 1})
    got, digest = read_and_hash(path)
    ok, reason = verify_checkpoint(path, data=got, digest=digest)
    assert ok, reason
    # a wrong precomputed digest must fail verification (the digest is
    # trusted in place of re-hashing, so it must actually be checked)
    ok, reason = verify_checkpoint(
        path, data=got, digest=hashlib.sha256(b"other").hexdigest()
    )
    assert not ok and "checksum" in reason
    # no data at all: verify pays its own (pipelined) read
    ok, reason = verify_checkpoint(path)
    assert ok, reason


# --------------------------------------------------- perf microbenchmarks


@pytest.mark.slow
@pytest.mark.perf
def test_prefetcher_hides_staging_latency():
    """The mechanism microbenchmark: with staging and consumption both
    taking ~T per chunk (sleep-based — deterministic, device-free), the
    synchronous pipeline costs ~2T per chunk while the prefetched one
    approaches T: staging hides behind the consumer."""
    chunks, stage_s, consume_s = 12, 0.02, 0.02

    def slow_source():
        for i in range(chunks):
            time.sleep(stage_s)
            yield np.full(2, i), np.full(2, i)

    def consume(chunk_iter):
        t0 = time.monotonic()
        for _ in chunk_iter:
            time.sleep(consume_s)
        return time.monotonic() - t0

    sync_wall = consume(chunked_batches(slow_source(), chunks, 1))
    pf = DevicePrefetcher(slow_source(), chunks, 1, place=lambda x: x, depth=2)
    try:
        overlap_wall = consume(pf)
    finally:
        pf.close()
    # perfect overlap would be ~0.5x; require a solid 0.75x with margin
    # for scheduler noise on a loaded CI host
    assert overlap_wall < 0.75 * sync_wall, (overlap_wall, sync_wall)


@pytest.mark.slow
@pytest.mark.perf
def test_read_and_hash_pipeline_correct_at_scale(tmp_path):
    """The pipelined path at a realistic chunk count (32 MB through 8 MB
    chunks, forced past the small-file threshold) must agree exactly with
    the one-shot read-then-hash.  Timing ratios are deliberately NOT
    asserted here: on a page-cached CI file the read is a memcpy the hash
    cannot hide behind — which is exactly why small files take the serial
    path in production (PIPELINE_MIN_BYTES); the overlap's win condition is
    slow storage, not a warm page cache."""
    data = np.random.default_rng(1).bytes(32 << 20)
    path = tmp_path / "payload.bin"
    path.write_bytes(data)
    got, digest = read_and_hash(path, pipeline_min_bytes=0)
    assert got == data
    assert digest == hashlib.sha256(data).hexdigest()
