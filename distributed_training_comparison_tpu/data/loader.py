"""Dataset construction and the loader-parity API.

Parity: reference ``get_trn_val_loader`` / ``get_tst_loader``
(``src/single/dataset.py:13-158``, ddp variant ``src/ddp/dataset.py``).

Two consumption modes:

- **Device-resident** (`DeviceDataset`, the default for CIFAR-scale data):
  the whole split is one uint8 array, transferred to HBM once; the trainer
  shuffles/batches/augments in-jit.  This is the TPU-fast path.
- **Host-streaming** (`HostLoader`): a numpy mini-batch iterator with
  per-epoch reshuffle and per-host sharding, for datasets that don't fit in
  HBM.  ``get_trn_val_loader``/``get_tst_loader`` return these, mirroring
  the reference's function signatures (sans torch-specific args).
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Iterator

import numpy as np

from ..obs import span as _obs_span
from .cifar100 import load_cifar100
from .sampler import shard_indices, train_val_split
from .synthetic import synthetic_dataset


@dataclasses.dataclass
class DeviceDataset:
    """A whole split as contiguous arrays, ready for one-shot device_put."""

    images: np.ndarray  # uint8 NHWC
    labels: np.ndarray  # int32
    num_classes: int = 100
    name: str = "cifar100"

    def __post_init__(self) -> None:
        assert len(self.images) == len(self.labels)

    def __len__(self) -> int:
        return len(self.images)

    def steps_per_epoch(self, batch_size: int, drop_last: bool = True) -> int:
        n = len(self)
        return n // batch_size if drop_last else -(-n // batch_size)

    def subset(self, indices: np.ndarray) -> "DeviceDataset":
        return DeviceDataset(
            self.images[indices], self.labels[indices], self.num_classes, self.name
        )


def _raw_split(hparams, split: str) -> tuple[np.ndarray, np.ndarray]:
    limit = getattr(hparams, "limit_examples", 0)
    if getattr(hparams, "synthetic_data", False):
        n = 50_000 if split == "train" else 10_000
        if limit:
            n = min(n, limit)
        size = getattr(hparams, "image_size", 32) or 32
        return synthetic_dataset(
            n,
            num_classes=100,
            image_shape=(size, size, 3),
            seed=hparams.seed + (split == "test"),
            anchor_seed=hparams.seed,
            noise=getattr(hparams, "synthetic_noise", 0.15),
        )
    if getattr(hparams, "image_size", 32) not in (0, 32):
        raise ValueError(
            "--image-size applies only to --synthetic-data "
            "(CIFAR-100 images are 32x32)"
        )
    if hparams.dset != "cifar100":
        raise ValueError(f"unknown dataset {hparams.dset!r}")
    images, labels = load_cifar100(hparams.dpath, split)
    if limit:
        images, labels = images[:limit], labels[:limit]
    return images, labels


def get_datasets(hparams) -> tuple[DeviceDataset, DeviceDataset, DeviceDataset]:
    """Build (train, valid, test) datasets with the reference's 90/10 split."""
    images, labels = _raw_split(hparams, "train")
    full = DeviceDataset(images, labels)
    trn_idx, val_idx = train_val_split(
        len(full), valid_size=0.1, seed=hparams.seed,
        valid_count=getattr(hparams, "valid_examples", 0),
    )
    test_images, test_labels = _raw_split(hparams, "test")
    return (
        full.subset(trn_idx),
        full.subset(val_idx),
        DeviceDataset(test_images, test_labels),
    )


class HostLoader:
    """Streaming numpy batch iterator with sharding + epoch reshuffle.

    The ``DataLoader(sampler=...)`` analogue.  Call ``set_epoch`` before each
    pass for a fresh deterministic shuffle (reference
    ``src/ddp/trainer.py:125``); sharding gives each host its own slice of
    every epoch's permutation.
    """

    def __init__(
        self,
        dataset: DeviceDataset,
        batch_size: int,
        *,
        shuffle: bool = True,
        drop_last: bool = False,
        seed: int = 42,
        num_shards: int = 1,
        shard: int = 0,
    ) -> None:
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.seed = seed
        self.num_shards = num_shards
        self.shard = shard
        self.epoch = 0
        # corrupt-shard quarantine (health/watchdog.py cooperation): example
        # ids excluded from every future epoch's permutation, each occurrence
        # substituted IN PLACE by a deterministically drawn clean example —
        # batch count, shapes, and every untouched batch stay identical, so
        # a rollback replay differs ONLY where the corrupt data sat
        self._quarantined: set[int] = set()

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    @property
    def quarantined(self) -> frozenset:
        """The excluded example ids (persisted in the resume manifest, so
        a supervisor relaunch re-applies them — a corrupt shard must not
        re-enter the stream just because the process restarted)."""
        return frozenset(self._quarantined)

    def quarantine(self, example_ids) -> int:
        """Exclude dataset example ids from all future permutations
        (returns how many NEW ids were added).  The watchdog passes the bad
        step window's batch indices here on a rollback so the replay skips
        the corrupt shard instead of re-firing on it.  A refusal (the set
        would cover the whole dataset) leaves the loader UNCHANGED — a
        refused quarantine must not poison the next epoch's permutation."""
        ids = {int(i) for i in np.asarray(example_ids, dtype=np.int64).ravel()}
        merged = self._quarantined | ids
        if len(merged) >= len(self.dataset):
            raise ValueError(
                f"quarantine would exclude every example "
                f"({len(merged)} of {len(self.dataset)})"
            )
        added = len(merged) - len(self._quarantined)
        self._quarantined = merged
        return added

    def batch_example_indices(self, epoch: int, step: int) -> np.ndarray:
        """The dataset example ids batch ``step`` of ``epoch`` serves (as
        this loader would iterate them NOW, current quarantine included) —
        what the trainer hands back to ``quarantine`` when the health
        watchdog condemns that step's window."""
        idx = self._permutation(epoch)
        return idx[step * self.batch_size : (step + 1) * self.batch_size].copy()

    def _permutation(self, epoch: int) -> np.ndarray:
        idx = np.arange(len(self.dataset))
        if self.shuffle:
            np.random.default_rng((self.seed, epoch)).shuffle(idx)
        if self.num_shards > 1:
            idx = shard_indices(idx, self.num_shards, self.shard, even=True)
        if self._quarantined:
            quarantined = np.fromiter(self._quarantined, np.int64)
            bad = np.isin(idx, quarantined)
            n_bad = int(bad.sum())
            if n_bad:
                # substitutes come from THIS loader's own slice of the
                # epoch (the post-shard permutation): drawing from the
                # whole dataset would hand this host examples another
                # host's shard also trains — cross-host duplication.
                # Falls back to the dataset-wide clean pool only in the
                # pathological case of a fully-quarantined slice.
                clean = np.setdiff1d(idx, quarantined)
                if not len(clean):
                    clean = np.setdiff1d(
                        np.arange(len(self.dataset)), quarantined
                    )
                # substitutions are a pure function of (seed, epoch, set):
                # every replay of this loader derives the same permutation
                rng = np.random.default_rng(
                    (self.seed, epoch, len(self._quarantined))
                )
                idx = idx.copy()
                idx[bad] = rng.choice(clean, size=n_bad)
        return idx

    def _indices(self) -> np.ndarray:
        return self._permutation(self.epoch)

    def __len__(self) -> int:
        n = len(self._indices())
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def __iter__(self) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        idx = self._indices()
        end = (len(idx) // self.batch_size) * self.batch_size if self.drop_last else len(idx)
        for start in range(0, end, self.batch_size):
            b = idx[start : start + self.batch_size]
            yield self.dataset.images[b], self.dataset.labels[b]


class PrefetchLoader:
    """Background-thread prefetch around any epoch-aware batch iterator.

    The reference's ``DataLoader(num_workers=4)`` (``src/single/dataset.py``)
    overlaps host-side batch assembly with device compute via worker
    processes; here one producer thread fills a bounded queue ``depth``
    batches ahead (numpy slicing releases the GIL, so a thread suffices —
    and unlike the per-step synchronous round-1 loader, the accelerator
    never waits on batch assembly).

    Yields exactly the wrapped loader's sequence — same order, same
    determinism.  A producer exception is re-raised at the consuming call
    site (the ``next()`` that would have received the failed batch), and the
    consumer never hangs on a dead producer: the queue read polls the
    thread's liveness, so a producer that died without signaling (a crash
    outside the except net, e.g. interpreter teardown) raises instead of
    blocking forever.  ``close()`` — also run by the iterator's ``finally``
    on abandon — signals the producer, drains the queue, and JOINS the
    thread, so an abort never leaks a runner stuck on a full queue.
    """

    _DONE = object()

    def __init__(self, loader, depth: int = 2) -> None:
        self.loader = loader
        self.depth = max(1, depth)
        self._stop: threading.Event | None = None
        self._thread: threading.Thread | None = None
        self._queue: queue.Queue | None = None

    def set_epoch(self, epoch: int) -> None:
        self.loader.set_epoch(epoch)

    @property
    def quarantined(self) -> frozenset:
        return self.loader.quarantined

    def quarantine(self, example_ids) -> int:
        """Delegate corrupt-shard quarantine to the wrapped loader (the
        next epoch's producer re-derives its permutation from it)."""
        return self.loader.quarantine(example_ids)

    def batch_example_indices(self, epoch: int, step: int) -> "np.ndarray":
        return self.loader.batch_example_indices(epoch, step)

    def __len__(self) -> int:
        return len(self.loader)

    def _shutdown(self, stop, q, thread) -> None:
        """Signal, drain, and JOIN one producer generation."""
        if stop is not None:
            stop.set()
        if q is not None:
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
        if thread is not None:
            thread.join(timeout=10.0)
            if thread.is_alive():  # pragma: no cover - diagnostic path
                raise RuntimeError(
                    "PrefetchLoader producer thread failed to stop within "
                    "10s of close(); a batch source is blocked inside "
                    f"{self.loader!r}"
                )

    def close(self) -> None:
        """Stop the current epoch's producer (if any): signal, drain, join.
        Idempotent; called by the iterator's cleanup and usable directly by
        an aborting consumer."""
        stop, thread, q = self._stop, self._thread, self._queue
        self._stop = self._thread = self._queue = None
        self._shutdown(stop, q, thread)

    def __iter__(self) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        self.close()  # a fresh epoch supersedes any abandoned producer
        q: queue.Queue = queue.Queue(maxsize=self.depth)
        stop = threading.Event()

        def _put(item) -> bool:
            """Bounded put that gives up when the consumer is gone."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def produce() -> None:
            try:
                it = iter(self.loader)
                while True:
                    # span the assembly only, not the bounded put: queue
                    # backpressure is the consumer running ahead, not work
                    with _obs_span("batch_assemble"):
                        try:
                            item = next(it)
                        except StopIteration:
                            break
                    if not _put(item):
                        return
                _put(self._DONE)
            except BaseException as e:  # surface producer errors, don't hang
                _put(e)

        thread = threading.Thread(
            target=produce, name="dtc-prefetch", daemon=True
        )
        self._stop, self._thread, self._queue = stop, thread, q
        thread.start()
        try:
            while True:
                try:
                    item = q.get(timeout=1.0)
                except queue.Empty:
                    if not thread.is_alive():
                        raise RuntimeError(
                            "PrefetchLoader producer thread died without "
                            "signaling completion or an exception"
                        ) from None
                    continue
                if item is self._DONE:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            # consumer may abandon mid-epoch (steps_per_epoch break, error):
            # signal the producer, drain, and join so it never blocks
            # forever.  Tear down THIS generation's locals — a stale
            # abandoned iterator must never kill a newer epoch's producer.
            if self._thread is thread:
                self._stop = self._thread = self._queue = None
            self._shutdown(stop, q, thread)


def chunked_batches(
    batches: Iterator[tuple[np.ndarray, np.ndarray]],
    total_steps: int,
    chunk_steps: int,
    start: int = 0,
) -> Iterator[tuple[int, int, dict[str, np.ndarray]]]:
    """Stack a batch iterator into ``(start, take, {"x", "y"})`` chunks of at
    most ``chunk_steps`` steps, covering steps ``[start, total_steps)`` — the
    host half of the chunked streaming path, shared by the synchronous
    fallback and the ``DevicePrefetcher`` producer so the two can never
    disagree on chunk boundaries."""
    done = start
    while done < total_steps:
        take = min(chunk_steps, total_steps - done)
        xs, ys = [], []
        for _ in range(take):
            try:
                x, y = next(batches)
            except StopIteration:  # source ran dry: yield the partial chunk
                break
            xs.append(x)
            ys.append(y)
        if not xs:
            return
        yield done, len(xs), {"x": np.stack(xs), "y": np.stack(ys)}
        done += len(xs)
        if len(xs) < take:
            return


class DevicePrefetcher:
    """Double-buffered host→device chunk staging for the streaming train path.

    A producer thread pulls the next ``chunk_steps`` batches from the (epoch's)
    batch iterator, stacks them ``(K, B, ...)``, and immediately issues the
    asynchronous ``jax.device_put`` via ``place`` (the trainer passes
    ``shard_batch`` bound to the mesh + chunk sharding) — so the H2D copy of
    chunk *i+1* rides the wire while chunk *i*'s scanned dispatch is still
    executing on device.  The chip never waits on batch assembly OR transfer;
    the main thread's only data-path work is a queue pop.

    ``depth`` bounds the staged chunks in flight (producer blocks when the
    queue is full), capping the extra HBM at ``depth`` chunk buffers — double
    buffering is ``depth=1``; the default 2 absorbs one chunk of jitter.

    Yields ``(start, take, device_batch)``.  A producer exception (loader
    failure, a ``device_put`` OOM) is re-raised at the consuming ``next()``;
    ``close()`` — idempotent, also the context-manager exit — signals the
    producer, drains staged chunks, and joins the thread, so an aborting
    consumer (preemption drain, error unwind) never leaks it.
    """

    _DONE = object()

    def __init__(
        self,
        batches: Iterator[tuple[np.ndarray, np.ndarray]],
        total_steps: int,
        chunk_steps: int,
        place,
        *,
        start: int = 0,
        depth: int = 2,
    ) -> None:
        self.depth = max(1, depth)
        self._q: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._chunks = chunked_batches(batches, total_steps, chunk_steps, start)
        self._place = place
        self._thread = threading.Thread(
            target=self._produce, name="dtc-device-prefetch", daemon=True
        )
        self._thread.start()

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self) -> None:
        try:
            while True:
                # one span per staged chunk: batch stacking + the async
                # device_put issue — the queue put is excluded (blocking
                # there is backpressure from a full prefetch window)
                with _obs_span("h2d_stage"):
                    try:
                        begin, take, host_batch = next(self._chunks)
                    except StopIteration:
                        break
                    staged = self._place(host_batch)  # async H2D
                if not self._put((begin, take, staged)):
                    return
            self._put(self._DONE)
        except BaseException as e:  # surfaced at the consumer's next()
            self._put(e)

    def __iter__(self):
        return self

    def __next__(self) -> tuple[int, int, dict]:
        while True:
            try:
                item = self._q.get(timeout=1.0)
            except queue.Empty:
                if not self._thread.is_alive():
                    raise RuntimeError(
                        "DevicePrefetcher producer thread died without "
                        "signaling completion or an exception"
                    ) from None
                continue
            if item is self._DONE:
                self._q.put(item)  # keep the sentinel for a re-entrant next()
                raise StopIteration
            if isinstance(item, BaseException):
                self.close()
                raise item
            return item

    def close(self) -> None:
        """Stop the producer and join it: signal, drain staged chunks (their
        device buffers free with the references), join."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=10.0)
        if self._thread.is_alive():  # pragma: no cover - diagnostic path
            raise RuntimeError(
                "DevicePrefetcher producer thread failed to stop within 10s "
                "of close(); the batch source or device_put is blocked"
            )

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def get_trn_val_loader(
    hparams,
    batch_size: int,
    *,
    valid_size: float = 0.1,
    shuffle: bool = True,
    num_shards: int = 1,
    shard: int = 0,
) -> tuple[HostLoader, HostLoader]:
    """Reference-shaped API (``src/single/dataset.py:13``): streaming train
    and valid loaders.  Train is sharded + drop_last (SPMD lockstep); valid
    is unsharded, mirroring ``src/ddp/dataset.py:109-114``."""
    train_ds, val_ds, _ = get_datasets(hparams)
    train_loader = HostLoader(
        train_ds,
        batch_size,
        shuffle=shuffle,
        drop_last=True,
        seed=hparams.seed,
        num_shards=num_shards,
        shard=shard,
    )
    valid_loader = HostLoader(val_ds, batch_size, shuffle=False, seed=hparams.seed)
    return train_loader, valid_loader


def get_tst_loader(
    hparams, batch_size: int, *, num_shards: int = 1, shard: int = 0
) -> HostLoader:
    """Reference-shaped test loader (``src/single/dataset.py:110``).  Sharded
    with ``even=False`` so a cross-host reduction sees every example exactly
    once (fixes SURVEY.md §5 quirk 1)."""
    _, _, test_ds = get_datasets(hparams)
    if num_shards > 1:
        idx = shard_indices(np.arange(len(test_ds)), num_shards, shard, even=False)
        test_ds = test_ds.subset(idx)
    return HostLoader(test_ds, batch_size, shuffle=False, seed=hparams.seed)
