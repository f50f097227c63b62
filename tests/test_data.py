"""Data pipeline tests: split/shard correctness, augmentation determinism,
normalization semantics, loaders, and the raw CIFAR-100 reader (against a
synthetic on-disk fixture in the official pickle format).

The reference has no tests at all (SURVEY.md §4); the sharding tests here
are the 'DistributedSampler covers the dataset' checks it never had.
"""

import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_comparison_tpu.data import (
    CIFAR100_MEAN,
    CIFAR100_STD,
    DeviceDataset,
    HostLoader,
    PrefetchLoader,
    epoch_permutation,
    get_datasets,
    get_trn_val_loader,
    get_tst_loader,
    load_cifar100,
    normalize_images,
    random_crop_flip,
    shard_indices,
    synthetic_dataset,
    train_val_split,
)
from distributed_training_comparison_tpu.data.cifar100 import save_npz_cache
from distributed_training_comparison_tpu.data.loader import HostLoader


class HP:
    """Minimal hparams stub."""

    dset = "cifar100"
    dpath = "data/"
    seed = 42
    synthetic_data = True


# ---------------------------------------------------------------- split/shard


def test_train_val_split_disjoint_cover():
    trn, val = train_val_split(50_000, valid_size=0.1, seed=42)
    assert len(val) == 5_000 and len(trn) == 45_000
    assert np.array_equal(np.sort(np.concatenate([trn, val])), np.arange(50_000))


def test_train_val_split_deterministic():
    a = train_val_split(1000, seed=7)
    b = train_val_split(1000, seed=7)
    c = train_val_split(1000, seed=8)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[1], c[1])


def test_train_val_split_takes_a_validation_count():
    """``--valid-examples``: the count itself where the tenth, rounded
    down, cannot give the split (36 -> 33 / 3); the same shuffle, so the
    count's first indices are the fraction's; a count that leaves no train
    example is refused; the token task's splits follow it."""
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.models import get_model
    from distributed_training_comparison_tpu.train.task import task_of

    tenth = train_val_split(36, valid_size=0.1, seed=3)
    trn, val = train_val_split(36, valid_size=0.1, seed=3, valid_count=4)
    assert (len(tenth[0]), len(tenth[1]), len(trn), len(val)) == (33, 3, 32, 4)
    assert np.array_equal(val[:3], tenth[1])
    assert np.array_equal(np.sort(np.concatenate([trn, val])), np.arange(36))
    with pytest.raises(ValueError):
        train_val_split(36, valid_count=36)
    argv = ["--synthetic-data", "--model", "afmoe_tiny", "--seq-len", "16",
            "--limit-examples", "36"]
    for extra, sizes in (([], (33, 3)), (["--valid-examples", "4"], (32, 4))):
        hp = load_config("tpu", argv + extra)
        model = get_model(hp.model, model_cut=hp.model_cut)
        train, valid, _ = task_of(model).datasets(hp, model)
        assert (len(train), len(valid)) == sizes


def test_shard_indices_even_lockstep():
    idx = np.arange(103)
    shards = [shard_indices(idx, 8, s, even=True) for s in range(8)]
    lens = {len(s) for s in shards}
    assert lens == {13}  # ceil(103/8), padded by wrapping
    covered = np.unique(np.concatenate(shards))
    assert np.array_equal(covered, idx)


def test_shard_indices_exact_cover_no_dupes():
    idx = np.arange(103)
    shards = [shard_indices(idx, 8, s, even=False) for s in range(8)]
    cat = np.concatenate(shards)
    assert len(cat) == 103 and len(np.unique(cat)) == 103


def test_epoch_permutation_deterministic_and_epoch_dependent():
    key = jax.random.key(0)
    p1 = epoch_permutation(key, 3, 64)
    p2 = epoch_permutation(key, 3, 64)
    p3 = epoch_permutation(key, 4, 64)
    assert jnp.array_equal(p1, p2)
    assert not jnp.array_equal(p1, p3)
    assert jnp.array_equal(jnp.sort(p1), jnp.arange(64))


# ---------------------------------------------------------------- augmentation


def test_random_crop_flip_shape_dtype_and_determinism():
    x = synthetic_dataset(16, seed=0)[0]
    key = jax.random.key(1)
    a = random_crop_flip(jnp.asarray(x), key)
    b = random_crop_flip(jnp.asarray(x), key)
    c = random_crop_flip(jnp.asarray(x), jax.random.key(2))
    assert a.shape == x.shape and a.dtype == jnp.uint8
    assert jnp.array_equal(a, b)
    assert not jnp.array_equal(a, c)


def test_random_crop_zero_offset_is_identity():
    # With padding=0 the only crop window is the image itself; flips remain.
    x = jnp.asarray(synthetic_dataset(8, seed=3)[0])
    out = np.asarray(random_crop_flip(x, jax.random.key(0), padding=0))
    x = np.asarray(x)
    for i in range(8):
        assert np.array_equal(out[i], x[i]) or np.array_equal(out[i], x[i, :, ::-1, :])


def test_random_crop_flip_matches_slice_reference():
    # The one-hot-matmul formulation must be bit-identical to the obvious
    # per-sample pad→dynamic_slice→flip formulation for the same key.
    x = jnp.asarray(synthetic_dataset(32, seed=5)[0])
    key = jax.random.key(9)
    out = np.asarray(random_crop_flip(x, key))

    padding = 4
    crop_key, flip_key = jax.random.split(key)
    offsets = np.asarray(jax.random.randint(crop_key, (32, 2), 0, 2 * padding + 1))
    flips = np.asarray(jax.random.bernoulli(flip_key, 0.5, (32,)))
    padded = np.pad(np.asarray(x), ((0, 0), (padding,) * 2, (padding,) * 2, (0, 0)))
    for i in range(32):
        dy, dx = offsets[i]
        ref = padded[i, dy : dy + 32, dx : dx + 32, :]
        if flips[i]:
            ref = ref[:, ::-1, :]
        assert np.array_equal(out[i], ref)


def test_random_crop_flip_float_input_preserved():
    x = jnp.asarray(synthetic_dataset(8, seed=1)[0]).astype(jnp.float32)
    out = random_crop_flip(x, jax.random.key(3))
    assert out.dtype == jnp.float32
    # float selection is exact too: every output value exists in the padded input
    assert set(np.unique(out)).issubset(set(np.unique(np.asarray(x))) | {0.0})


def test_normalize_matches_torchvision_semantics():
    x = jnp.full((2, 4, 4, 3), 128, dtype=jnp.uint8)
    out = np.asarray(normalize_images(x))
    expect = (128 / 255.0 - np.array(CIFAR100_MEAN)) / np.array(CIFAR100_STD)
    np.testing.assert_allclose(out[0, 0, 0], expect, rtol=1e-5)


def test_normalize_bf16_output():
    x = jnp.zeros((1, 2, 2, 3), dtype=jnp.uint8)
    assert normalize_images(x, dtype=jnp.bfloat16).dtype == jnp.bfloat16


# ---------------------------------------------------------------- synthetic


def test_synthetic_learnable_structure():
    x, y = synthetic_dataset(512, num_classes=4, seed=0)
    xf = x.reshape(len(x), -1).astype(np.float32)
    same = np.linalg.norm(xf[y == 0][0] - xf[y == 0][1])
    diff = np.linalg.norm(xf[y == 0][0] - xf[y == 1][0])
    assert same < diff  # same-class images cluster around their anchor


# ---------------------------------------------------------------- loaders


def test_get_datasets_split_sizes():
    trn, val, tst = get_datasets(HP())
    assert len(trn) == 45_000 and len(val) == 5_000 and len(tst) == 10_000


def test_host_loader_epoch_reshuffle_and_drop_last():
    ds = DeviceDataset(*synthetic_dataset(70, num_classes=4, seed=0), num_classes=4)
    loader = HostLoader(ds, 32, shuffle=True, drop_last=True, seed=1)
    assert len(loader) == 2
    loader.set_epoch(0)
    e0 = [lbl.copy() for _, lbl in loader]
    loader.set_epoch(0)
    e0b = [lbl.copy() for _, lbl in loader]
    loader.set_epoch(1)
    e1 = [lbl.copy() for _, lbl in loader]
    assert all(np.array_equal(a, b) for a, b in zip(e0, e0b))
    assert not all(np.array_equal(a, b) for a, b in zip(e0, e1))


def test_prefetch_loader_preserves_order_and_determinism():
    """PrefetchLoader must yield exactly the wrapped loader's sequence —
    same batches, same order, every epoch (the background thread buys
    overlap, never reordering)."""
    x, y = synthetic_dataset(256, num_classes=10, seed=3)
    ds = DeviceDataset(x, y, num_classes=10)
    for epoch in (0, 1):
        raw = HostLoader(ds, 32, shuffle=True, drop_last=True, seed=9)
        pre = PrefetchLoader(
            HostLoader(ds, 32, shuffle=True, drop_last=True, seed=9), depth=3
        )
        raw.set_epoch(epoch)
        pre.set_epoch(epoch)
        raw_batches = list(raw)
        pre_batches = list(pre)
        assert len(pre) == len(raw) == len(raw_batches) == len(pre_batches)
        for (rx, ry), (px, py) in zip(raw_batches, pre_batches):
            np.testing.assert_array_equal(rx, px)
            np.testing.assert_array_equal(ry, py)


def test_prefetch_loader_abandoned_iteration_stops_producer():
    """Breaking out mid-epoch must not leave the producer thread blocked
    (trainer breaks at steps_per_epoch; errors abandon the generator)."""
    import threading
    import time

    x, y = synthetic_dataset(512, num_classes=10, seed=4)
    ds = DeviceDataset(x, y, num_classes=10)
    before = threading.active_count()
    for _ in range(5):
        pre = PrefetchLoader(HostLoader(ds, 32, shuffle=False, seed=1), depth=2)
        it = iter(pre)
        next(it)
        it.close()  # GeneratorExit → finally: stop + drain + join
    time.sleep(1.0)
    assert threading.active_count() <= before + 1


def test_prefetch_loader_propagates_producer_errors():
    class Boom:
        def set_epoch(self, e):
            pass

        def __iter__(self):
            yield (np.zeros(1), np.zeros(1))
            raise RuntimeError("producer failed")

    pre = PrefetchLoader(Boom(), depth=2)
    it = iter(pre)
    next(it)
    with pytest.raises(RuntimeError, match="producer failed"):
        next(it)


@pytest.mark.slow
def test_sharded_train_loaders_disjoint_per_epoch():
    hp = HP()
    loaders = [
        get_trn_val_loader(hp, 64, num_shards=4, shard=s)[0] for s in range(4)
    ]
    for ld in loaders:
        ld.set_epoch(2)
    seen = [np.concatenate([lbl for _, lbl in ld]) for ld in loaders]
    sizes = {len(s) for s in seen}
    assert len(sizes) == 1  # lockstep: same steps on every shard


@pytest.mark.slow
def test_tst_loader_shards_cover_test_set_exactly():
    hp = HP()
    total = sum(
        sum(len(lbl) for _, lbl in get_tst_loader(hp, 128, num_shards=4, shard=s))
        for s in range(4)
    )
    assert total == 10_000  # no duplication — fixes SURVEY.md §5 quirk 1


# ---------------------------------------------------------------- raw reader


@pytest.fixture()
def fake_cifar_dir(tmp_path):
    """Write tiny train/test files in the official pickle format."""
    d = tmp_path / "cifar-100-python"
    d.mkdir()
    rng = np.random.default_rng(0)
    for split, n in (("train", 20), ("test", 10)):
        data = rng.integers(0, 256, size=(n, 3072), dtype=np.uint8)
        labels = rng.integers(0, 100, size=n).tolist()
        with open(d / split, "wb") as f:
            pickle.dump({b"data": data, b"fine_labels": labels}, f)
    return tmp_path


def test_load_cifar100_pickle_roundtrip(fake_cifar_dir):
    x, y = load_cifar100(fake_cifar_dir, "train")
    assert x.shape == (20, 32, 32, 3) and x.dtype == np.uint8
    assert y.shape == (20,) and y.dtype == np.int32
    # CHW→HWC transpose correctness: reconstruct flat layout
    with open(fake_cifar_dir / "cifar-100-python" / "train", "rb") as f:
        raw = pickle.load(f, encoding="bytes")[b"data"]
    np.testing.assert_array_equal(
        x[0], raw[0].reshape(3, 32, 32).transpose(1, 2, 0)
    )


def test_tarball_auto_extraction(fake_cifar_dir, tmp_path):
    """Dropping the official cifar-100-python.tar.gz in --dpath must be
    enough: the loader extracts it and reads the pickles."""
    import tarfile

    tar_dir = tmp_path / "tardrop"
    tar_dir.mkdir()
    with tarfile.open(tar_dir / "cifar-100-python.tar.gz", "w:gz") as t:
        t.add(fake_cifar_dir / "cifar-100-python", arcname="cifar-100-python")
    x, y = load_cifar100(tar_dir, "train")
    assert x.shape == (20, 32, 32, 3) and y.shape == (20,)
    # extraction is one-time: the extracted dir now exists alongside the tar
    assert (tar_dir / "cifar-100-python" / "train").is_file()


def test_npz_cache_roundtrip(fake_cifar_dir):
    x0, y0 = load_cifar100(fake_cifar_dir, "test")
    save_npz_cache(fake_cifar_dir)
    x1, y1 = load_cifar100(fake_cifar_dir, "test")  # now served from npz
    np.testing.assert_array_equal(x0, x1)
    np.testing.assert_array_equal(y0, y1)


def test_missing_data_raises_helpfully(tmp_path):
    with pytest.raises(FileNotFoundError, match="synthetic"):
        load_cifar100(tmp_path, "train")


@pytest.mark.slow
def test_north_star_command_end_to_end_on_fake_official_data(tmp_path):
    """The north-star recipe (real CIFAR-100 files, NOT --synthetic-data)
    run end to end: Trainer.fit() for 2 epochs + test() off an official-
    pickle-format ``cifar-100-python/`` dir, on the CPU mesh — so the day
    the real dataset lands, the ``run_tpu.sh`` command path has already
    executed in CI (VERDICT r4 item 7).  Uses the real resnet18 flagship
    at a CI-sized batch/example count."""
    from distributed_training_comparison_tpu.config import load_config
    from distributed_training_comparison_tpu.train import Trainer

    data_dir = tmp_path / "data"
    d = data_dir / "cifar-100-python"
    d.mkdir(parents=True)
    rng = np.random.default_rng(7)
    for split, n in (("train", 96), ("test", 32)):
        with open(d / split, "wb") as f:
            pickle.dump(
                {
                    b"data": rng.integers(0, 256, size=(n, 3072), dtype=np.uint8),
                    b"fine_labels": rng.integers(0, 100, size=n).tolist(),
                },
                f,
            )

    hp = load_config(
        "tpu",
        argv=[
            "--dpath", str(data_dir),
            "--batch-size", "32",
            "--epoch", "2",
            "--ckpt-path", str(tmp_path / "ckpt"),
        ],
    )
    assert not getattr(hp, "synthetic_data", False)
    trainer = Trainer(hp)  # real model zoo entry: resnet18
    version = trainer.fit()
    results = trainer.test()
    trainer.close()

    vdir = tmp_path / "ckpt" / f"version-{version}"
    assert (vdir / "last.ckpt").exists()
    assert set(results) == {"test_loss", "test_top1", "test_top5"}
    assert np.isfinite(results["test_loss"])
