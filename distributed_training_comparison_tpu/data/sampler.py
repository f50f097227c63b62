"""Split, shuffle, and shard index logic.

Parity targets:
- 90/10 train/val split by shuffled indices — reference
  ``src/single/dataset.py:79-89`` (``np.random.shuffle``; first 10% = val).
- ``DistributedSampler`` per-rank sharding with per-epoch reshuffle via
  ``set_epoch`` — reference ``src/ddp/dataset.py:98`` +
  ``src/ddp/trainer.py:125``.

TPU-native redesign: all of this is explicit index arithmetic on seeded
``numpy.random.Generator`` / ``jax.random`` keys — no sampler objects, no
reliance on global RNG state being identical across ranks (SURVEY.md §5
quirk 6).  The same (seed, epoch) always yields the same permutation on
every host; each host then takes its own contiguous slice.
"""

from __future__ import annotations

import jax
import numpy as np


def train_val_split(
    n: int, valid_size: float = 0.1, seed: int = 42, shuffle: bool = True,
    valid_count: int = 0,
) -> tuple[np.ndarray, np.ndarray]:
    """Disjoint (train_idx, valid_idx) covering ``range(n)``.

    Matches the reference's convention: shuffle indices, first
    ``floor(valid_size*n)`` are validation, rest are train
    (``src/single/dataset.py:79-87``) — but with an explicit seeded
    Generator instead of global ``np.random`` state.  A ``valid_count``
    above 0 (``--valid-examples``) is the validation count itself, for the
    job whose split no fraction of a tenth gives (32 and 4 of 36).
    """
    if not 0.0 <= valid_size <= 1.0:
        raise ValueError("valid_size should be in the range [0, 1].")
    if not 0 <= valid_count < max(n, 1):
        raise ValueError(f"valid_count {valid_count} leaves no train example of {n}")
    indices = np.arange(n)
    if shuffle:
        np.random.default_rng(seed).shuffle(indices)
    split = valid_count or int(np.floor(valid_size * n))
    return indices[split:], indices[:split]


def shard_indices(
    indices: np.ndarray, num_shards: int, shard: int, *, even: bool = True
) -> np.ndarray:
    """The ``DistributedSampler`` analogue: this shard's slice of ``indices``.

    With ``even=True`` the index list is padded by wrapping (like
    DistributedSampler's sample duplication) so every shard has the same
    length — required for SPMD lockstep where all hosts must run the same
    number of steps.  ``even=False`` gives a no-duplicate cover for exact
    one-pass evaluation (fixes the reference quirk of rank 0 testing on 1/N
    of the test set, SURVEY.md §5 quirk 1).
    """
    if not 0 <= shard < num_shards:
        raise ValueError(f"shard {shard} out of range for {num_shards} shards")
    n = len(indices)
    if even:
        per = -(-n // num_shards)  # ceil
        padded = np.concatenate([indices, indices[: per * num_shards - n]])
        return padded[shard * per : (shard + 1) * per]
    return indices[shard::num_shards]


def epoch_permutation(key: jax.Array, epoch: int, n: int) -> jax.Array:
    """Device-side per-epoch shuffle: fold the epoch into the root key and
    permute.  The ``set_epoch`` analogue, but explicit and device-resident —
    used by the scanned epoch loop to gather shuffled batches in-jit."""
    return jax.random.permutation(jax.random.fold_in(key, epoch), n)
