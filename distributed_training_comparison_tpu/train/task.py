"""The model-family seam: what a family trains on.

A zoo model names its task (the class attribute ``task``; image
classification where it names none), and everything that differs between
an image classifier and a next-token decoder hangs on the ``Task`` — the
splits and their device-resident form, the array a model is initialised
on, how a batch becomes the model's input, and how hits are counted.  The
loss is the same for both: the mean over every label of ``-log
softmax(logits)[label]`` — one label an image, one a token.  ``Trainer``,
``_make_step_core`` and ``_make_eval_core`` ask the task and never the
model's class or name.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from ..data.augment import normalize_images, random_crop_flip
from ..data.loader import DeviceDataset, get_datasets
from ..data.sampler import train_val_split
from ..data.tokens import markov_tokens


@dataclasses.dataclass(frozen=True)
class Task:
    name: str
    # (hparams, model) -> (train, valid, test) ``DeviceDataset``s
    datasets: Callable
    # image shape (1, H, W, 3) -> the array ``model.init`` sees
    init_input: Callable
    # (inputs, key, *, augment, mean, std, dtype, draw_sharding) -> model input
    prepare: Callable
    # (logits, labels) -> (top-1 hit, top-5 hit), one boolean a label
    hits: Callable


# ------------------------------------------------------------------ images


def _image_input(image_shape):
    return jnp.zeros(image_shape, jnp.float32)


def _prepare_images(images, key, *, augment, mean, std, dtype, draw_sharding=None):
    with jax.named_scope("augment"):
        if augment:
            # draw_sharding pins the crop/flip draws replicated: without
            # it GSPMD may partition the threefry generation differently
            # per mesh shape, and the SAME (seed, epoch, step) would
            # augment differently under DP than under DP×TP×PP
            # (data/augment.py) — breaking cross-layout trajectory parity
            images = random_crop_flip(images, key, draw_sharding=draw_sharding)
        return normalize_images(images, mean, std, dtype=dtype)


def _topk_hits(logits, labels):
    _, top5 = jax.lax.top_k(logits, 5)
    hits = top5 == labels[:, None]
    return hits[:, :1].any(-1), hits.any(-1)


IMAGE_CLASSIFICATION = Task(
    "image_classification", lambda hparams, model: get_datasets(hparams),
    _image_input, _prepare_images,
    _topk_hits,
)

# ------------------------------------------------------------------ tokens


def _token_datasets(hparams, model):
    """Train / valid / test splits of Markov walks (``data/tokens.py``),
    cut like the image splits: ``--limit-examples`` sequences, 90/10 or
    ``--valid-examples`` of them for validation.  The vocabulary is the
    model's held slice."""
    if not getattr(hparams, "synthetic_data", False):
        raise ValueError(
            "a token model trains on the seeded Markov source only: there "
            "is no corpus on disk; pass --synthetic-data"
        )
    vocab = model.config["vocab_size"]
    seq = hparams.seq_len
    n = getattr(hparams, "limit_examples", 0) or 1024

    def split(count, seed):
        rows = markov_tokens(count, seq, vocab, seed=seed, anchor_seed=hparams.seed)
        return DeviceDataset(rows[:, :-1], rows[:, 1:], vocab, "markov_tokens")

    full = split(n, hparams.seed)
    trn_idx, val_idx = train_val_split(
        len(full), valid_size=0.1, seed=hparams.seed,
        valid_count=getattr(hparams, "valid_examples", 0),
    )
    return full.subset(trn_idx), full.subset(val_idx), split(min(n, 256), hparams.seed + 1)


def _token_input(image_shape):  # no parameter's shape depends on the length
    return jnp.zeros((1, 8), jnp.int32)


def _prepare_tokens(tokens, key, **_image_options):
    return tokens


def _rank_hits(logits, labels):
    """The label's rank among the logits, by counting the larger ones: a
    sort of a vocabulary-wide row per token is what ``top_k`` would cost."""
    at_label = jnp.take_along_axis(logits, labels[..., None], axis=-1)
    rank = jnp.sum(logits > at_label, axis=-1)
    return rank == 0, rank < 5


NEXT_TOKEN = Task(
    "next_token", _token_datasets, _token_input, _prepare_tokens, _rank_hits
)

TASKS = {t.name: t for t in (IMAGE_CLASSIFICATION, NEXT_TOKEN)}


def task_of(model) -> Task:
    """The task a model registers through its ``task`` attribute."""
    return TASKS[getattr(model, "task", IMAGE_CLASSIFICATION.name)]
