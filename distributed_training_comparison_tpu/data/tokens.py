"""A seeded token source a decoder can learn: a sparse first-order Markov
chain over the held vocabulary.

There is no corpus on disk and no network.  Each id has ``FANOUT``
successors with fixed probabilities (the chain is drawn from
``anchor_seed``, so train and test splits share it), and a sequence is a
walk from a uniform start.  The next token's entropy is about 1.2 nats
against ``log(vocab)`` for an untrained model, so the per-token loss falls
within the first epochs — which the benchmark's ``correct`` and the
convergence tests rely on.  One document a sequence: no packing, no
boundary mask.
"""

from __future__ import annotations

import numpy as np

FANOUT = 4
SUCCESSOR_P = (0.55, 0.25, 0.15, 0.05)


def markov_tokens(
    n: int, seq_len: int, vocab: int, seed: int = 0,
    anchor_seed: int | None = None,
) -> np.ndarray:
    """``(n, seq_len + 1)`` int32 walks; inputs are ``[:, :-1]`` and
    next-token labels ``[:, 1:]``.  Deterministic in the two seeds."""
    chain = np.random.default_rng(seed if anchor_seed is None else anchor_seed)
    successors = chain.integers(0, vocab, size=(vocab, FANOUT), dtype=np.int32)
    rng = np.random.default_rng(seed)
    choice = rng.choice(FANOUT, size=(n, seq_len), p=SUCCESSOR_P)
    rows = np.empty((n, seq_len + 1), np.int32)
    rows[:, 0] = rng.integers(0, vocab, size=n)
    for t in range(seq_len):
        rows[:, t + 1] = successors[rows[:, t], choice[:, t]]
    return rows
