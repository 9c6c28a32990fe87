"""Synthetic token streams: the bigram source of the trainer's
`data.pipeline.make_token_stream`, drawn in bulk.

Each token has ``successors`` likely next tokens, chosen uniformly; with
probability ``jump`` the chain instead jumps to a uniform token.  The
trainer's version steps the chain one token at a time in Python, which
takes seconds per run at 65536 tokens; here the chain is split at its jumps
and every segment advances at once, so the number of Python steps is the
longest run without a jump (a few hundred), not the stream length.
"""
from __future__ import annotations

import numpy as np


def bigram_stream(workers: int, length: int, vocab: int, seed: int, *,
                  successors: int = 8, jump: float = 0.1) -> np.ndarray:
    """(workers, length) int32 tokens, a function of the seed alone."""
    rng = np.random.default_rng(seed)
    succ = rng.integers(0, vocab, size=(vocab, successors), dtype=np.int32)
    n = workers * length
    starts = rng.random(n) < jump
    starts[::length] = True                  # every worker's chain starts
    fresh = rng.integers(0, vocab, size=n, dtype=np.int32)
    pick = rng.integers(0, successors, size=n)
    out = np.where(starts, fresh, 0).astype(np.int32)
    # offset of each position from the last jump at or before it
    last = np.maximum.accumulate(np.where(starts, np.arange(n), 0))
    offset = np.arange(n) - last
    for k in range(1, int(offset.max()) + 1):
        idx = np.flatnonzero(offset == k)
        out[idx] = succ[out[idx - 1], pick[idx]]
    return out.reshape(workers, length)


def bigram_stream_loop(workers: int, length: int, vocab: int, seed: int, *,
                       successors: int = 8, jump: float = 0.1) -> np.ndarray:
    """`bigram_stream` one position at a time: the plain form the tests
    hold the bulk form to."""
    rng = np.random.default_rng(seed)
    succ = rng.integers(0, vocab, size=(vocab, successors), dtype=np.int32)
    n = workers * length
    starts = rng.random(n) < jump
    starts[::length] = True
    fresh = rng.integers(0, vocab, size=n, dtype=np.int32)
    pick = rng.integers(0, successors, size=n)
    out = np.zeros(n, np.int32)
    for i in range(n):
        out[i] = fresh[i] if starts[i] else succ[out[i - 1], pick[i]]
    return out.reshape(workers, length)


def sample(stream: np.ndarray, rng: np.random.Generator, seq: int,
           batch: int) -> dict:
    """One slot's batch: for each worker, ``batch`` windows of seq + 1
    tokens at uniform starts; inputs and next-token labels (W, B, seq)."""
    w, t = stream.shape
    starts = rng.integers(0, t - seq - 1, size=(w, batch))
    idx = starts[..., None] + np.arange(seq + 1)
    rows = np.stack([stream[i][idx[i]] for i in range(w)])
    return {"tokens": rows[..., :-1], "labels": rows[..., 1:]}
