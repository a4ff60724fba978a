"""Seeded traffic: token rows for training steps and prompts for batches.

Uniform tokens over the vocabulary, a pure function of (seed, index), as the
program's ``data.pipeline.SyntheticLM`` makes them; the copy lives here so
that no later change to the program moves the traffic.
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int, index: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, int(index)])


def token_rows(seed: int, index: int, rows: int, seq: int, vocab: int) -> np.ndarray:
    """(rows, seq) int32 tokens, uniform over [0, vocab)."""
    return _rng(seed, index).integers(0, vocab, (rows, seq), dtype=np.int32)


def sample(seed: int, population: int, k: int, salt: int = 7919) -> np.ndarray:
    """``k`` distinct indices of ``range(population)`` drawn from the seed."""
    k = min(k, population)
    return np.sort(_rng(seed, 2**32 + salt).choice(population, k, replace=False))


def jax_key(seed: int):
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
