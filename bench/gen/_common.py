"""Seed-independent size sets shared by the traffic generators.

Every generator draws its lengths as stratified
quantiles of the mix's distributions, and pairs them (a prompt with its
output length, a session with its turns) with a fixed generator,
``layout_rng()``: the same requests for every seed. The seed draws the
token ids and reorders the requests, but only within blocks
(``blocked``): each run of ``BLOCK`` consecutive requests, or gaps, is the
same stratified sample of the whole set for every seed, so that a window
that sees part of the set sees the same work whatever the seed. Two seeds
then offer the same work, a difference between their runs is the
system's, and the page counts that retirement and admission use
(``page_counts``) are known before the run, so that set-up builds exactly
those programs.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal(n: int, spec: dict) -> np.ndarray:
    """n lengths from a lognormal with ``median`` and ``sigma``, rounded
    and clipped to [``min``, ``max``]."""
    z = np.array([NormalDist().inv_cdf(q) for q in quantiles(n)])
    v = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(v, spec["min"], spec["max"]).astype(np.int64)


BLOCK = 8


def blocked(rng: np.random.Generator, keys) -> np.ndarray:
    """An order of ``len(keys)`` items: block j holds the items of rank j,
    j + m, j + 2m, ... by key (m blocks), so every block spans the whole
    range; blocks come in a fixed sequence, and ``rng`` shuffles the items
    within each block."""
    ranks = np.argsort(np.asarray(keys), kind="stable")
    m = -(-len(ranks) // BLOCK)
    return np.concatenate([shuffled(rng, ranks[j::m]) for j in range(m)])


def shuffled(rng: np.random.Generator, values) -> np.ndarray:
    values = np.asarray(values)
    return values[rng.permutation(len(values))]


def tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    """n random token ids (id 0 left out)."""
    return rng.integers(1, vocab, size=n, dtype=np.int64).astype(np.int32)


def layout_rng() -> np.random.Generator:
    return np.random.default_rng(0)


def pages_of(n_tokens: int, page_size: int) -> int:
    """Full pages of a finished sequence of ``n_tokens`` prompt and output
    tokens: its KV covers all but the last output token."""
    return (n_tokens - 1) // page_size
