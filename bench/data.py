"""Seeded corpora and queries: the benchmark's own copies.

The generators follow ``benchmarks/common.py`` (``sift_like``,
``queries_from``), kept here so that a later change to the
program cannot move the yardstick.  They draw float32 normals directly,
which makes a 1M x 128 corpus in about a second.
"""

from __future__ import annotations

import numpy as np

CHUNK_ROWS = 1 << 16


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose): the same seed always
    gives the same corpus, queries and arrivals, whatever else changes."""
    words = [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF]
    words += [ord(c) for c in stream]
    return np.random.default_rng(words)


def clustered(n: int, dim: int, rng: np.random.Generator,
              n_clusters: int = 64, center_scale: float = 3.0) -> np.ndarray:
    """Gaussian clusters: SIFT-like local-feature structure (L2) or, once
    the metric normalizes them, topic-like document vectors (angular)."""
    centers = rng.standard_normal((n_clusters, dim), dtype=np.float32)
    centers *= np.float32(center_scale)
    assign = rng.integers(0, n_clusters, n)
    out = rng.standard_normal((n, dim), dtype=np.float32)
    for lo in range(0, n, CHUNK_ROWS):
        hi = min(lo + CHUNK_ROWS, n)
        out[lo:hi] += centers[assign[lo:hi]]
    return out


def queries_near(base: np.ndarray, picks: np.ndarray, noise: float,
                 rng: np.random.Generator) -> np.ndarray:
    """Queries a small perturbation away from the picked base rows."""
    q = base[picks].copy()
    q += np.float32(noise) * rng.standard_normal(q.shape, dtype=np.float32)
    return q
