"""Shared benchmark helpers: synthetic SIFT/DEEP-like datasets, timing,
CSV emission (``name,us_per_call,derived``)."""

from __future__ import annotations

import os
import time

import numpy as np


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it itself;
    otherwise the cache lives in ``<checkout>/.jax_cache``.  The path never
    depends on a pid, a temp name or the time, so a later run finds what an
    earlier one cached.  Entry points call this; importing the library does
    not.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(checkout, ".jax_cache")
        )


def sift_like(n: int, dim: int = 128, seed: int = 0, n_clusters: int = 64):
    """Clustered f32 vectors approximating SIFT's local-feature structure."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_clusters, dim)).astype(np.float32) * 3.0
    assign = rng.integers(0, n_clusters, n)
    base = centers[assign] + rng.standard_normal((n, dim)).astype(np.float32)
    return base.astype(np.float32)


def deep_like(n: int, dim: int = 96, seed: int = 1):
    """Unit-norm vectors (DEEP1B-style CNN descriptors); IP metric."""
    x = sift_like(n, dim, seed)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def queries_from(base: np.ndarray, nq: int, seed: int = 99, noise: float = 0.3):
    rng = np.random.default_rng(seed)
    picks = rng.choice(len(base), nq, replace=False)
    q = base[picks] + noise * rng.standard_normal((nq, base.shape[1])).astype(np.float32)
    return q.astype(np.float32)


def brute_force_topk(base, queries, k, metric="l2"):
    if metric == "l2":
        d = np.sum(queries**2, 1, keepdims=True) - 2 * queries @ base.T + np.sum(base**2, 1)
        return np.argsort(d, axis=1)[:, :k]
    return np.argsort(-(queries @ base.T), axis=1)[:, :k]


def recall_of(found, gt):
    hits = sum(len(set(found[r].tolist()) & set(gt[r].tolist())) for r in range(len(gt)))
    return hits / gt.size


def timeit_us(fn, warmup: int = 1, iters: int = 3, best_of: int = 1) -> float:
    """Mean us/call over ``iters``; with ``best_of`` > 1, the minimum of
    that many repeated measurements (robust against noisy neighbours on
    shared machines)."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(max(1, best_of)):
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        best = min(best, (time.perf_counter() - t0) / iters * 1e6)
    return best


def emit(rows: list[tuple[str, float, str]]) -> None:
    """Print the required ``name,us_per_call,derived`` CSV rows."""
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


def python_dedup_merge(s, p, k, metric="l2"):
    """The pre-fusion per-row Python dedup merge (QueryNode/Proxy before
    the merge_topk kernel): stable score order, skip pk<0 / seen pks /
    non-finite, keep-best.  Kept as the semantic baseline for the merge
    equivalence tests and the merge-stage benchmark."""
    nq = s.shape[0]
    out_s = np.full((nq, k), np.inf if metric == "l2" else -np.inf, np.float32)
    out_p = np.full((nq, k), -1, np.int64)
    order = np.argsort(s if metric == "l2" else -s, axis=1, kind="stable")
    for r in range(nq):
        seen, slot = set(), 0
        for j in order[r]:
            pk = int(p[r, j])
            if pk < 0 or pk in seen:
                continue
            if not np.isfinite(s[r, j]):
                continue
            seen.add(pk)
            out_s[r, slot] = s[r, j]
            out_p[r, slot] = pk
            slot += 1
            if slot >= k:
                break
    return out_s, out_p
