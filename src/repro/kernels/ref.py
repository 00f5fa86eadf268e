"""Pure-jnp oracles for every Pallas kernel.

These are the semantic ground truth: tests sweep shapes/dtypes and assert
``assert_allclose(kernel(...), ref(...))``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "l2_topk_ref",
    "ip_topk_ref",
    "pq_adc_topk_ref",
    "sq_encode_ref",
    "sq_decode_ref",
    "sq_l2_topk_ref",
    "kmeans_assign_ref",
    "merge_topk_ref",
]


def _mask_scores(scores: jnp.ndarray, valid: jnp.ndarray | None, fill: float) -> jnp.ndarray:
    if valid is None:
        return scores
    return jnp.where(valid[None, :], scores, fill)


def l2_topk_ref(
    queries: jnp.ndarray,
    base: jnp.ndarray,
    k: int,
    valid: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact squared-L2 top-k.  Returns (dists [nq,k], idx [nq,k]) ascending."""
    q = queries.astype(jnp.float32)
    x = base.astype(jnp.float32)
    d2 = (
        jnp.sum(q * q, axis=1, keepdims=True)
        - 2.0 * q @ x.T
        + jnp.sum(x * x, axis=1)[None, :]
    )
    d2 = _mask_scores(d2, valid, jnp.inf)
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx


def ip_topk_ref(
    queries: jnp.ndarray,
    base: jnp.ndarray,
    k: int,
    valid: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Max inner-product top-k.  Returns (scores [nq,k], idx) descending."""
    s = queries.astype(jnp.float32) @ base.astype(jnp.float32).T
    s = _mask_scores(s, valid, -jnp.inf)
    return jax.lax.top_k(s, k)


def pq_adc_topk_ref(
    luts: jnp.ndarray,  # [nq, m, ksub] f32 per-query ADC tables
    codes: jnp.ndarray,  # [n, m] integer codes
    k: int,
    valid: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """PQ asymmetric-distance top-k: dist[q,i] = sum_m lut[q,m,codes[i,m]]."""
    nq, m, ksub = luts.shape
    c = codes.astype(jnp.int32)
    # [nq, n, m] gather then sum over m
    gathered = jnp.take_along_axis(
        luts[:, None, :, :].repeat(c.shape[0], axis=1),
        c[None, :, :, None],
        axis=3,
    )[..., 0]
    d = gathered.sum(axis=2)  # [nq, n]
    d = _mask_scores(d, valid, jnp.inf)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


def sq_encode_ref(x: jnp.ndarray, vmin: jnp.ndarray, vmax: jnp.ndarray) -> jnp.ndarray:
    """Scalar quantization to uint8 codes with per-dim affine range."""
    scale = jnp.maximum(vmax - vmin, 1e-12) / 255.0
    q = jnp.round((x - vmin[None, :]) / scale[None, :])
    return jnp.clip(q, 0, 255).astype(jnp.uint8)


def sq_decode_ref(codes: jnp.ndarray, vmin: jnp.ndarray, vmax: jnp.ndarray) -> jnp.ndarray:
    scale = jnp.maximum(vmax - vmin, 1e-12) / 255.0
    return codes.astype(jnp.float32) * scale[None, :] + vmin[None, :]


def sq_l2_topk_ref(
    queries: jnp.ndarray,
    codes: jnp.ndarray,
    vmin: jnp.ndarray,
    vmax: jnp.ndarray,
    k: int,
    valid: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """L2 top-k computed against SQ-compressed base (dequant fused)."""
    return l2_topk_ref(queries, sq_decode_ref(codes, vmin, vmax), k, valid)


def merge_topk_ref(
    scores: jnp.ndarray,  # [nq, m] pooled candidate scores
    pks: jnp.ndarray,  # [nq, m] integer pks, -1 = empty slot
    k: int,
    metric: str = "l2",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Segmented k-way top-k merge with pk-dedup (two-phase reduce, §3.6).

    Pools of per-segment/per-node top-k candidates are merged into the
    final per-query top-k, keeping the best occurrence of each pk.
    Candidates with pk < 0 or a non-finite score are ignored.  Output is
    ascending distance for L2, descending similarity for IP; empty output
    slots carry pk == -1 and the metric's fill score (+inf L2 / -inf IP).

    Ties are broken by pool column order (stable), matching a stable
    per-row selection over the concatenated pools.
    """
    s = scores.astype(jnp.float32)
    p = pks
    nq, m = s.shape
    fill = jnp.inf if metric == "l2" else -jnp.inf
    if m == 0:
        return (
            jnp.full((nq, k), fill, jnp.float32),
            jnp.full((nq, k), -1, p.dtype),
        )
    alive = (p >= 0) & jnp.isfinite(s)
    key = jnp.where(alive, s if metric == "l2" else -s, jnp.inf)
    # Group rows by (pk, key, column) via two stable argsorts; the first
    # element of each pk group is its best occurrence.
    ord_key = jnp.argsort(key, axis=1, stable=True)
    ord_pk = jnp.argsort(jnp.take_along_axis(p, ord_key, 1), axis=1, stable=True)
    perm = jnp.take_along_axis(ord_key, ord_pk, 1)
    p_grouped = jnp.take_along_axis(p, perm, 1)
    dup = jnp.concatenate(
        [jnp.zeros((nq, 1), bool), p_grouped[:, 1:] == p_grouped[:, :-1]], axis=1
    )
    # Scatter the duplicate flags back to pool-column order.
    killed = jnp.take_along_axis(dup, jnp.argsort(perm, axis=1, stable=True), 1)
    key = jnp.where(killed, jnp.inf, key)
    order = jnp.argsort(key, axis=1, stable=True)[:, : min(k, m)]
    sel_alive = jnp.take_along_axis(alive & ~killed, order, 1)
    out_s = jnp.where(sel_alive, jnp.take_along_axis(s, order, 1), fill)
    out_p = jnp.where(sel_alive, jnp.take_along_axis(p, order, 1), -1)
    if m < k:
        out_s = jnp.concatenate(
            [out_s, jnp.full((nq, k - m), fill, jnp.float32)], axis=1
        )
        out_p = jnp.concatenate([out_p, jnp.full((nq, k - m), -1, p.dtype)], axis=1)
    return out_s.astype(jnp.float32), out_p


def kmeans_assign_ref(
    x: jnp.ndarray, centroids: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Nearest centroid per row: returns (assignment [n] int32, sq-dist [n])."""
    xf = x.astype(jnp.float32)
    cf = centroids.astype(jnp.float32)
    d2 = (
        jnp.sum(xf * xf, axis=1, keepdims=True)
        - 2.0 * xf @ cf.T
        + jnp.sum(cf * cf, axis=1)[None, :]
    )
    return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.min(d2, axis=1)
