"""Fused distance-scan + top-k Pallas TPU kernel.

This is Manu's hottest loop: brute-force scan of growing segments and the
inner loop of IVF-FLAT / bucket scans.  The GPU/SIMD implementation in the
paper becomes an MXU matmul here:

    L2:  d(q,x) = |q|^2 - 2 q.x + |x|^2        (ascending top-k)
    IP:  s(q,x) = q.x                          (descending; negated inside)

Tiling: the query block [TQ, D] stays resident in VMEM while base tiles
[TN, D] stream through HBM->VMEM via the grid; a running per-query top-k
buffer lives in VMEM scratch and is merged once per tile (see
``topk_util``).  Grid = (query_tiles, base_tiles), base axis innermost so
the scratch accumulates sequentially — the canonical Pallas reduction
pattern.

Alignment: TQ, TN, D should be multiples of the 128-lane VREG / MXU tile.
The jitted program pads what is short of its tile (the query rows, and
the base and mask rows unless the caller padded them) and slices the
outputs back to the query rows, so that a call is one device program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .topk_util import BIG_F32, NEG_I32, merge_topk, pad_rows_to, tile_base_indices

DEFAULT_TQ = 128
DEFAULT_TN = 512


def _scan_kernel(
    q_ref,  # [TQ, D] queries (VMEM-resident across base tiles)
    x_ref,  # [TN, D] base tile
    valid_ref,  # [1, TN] int32 validity mask tile (1 = live row)
    out_v_ref,  # [TQ, K]
    out_i_ref,  # [TQ, K]
    acc_v,  # scratch [TQ, K] f32
    acc_i,  # scratch [TQ, K] i32
    *,
    k: int,
    metric: str,
    n_base_tiles: int,
):
    jt = pl.program_id(1)  # base-tile index (innermost)

    @pl.when(jt == 0)
    def _init():
        acc_v[...] = jnp.full_like(acc_v[...], BIG_F32)
        acc_i[...] = jnp.full_like(acc_i[...], NEG_I32)

    q = q_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    # Full-f32 MXU contraction: a single bf16 pass would swamp the small
    # distances left after the |q|^2 - 2q.x + |x|^2 cancellation.
    qx = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [TQ, TN]
    if metric == "l2":
        qn = jnp.sum(q * q, axis=1, keepdims=True)
        xn = jnp.sum(x * x, axis=1)[None, :]
        scores = qn - 2.0 * qx + xn
    elif metric == "ip":
        scores = -qx  # minimize negated similarity
    else:
        raise ValueError(f"unknown metric {metric}")

    live = valid_ref[0, :][None, :] > 0  # [1, TN]
    scores = jnp.where(live, scores, BIG_F32)

    idx = tile_base_indices(x.shape[0], jt, q.shape[0])
    new_v, new_i = merge_topk(acc_v[...], acc_i[...], scores, idx, k)
    acc_v[...] = new_v
    acc_i[...] = new_i

    @pl.when(jt == n_base_tiles - 1)
    def _emit():
        out = acc_v[...]
        if metric == "ip":
            out = -out  # back to similarity scale
        out_v_ref[...] = out
        out_i_ref[...] = acc_i[...]


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "tq", "tn", "interpret")
)
def l2_topk_pallas(
    queries: jnp.ndarray,  # [NQ, D], any NQ
    base: jnp.ndarray,  # [N, D]
    valid: jnp.ndarray,  # [N] or [N padded to TN] bool / int32, 1 = live
    k: int,
    metric: str = "l2",
    tq: int = DEFAULT_TQ,
    tn: int = DEFAULT_TN,
    *,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k of each query row over ``base``: ([NQ, K] scores, [NQ, K]
    int32 row indices).  Rows past ``base``'s and ``valid``'s length up to
    the tile are dead."""
    nq, d = queries.shape
    nq_pad = -(-nq // tq) * tq
    n = -(-base.shape[0] // tn) * tn
    assert valid.shape[0] <= n, (valid.shape, n)
    queries = pad_rows_to(queries, nq_pad)
    base = pad_rows_to(base, n)
    valid = pad_rows_to(valid.astype(jnp.int32), n)
    n_q_tiles, n_b_tiles = nq_pad // tq, n // tn

    grid = (n_q_tiles, n_b_tiles)
    kernel = functools.partial(
        _scan_kernel, k=k, metric=metric, n_base_tiles=n_b_tiles
    )
    out_v, out_i = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, tn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((tq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((nq_pad, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, k), jnp.float32),
            pltpu.VMEM((tq, k), jnp.int32),
        ],
        interpret=interpret,
    )(queries, base, valid[None, :])
    return out_v[:nq], out_i[:nq]
