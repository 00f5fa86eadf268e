"""Top-k maintenance helpers shared by the Pallas kernel bodies.

TPUs have no warp-shuffle top-k (the CUDA idiom Manu/Faiss use); the
idiomatic Mosaic equivalent is a K-step selection over a candidate tile
using only reductions, broadcasted iota, and one-hot arithmetic — all of
which lower cleanly to the VPU.  ``select_topk_small`` extracts the K
smallest entries of a [TQ, M] candidate tile; ``merge_topk`` folds a new
candidate tile into the running per-query buffer kept in VMEM scratch.
``pad_rows_to`` pads a jitted program's operand to its tile before the
``pallas_call``, so that the pad runs inside that program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_I32 = -1
BIG_F32 = 3.0e38


def select_topk_small(
    vals: jnp.ndarray, idx: jnp.ndarray, k: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """K smallest of each row of ``vals`` [TQ, M] with carried indices.

    Pure min/argmin selection loop: K iterations, each picks the row-wise
    minimum, emits it, and masks it out with a one-hot.  Ascending output.
    Every step is a full-tile masked write (Mosaic cannot store at a
    loop-carried column), and the picked index is an int32 masked sum, so
    it is exact for any int32 row index.
    """
    tq, m = vals.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, m), 1)
    out_col = jax.lax.broadcasted_iota(jnp.int32, (tq, k), 1)
    idx = idx.astype(jnp.int32)
    out_v = jnp.full((tq, k), BIG_F32, dtype=jnp.float32)
    out_i = jnp.full((tq, k), NEG_I32, dtype=jnp.int32)

    def body(j, carry):
        cv, ov, oi = carry
        row_min = jnp.min(cv, axis=1, keepdims=True)  # [TQ, 1]
        row_arg = jnp.argmin(cv, axis=1).astype(jnp.int32)  # [TQ]
        oh = col == row_arg[:, None]  # [TQ, M] one-hot
        picked_idx = jnp.sum(jnp.where(oh, idx, 0), axis=1, keepdims=True)
        slot = out_col == j
        ov = jnp.where(slot, row_min, ov)
        oi = jnp.where(slot, picked_idx, oi)
        cv = jnp.where(oh, BIG_F32, cv)
        return cv, ov, oi

    _, out_v, out_i = jax.lax.fori_loop(
        0, k, body, (vals.astype(jnp.float32), out_v, out_i)
    )
    return out_v, out_i


def merge_topk(
    acc_v: jnp.ndarray,
    acc_i: jnp.ndarray,
    new_v: jnp.ndarray,
    new_i: jnp.ndarray,
    k: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Merge running top-k [TQ,K] with a fresh candidate tile [TQ,M]."""
    cand_v = jnp.concatenate([acc_v, new_v.astype(jnp.float32)], axis=1)
    cand_i = jnp.concatenate([acc_i, new_i], axis=1)
    return select_topk_small(cand_v, cand_i, k)


def tile_base_indices(tile_rows: int, tile_idx: jnp.ndarray, tq: int) -> jnp.ndarray:
    """Global base-row indices for the current [TQ, TN] tile."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (tq, tile_rows), 1)
    return iota + (tile_idx * tile_rows).astype(jnp.int32)


def pad_rows_to(x: jnp.ndarray, rows: int, fill=0) -> jnp.ndarray:
    """``x`` with its leading axis padded with ``fill`` to ``rows``."""
    pad = rows - x.shape[0]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1), constant_values=fill)
