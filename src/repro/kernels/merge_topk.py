"""Segmented k-way top-k merge with pk-dedup — Pallas TPU kernel.

The two-phase reduce (paper §3.6) pools per-segment / per-node top-k
candidates into [NQ, M] score+pk tiles; this kernel folds them into the
final per-query top-k while dropping duplicate primary keys (a row may
surface from both a growing copy and the sealed segment, or from two
nodes during segment hand-off).  Keep-best-occurrence semantics: for
each pk the minimum key (L2 distance, negated IP similarity) wins.

The body is the K-step min/argmin selection loop from ``topk_util`` with
one extension: after emitting a winner, EVERY candidate carrying the
same pk is masked out with a vectorized compare against the picked pk —
the per-row dedup the host merge used to run as a Python loop.  The grid
tiles queries and pool columns: each [TQ, TM] pool tile is merged into a
running [TQ, K] top-k kept in VMEM scratch, so VMEM use does not grow
with the pool (a whole [TQ, M] pool of 4 segments x nprobe=32 x k=100
overran it).  Folding tile by tile gives the same answer as one pass:
a pk dropped from the running top-k has K distinct better pks ahead of
it, and the running entries come from earlier columns, so ties still
break by column order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .topk_util import BIG_F32, NEG_I32, pad_rows_to

DEFAULT_TQ = 128
DEFAULT_TM = 512


def _merge_kernel(
    s_ref,  # [TQ, TM] pooled candidate scores
    p_ref,  # [TQ, TM] int32 pks, -1 = empty slot
    out_v_ref,  # [TQ, K]
    out_p_ref,  # [TQ, K]
    acc_v,  # scratch [TQ, K] f32 running keys (min-semantics)
    acc_p,  # scratch [TQ, K] i32 running pks
    *,
    k: int,
    metric: str,
    n_m_tiles: int,
):
    jm = pl.program_id(1)

    @pl.when(jm == 0)
    def _init():
        acc_v[...] = jnp.full_like(acc_v[...], BIG_F32)
        acc_p[...] = jnp.full_like(acc_p[...], NEG_I32)

    s = s_ref[...].astype(jnp.float32)
    p = p_ref[...]
    key = s if metric == "l2" else -s
    ok = (p >= 0) & (key < BIG_F32) & (key > -BIG_F32) & ~jnp.isnan(key)
    key = jnp.where(ok, key, BIG_F32)
    # running entries first: they hold the earlier columns
    key = jnp.concatenate([acc_v[...], key], axis=1)
    p = jnp.concatenate([acc_p[...], p], axis=1)
    tq, m = key.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (tq, m), 1)
    out_col = jax.lax.broadcasted_iota(jnp.int32, (tq, k), 1)

    def body(j, carry):
        cand, ov, op = carry
        row_min = jnp.min(cand, axis=1)
        row_arg = jnp.argmin(cand, axis=1).astype(jnp.int32)
        picked_oh = iota == row_arg[:, None]  # [TQ, M] one-hot
        # integer one-hot reduce: exact for any int32 pk (no f32 rounding)
        picked_pk = jnp.sum(jnp.where(picked_oh, p, 0), axis=1)
        have = row_min < BIG_F32
        picked_pk = jnp.where(have, picked_pk, NEG_I32)
        slot = out_col == j  # masked write: Mosaic cannot store at column j
        ov = jnp.where(slot, jnp.where(have, row_min, BIG_F32)[:, None], ov)
        op = jnp.where(slot, picked_pk[:, None], op)
        # pk-dedup: retire every occurrence of the picked pk, not just the
        # winning slot (keep-best-occurrence)
        kill = (p == picked_pk[:, None]) & have[:, None]
        return jnp.where(kill, BIG_F32, cand), ov, op

    out_v = jnp.full((tq, k), BIG_F32, jnp.float32)
    out_p = jnp.full((tq, k), NEG_I32, jnp.int32)
    _, out_v, out_p = jax.lax.fori_loop(0, k, body, (key, out_v, out_p))
    acc_v[...] = out_v
    acc_p[...] = out_p

    @pl.when(jm == n_m_tiles - 1)
    def _emit():
        v = acc_v[...]
        # back to similarity scale for IP (empty slots -> -BIG)
        out_v_ref[...] = v if metric == "l2" else -v
        out_p_ref[...] = acc_p[...]


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "tq", "tm", "interpret")
)
def merge_topk_pallas(
    scores: jnp.ndarray,  # [NQ, M], any NQ, M a TM multiple (or below TM)
    pks: jnp.ndarray,  # [NQ, M] integer pks, -1 = empty slot
    k: int,
    metric: str = "l2",
    tq: int = DEFAULT_TQ,
    tm: int = DEFAULT_TM,
    *,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The merged top-k of each pool row: ([NQ, K] scores, [NQ, K] int32
    pks).  The rows are padded to a TQ multiple inside the program (empty
    slots) and the outputs sliced back, so that a call is one program."""
    nq, m = scores.shape
    nq_pad = -(-nq // tq) * tq
    tm = min(tm, m)
    assert m % tm == 0, (m, tm)
    scores = pad_rows_to(scores, nq_pad)
    pks = pad_rows_to(pks.astype(jnp.int32), nq_pad, fill=-1)
    n_m_tiles = m // tm
    kernel = functools.partial(_merge_kernel, k=k, metric=metric, n_m_tiles=n_m_tiles)
    out_v, out_p = pl.pallas_call(
        kernel,
        grid=(nq_pad // tq, n_m_tiles),
        in_specs=[
            pl.BlockSpec((tq, tm), lambda i, j: (i, j)),
            pl.BlockSpec((tq, tm), lambda i, j: (i, j)),
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
    )(scores, pks)
    return out_v[:nq], out_p[:nq]
