"""PQ asymmetric-distance (ADC) scan + top-k Pallas TPU kernel.

Product quantization stores each vector as ``m`` sub-codes; query-time
distance is a table lookup: ``d(q, x) = sum_m LUT[m, code_m(x)]``.  The GPU
version keeps the LUT in shared memory and gathers; on TPU there is no fast
per-lane gather, so we replace the lookup with a **one-hot MXU contraction**
per subquantizer:

    LUT[m] [TQ, KSUB]  @  onehot(codes[m, :]) [KSUB, TN]  ->  [TQ, TN]

which is exactly the hardware-adaptation pattern DESIGN.md §3 describes:
LUT pinned in VMEM, codes streamed in int tiles, gathers turned into
systolic matmuls.  The grid tiles queries as well as rows so the resident
LUT block is ``[M, TQ, KSUB]`` (``query_tile`` sizes TQ to the VMEM
budget).  The loop over sub-quantizers indexes the VMEM refs: Mosaic does
not lower a ``dynamic_slice`` of a loaded value, and a static unroll keeps
every sub-quantizer's one-hot live (35 MB of VMEM at m=96).
Running top-k identical to ``l2_topk``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .topk_util import BIG_F32, NEG_I32, merge_topk, pad_rows_to, tile_base_indices

DEFAULT_TN = 512
# Bytes one LUT block may take in VMEM (it is double-buffered by the grid).
LUT_BLOCK_BYTES = 2 << 20


def query_tile(nq: int, m: int, ksub: int) -> int:
    """Largest power-of-two query tile in [8, 128] whose LUT block fits
    ``LUT_BLOCK_BYTES``, shrunk while it is twice the query count."""
    tq = 128
    while tq > 8 and (tq >= 2 * max(nq, 8) or m * tq * ksub * 4 > LUT_BLOCK_BYTES):
        tq //= 2
    return tq


def _adc_kernel(
    lut_ref,  # [M, TQ, KSUB] f32 LUT block of one query tile
    codes_ref,  # [M, TN] int32 codes tile (sub-quantizer major)
    valid_ref,  # [1, TN] int32
    out_v_ref,  # [TQ, K]
    out_i_ref,  # [TQ, K]
    acc_v,
    acc_i,
    *,
    k: int,
    n_base_tiles: int,
):
    jt = pl.program_id(1)

    @pl.when(jt == 0)
    def _init():
        acc_v[...] = jnp.full_like(acc_v[...], BIG_F32)
        acc_i[...] = jnp.full_like(acc_i[...], NEG_I32)

    m, tq, ksub = lut_ref.shape
    tn = codes_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (ksub, tn), 0)

    def per_sub(mi, scores):
        onehot = (iota == codes_ref[pl.ds(mi, 1), :]).astype(jnp.float32)
        return scores + jax.lax.dot_general(
            lut_ref[mi], onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # [TQ, TN]

    scores = jax.lax.fori_loop(0, m, per_sub, jnp.zeros((tq, tn), jnp.float32))
    live = valid_ref[0, :][None, :] > 0
    scores = jnp.where(live, scores, BIG_F32)

    idx = tile_base_indices(tn, jt, tq)
    new_v, new_i = merge_topk(acc_v[...], acc_i[...], scores, idx, k)
    acc_v[...] = new_v
    acc_i[...] = new_i

    @pl.when(jt == n_base_tiles - 1)
    def _emit():
        out_v_ref[...] = acc_v[...]
        out_i_ref[...] = acc_i[...]


@functools.partial(jax.jit, static_argnames=("k", "tn", "interpret"))
def pq_adc_topk_pallas(
    luts: jnp.ndarray,  # [NQ, M, KSUB] f32
    codes: jnp.ndarray,  # [N, M] integer codes
    valid: jnp.ndarray,  # [N] or [N padded to TN] bool / int32, 1 = live
    k: int,
    tn: int = DEFAULT_TN,
    *,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """ADC top-k of each query over ``codes``: ([NQ, K] distances, [NQ, K]
    int32 rows).  The query rows, codes and mask are padded to their tiles
    inside the program (padded rows are dead), so that a call is one
    program."""
    nq, m, ksub = luts.shape
    n = -(-codes.shape[0] // tn) * tn
    assert valid.shape[0] <= n, (valid.shape, n)
    tq = query_tile(nq, m, ksub)
    nq_pad = -(-nq // tq) * tq
    n_b_tiles = n // tn

    kernel = functools.partial(_adc_kernel, k=k, n_base_tiles=n_b_tiles)
    out_v, out_i = pl.pallas_call(
        kernel,
        grid=(nq_pad // tq, n_b_tiles),
        in_specs=[
            pl.BlockSpec((m, tq, ksub), lambda i, j: (0, i, 0)),
            pl.BlockSpec((m, tn), lambda i, j: (0, j)),
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
    )(
        jnp.pad(
            jnp.transpose(luts.astype(jnp.float32), (1, 0, 2)),
            ((0, 0), (0, nq_pad - nq), (0, 0)),
        ),
        pad_rows_to(codes.astype(jnp.int32), n).T,
        pad_rows_to(valid.astype(jnp.int32), n)[None, :],
    )
    return out_v[:nq], out_i[:nq]
