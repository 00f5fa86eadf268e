"""Scalar-quantization codec + fused SQ-domain distance scan (Pallas).

The paper's SSD design compresses vectors with scalar quantization so each
4 KB page holds more rows; our TPU adaptation keeps segments SQ-compressed
in HBM and dequantizes **inside the kernel**, right before the MXU
contraction — the bytes streamed from HBM are 4x smaller than f32, moving
the memory-roofline term down by the same factor.

Kernels:
  * ``sq_encode_pallas``  — f32 [N,D] -> int32 codes in [0,255]
  * ``sq_decode_pallas``  — codes -> f32
  * ``sq_l2_topk_pallas`` — fused dequant + L2/IP scan + running top-k
    (structure identical to ``l2_topk``; base tiles are int codes).

Each jitted program pads what is short of its tile (rows, codes, the
mask, the query rows), casts codes and the mask to int32, and slices its
outputs back to the rows it was given, so that a call is one program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .topk_util import BIG_F32, NEG_I32, merge_topk, pad_rows_to, tile_base_indices

DEFAULT_TN = 512
DEFAULT_TQ = 128


def _encode_kernel(x_ref, vmin_ref, vmax_ref, out_ref):
    x = x_ref[...]
    vmin = vmin_ref[0, :][None, :]
    vmax = vmax_ref[0, :][None, :]
    scale = jnp.maximum(vmax - vmin, 1e-12) / 255.0
    q = jnp.round((x - vmin) / scale)
    out_ref[...] = jnp.clip(q, 0.0, 255.0).astype(jnp.int32)


def _decode_kernel(c_ref, vmin_ref, vmax_ref, out_ref):
    c = c_ref[...].astype(jnp.float32)
    vmin = vmin_ref[0, :][None, :]
    vmax = vmax_ref[0, :][None, :]
    scale = jnp.maximum(vmax - vmin, 1e-12) / 255.0
    out_ref[...] = c * scale + vmin


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def sq_encode_pallas(
    x: jnp.ndarray, vmin: jnp.ndarray, vmax: jnp.ndarray,
    tn: int = DEFAULT_TN, *, interpret: bool,
) -> jnp.ndarray:
    rows, d = x.shape
    n = -(-rows // tn) * tn
    return pl.pallas_call(
        _encode_kernel,
        grid=(n // tn,),
        in_specs=[
            pl.BlockSpec((tn, d), lambda j: (j, 0)),
            pl.BlockSpec((1, d), lambda j: (0, 0)),
            pl.BlockSpec((1, d), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tn, d), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.int32),
        interpret=interpret,
    )(pad_rows_to(x.astype(jnp.float32), n), vmin[None, :], vmax[None, :])[:rows]


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def sq_decode_pallas(
    codes: jnp.ndarray, vmin: jnp.ndarray, vmax: jnp.ndarray,
    tn: int = DEFAULT_TN, *, interpret: bool,
) -> jnp.ndarray:
    rows, d = codes.shape
    n = -(-rows // tn) * tn
    return pl.pallas_call(
        _decode_kernel,
        grid=(n // tn,),
        in_specs=[
            pl.BlockSpec((tn, d), lambda j: (j, 0)),
            pl.BlockSpec((1, d), lambda j: (0, 0)),
            pl.BlockSpec((1, d), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tn, d), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(pad_rows_to(codes.astype(jnp.int32), n), vmin[None, :], vmax[None, :])[:rows]


def _sq_scan_kernel(
    q_ref,  # [TQ, D] f32 queries
    c_ref,  # [TN, D] int32 codes tile
    vmin_ref,  # [1, D]
    vmax_ref,  # [1, D]
    valid_ref,  # [1, TN]
    out_v_ref,
    out_i_ref,
    acc_v,
    acc_i,
    *,
    k: int,
    metric: str,
    n_base_tiles: int,
):
    jt = pl.program_id(1)

    @pl.when(jt == 0)
    def _init():
        acc_v[...] = jnp.full_like(acc_v[...], BIG_F32)
        acc_i[...] = jnp.full_like(acc_i[...], NEG_I32)

    q = q_ref[...].astype(jnp.float32)
    vmin = vmin_ref[0, :][None, :]
    vmax = vmax_ref[0, :][None, :]
    scale = jnp.maximum(vmax - vmin, 1e-12) / 255.0
    x = c_ref[...].astype(jnp.float32) * scale + vmin  # fused dequant in VMEM

    qx = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if metric == "l2":
        qn = jnp.sum(q * q, axis=1, keepdims=True)
        xn = jnp.sum(x * x, axis=1)[None, :]
        scores = qn - 2.0 * qx + xn
    else:
        scores = -qx

    live = valid_ref[0, :][None, :] > 0
    scores = jnp.where(live, scores, BIG_F32)
    idx = tile_base_indices(x.shape[0], jt, q.shape[0])
    new_v, new_i = merge_topk(acc_v[...], acc_i[...], scores, idx, k)
    acc_v[...] = new_v
    acc_i[...] = new_i

    @pl.when(jt == n_base_tiles - 1)
    def _emit():
        out = acc_v[...]
        if metric == "ip":
            out = -out
        out_v_ref[...] = out
        out_i_ref[...] = acc_i[...]


@functools.partial(jax.jit, static_argnames=("k", "metric", "tq", "tn", "interpret"))
def sq_l2_topk_pallas(
    queries: jnp.ndarray,  # [NQ, D], any NQ
    codes: jnp.ndarray,  # [N, D] integer codes
    vmin: jnp.ndarray,
    vmax: jnp.ndarray,
    valid: jnp.ndarray,  # [N] or [N padded to TN] bool / int32, 1 = live
    k: int,
    metric: str = "l2",
    tq: int = DEFAULT_TQ,
    tn: int = DEFAULT_TN,
    *,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    nq, d = queries.shape
    nq_pad = -(-nq // tq) * tq
    n = -(-codes.shape[0] // tn) * tn
    assert valid.shape[0] <= n, (valid.shape, n)
    kernel = functools.partial(
        _sq_scan_kernel, k=k, metric=metric, n_base_tiles=n // tn
    )
    out_v, out_i = pl.pallas_call(
        kernel,
        grid=(nq_pad // tq, n // tn),
        in_specs=[
            pl.BlockSpec((tq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
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
        pad_rows_to(queries.astype(jnp.float32), nq_pad),
        pad_rows_to(codes.astype(jnp.int32), n),
        vmin[None, :].astype(jnp.float32),
        vmax[None, :].astype(jnp.float32),
        pad_rows_to(valid.astype(jnp.int32), n)[None, :],
    )
    return out_v[:nq], out_i[:nq]
