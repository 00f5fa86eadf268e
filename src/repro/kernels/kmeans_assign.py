"""Nearest-centroid assignment Pallas kernel (k-means E-step).

Index building (IVF coarse quantizer, PQ codebooks, the bucket index's
hierarchical k-means) is dominated by assignment: for every row find the
closest centroid.  Structure: centroids tiled over VMEM [TC, D], rows tiled
[TN, D]; running (min-dist, argmin) per row accumulates across centroid
tiles in VMEM scratch — the K=1 special case of the scan kernels, kept
separate because the reduction is a plain min (no selection loop).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .topk_util import BIG_F32

DEFAULT_TN = 512
DEFAULT_TC = 512


def _assign_kernel(
    x_ref,  # [TN, D]
    c_ref,  # [TC, D]
    out_a_ref,  # [TN, 1] int32
    out_d_ref,  # [TN, 1] f32
    best_d,  # scratch [TN, 1]
    best_a,  # scratch [TN, 1]
    *,
    n_c_tiles: int,
    tc: int,
):
    jc = pl.program_id(1)

    @pl.when(jc == 0)
    def _init():
        best_d[...] = jnp.full_like(best_d[...], BIG_F32)
        best_a[...] = jnp.zeros_like(best_a[...])

    x = x_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [TN, TC]
    xn = jnp.sum(x * x, axis=1, keepdims=True)
    cn = jnp.sum(c * c, axis=1)[None, :]
    d2 = xn - 2.0 * xc + cn

    tile_min = jnp.min(d2, axis=1, keepdims=True)  # [TN,1]
    tile_arg = jnp.argmin(d2, axis=1).astype(jnp.int32)[:, None] + jc * tc

    better = tile_min < best_d[...]
    best_d[...] = jnp.where(better, tile_min, best_d[...])
    best_a[...] = jnp.where(better, tile_arg, best_a[...])

    @pl.when(jc == n_c_tiles - 1)
    def _emit():
        out_a_ref[...] = best_a[...]
        out_d_ref[...] = best_d[...]


@functools.partial(jax.jit, static_argnames=("tn", "tc", "interpret"))
def kmeans_assign_pallas(
    x: jnp.ndarray,  # [N, D] padded to TN
    centroids: jnp.ndarray,  # [C, D] padded to TC (pad rows = +inf-ish far away)
    tn: int = DEFAULT_TN,
    tc: int = DEFAULT_TC,
    *,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    n, d = x.shape
    c, _ = centroids.shape
    assert n % tn == 0 and c % tc == 0
    kernel = functools.partial(_assign_kernel, n_c_tiles=c // tc, tc=tc)
    out_a, out_d = pl.pallas_call(
        kernel,
        grid=(n // tn, c // tc),
        in_specs=[
            pl.BlockSpec((tn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tc, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tn, 1), jnp.float32),
            pltpu.VMEM((tn, 1), jnp.int32),
        ],
        interpret=interpret,
    )(x.astype(jnp.float32), centroids.astype(jnp.float32))
    return out_a[:, 0], out_d[:, 0]
