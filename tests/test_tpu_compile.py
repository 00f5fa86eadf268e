"""Ahead-of-time compiles of the Pallas kernels for one TPU v5e chip at
real widths.

Nothing runs: the TPU compiler installed with JAX compiles for a described
(not attached) v5e, which refuses what interpret mode accepts — primitives
Mosaic cannot lower, misaligned tiles, kernels that overrun VMEM.  The
topology is described only inside a fixture, never at import, so every
test worker collects the same tests.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kmeans_assign import kmeans_assign_pallas
from repro.kernels.l2_topk import l2_topk_pallas
from repro.kernels.merge_topk import merge_topk_pallas
from repro.kernels.pq_adc import pq_adc_topk_pallas
from repro.kernels.sq_codec import sq_decode_pallas, sq_encode_pallas, sq_l2_topk_pallas

F32, I32, U8 = jnp.float32, jnp.int32, jnp.uint8
N = 4096  # rows per compile: 8 base tiles of 512
SHORT = 300  # rows short of the 512-row tile, padded inside the program


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs outside the checkout
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip cannot be read back from the
        # persistent cache, so keep these compiles out of it.
        cache_was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            compilation_cache.reset_cache()


def _l2(d, k, tq=128, metric="l2", nq=None, mask=I32):
    fn = functools.partial(l2_topk_pallas, k=k, metric=metric, tq=tq, tn=512)
    return fn, [((nq or tq, d), F32), ((N, d), F32), ((N,), mask)]


CASES = {
    **{f"l2_topk-d{d}-k{k}": _l2(d, k) for d in (128, 768, 1536) for k in (10, 100)},
    "l2_topk-ip-tq8": _l2(128, 10, tq=8, metric="ip"),
    # an online search's call: one query row and a bool mask, padded and
    # cast inside the program
    "l2_topk-nq1-bool": _l2(128, 10, tq=8, nq=1, mask=jnp.bool_),
    "sq_l2_topk-d128-k10": (
        functools.partial(sq_l2_topk_pallas, k=10, tq=128, tn=512),
        [((128, 128), F32), ((N, 128), I32), ((128,), F32), ((128,), F32),
         ((N,), I32)],
    ),
    # one IVF segment's reduce pool: nprobe=32 slots of k=10, lane-padded
    "merge_topk-m384-k10": (
        functools.partial(merge_topk_pallas, k=10, tq=128, tm=128),
        [((128, 384), F32), ((128, 384), I32)],
    ),
    "merge_topk-nq1-m128-k10": (
        functools.partial(merge_topk_pallas, k=10, tq=8, tm=128),
        [((1, 128), F32), ((1, 128), I32)],
    ),
    # a query node's pool over 4 IVF segments x nprobe=32 x k=100
    "merge_topk-m12800-k100": (
        functools.partial(merge_topk_pallas, k=100, tq=128, tm=512),
        [((128, 12800), F32), ((128, 12800), I32)],
    ),
    "kmeans_assign-d768-c1024": (
        functools.partial(kmeans_assign_pallas, tn=512, tc=512),
        [((N, 768), F32), ((1024, 768), F32)],
    ),
    "sq_encode-d128": (
        functools.partial(sq_encode_pallas, tn=512),
        [((N, 128), F32), ((128,), F32), ((128,), F32)],
    ),
    "sq_decode-d128": (
        functools.partial(sq_decode_pallas, tn=512),
        [((N, 128), I32), ((128,), F32), ((128,), F32)],
    ),
    "pq_adc-m96-nq128": (
        functools.partial(pq_adc_topk_pallas, k=10, tn=512),
        [((128, 96, 256), F32), ((N, 96), I32), ((N,), I32)],
    ),
    # the entry points' calls: one query row, uint8 codes and a bool mask
    # short of the tile, and unpadded centroids, each padded and cast
    # inside the program
    "sq_l2_topk-nq1-uint8-short": (
        functools.partial(sq_l2_topk_pallas, k=10, tq=8, tn=512),
        [((1, 128), F32), ((SHORT, 128), U8), ((128,), F32), ((128,), F32),
         ((SHORT,), jnp.bool_)],
    ),
    "pq_adc-nq1-uint8-short": (
        functools.partial(pq_adc_topk_pallas, k=10, tn=512),
        [((1, 96, 256), F32), ((SHORT, 96), U8), ((SHORT,), jnp.bool_)],
    ),
    "kmeans_assign-short-c1000": (
        functools.partial(kmeans_assign_pallas, tn=512, tc=512),
        [((SHORT, 128), F32), ((1000, 128), F32)],
    ),
    "sq_encode-short": (
        functools.partial(sq_encode_pallas, tn=512),
        [((SHORT, 128), F32), ((128,), F32), ((128,), F32)],
    ),
    "sq_decode-uint8-short": (
        functools.partial(sq_decode_pallas, tn=512),
        [((SHORT, 128), U8), ((128,), F32), ((128,), F32)],
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
