"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracles,
swept over shapes and dtypes."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.kmeans_assign import kmeans_assign_pallas
from repro.kernels.l2_topk import l2_topk_pallas
from repro.kernels.pq_adc import pq_adc_topk_pallas
from repro.kernels.sq_codec import (
    sq_decode_pallas,
    sq_encode_pallas,
    sq_l2_topk_pallas,
)

SHAPES = [
    # (nq, n, d, k)
    (8, 128, 32, 5),
    (16, 512, 64, 10),
    (32, 1024, 128, 50),
    (8, 256, 16, 17),
]
DTYPES = [np.float32, np.float16]


def _pad(a, m, fill=0.0):
    pad = (-a.shape[0]) % m
    if pad == 0:
        return a
    w = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, w, constant_values=fill)


@pytest.mark.parametrize("nq,n,d,k", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_topk_scan_matches_ref(rng, nq, n, d, k, dtype, metric):
    q = rng.standard_normal((nq, d)).astype(dtype)
    x = rng.standard_normal((n, d)).astype(dtype)
    valid = (rng.random(n) > 0.15).astype(np.int32)

    tq = min(128, max(8, nq))
    tn = min(512, max(128, n))
    qp = _pad(q.astype(np.float32), tq)
    xp = _pad(x.astype(np.float32), tn)
    vp = _pad(valid, tn)
    vals, idx = l2_topk_pallas(
        jnp.asarray(qp), jnp.asarray(xp), jnp.asarray(vp), k,
        metric=metric, tq=tq, tn=tn, interpret=True,
    )
    vals, idx = np.asarray(vals)[:nq], np.asarray(idx)[:nq]

    fn = ref.l2_topk_ref if metric == "l2" else ref.ip_topk_ref
    rv, ri = fn(jnp.asarray(q, jnp.float32), jnp.asarray(x, jnp.float32), k,
                valid=jnp.asarray(valid, bool))
    rv, ri = np.asarray(rv), np.asarray(ri)
    np.testing.assert_allclose(vals, rv, rtol=3e-4, atol=3e-4)
    # indices may differ at exact-tie distances; values must agree
    agree = (idx == ri).mean()
    assert agree > 0.9, f"index agreement {agree}"


def test_topk_all_invalid(rng):
    q = rng.standard_normal((8, 32)).astype(np.float32)
    x = rng.standard_normal((128, 32)).astype(np.float32)
    valid = np.zeros(128, np.int32)
    vals, idx = l2_topk_pallas(
        jnp.asarray(q), jnp.asarray(x), jnp.asarray(valid), 5,
        tq=8, tn=128, interpret=True,
    )
    assert (np.asarray(vals) >= 1e38).all()


@pytest.mark.parametrize("nq,n,m,ksub,k", [(4, 256, 8, 256, 10), (8, 512, 16, 256, 5)])
def test_pq_adc_matches_ref(rng, nq, n, m, ksub, k):
    luts = rng.standard_normal((nq, m, ksub)).astype(np.float32)
    codes = rng.integers(0, ksub, (n, m)).astype(np.int32)
    valid = (rng.random(n) > 0.1).astype(np.int32)
    vals, idx = pq_adc_topk_pallas(
        jnp.asarray(luts), jnp.asarray(codes), jnp.asarray(valid), k,
        tn=min(512, n), interpret=True,
    )
    rv, ri = ref.pq_adc_topk_ref(jnp.asarray(luts), jnp.asarray(codes), k,
                                 valid=jnp.asarray(valid, bool))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rv), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,d", [(256, 32), (512, 128)])
def test_sq_roundtrip_and_scan(rng, n, d):
    x = rng.standard_normal((n, d)).astype(np.float32) * 3
    vmin, vmax = x.min(0), x.max(0)
    codes = sq_encode_pallas(jnp.asarray(x), jnp.asarray(vmin), jnp.asarray(vmax),
                             tn=min(512, n), interpret=True)
    rcodes = np.asarray(ref.sq_encode_ref(jnp.asarray(x), jnp.asarray(vmin), jnp.asarray(vmax)))
    # allow 1-ulp rounding ties
    assert np.abs(np.asarray(codes).astype(int) - rcodes.astype(int)).max() <= 1

    dec = sq_decode_pallas(codes, jnp.asarray(vmin), jnp.asarray(vmax),
                           tn=min(512, n), interpret=True)
    scale = np.maximum(vmax - vmin, 1e-12) / 255.0
    assert np.abs(np.asarray(dec) - x).max() <= scale.max() * 1.01  # quant error bound

    q = rng.standard_normal((8, d)).astype(np.float32)
    valid = np.ones(n, np.int32)
    vals, idx = sq_l2_topk_pallas(
        jnp.asarray(q), codes, jnp.asarray(vmin), jnp.asarray(vmax),
        jnp.asarray(valid), 10, tq=8, tn=min(512, n), interpret=True,
    )
    rv, ri = ref.sq_l2_topk_ref(jnp.asarray(q), codes, jnp.asarray(vmin),
                                jnp.asarray(vmax), 10)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rv), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("n,c,d", [(256, 16, 32), (512, 512, 64), (512, 600, 16)])
def test_kmeans_assign_matches_ref(rng, n, c, d):
    x = rng.standard_normal((n, d)).astype(np.float32)
    cents = rng.standard_normal((c, d)).astype(np.float32)
    tn = min(512, n)
    tc = 512 if c >= 512 else max(128, 1 << (c - 1).bit_length())
    pad_c = (-c) % tc
    cp = np.concatenate([cents, np.full((pad_c, d), 1e18, np.float32)]) if pad_c else cents
    a, dist = kmeans_assign_pallas(jnp.asarray(x), jnp.asarray(cp), tn=tn, tc=tc, interpret=True)
    ra, rd = ref.kmeans_assign_ref(jnp.asarray(x), jnp.asarray(cents))
    assert (np.asarray(a) == np.asarray(ra)).all()
    np.testing.assert_allclose(np.asarray(dist), np.asarray(rd), rtol=3e-4, atol=3e-4)


def test_ops_dispatch_consistency(rng):
    """The public ops wrappers (numpy fast path) match the oracles."""
    from repro.kernels import ops

    q = rng.standard_normal((6, 24)).astype(np.float32)
    x = rng.standard_normal((300, 24)).astype(np.float32)
    valid = rng.random(300) > 0.2
    for metric in ("l2", "ip"):
        v, i = ops.topk_scan(q, x, 7, metric=metric, valid=valid)
        fn = ref.l2_topk_ref if metric == "l2" else ref.ip_topk_ref
        rv, ri = fn(jnp.asarray(q), jnp.asarray(x), 7, valid=jnp.asarray(valid))
        np.testing.assert_allclose(v, np.asarray(rv), rtol=1e-4, atol=1e-4)

    # k > n edge case
    v, i = ops.topk_scan(q, x[:3], 10)
    assert (i[:, 3:] == -1).all()
    # empty base
    v, i = ops.topk_scan(q, x[:0], 4)
    assert (i == -1).all()
