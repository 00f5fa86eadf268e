"""One device program per kernel call: ``topk_scan`` and ``merge_topk``
launch their jitted program once (it pads the query rows and slices the
outputs itself) and fetch both outputs in one transfer.

The kernel path runs on the CPU in interpret mode (``ops.use_pallas``
patched to True).  Vectors hold small integers, so every distance is
exact in f32 and the answers can equal ``ref.py`` bit for bit."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ManuConfig, ManuSystem, SearchRequest, telemetry
from repro.kernels import ops, ref
from repro.kernels.l2_topk import l2_topk_pallas
from repro.kernels.merge_topk import merge_topk_pallas

DIM, K, ROWS = 8, 10, 300  # 300 rows: short of the 512-row tile


@pytest.fixture
def kernel_path(monkeypatch):
    monkeypatch.setattr(ops, "use_pallas", lambda: True)


def _ints(rng, shape):
    return rng.integers(-3, 4, shape).astype(np.float32)


def _traced(name: str):
    """A trace with one span open, as a query node has around its calls."""
    ctx = telemetry.TraceContext("search")
    return ctx, ctx.timed(ctx.span(name))


def _assert_scan_equals_ref(got, want):
    """``topk_scan``'s answer against ``ref.l2_topk_ref``'s: the same
    scores and rows, and a dead slot (ref's +inf) as row -1."""
    (vals, idx), (rv, ri) = got, (np.asarray(want[0]), np.asarray(want[1]))
    dead = np.isinf(rv)
    np.testing.assert_array_equal(idx, np.where(dead, -1, ri))
    np.testing.assert_array_equal(vals[~dead], rv[~dead])
    assert (vals[dead] >= 1e38).all()


@pytest.mark.parametrize("nq", [1, 3, 8, 130])
@pytest.mark.parametrize("base_kind", ["host", "resident", "masked"])
def test_topk_scan_is_one_program_equal_to_ref(rng, kernel_path, base_kind, nq):
    base = _ints(rng, (ROWS, DIM))
    q = _ints(rng, (nq, DIM))
    valid = rng.random(ROWS) < 0.7 if base_kind == "masked" else None
    operand = base if base_kind == "host" else ops.resident(base)
    ctx, timer = _traced("scan")
    with timer:
        got = ops.topk_scan(q, operand, K, valid=valid)
    (span,) = ctx.root.children[0].children
    assert span.name == "kernel_l2_topk" and span.programs == 1
    assert got[0].shape == got[1].shape == (nq, K)
    want = ref.l2_topk_ref(
        jnp.asarray(q), jnp.asarray(base), K,
        valid=None if valid is None else jnp.asarray(valid),
    )
    _assert_scan_equals_ref(got, want)


@pytest.mark.parametrize("nq", [1, 3, 8, 130])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_merge_topk_is_one_program_equal_to_ref(rng, kernel_path, metric, nq):
    m = 40  # padded to 128 columns on the host
    s = _ints(rng, (nq, m))
    s[rng.random((nq, m)) < 0.1] = np.inf
    p = rng.integers(-1, 25, (nq, m)).astype(np.int64)  # duplicates and holes
    ctx, timer = _traced("merge")
    with timer:
        got = ops.merge_topk(s, p, K, metric)
    (span,) = ctx.root.children[0].children
    assert span.name == "kernel_merge_topk" and span.programs == 1
    want = ref.merge_topk_ref(jnp.asarray(s), jnp.asarray(p), K, metric)
    np.testing.assert_array_equal(got[0], np.asarray(want[0]))
    np.testing.assert_array_equal(got[1], np.asarray(want[1]))


@pytest.mark.parametrize("nq", [1, 3, 130])
def test_the_programs_take_padded_and_unpadded_rows(rng, nq):
    """Rows a caller padded to the tiles come back padded, as before; rows
    it did not are padded and sliced inside the program."""
    tq = 128 if nq >= 128 else 8
    nq_pad = -(-nq // tq) * tq
    q = np.zeros((nq_pad, DIM), np.float32)
    q[:nq] = _ints(rng, (nq, DIM))
    base = np.zeros((512, DIM), np.float32)
    base[:ROWS] = _ints(rng, (ROWS, DIM))
    valid = np.arange(512) < ROWS
    padded = l2_topk_pallas(q, base, valid.astype(np.int32), K, tq=tq, tn=512,
                            interpret=True)
    bare = l2_topk_pallas(q[:nq], base[:ROWS], np.ones(ROWS, bool), K, tq=tq,
                          tn=512, interpret=True)
    assert padded[0].shape == (nq_pad, K) and bare[0].shape == (nq, K)
    for a, b in zip(padded, bare):
        np.testing.assert_array_equal(np.asarray(a)[:nq], np.asarray(b))

    s = np.full((nq_pad, 128), 0.0, np.float32)
    p = np.full((nq_pad, 128), -1, np.int32)
    s[:nq] = _ints(rng, (nq, 128))
    p[:nq] = rng.integers(-1, 50, (nq, 128))
    padded = merge_topk_pallas(s, p, K, tq=tq, interpret=True)
    bare = merge_topk_pallas(s[:nq], p[:nq], K, tq=tq, interpret=True)
    assert padded[0].shape == (nq_pad, K) and bare[0].shape == (nq, K)
    for a, b in zip(padded, bare):
        np.testing.assert_array_equal(np.asarray(a)[:nq], np.asarray(b))


def _system(rng, kind):
    """One query node, two sealed 300-row segments: scanned brute force
    (``kind`` None), or through a FLAT or IVF_FLAT index."""
    system = ManuSystem(ManuConfig(num_query_nodes=1, seal_rows=ROWS))
    coll = system.create_collection("c", dim=DIM)
    if kind is not None:
        coll.create_index("vector", kind=kind, params={"nlist": 16, "nprobe": 4})
    coll.insert({"vector": rng.standard_normal((2 * ROWS, DIM)).astype(np.float32)})
    coll.flush()
    return coll


@pytest.mark.parametrize("kind", [None, "flat", "ivf_flat"])
def test_a_warm_search_dispatches_no_eager_op(rng, kernel_path, monkeypatch, kind):
    coll = _system(rng, kind)
    nq, k = 2, 5
    q = rng.standard_normal((nq, DIM)).astype(np.float32)

    def search():
        return coll.search(SearchRequest.single(q, k=k, staleness_ms=0.0, trace=True))

    search()  # compiles every program the search runs

    def refuse(*args, **kwargs):
        raise AssertionError("an eager pad on a warm search")

    with monkeypatch.context() as m:
        m.setattr(jnp, "pad", refuse)
        m.setattr(ops, "_pad_rows", refuse)
        trace = search().trace
    scans = trace.spans_named("kernel_l2_topk")
    merges = trace.spans_named("kernel_merge_topk")
    assert len(scans) == 2 and len(merges) == 2  # a segment each; node, proxy
    assert all(s.programs == 1 for s in scans + merges)
    # Bytes by hand, as before: the f32 queries, and per kind what else is
    # sent (a brute scan's rows and mask, a FLAT scan's mask, padded on the
    # host to the 512-row tile; nothing for IVF's centroid probe)...
    sent = {None: 512 * DIM * 4 + 512, "flat": 512, "ivf_flat": 0}[kind]
    for span in scans:
        assert span.children[0].bytes_h2d == nq * DIM * 4 + sent
    # ...and the merges' f32 scores and int32 pks padded to 128 columns.
    for span in merges:
        assert span.children[0].bytes_h2d == 2 * nq * 128 * 4


def test_other_kernels_count_their_eager_ops(rng, kernel_path):
    """``kmeans_assign`` keeps its eager ops, each counted: the row pad,
    the far centroids and their concatenation, the program, two slices."""
    x = rng.standard_normal((ROWS, DIM)).astype(np.float32)
    cents = rng.standard_normal((16, DIM)).astype(np.float32)
    ctx, timer = _traced("build")
    with timer:
        got = ops.kmeans_assign(x, cents)
    (span,) = ctx.root.children[0].children
    assert span.name == "kernel_kmeans_assign" and span.programs == 6
    want = ref.kmeans_assign_ref(jnp.asarray(x), jnp.asarray(cents))
    np.testing.assert_array_equal(got[0], np.asarray(want[0]))
