"""Equivalence tests for the fused search execution path.

``merge_topk`` (host vectorized + jnp ref + Pallas interpret) must match
the original per-row Python dedup merge bit-for-bit; the fused segmented
scan must match per-segment ``topk_scan`` up to gemm accumulation order;
and the node-level engine must reproduce the seed scan-then-merge
pipeline end to end.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.collection import Metric
from repro.core.consistency import GuaranteeTs
from repro.core.log import LogBroker
from repro.core.object_store import MemoryObjectStore
from repro.core.query_node import QueryNode, SealedHandle
from repro.core.segment import Segment
from repro.core.timestamp import INFINITE_STALENESS
from repro.kernels import ops, ref
from repro.kernels.merge_topk import merge_topk_pallas

# the pre-fusion per-row Python dedup merge: the semantic baseline
from benchmarks.common import python_dedup_merge as seed_merge


def random_pool(rng, nq, m, metric, pk_range=10):
    """Candidate pool with duplicate pks, -1 slots and non-finite scores."""
    s = rng.standard_normal((nq, m)).astype(np.float32)
    if metric == "l2":
        s = np.abs(s)
    p = rng.integers(-1, pk_range, (nq, m)).astype(np.int64)
    s[rng.random((nq, m)) < 0.10] = np.inf
    s[rng.random((nq, m)) < 0.05] = -np.inf
    s[rng.random((nq, m)) < 0.05] = np.nan
    # exact score ties to exercise stable tie-breaks
    ties = rng.random((nq, m)) < 0.1
    s[ties] = 1.25
    return s, p


@given(
    nq=st.integers(1, 8),
    m=st.integers(1, 48),
    k=st.integers(1, 24),
    seed=st.integers(0, 10_000),
    metric=st.one_of(st.just("l2"), st.just("ip")),
)
@settings(max_examples=60, deadline=None)
def test_merge_topk_matches_seed_python_merge(nq, m, k, seed, metric):
    rng = np.random.default_rng(seed)
    s, p = random_pool(rng, nq, m, metric)
    want_s, want_p = seed_merge(s, p, k, metric)
    got_s, got_p = ops.merge_topk(s, p, k, metric)
    np.testing.assert_array_equal(want_s, got_s)
    np.testing.assert_array_equal(want_p, got_p)


@given(seed=st.integers(0, 10_000), metric=st.one_of(st.just("l2"), st.just("ip")))
@settings(max_examples=20, deadline=None)
def test_merge_topk_ref_matches_seed(seed, metric):
    rng = np.random.default_rng(seed)
    s, p = random_pool(rng, 4, 32, metric)
    want_s, want_p = seed_merge(s, p, 10, metric)
    got_s, got_p = ref.merge_topk_ref(jnp.asarray(s), jnp.asarray(p), 10, metric)
    np.testing.assert_array_equal(want_s, np.asarray(got_s))
    np.testing.assert_array_equal(want_p, np.asarray(got_p))


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_merge_topk_pallas_interpret_matches_ref(metric):
    rng = np.random.default_rng(7)
    nq, m, k = 8, 128, 12
    s, p = random_pool(rng, nq, m, metric, pk_range=40)
    want_s, want_p = ref.merge_topk_ref(jnp.asarray(s), jnp.asarray(p), k, metric)
    got_v, got_p = merge_topk_pallas(
        jnp.asarray(s), jnp.asarray(p, np.int32), k, metric=metric, tq=8, interpret=True
    )
    got_v, got_p = np.asarray(got_v), np.asarray(got_p, np.int64)
    bad = np.abs(got_v) >= 1e38  # kernel sentinel -> public fill convention
    fill = np.inf if metric == "l2" else -np.inf
    np.testing.assert_array_equal(np.asarray(want_s), np.where(bad, fill, got_v))
    np.testing.assert_array_equal(np.asarray(want_p), np.where(bad, -1, got_p))


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("k", [5, 40])
def test_merge_topk_pallas_column_tiles_match_ref(metric, k):
    """Folding the pool tile by tile into a running top-k gives the
    one-pass answer: duplicates span tiles and ties span tile borders."""
    rng = np.random.default_rng(11)
    nq, m = 8, 512
    s, p = random_pool(rng, nq, m, metric, pk_range=60)
    want_s, want_p = ref.merge_topk_ref(jnp.asarray(s), jnp.asarray(p), k, metric)
    got_v, got_p = merge_topk_pallas(
        jnp.asarray(s), jnp.asarray(p, np.int32), k, metric=metric, tq=8, tm=128,
        interpret=True,
    )
    got_v, got_p = np.asarray(got_v), np.asarray(got_p, np.int64)
    bad = np.abs(got_v) >= 1e38
    fill = np.inf if metric == "l2" else -np.inf
    np.testing.assert_array_equal(np.asarray(want_s), np.where(bad, fill, got_v))
    np.testing.assert_array_equal(np.asarray(want_p), np.where(bad, -1, got_p))


def test_merge_topk_empty_and_padding():
    s = np.zeros((3, 0), np.float32)
    p = np.zeros((3, 0), np.int64)
    out_s, out_p = ops.merge_topk(s, p, 5, "l2")
    assert out_s.shape == (3, 5) and np.isinf(out_s).all()
    assert (out_p == -1).all()
    # fewer live candidates than k -> -1 padded tail
    s = np.array([[1.0, 1.0, 2.0]], np.float32)
    p = np.array([[7, 7, 9]], np.int64)
    out_s, out_p = ops.merge_topk(s, p, 5, "l2")
    assert out_p.tolist() == [[7, 9, -1, -1, -1]]
    assert out_s[0, :2].tolist() == [1.0, 2.0]


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_topk_scan_segmented_matches_per_segment(metric):
    rng = np.random.default_rng(3)
    q = rng.standard_normal((9, 24)).astype(np.float32)
    bases, valids = [], []
    for n in (0, 7, 130, 64):
        bases.append(rng.standard_normal((n, 24)).astype(np.float32))
        valids.append(rng.random(n) < 0.7 if n else None)
    k = 11
    fused_v, fused_i = ops.topk_scan_segmented(q, bases, k, metric=metric, valids=valids)
    assert fused_v.shape == (9, len(bases) * k)
    for s_idx, (b, v) in enumerate(zip(bases, valids)):
        want_v, want_i = ops.topk_scan(q, b, k, metric=metric, valid=v)
        blk = slice(s_idx * k, (s_idx + 1) * k)
        got_v, got_i = fused_v[:, blk], fused_i[:, blk]
        # same selected rows; scores equal up to gemm accumulation order
        np.testing.assert_array_equal(want_i, got_i)
        np.testing.assert_allclose(
            np.where(np.isfinite(want_v), want_v, 0.0),
            np.where(np.isfinite(got_v), got_v, 0.0),
            rtol=1e-5,
            atol=1e-4,
        )


def _node_with_segments(rng, dim=12, slice_rows=16):
    """A query node holding sealed-brute + growing segments directly."""
    broker = LogBroker()
    node = QueryNode("qn-test", broker, MemoryObjectStore(), slice_rows=slice_rows)
    coll = "c"
    # two sealed brute segments with interleaved timestamps and deletes
    for sid, n in ((1, 40), (2, 25)):
        seg = Segment(sid, coll, 0, dim, slice_rows=slice_rows)
        seg.append(
            np.arange(sid * 1000, sid * 1000 + n),
            rng.standard_normal((n, dim)).astype(np.float32),
            np.arange(100, 100 + n, dtype=np.int64),
        )
        seg.delete(np.array([sid * 1000 + 3, sid * 1000 + 4]), ts=120)
        node.sealed[(coll, sid)] = SealedHandle(seg)
    # one growing segment: enough rows for full slices + a tail
    seg = Segment(3, coll, 0, dim, slice_rows=slice_rows)
    n = 40
    seg.append(
        np.arange(3000, 3000 + n),
        rng.standard_normal((n, dim)).astype(np.float32),
        np.arange(100, 100 + n, dtype=np.int64),
    )
    from repro.core.query_node import GrowingState

    node.growing[(coll, 3)] = GrowingState(seg)
    node._build_slice_indexes()
    # a duplicated pk across segments (handoff-style) via delta deletes path
    node.delta_deletes[coll] = {1005: 130}
    return node, coll


def _seed_node_search(node, collection, queries, k, metric, ts):
    """The pre-fusion pipeline: per-segment scans + Python merge."""
    pool_s, pool_p = [], []
    mstr = "l2" if metric is Metric.L2 else "ip"
    for (coll, sid), handle in node.sealed.items():
        if coll != collection or handle.segment.num_rows == 0:
            continue
        seg = handle.segment
        mask = node._visible(collection, seg, ts)
        if not mask.any():
            continue
        if handle.index is not None:
            s, i = handle.index.search(queries, k, valid=mask)
        else:
            s, i = ops.topk_scan(queries, seg.vectors(), k, metric=mstr, valid=mask)
        pks = seg.pks()
        pool_s.append(s)
        pool_p.append(np.where(i >= 0, pks[np.clip(i, 0, len(pks) - 1)], -1))
    for (coll, sid), gs in node.growing.items():
        if coll != collection or gs.segment.num_rows == 0:
            continue
        seg = gs.segment
        mask = node._visible(collection, seg, ts)
        pks = seg.pks()
        covered = np.zeros(seg.num_rows, dtype=bool)
        for s_idx, temp in gs.slice_index_built.items():
            lo, hi = seg.slice_bounds(s_idx)
            covered[lo:hi] = True
            if not mask[lo:hi].any():
                continue
            s, i = temp.search(queries, k, valid=mask[lo:hi])
            pool_s.append(s)
            pool_p.append(np.where(i >= 0, pks[lo:hi][np.clip(i, 0, hi - lo - 1)], -1))
        tail_mask = mask & ~covered
        if tail_mask.any():
            s, i = ops.topk_scan(queries, seg.vectors(), k, metric=mstr, valid=tail_mask)
            pool_s.append(s)
            pool_p.append(np.where(i >= 0, pks[np.clip(i, 0, len(pks) - 1)], -1))
    s = np.concatenate(pool_s, axis=1)
    p = np.concatenate(pool_p, axis=1)
    return seed_merge(s, p, k, mstr)


@pytest.mark.parametrize("ts", [110, 125, 10_000])
def test_query_node_engine_matches_seed_pipeline(ts):
    rng = np.random.default_rng(11)
    node, coll = _node_with_segments(rng)
    queries = rng.standard_normal((6, 12)).astype(np.float32)
    k = 8
    g = GuaranteeTs(query_ts=ts, staleness_ms=INFINITE_STALENESS)
    got_s, got_p = node.search(coll, queries, k, Metric.L2, g)
    want_s, want_p = _seed_node_search(node, coll, queries, k, Metric.L2, ts)
    # same selected pks in the same order; scores equal up to gemm order
    np.testing.assert_array_equal(want_p, got_p)
    np.testing.assert_allclose(
        np.where(np.isfinite(want_s), want_s, 0.0),
        np.where(np.isfinite(got_s), got_s, 0.0),
        rtol=1e-5,
        atol=1e-4,
    )


def test_query_node_plan_classes():
    rng = np.random.default_rng(12)
    node, coll = _node_with_segments(rng)
    plan = node.plan_search(coll, 10_000)
    assert len(plan.brute_sealed) == 2
    assert len(plan.growing_slice) == 2  # 40 rows / 16 slice_rows -> 2 full
    assert len(plan.brute_tail) == 1
    assert not plan.indexed
    assert len(plan.units()) == 5
    # queries pinned before any insert see an empty plan
    assert not node.plan_search(coll, 50).units()


def _tied_node(dim=4, slice_rows=16):
    """A query node whose segments each hold one row at distance 1 from the
    origin (pk = 100 * segment id, or 450 in the growing tail) among rows
    at distance >= 36: a FLAT-indexed and an IVF_FLAT-indexed sealed
    segment (two index specs), an unindexed one, and a growing one with a
    full slice (temporary index) and a tail."""
    from repro.core.query_node import GrowingState
    from repro.index import IndexSpec, create_index

    rng = np.random.default_rng(5)
    node = QueryNode("qn-order", LogBroker(), MemoryObjectStore(), slice_rows=slice_rows)
    tie = np.eye(dim, dtype=np.float32)[0]

    def rows(n, pk0, tie_at):
        x = rng.integers(3, 30, (n, dim)).astype(np.float32)
        x[tie_at] = tie
        pks = np.arange(pk0 + 1, pk0 + 1 + n)
        pks[tie_at] = pk0
        return pks, x

    for sid, kind in ((1, "flat"), (2, "ivf_flat"), (3, None)):
        pks, x = rows(12, 100 * sid, 5)
        seg = Segment(sid, "c", 0, dim, slice_rows=slice_rows)
        seg.append(pks, x, np.full(12, 100, np.int64))
        index = None
        if kind is not None:
            params = {"nlist": 4, "nprobe": 4} if kind == "ivf_flat" else {}
            index = create_index(IndexSpec(kind=kind, metric=Metric.L2, params=params))
            index.build(x)
        node.sealed[("c", sid)] = SealedHandle(seg, index=index, index_kind=kind)
    seg = Segment(4, "c", 0, dim, slice_rows=slice_rows)
    pks, x = rows(20, 400, 3)
    x[18], pks[18] = tie, 450  # in the tail, after the full slice
    seg.append(pks, x, np.full(20, 100, np.int64))
    node.growing[("c", 4)] = GrowingState(seg)
    node._build_slice_indexes()
    return node


@pytest.mark.parametrize("strategy,classes,pks", [
    (None,
     ["indexed", "indexed", "growing_slice", "brute_sealed", "brute_tail"],
     [100, 200, 400, 300, 450]),
    ("post",
     ["growing_slice", "brute_tail", "post_indexed", "post_indexed", "post_brute"],
     [400, 450, 100, 200, 300]),
    ("brute",
     ["growing_slice", "brute_tail", "brute_filtered"],
     [400, 450, 100, 200, 300]),
])
def test_exact_ties_keep_the_pool_column_order_of_the_classes(strategy, classes, pks):
    """Equal scores across execution classes come back in the node's
    candidate-pool column order (``merge_topk`` breaks ties by column),
    which is the order the classes run in."""
    from repro.core.request import AnnsQuery, NodeSearchRequest
    from repro.core.telemetry import TraceContext
    from repro.index.attribute import FilterExpr

    node = _tied_node()
    ctx = TraceContext("search")
    request = NodeSearchRequest(
        collection="c", k=5, metric=Metric.L2,
        guarantee=GuaranteeTs(query_ts=10_000, staleness_ms=INFINITE_STALENESS),
        anns=[AnnsQuery("vector", np.zeros((2, 4), np.float32))],
        filter=None if strategy is None else FilterExpr("pk != 301"),
        filter_strategy=strategy,
        trace=(ctx, ctx.root),
    )
    (scores, got), = node.search_request(request)
    assert got.tolist() == [pks, pks]
    assert (scores == 1.0).all()
    scans = [s.name for s in ctx.root.children if s.name.startswith("scan_")]
    assert scans == ["scan_" + c for c in classes]
