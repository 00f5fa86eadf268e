"""Batched IVF execution engine: fuzz equivalence against the scalar
per-list reference oracle (``IVFBase._search_reference``), padded-probe
edge cases (tiny collections), and the ``search_batched`` candidate-pool
surface (including through ``QueryNode``)."""

import numpy as np
import pytest

from repro.core.collection import Metric
from repro.index import IndexSpec, create_index
from repro.index.base import VectorIndex
from repro.index.ivf import IVFFlatIndex
from repro.kernels import ops

KINDS = {
    "ivf_flat": {"nlist": 16},
    "ivf_sq": {"nlist": 16},
    "ivf_pq": {"nlist": 8, "m": 4, "ksub": 16},
}
METRICS = [Metric.L2, Metric.IP, Metric.COSINE]


def make_data(seed=7, n=800, d=32, nq=9):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((12, d)).astype(np.float32) * 3
    base = centers[rng.integers(0, 12, n)] + rng.standard_normal((n, d)).astype(
        np.float32
    )
    q = centers[rng.integers(0, 12, nq)] + rng.standard_normal((nq, d)).astype(
        np.float32
    )
    return base.astype(np.float32), q.astype(np.float32)


def reference(idx, q, k, valid=None):
    return idx._search_reference(q, k, valid=valid)


def _search_by_reference(monkeypatch):
    """IVF_FLAT searches run the oracle, a group of indexes one search
    each through the base per-index ``search_batched`` (as HNSW's do)."""
    monkeypatch.setattr(IVFFlatIndex, "search", IVFFlatIndex._search_reference)
    monkeypatch.setattr(IVFFlatIndex, "search_batched", VectorIndex.search_batched)


def assert_topk_equiv(batched, ref, metric, atol=3e-3):
    """Top-k set parity at equal scores: same live count, same sorted
    score multiset, and any id disagreement confined to ties at the
    boundary (the k-th score)."""
    sb, ib = batched
    sr, ir = ref
    assert sb.shape == sr.shape and ib.shape == ir.shape
    for r in range(len(sb)):
        lb, lr = ib[r] >= 0, ir[r] >= 0
        assert lb.sum() == lr.sum(), f"row {r}: live counts differ"
        kb = np.sort(sb[r][lb] if metric is Metric.L2 else -sb[r][lb])
        kr = np.sort(sr[r][lr] if metric is Metric.L2 else -sr[r][lr])
        np.testing.assert_allclose(kb, kr, atol=atol, rtol=2e-4)
        only = set(ib[r][lb].tolist()) ^ set(ir[r][lr].tolist())
        if only:
            boundary = kb[-1]
            key = {}
            key.update(
                zip(ib[r][lb].tolist(), (sb[r][lb] if metric is Metric.L2 else -sb[r][lb]).tolist())
            )
            key.update(
                zip(ir[r][lr].tolist(), (sr[r][lr] if metric is Metric.L2 else -sr[r][lr]).tolist())
            )
            for pk in only:
                assert abs(key[pk] - boundary) <= atol + 1e-4 * abs(boundary), (
                    f"row {r}: id {pk} differs beyond a boundary tie"
                )


_built = {}


def build(kind, metric):
    if (kind, metric) not in _built:
        base, q = make_data()
        params = dict(KINDS[kind], nprobe=8)
        idx = create_index(IndexSpec(kind=kind, metric=metric, params=params))
        idx.build(base)
        _built[(kind, metric)] = (idx, base, q)
    return _built[(kind, metric)]


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("metric", METRICS, ids=[m.value for m in METRICS])
@pytest.mark.parametrize("nprobe", [1, 8, "nlist"])
def test_batched_matches_reference(kind, metric, nprobe):
    idx, base, q = build(kind, metric)
    idx.params["nprobe"] = idx.nlist if nprobe == "nlist" else nprobe
    rng = np.random.default_rng(3)
    masks = [None, rng.random(len(base)) < 0.7]
    for valid in masks:
        got = idx.search(q, 10, valid=valid)
        want = reference(idx, q, 10, valid=valid)
        assert_topk_equiv(got, want, metric)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_batched_fuzz_with_deletes(kind):
    """Random shapes/masks, including sparse and empty visibility."""
    for seed in range(3):
        rng = np.random.default_rng(100 + seed)
        n = int(rng.integers(30, 400))
        d = 16
        base = rng.standard_normal((n, d)).astype(np.float32)
        q = rng.standard_normal((int(rng.integers(1, 6)), d)).astype(np.float32)
        params = dict(KINDS[kind])
        params["nlist"] = min(params["nlist"], max(2, n // 4))
        params["nprobe"] = int(rng.integers(1, params["nlist"] + 1))
        if kind == "ivf_pq":
            params["ksub"] = 8
        idx = create_index(IndexSpec(kind=kind, metric=Metric.L2, params=params))
        idx.build(base)
        k = int(rng.integers(1, 15))
        for valid in (None, rng.random(n) < 0.5, np.zeros(n, bool)):
            got = idx.search(q, k, valid=valid)
            want = reference(idx, q, k, valid=valid)
            assert_topk_equiv(got, want, Metric.L2, atol=1e-3)
            if valid is not None and not valid.any():
                assert (got[1] == -1).all()


def test_tiny_collection_padded_probes():
    """n < nlist: probes carry -1 padding; every row must still be found."""
    rng = np.random.default_rng(5)
    base = rng.standard_normal((5, 16)).astype(np.float32)
    q = rng.standard_normal((3, 16)).astype(np.float32)
    for kind in ("ivf_flat", "ivf_sq"):
        idx = create_index(
            IndexSpec(kind=kind, metric=Metric.L2, params={"nlist": 64, "nprobe": 8})
        )
        idx.build(base)
        s, i = idx.search(q, 10)
        for r in range(len(q)):
            assert set(i[r][i[r] >= 0].tolist()) == set(range(5)), kind
        # nprobe param raised beyond nlist after build: same, via -1 pads
        idx.params["nprobe"] = 999
        s, i = idx.search(q, 10)
        for r in range(len(q)):
            assert set(i[r][i[r] >= 0].tolist()) == set(range(5)), kind
        # reference oracle agrees on the padded-probe edge
        assert_topk_equiv((s, i), reference(idx, q, 10), Metric.L2)


def test_search_empty_query_batch():
    idx, base, q = build("ivf_flat", Metric.L2)
    s, i = idx.search(np.zeros((0, base.shape[1]), np.float32), 5)
    assert s.shape == (0, 5) and i.shape == (0, 5)


def test_search_batched_pools_match_per_index_search():
    """Each unit's candidate-pool block, reduced with merge_topk, must
    equal that unit's own search()."""
    rng = np.random.default_rng(11)
    idxs = []
    for u in range(3):
        base = rng.standard_normal((300 + 40 * u, 16)).astype(np.float32)
        ix = create_index(
            IndexSpec(kind="ivf_flat", metric=Metric.L2, params={"nlist": 8, "nprobe": 4})
        )
        ix.build(base)
        idxs.append(ix)
    q = rng.standard_normal((6, 16)).astype(np.float32)
    s, i, splits = IVFFlatIndex.search_batched(idxs, q, 7)
    assert len(splits) == len(idxs) + 1 and splits[0] == 0
    for u, ix in enumerate(idxs):
        blk = slice(splits[u], splits[u + 1])
        ms, mi = ops.merge_topk(s[:, blk], i[:, blk], 7, metric="l2")
        ss, si = ix.search(q, 7)
        assert_topk_equiv((ms, mi), (ss, si), Metric.L2, atol=1e-4)


def test_search_batched_base_fallback_runs_per_index_searches(monkeypatch):
    """The base ``search_batched``, which the kinds without a batched
    engine use, runs each index's own search: blocks of width k."""
    rng = np.random.default_rng(12)
    base = rng.standard_normal((200, 16)).astype(np.float32)
    ix = create_index(
        IndexSpec(kind="ivf_flat", metric=Metric.L2, params={"nlist": 8, "nprobe": 8})
    )
    ix.build(base)
    q = rng.standard_normal((4, 16)).astype(np.float32)
    _search_by_reference(monkeypatch)
    s, i, splits = IVFFlatIndex.search_batched([ix, ix], q, 5)
    assert splits == [0, 5, 10]
    ss, si = reference(ix, q, 5)
    np.testing.assert_array_equal(i[:, :5], si)
    np.testing.assert_array_equal(i[:, 5:], si)


def test_query_node_indexed_equivalence_with_deletes(monkeypatch):
    """Node-level search over sealed+indexed segments (grouped
    search_batched dispatch) matches the reference oracle path, with
    delta-deletes in play."""
    from repro.core.consistency import GuaranteeTs
    from repro.core.log import LogBroker
    from repro.core.object_store import MemoryObjectStore
    from repro.core.query_node import QueryNode, SealedHandle
    from repro.core.segment import Segment
    from repro.core.timestamp import INFINITE_STALENESS

    rng = np.random.default_rng(21)
    dim, n_seg, rows = 24, 4, 300
    node = QueryNode("qn-ivf", LogBroker(), MemoryObjectStore())
    base = rng.standard_normal((n_seg * rows, dim)).astype(np.float32)
    for sid in range(n_seg):
        lo = sid * rows
        seg = Segment(sid, "c", 0, dim)
        seg.append(
            np.arange(lo, lo + rows),
            base[lo : lo + rows],
            np.full(rows, 100, np.int64),
        )
        idx = create_index(
            IndexSpec(kind="ivf_flat", metric=Metric.L2, params={"nlist": 8, "nprobe": 8})
        )
        idx.build(base[lo : lo + rows])
        node.sealed[("c", sid)] = SealedHandle(seg, index=idx, index_kind="ivf_flat")
    # delete a slice of pks across segments
    doomed = rng.choice(n_seg * rows, 80, replace=False)
    node.delta_deletes["c"] = {int(pk): 200 for pk in doomed}
    q = rng.standard_normal((7, dim)).astype(np.float32)
    g = GuaranteeTs(query_ts=10_000, staleness_ms=INFINITE_STALENESS)

    got = node.search("c", q, 10, Metric.L2, g)
    _search_by_reference(monkeypatch)
    want = node.search("c", q, 10, Metric.L2, g)
    assert_topk_equiv(got, want, Metric.L2)
    assert not set(got[1][got[1] >= 0].ravel().tolist()) & set(doomed.tolist())
