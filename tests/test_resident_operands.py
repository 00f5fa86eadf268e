"""Resident index operands: a sealed FLAT index's rows and an IVF index's
centroids stay on the device from their first kernel call, and the kernel
reads them there (``ops.resident``, ``ops.ResidentOperand``).

The kernel path runs on the CPU in interpret mode (``ops.use_pallas``
patched to True); the host-array path through the same kernel is the
reference the answers must equal bit for bit."""

import gc
import weakref

import numpy as np
import pytest

from repro.core import ManuConfig, ManuSystem, SearchRequest
from repro.index.flat import FlatIndex
from repro.kernels import ops

DIM, NQ, K = 8, 2, 5


@pytest.fixture
def kernel_path(monkeypatch):
    monkeypatch.setattr(ops, "use_pallas", lambda: True)


def _indexed_system(vectors, kind, params, seal_rows=300):
    """One query node serving ``len(vectors) // seal_rows`` sealed
    segments, each with an index of ``kind``."""
    system = ManuSystem(ManuConfig(num_query_nodes=1, seal_rows=seal_rows))
    coll = system.create_collection("c", dim=vectors.shape[1])
    coll.create_index("vector", kind=kind, params=params)
    coll.insert({"vector": vectors})
    coll.flush()
    return system, coll


def _search(coll, q, trace=True):
    return coll.search(SearchRequest.single(q, k=K, staleness_ms=0.0, trace=trace))


def _host_path_answers(monkeypatch, vectors, kind, params, q):
    """The same search with every index operand sent per call, as a host
    array (today's path where nothing is resident)."""
    with monkeypatch.context() as m:
        m.setattr(ops, "resident", lambda x: x)
        _, coll = _indexed_system(vectors, kind, params)
        return _search(coll, q, trace=False)


def _node(system):
    (qn,) = system.query_nodes.values()
    return qn


def _probe_or_scan_spans(trace):
    (scan,) = [s for s in trace.walk() if s.name.startswith("scan_")]
    assert scan.name == "scan_indexed"
    return trace.spans_named("kernel_l2_topk")


def test_flat_search_reads_its_rows_on_the_device(rng, kernel_path, monkeypatch):
    seg_rows = 300
    vectors = rng.standard_normal((2 * seg_rows, DIM)).astype(np.float32)
    q = rng.standard_normal((NQ, DIM)).astype(np.float32)
    system, coll = _indexed_system(vectors, "flat", {})
    first = _search(coll, q)
    second = _search(coll, q)
    for res in (first, second):
        l2 = _probe_or_scan_spans(res.trace)
        assert len(l2) == 2
        for span in l2:
            h2d = span.children[0]
            # Only the f32 queries and the bool mask (padded on the host to
            # the 512-row tile) cross; the rows are read where they lie.
            assert h2d.bytes_h2d == NQ * DIM * 4 + 512
            assert h2d.bytes_resident == seg_rows * DIM * 4
    for handle in _node(system).sealed.values():
        assert isinstance(handle.index._operand, ops.ResidentOperand)
    host = _host_path_answers(monkeypatch, vectors, "flat", {}, q)
    for res in (second, host):
        np.testing.assert_array_equal(res.pks, first.pks)
        np.testing.assert_array_equal(res.scores, first.scores)


def test_ivf_probe_reads_its_centroids_on_the_device(rng, kernel_path, monkeypatch):
    nlist, seg_rows = 16, 300
    params = {"nlist": nlist, "nprobe": 4}
    vectors = rng.standard_normal((2 * seg_rows, DIM)).astype(np.float32)
    q = rng.standard_normal((NQ, DIM)).astype(np.float32)
    system, coll = _indexed_system(vectors, "ivf_flat", params)
    first = _search(coll, q)
    second = _search(coll, q)
    probes = _probe_or_scan_spans(second.trace)
    assert len(probes) == 2
    for span in probes:
        h2d = span.children[0]
        assert h2d.bytes_h2d == NQ * DIM * 4  # the queries; no mask
        assert h2d.bytes_resident == nlist * DIM * 4
    for handle in _node(system).sealed.values():
        assert isinstance(handle.index._centroid_operand, ops.ResidentOperand)
        assert handle.index._centroid_operand.shape == (nlist, DIM)
    host = _host_path_answers(monkeypatch, vectors, "ivf_flat", params, q)
    for res in (second, host):
        np.testing.assert_array_equal(res.pks, first.pks)
        np.testing.assert_array_equal(res.scores, first.scores)


def _operand_refs(system):
    """Weak references to each sealed index's resident device array."""
    return {
        key: weakref.ref(handle.index._operand.array)
        for key, handle in _node(system).sealed.items()
    }


def test_released_segments_free_their_device_rows(rng, kernel_path):
    vectors = rng.standard_normal((600, DIM)).astype(np.float32)
    system, coll = _indexed_system(vectors, "flat", {})
    _search(coll, rng.standard_normal((NQ, DIM)).astype(np.float32))
    qn = _node(system)
    refs = _operand_refs(system)
    (released, retired) = sorted(refs)
    qn.release_segment(*released)
    gc.collect()
    assert refs[released]() is None
    assert refs[retired]() is not None  # the other segment still serves
    qn.retire_segment(*retired, retired_at_ts=10)
    qn.apply_retention(horizon_ts=9)
    gc.collect()
    assert refs[retired]() is not None  # not yet behind the horizon
    qn.apply_retention(horizon_ts=10)
    gc.collect()
    assert refs[retired]() is None


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("masked", [False, True])
def test_ragged_rows_pad_once_and_match_the_host_path(
    rng, kernel_path, monkeypatch, metric, masked
):
    rows = 300  # not a multiple of the 512-row tile
    base = rng.standard_normal((rows, DIM)).astype(np.float32)
    q = rng.standard_normal((NQ, DIM)).astype(np.float32)
    valid = rng.random(rows) < 0.7 if masked else None
    operand = ops.resident(base)
    assert operand.shape == (rows, DIM)
    assert operand.array.shape == (512, DIM)
    np.testing.assert_array_equal(np.asarray(operand.array)[:rows], base)
    assert not np.asarray(operand.array)[rows:].any()
    want = ops.topk_scan(q, base, K, metric=metric, valid=valid)

    padded = []
    real_pad = ops._pad_rows

    def pad_rows(arr, multiple, fill=0):
        out = real_pad(arr, multiple, fill)
        if out is not arr:
            padded.append(arr.shape)
        return out

    monkeypatch.setattr(ops, "_pad_rows", pad_rows)
    for _ in range(2):
        got = ops.topk_scan(q, operand, K, metric=metric, valid=valid)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
    assert (rows, DIM) not in padded  # the base was padded once, up front


def test_host_backends_keep_the_host_array(rng):
    base = rng.standard_normal((300, DIM)).astype(np.float32)
    assert ops.resident(base) is base
    index = FlatIndex()
    index.build(base)
    q = rng.standard_normal((NQ, DIM)).astype(np.float32)
    got = index.search(q, K)
    assert index._operand is index.vectors
    want = ops.topk_scan(q, base, K)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
