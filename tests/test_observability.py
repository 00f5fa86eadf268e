"""Unit tests for the observability plane (``core/telemetry.py``): the
log-bucket histograms, the metrics registry and its Prometheus export,
the bounded control-plane event log, and the per-request span trees."""

import json

import numpy as np
import pytest

from repro.core import ManuConfig, ManuSystem, SearchRequest
from repro.core.request import InsertRequest
from repro.core.telemetry import (
    EventLog,
    Histogram,
    MetricsRegistry,
    TraceContext,
)
from repro.core.timestamp import ManualClock
from repro.kernels import ops


# ---------------------------------------------------------------- histogram


def test_histogram_percentiles_log_buckets():
    h = Histogram("lat_us")
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=7.0, sigma=1.0, size=20_000)  # ~1.1ms median
    h.record_many(vals)
    assert h.counts.sum() == 20_000
    for q in (50, 95, 99):
        est, exact = h.percentile(q), float(np.percentile(vals, q))
        # log10 buckets at 8/64 decade width: estimate within ~±35%
        assert exact / 1.5 < est < exact * 1.5, (q, est, exact)
    assert h.percentile(50) <= h.percentile(95) <= h.percentile(99)


def test_histogram_edge_values():
    h = Histogram("lat_us")
    assert h.percentile(99) == 0.0  # empty
    h.record(0.0)  # below the first edge: clamps into bucket 0
    h.record(1e12)  # beyond the last edge: clamps into the top bucket
    assert h.counts.sum() == 2
    assert h.mean > 0


# ----------------------------------------------------------------- registry


def test_registry_counters_gauges_labels():
    reg = MetricsRegistry()
    reg.inc("reqs_total")
    reg.inc("reqs_total", 2, labels={"op": "insert"})
    reg.inc("reqs_total", labels={"op": "insert"})
    reg.set_gauge("inflight", 7, labels={"node": "qn-0"})
    assert reg.counter_value("reqs_total") == 1
    assert reg.counter_value("reqs_total", labels={"op": "insert"}) == 3
    assert reg.gauge_value("inflight", labels={"node": "qn-0"}) == 7
    # label order never forks a series
    assert MetricsRegistry._key("m", {"b": 1, "a": 2}) == \
        MetricsRegistry._key("m", {"a": 2, "b": 1})


def test_registry_export_prometheus_text():
    reg = MetricsRegistry()
    reg.inc("searches_total", 5)
    reg.observe("lat_us", 100.0)
    reg.observe("lat_us", 200.0)
    text = reg.export()
    assert "# TYPE searches_total counter" in text
    assert "searches_total 5" in text
    assert "# TYPE lat_us summary" in text
    assert 'lat_us{quantile="0.50"}' in text
    assert "lat_us_count 2" in text


# ---------------------------------------------------------------- event log


def test_event_log_bounded_ring_and_query():
    clock = ManualClock(1000)
    log = EventLog(clock, capacity=4)
    for i in range(6):
        clock.advance(10)
        log.emit("tick", "test", i=i)
    assert len(log) == 4
    assert log.dropped == 2
    assert [e.detail["i"] for e in log.query()] == [2, 3, 4, 5]
    assert [e.detail["i"] for e in log.query(since_ts=1045)] == [4, 5]
    assert [e.kind for e in log.query(kind="nope")] == []
    # numpy payloads become plain JSON types
    e = log.emit("np", "test", sid=np.int64(7), ids=[np.int32(1)])
    d = json.loads(json.dumps(e.to_dict()))
    assert d["detail"] == {"sid": 7, "ids": [1]}


# ------------------------------------------------------------------- traces


def test_trace_context_span_tree():
    ctx = TraceContext("search")
    a = ctx.span("dispatch", node_id="qn-0", segment_ids=(1, 2))
    b = ctx.span("scan", parent=a, node_id="qn-0", segment_ids=(1,))
    b.rows_scanned = 100
    trace = ctx.finish(duration_us=1234.0)
    assert trace.kind == "search"
    assert [s.name for s in trace.walk()] == ["search", "dispatch", "scan"]
    assert trace.spans_named("scan") == [b]
    d = trace.to_dict()
    assert d["root"]["children"][0]["children"][0]["rows_scanned"] == 100
    out = trace.format()
    assert "dispatch" in out and "segments=[1, 2]" in out


# ------------------------------------------------------------- system level


def test_system_metrics_snapshot_and_trace_off_by_default(rng):
    system = ManuSystem(ManuConfig(num_query_nodes=2, seal_rows=300))
    coll = system.create_collection("c", dim=8)
    coll.insert({"vector": rng.standard_normal((900, 8)).astype(np.float32)})
    coll.flush()
    q = rng.standard_normal((2, 8)).astype(np.float32)
    res = coll.search(q, limit=5, staleness_ms=0.0)
    assert res.trace is None  # tracing is opt-in
    mres = coll.insert(InsertRequest({"vector": q}))
    assert mres.trace is None

    snap = system.metrics()
    assert snap.counter("proxy_searches_total") == 1
    assert snap.counter("logger_rows_written_total") == 902
    h = snap.histogram("proxy_search_latency_us")
    assert h is not None and h.count == 1 and h.p99 > 0
    # typed snapshot survives JSON round-trip
    again = json.loads(json.dumps(snap.to_dict()))
    assert again["counters"]["proxy_searches_total"] == 1
    # scan accounting covers the rows actually scanned (masks are
    # per-segment, query-count independent): every sealed row, once
    scanned = sum(
        v for k, v in snap.counters.items()
        if k.startswith("query_node_rows_scanned_total")
    )
    assert scanned == 900


def test_hedge_accounting_splits_primary_and_hedged(rng):
    system = ManuSystem(
        ManuConfig(num_query_nodes=2, replication_factor=2, seal_rows=200)
    )
    coll = system.create_collection("c", dim=8)
    coll.insert({"vector": rng.standard_normal((600, 8)).astype(np.float32)})
    coll.flush()
    q = rng.standard_normal((2, 8)).astype(np.float32)
    straggler = next(
        system.query_nodes[n]
        for n, st in system.query_coord.nodes.items()
        if st.segments
    )
    straggler.inject_delay_s = 2.0
    coll.search(q, limit=10, staleness_ms=0.0, hedge_timeout_s=0.05)
    straggler.inject_delay_s = 0.0
    snap = system.metrics()
    assert snap.counter("proxy_hedges_total") >= 1
    hedged = sum(
        qn.searches_hedged for qn in system.query_nodes.values()
    )
    assert hedged >= 1
    cs = system.cluster_state()
    assert sum(ns.searches_hedged for ns in cs.nodes) == hedged
    # hedged work is excluded from the load the replica picker sees
    for qn in system.query_nodes.values():
        assert qn.inflight_primary <= qn.inflight


def test_control_plane_events_on_failover(rng):
    system = ManuSystem(
        ManuConfig(num_query_nodes=2, replication_factor=2, seal_rows=200)
    )
    coll = system.create_collection("c", dim=8)
    coll.insert({"vector": rng.standard_normal((600, 8)).astype(np.float32)})
    coll.flush()
    mark = system.clock.now_ms()
    victim_id = next(
        n for n, st in system.query_coord.nodes.items() if st.segments
    )
    system.query_nodes[victim_id].alive = False
    system.clock.advance(system.config.heartbeat_ttl_ms + 1)
    system.recover_failures()
    kinds = {e.kind for e in system.events(since_ts=mark)}
    assert "node_dead" in kinds
    assert "node_status_change" in kinds
    dead_events = system.events(kind="node_dead")
    assert dead_events and dead_events[-1].detail["node"] == victim_id


# ----------------------------------------------------- spans in the program


def _flat_system(rng, rows=600, seal_rows=300):
    """One query node, ``rows // seal_rows`` sealed FLAT segments."""
    system = ManuSystem(ManuConfig(num_query_nodes=1, seal_rows=seal_rows))
    coll = system.create_collection("c", dim=8)
    coll.insert({"vector": rng.standard_normal((rows, 8)).astype(np.float32)})
    coll.flush()
    return system, coll


def _strong_search(coll, q, k=5, trace=True):
    return coll.search(
        SearchRequest.single(q, k=k, staleness_ms=0.0, trace=trace)
    )


def test_span_start_times_nest_inside_their_parents(rng):
    system, coll = _flat_system(rng)
    # Rows the query node has not consumed yet: the search must wait.
    coll.insert({"vector": rng.standard_normal((7, 8)).astype(np.float32)})
    res = _strong_search(coll, rng.standard_normal((2, 8)).astype(np.float32))
    trace = res.trace
    (wait,) = trace.spans_named("consistency_wait")
    assert wait in trace.root.children
    assert wait.detail.startswith("dml/c/")
    assert [s.name for s in trace.root.children].index("consistency_wait") < \
        [s.name for s in trace.root.children].index("dispatch")
    eps = 1e-3  # microseconds of float rounding in start + duration
    for parent in trace.walk():
        for child in parent.children:
            assert child.start_us >= parent.start_us - eps, (parent.name, child.name)
            assert child.start_us + child.duration_us <= \
                parent.start_us + parent.duration_us + eps, (parent.name, child.name)
    # The root holds the reduce, so its self time is not negative.
    assert trace.root.duration_us >= sum(c.duration_us for c in trace.root.children)
    assert trace.root.duration_us >= res.waited_ms * 1e3


def test_routed_search_records_no_wait_span(rng):
    system, coll = _flat_system(rng)
    _strong_search(coll, rng.standard_normal((1, 8)).astype(np.float32))
    # Eventual reads are covered by what the node consumed: no wait call.
    res = coll.search(SearchRequest.single(
        rng.standard_normal((1, 8)).astype(np.float32), k=5,
        staleness_ms=float("inf"), trace=True))
    assert res.trace.spans_named("consistency_wait") == []
    assert res.trace.spans_named("dispatch")


def test_kernel_spans_split_copy_and_result_wait(rng, monkeypatch):
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    system, coll = _flat_system(rng)
    nq, k, dim, seg_rows = 2, 5, 8, 300
    res = _strong_search(coll, rng.standard_normal((nq, dim)).astype(np.float32), k=k)
    trace = res.trace
    scans = [s for s in trace.walk() if s.name.startswith("scan_")]
    assert [s.name for s in scans] == ["scan_brute_sealed"]
    l2 = trace.spans_named("kernel_l2_topk")
    assert len(l2) == 2 and all(s in scans[0].children for s in l2)
    merges = trace.spans_named("kernel_merge_topk")
    (node_merge,) = trace.spans_named("node_merge_topk")
    (proxy_merge,) = trace.spans_named("merge_topk")
    assert merges == node_merge.children + proxy_merge.children
    for span in l2 + merges:
        assert [c.name for c in span.children] == ["h2d", "result_wait"]
        assert sum(c.duration_us for c in span.children) <= span.duration_us
    # Bytes by hand: f32 queries and segment rows plus the bool mask...
    for span in l2:
        assert span.children[0].bytes_h2d == nq * dim * 4 + seg_rows * dim * 4 + seg_rows
    # ...and the merges' f32 scores and int32 pks: 2 segments x k on the
    # node, 1 node x k at the proxy.
    assert node_merge.children[0].children[0].bytes_h2d == 2 * nq * (2 * k) * 4
    assert proxy_merge.children[0].children[0].bytes_h2d == 2 * nq * k * 4
    assert sum(s.bytes_h2d for s in trace.walk()) == \
        2 * (nq * dim * 4 + seg_rows * dim * 4 + seg_rows) + 2 * nq * 3 * k * 4


def test_untraced_search_records_and_waits_for_nothing(rng, monkeypatch):
    import jax

    from repro.core import telemetry

    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    system, coll = _flat_system(rng)
    q = rng.standard_normal((2, 8)).astype(np.float32)
    traced = _strong_search(coll, q)  # compiles the same kernels first
    calls = {"block": 0, "span": 0}
    real_block = jax.block_until_ready

    def block(x):
        calls["block"] += 1
        return real_block(x)

    class CountedSpan(telemetry.Span):
        def __init__(self, *args, **kwargs):
            calls["span"] += 1
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(jax, "block_until_ready", block)
    monkeypatch.setattr(telemetry, "Span", CountedSpan)
    res = _strong_search(coll, q, trace=False)
    assert res.trace is None
    assert calls == {"block": 0, "span": 0}
    assert telemetry.open_span() is None
    np.testing.assert_array_equal(res.pks, traced.pks)
    # The same counters do count in a traced search.
    _strong_search(coll, q)
    assert calls["block"] > 0 and calls["span"] > 0


def test_spans_are_profiler_annotations(rng, tmp_path):
    import glob

    import jax

    system, coll = _flat_system(rng)
    coll.insert({"vector": rng.standard_normal((7, 8)).astype(np.float32)})
    q = rng.standard_normal((1, 8)).astype(np.float32)
    with jax.profiler.trace(str(tmp_path)):
        res = _strong_search(coll, q)
    assert res.trace.spans_named("consistency_wait")
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    names = {
        ev.name
        for plane in jax.profiler.ProfileData.from_file(path).planes
        for line in plane.lines
        for ev in line.events
    }
    assert {"manu.dispatch", "manu.consistency_wait", "manu.plan_search",
            "manu.merge_topk"} <= names
    assert any(n.startswith("manu.scan_") for n in names)
