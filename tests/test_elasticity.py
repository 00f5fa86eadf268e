"""Chaos tests for the elasticity control plane (paper §6.3): replica
groups, the HealthMonitor/StateReconciler loop, replica-aware dispatch
with mid-request failover, and the typed cluster-admin API
(``ManuSystem.cluster_state()`` / ``ManuCollection.describe()``)."""

import time

import numpy as np
import pytest

from repro.core import ManuConfig, ManuSystem, SearchRequest


def ingest(coll, rng, n, dim, batches=4):
    vecs = rng.standard_normal((n, dim)).astype(np.float32)
    step = n // batches
    for i in range(batches):
        coll.insert({"vector": vecs[i * step : (i + 1) * step]})
    return vecs


def sorted_rows(res):
    """(pks, scores) of a SearchResult, row-sorted by pk for bit-for-bit
    comparison independent of merge order."""
    order = np.argsort(res.pks, axis=1)
    return (
        np.take_along_axis(res.pks, order, 1),
        np.take_along_axis(res.scores, order, 1),
    )


# --------------------------------------------------------- replica groups


def test_replica_groups_full_replication(rng):
    """rf=2 over 3 nodes: every sealed segment gets two distinct replicas,
    each with the copy actually loaded, and nothing is under-replicated."""
    system = ManuSystem(
        ManuConfig(num_query_nodes=3, replication_factor=2, seal_rows=200)
    )
    coll = system.create_collection("c", dim=8)
    ingest(coll, rng, 1000, 8, batches=5)
    coll.flush()
    sealed = system.data_coord.sealed_segments("c")
    assert len(sealed) >= 3
    cs = system.cluster_state()
    assert cs.replication_factor == 2
    assert cs.under_replicated == 0
    for sid in sealed:
        reps = cs.replicas_of("c", sid)
        assert len(reps) == 2 and len(set(reps)) == 2
        for n in reps:
            assert ("c", sid) in system.query_nodes[n].sealed
    # replicated reads return unique pks (dedup at the global reduce)
    q = rng.standard_normal((2, 8)).astype(np.float32)
    res = coll.search(q, limit=10, staleness_ms=0.0)
    for r in range(2):
        live = res.pks[r][res.pks[r] >= 0]
        assert len(set(live.tolist())) == len(live) == 10


def test_replication_factor_validated(rng):
    system = ManuSystem(ManuConfig(num_query_nodes=1))
    with pytest.raises(ValueError):
        system.create_collection("bad", dim=4, replication_factor=0)
    with pytest.raises(ValueError):
        system.create_collection("bad2", dim=4, replication_factor=1.5)


def test_per_collection_override_degrades_gracefully(rng):
    """replication_factor=3 on a 2-node cluster must not raise: placements
    commit with 2 replicas and a recorded under-replication flag."""
    system = ManuSystem(
        ManuConfig(num_query_nodes=2, replication_factor=1, seal_rows=200)
    )
    coll = system.create_collection("c", dim=8, replication_factor=3)
    assert coll.describe().replication_factor == 3
    ingest(coll, rng, 600, 8, batches=3)
    coll.flush()
    cs = system.cluster_state()
    placed = [p for p in cs.placement if p.collection == "c"]
    assert placed
    for p in placed:
        assert len(p.replicas) == 2  # capacity-limited, not raised
        assert p.under_replicated
    assert cs.under_replicated == len(placed)
    q = rng.standard_normal((1, 8)).astype(np.float32)
    assert (coll.search(q, limit=5, staleness_ms=0.0).pks[0] >= 0).all()


# ------------------------------------------------------------- failover


def test_kill_node_mid_search_bit_for_bit(rng):
    """A node dying between planning and scan: the proxy reports it to the
    control loop, re-dispatches to surviving replicas, and the answer is
    bit-for-bit the single-node oracle's."""
    dim, n = 8, 900
    oracle_sys = ManuSystem(
        ManuConfig(num_query_nodes=1, seal_rows=200, num_shards=2)
    )
    system = ManuSystem(
        ManuConfig(
            num_query_nodes=3, replication_factor=2, seal_rows=200,
            num_shards=2,
        )
    )
    rng_a, rng_b = np.random.default_rng(7), np.random.default_rng(7)
    o_coll = oracle_sys.create_collection("c", dim=dim)
    o_coll.create_index("vector", kind="flat")
    coll = system.create_collection("c", dim=dim)
    coll.create_index("vector", kind="flat")
    ingest(o_coll, rng_a, n, dim, batches=3)
    ingest(coll, rng_b, n, dim, batches=3)
    o_coll.flush()
    coll.flush()
    q = np.random.default_rng(9).standard_normal((4, dim)).astype(np.float32)
    oracle = o_coll.search(q, limit=10, staleness_ms=0.0)

    # victim: any node holding sealed replicas; dies on its next scan
    victim_id = next(
        n for n, st in system.query_coord.nodes.items() if st.segments
    )
    victim = system.query_nodes[victim_id]

    def dying(request):
        victim.alive = False
        raise RuntimeError("injected crash mid-request")

    victim.search_request = dying
    res = coll.search(q, limit=10, staleness_ms=0.0)
    pk_a, sc_a = sorted_rows(oracle)
    pk_b, sc_b = sorted_rows(res)
    np.testing.assert_array_equal(pk_a, pk_b)
    np.testing.assert_allclose(sc_a, sc_b, rtol=1e-5)

    # the control loop reassigned: cluster_state reflects the takeover
    cs = system.cluster_state()
    assert victim_id not in cs.live_node_ids
    for p in cs.placement:
        assert victim_id not in p.replicas
        assert not p.under_replicated  # healed back to rf=2 on survivors


def test_scan_error_on_live_node_is_raised_not_failed_over(rng):
    """A scan that raises on a node that is still alive (a device or
    compile error) reaches the caller; it is not mistaken for a node death,
    so placement stays as it was."""
    system = ManuSystem(ManuConfig(num_query_nodes=2, seal_rows=200))
    coll = system.create_collection("c", dim=8)
    ingest(coll, rng, 600, 8, batches=3)
    coll.flush()
    before = system.cluster_state().live_node_ids
    node_id = next(
        n for n, st in system.query_coord.nodes.items() if st.segments
    )

    def device_error(request):
        raise RuntimeError("RESOURCE_EXHAUSTED: scoped vmem")

    system.query_nodes[node_id].search_request = device_error
    q = rng.standard_normal((1, 8)).astype(np.float32)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        coll.search(q, limit=5, staleness_ms=0.0)
    assert system.cluster_state().live_node_ids == before


def test_node_join_heals_under_replication(rng):
    """Under-replicated (1 node, rf=2) -> node join -> the reconciler heals
    every segment back to full replication."""
    system = ManuSystem(
        ManuConfig(num_query_nodes=1, replication_factor=2, seal_rows=200)
    )
    coll = system.create_collection("c", dim=8)
    ingest(coll, rng, 600, 8, batches=3)
    coll.flush()
    cs = system.cluster_state()
    assert cs.under_replicated == len(cs.placement) > 0
    system.add_query_node()
    cs = system.cluster_state()
    assert cs.under_replicated == 0
    for p in cs.placement:
        assert len(p.replicas) == 2
    q = rng.standard_normal((2, 8)).astype(np.float32)
    res = coll.search(q, limit=10, staleness_ms=0.0)
    assert (res.pks >= 0).all()


def test_heartbeat_expiry_reassignment_cas_safe(rng):
    """A dead node detected by lease expiry is reassigned through the CAS
    loop even when a concurrent rebalance commits first: the healer retries
    against the winner's committed record instead of clobbering it."""
    system = ManuSystem(
        ManuConfig(num_query_nodes=3, replication_factor=1, seal_rows=200)
    )
    coll = system.create_collection("c", dim=8)
    ingest(coll, rng, 900, 8, batches=3)
    coll.flush()
    coord = system.query_coord
    victim_id = next(n for n, st in coord.nodes.items() if st.segments)
    survivors = sorted(set(coord.nodes) - {victim_id})
    system.query_nodes[victim_id].alive = False  # crash: no dereg

    # heartbeats stop; the manual clock sails past the lease TTL.  The
    # survivors keep beating (one pump round), the victim cannot.
    system.clock.advance(system.config.heartbeat_ttl_ms + 1)
    system.pump()
    statuses = coord.health.observe()
    assert statuses[victim_id] == "dead"
    assert all(statuses[n] == "healthy" for n in survivors)

    # interleave a competing committed write under the first CAS attempt
    real_cas = system.meta.cas
    raced = {"hit": 0}

    def racing_cas(key, rev, value):
        if key.startswith("assignment/c/") and raced["hit"] == 0:
            raced["hit"] += 1
            cur = system.meta.get(key) or {}
            competitor = dict(cur)
            competitor["nodes"] = [survivors[0]]
            competitor["node"] = survivors[0]
            system.meta.put(key, competitor)  # bumps rev: CAS below loses
        return real_cas(key, rev, value)

    system.meta.cas = racing_cas
    try:
        report = system.query_coord.reconciler.reconcile()
    finally:
        system.meta.cas = real_cas
    system.run_until_idle()
    assert victim_id in report["dead"]
    assert raced["hit"] == 1  # the race actually fired

    # converged: committed records match the in-memory mirror, the dead
    # node is gone everywhere, and every segment kept exactly one replica
    for (c, sid), reps in coord.replica_sets.items():
        rec = system.meta.get(f"assignment/{c}/{sid}")
        assert rec["nodes"] == list(reps)
        assert victim_id not in reps
        assert len(reps) == 1
    q = rng.standard_normal((2, 8)).astype(np.float32)
    res = coll.search(q, limit=10, staleness_ms=0.0)
    assert (res.pks >= 0).all()


def test_drain_preserves_pinned_mvcc_reads(rng):
    """Graceful scale-down moves replicas load-before-release with their
    MVCC epoch pins intact: a read pinned before the drain returns the
    exact same rows afterwards (including through a compaction swap)."""
    system = ManuSystem(
        ManuConfig(
            num_query_nodes=2, replication_factor=1, seal_rows=200,
            compaction_delete_ratio=0.1,
        )
    )
    coll = system.create_collection("c", dim=8)
    ingest(coll, rng, 800, 8, batches=4)
    coll.flush()
    coll.delete(rng.choice(800, 200, replace=False))
    coll.compact()  # placements now carry visible_from_ts epoch pins
    q = rng.standard_normal((3, 8)).astype(np.float32)
    pinned = coll.search(q, limit=10, staleness_ms=0.0)
    pins_before = {
        p.segment_id: p.visible_from_ts
        for p in system.cluster_state().placement
    }
    assert any(ts > 0 for ts in pins_before.values())

    drained = system.remove_query_node()
    assert drained is not None
    cs = system.cluster_state()
    for p in cs.placement:
        assert drained not in p.replicas
        assert p.visible_from_ts == pins_before[p.segment_id]  # pin intact

    replay = coll.search(q, limit=10, time_travel_ts=pinned.query_ts)
    pk_a, sc_a = sorted_rows(pinned)
    pk_b, sc_b = sorted_rows(replay)
    np.testing.assert_array_equal(pk_a, pk_b)
    np.testing.assert_allclose(sc_a, sc_b, rtol=1e-5)


# ----------------------------------------------------- hedged dispatch


def test_hedge_goes_to_different_replica(rng):
    """With rf=2 a straggler's plan units hedge to the *other* replica:
    the request completes well under the injected delay, exactly."""
    system = ManuSystem(
        ManuConfig(num_query_nodes=2, replication_factor=2, seal_rows=200)
    )
    coll = system.create_collection("c", dim=8)
    ingest(coll, rng, 600, 8, batches=3)
    coll.flush()
    q = rng.standard_normal((2, 8)).astype(np.float32)
    oracle = coll.search(q, limit=10, staleness_ms=0.0)

    straggler = next(
        system.query_nodes[n]
        for n, st in system.query_coord.nodes.items()
        if st.segments
    )
    straggler.inject_delay_s = 2.0
    t0 = time.perf_counter()
    res = coll.search(q, limit=10, staleness_ms=0.0, hedge_timeout_s=0.05)
    elapsed = time.perf_counter() - t0
    straggler.inject_delay_s = 0.0
    np.testing.assert_array_equal(sorted_rows(oracle)[0], sorted_rows(res)[0])
    assert elapsed < 1.5  # did not block on the straggler's full delay


# ------------------------------------------------- traced chaos coverage


def _executed_dispatch_leaves(trace):
    """(node, frozenset(segments)) of every dispatch in the span tree that
    actually executed (has a plan_search child), asserting along the way
    that each dispatch's segment scope == its plan's scoped segments ==
    the union of its scan spans' segments."""
    leaves = set()
    for span in trace.walk():
        if span.name not in ("dispatch", "hedge_dispatch"):
            continue
        plans = [c for c in span.children if c.name == "plan_search"]
        if not plans:
            continue  # dispatch died (or is still in flight): no scan ran
        plan_segs = set()
        for p in plans:
            plan_segs |= set(p.segment_ids)
        scan_segs = set()
        for c in span.children:
            if c.name.startswith("scan_"):
                scan_segs |= set(c.segment_ids)
        assert plan_segs == set(span.segment_ids)
        assert scan_segs == plan_segs
        leaves.add((span.node_id, frozenset(span.segment_ids)))
    return leaves


def test_traced_chaos_search_span_tree_covers_every_scan(rng):
    """Kill a node mid-search, then hedge a straggler, both with tracing
    on: the span tree's (segment, node) leaves must equal exactly the
    scans that actually ran — including the failover re-plan's dispatches
    and the hedge that won — and every span's segment-id set must equal
    its plan's scoped segments."""
    dim = 8
    system = ManuSystem(
        ManuConfig(
            num_query_nodes=3, replication_factor=2, seal_rows=200,
            num_shards=2,
        )
    )
    coll = system.create_collection("c", dim=dim)
    ingest(coll, rng, 900, dim, batches=3)
    coll.flush()
    q = rng.standard_normal((3, dim)).astype(np.float32)
    oracle = coll.search(q, limit=10, staleness_ms=0.0)

    # Ground truth: record every (node, scoped segment set) scan that
    # actually completes on any node.
    scanned: list[tuple[str, frozenset]] = []
    for node_id, qn in system.query_nodes.items():
        def wrapped(request, orig=qn.search_request, node_id=node_id):
            out = orig(request)
            assert request.segments is not None  # replica-scoped dispatch
            scanned.append((node_id, frozenset(request.segments)))
            return out

        qn.search_request = wrapped

    # --- phase 1: node dies between planning and scan (failover re-plan)
    victim_id = next(
        n for n, st in system.query_coord.nodes.items() if st.segments
    )
    victim = system.query_nodes[victim_id]

    def dying(request):
        victim.alive = False
        raise RuntimeError("injected crash mid-request")

    victim.search_request = dying
    res = coll.search(
        SearchRequest.single(q, field="vector", k=10, staleness_ms=0.0,
                             trace=True)
    )
    np.testing.assert_array_equal(sorted_rows(oracle)[0], sorted_rows(res)[0])
    trace = res.trace
    assert trace is not None and trace.kind == "search"
    assert trace.spans_named("failover_replan"), "no failover re-plan span"
    assert not trace.spans_named("hedge")
    assert _executed_dispatch_leaves(trace) == set(scanned)
    # the dead node's dispatch is in the tree but has no scan children
    dead_dispatches = [
        s for s in trace.walk()
        if s.name == "dispatch" and s.node_id == victim_id
    ]
    assert dead_dispatches and all(not s.children for s in dead_dispatches)

    # --- phase 2: a straggling survivor forces a hedge that wins
    system.run_until_idle()  # survivors finish loading healed replicas
    scanned.clear()
    straggler_id = next(
        n for n, st in system.query_coord.nodes.items()
        if st.segments and n != victim_id
    )
    straggler = system.query_nodes[straggler_id]
    straggler.inject_delay_s = 0.4
    res2 = coll.search(
        SearchRequest.single(q, field="vector", k=10, staleness_ms=0.0,
                             trace=True),
        hedge_timeout_s=0.05,
    )
    straggler.inject_delay_s = 0.0
    np.testing.assert_array_equal(sorted_rows(oracle)[0], sorted_rows(res2)[0])
    trace2 = res2.trace
    assert trace2.spans_named("hedge"), "no hedge span despite straggler"
    # let the abandoned straggler thread finish so its late scan lands in
    # both the span tree and the ground truth before comparing
    time.sleep(0.6)
    hedge_wins = [
        s for s in trace2.walk()
        if s.name == "hedge_dispatch"
        and any(c.name == "plan_search" for c in s.children)
    ]
    assert hedge_wins, "the hedged re-dispatch never executed"
    assert _executed_dispatch_leaves(trace2) == set(scanned)


# ------------------------------------------------------ cluster-admin API


def test_cluster_state_and_describe_typed_api(rng):
    system = ManuSystem(
        ManuConfig(num_query_nodes=2, replication_factor=2, seal_rows=200)
    )
    coll = system.create_collection("c", dim=8)
    coll.create_index("vector", kind="ivf_flat", params={"nlist": 4})
    ingest(coll, rng, 600, 8, batches=3)
    coll.flush()
    coll.create_partition("hot")

    desc = coll.describe()
    assert desc.name == "c"
    assert {f.name for f in desc.fields} >= {"pk", "vector"}
    assert "hot" in desc.partitions
    assert desc.num_entities == 600
    assert desc.replication_factor == 2
    ix = desc.index_on("vector")
    assert ix is not None and ix.kind == "ivf_flat"
    assert ix.params["nlist"] == 4

    cs = system.cluster_state()
    assert set(cs.live_node_ids) == set(system.query_nodes)
    for ns in cs.nodes:
        assert ns.status == "healthy"
        assert ns.load == len(ns.segments)
    assert cs.node(cs.nodes[0].node_id) is cs.nodes[0]
    with pytest.raises(KeyError):
        cs.node("qn-nope")
    # legacy stats() survives as a facade over the same state
    st = system.stats()
    assert set(st["query_nodes"]) == set(system.query_nodes)
    for n, entry in st["query_nodes"].items():
        assert entry["status"] == "healthy"
    assert st["cluster"]["under_replicated"] == cs.under_replicated


def test_reconcile_rebalances_on_join(rng):
    """Node join: the reconciler's rebalance step converges replica counts
    toward even load without ever dropping below the replication factor."""
    system = ManuSystem(
        ManuConfig(num_query_nodes=2, replication_factor=1, seal_rows=100)
    )
    coll = system.create_collection("c", dim=8)
    ingest(coll, rng, 800, 8, batches=4)
    coll.flush()
    system.add_query_node()
    counts = {
        n: len(st.segments) for n, st in system.query_coord.nodes.items()
    }
    assert max(counts.values()) - min(counts.values()) <= 1
    assert system.cluster_state().under_replicated == 0
