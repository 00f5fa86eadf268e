"""Streaming ingest on the threaded runtime: searches at STRONG
consistency while inserts, deletes, seals and index builds run.

Answers are judged against a plain MVCC reference: the exact top-k over
the rows whose acknowledged write timestamp is at or below the answer's
``query_ts`` and that no delete at or below it removed.
"""

import threading
import time

import numpy as np
import pytest

from repro.core import ConsistencyLevel, ManuConfig, ManuSystem, SearchRequest
from repro.core import manu as manu_mod
from repro.core.collection import Metric
from repro.core.consistency import GuaranteeTs
from repro.core.index_node import IndexNode
from repro.core.log import EntryType, LogBroker, LogEntry
from repro.core.object_store import MemoryObjectStore
from repro.core.query_node import GrowingState, QueryNode
from repro.core.segment import Segment
from repro.core.timestamp import INFINITE_STALENESS

NEVER = np.iinfo(np.int64).max
DIM = 16


# ------------------------------------------------------------------ reference
class Reference:
    """Every row a test may write (pk = row), with the timestamps at which
    its insert and its delete were acknowledged (``NEVER`` until then)."""

    def __init__(self, vectors: np.ndarray):
        self.vectors = vectors.astype(np.float32)
        self.ins_ts = np.full(len(vectors), NEVER, np.int64)
        self.del_ts = np.full(len(vectors), NEVER, np.int64)

    def visible(self, qts: int) -> np.ndarray:
        return (self.ins_ts <= qts) & (self.del_ts > qts)

    def exact(self, q: np.ndarray, pks: np.ndarray) -> np.ndarray:
        """Float64 squared L2 distance of ``q`` to each row ``pks``."""
        x = self.vectors[pks].astype(np.float64)
        return ((x - q.astype(np.float64)) ** 2).sum(-1)

    def topk(self, q: np.ndarray, qts: int, k: int) -> np.ndarray:
        rows = np.nonzero(self.visible(qts))[0]
        d = self.exact(q, rows)
        return rows[np.argsort(d, kind="stable")[:k]]


def judge(ref: Reference, answers: list, k: int) -> dict:
    """Bad slots (a row not visible at the answer's timestamp, a row twice,
    an empty slot while k rows are visible), the largest relative error of
    a returned score, and mean recall@k."""
    bad, worst, recalls = 0, 0.0, []
    for q, qts, pks, scores in answers:
        vis = ref.visible(qts)
        for qi in range(len(q)):
            got = pks[qi][pks[qi] >= 0]
            bad += int((~vis[got]).sum()) + len(got) - len(set(got.tolist()))
            want = ref.topk(q[qi], qts, k)
            bad += max(0, len(want) - len(got))
            if len(got):
                exact = ref.exact(q[qi], got)
                err = np.abs(scores[qi][: len(got)] - exact) / np.maximum(exact, 1e-6)
                worst = max(worst, float(err.max()))
            recalls.append(len(set(got.tolist()) & set(want.tolist())) / max(1, len(want)))
    return {"bad": bad, "score_err": worst, "recall": float(np.mean(recalls))}


def clustered(rng, n: int, centres: np.ndarray) -> np.ndarray:
    pick = rng.integers(0, len(centres), n)
    return (centres[pick] + rng.standard_normal((n, centres.shape[1]))).astype(np.float32)


def threaded_system(**over) -> ManuSystem:
    kw = dict(manual_clock=False, threaded=True, num_shards=2, num_query_nodes=2,
              seal_rows=1024, slice_rows=256, tick_interval_ms=20.0)
    kw.update(over)
    return ManuSystem(ManuConfig(**kw))


def strong(q: np.ndarray, k: int = 10) -> SearchRequest:
    return SearchRequest.single(q, k=k, consistency=ConsistencyLevel.STRONG)


# -------------------------------------------------------- (a) one plan, one view
class AppendingSegment(Segment):
    """A growing segment that gains a row after every read call: a plan
    that reads it twice sees two different segments."""

    def __init__(self, *a, late_ts: int, **kw):
        super().__init__(*a, **kw)
        self.late_ts = late_ts
        self.reads = 0

    def _grow(self, out):
        self.reads += 1
        pk = 10_000 + self.reads
        self.append(np.array([pk]), np.ones((1, self.dim), np.float32),
                    np.array([self.late_ts + self.reads], np.int64))
        return out

    def pks(self):
        return self._grow(super().pks())

    def vectors(self):
        return self._grow(super().vectors())

    def timestamps(self):
        return self._grow(super().timestamps())

    def visible_mask(self, ts):
        return self._grow(super().visible_mask(ts))

    def snapshot(self):
        return self._grow(super().snapshot())


@pytest.mark.parametrize("deletes", [False, True])
def test_plan_reads_one_view_of_a_growing_segment(rng, deletes):
    node = QueryNode("qn", LogBroker(), MemoryObjectStore(), slice_rows=64)
    qts = 1_000
    seg = AppendingSegment(7, "c", 0, DIM, slice_rows=64, late_ts=qts)
    n = 200
    x = rng.standard_normal((n, DIM)).astype(np.float32)
    seg.append(np.arange(n), x, np.arange(1, n + 1, dtype=np.int64))
    node.growing[("c", 7)] = GrowingState(seg)
    node._build_slice_indexes()
    if deletes:
        node.delta_deletes["c"] = {3: 500, 150: 500}

    plan = node.plan_search("c", qts)

    assert plan.growing_slice and plan.brute_tail
    for unit in plan.units():
        assert len(unit.pks) == len(unit.mask)
        if unit.vectors is not None:
            assert len(unit.vectors) == len(unit.mask)
    assert seg.reads > 0  # the segment did grow while the plan read it

    q = x[:3] + 0.01
    guarantee = GuaranteeTs(query_ts=qts, staleness_ms=INFINITE_STALENESS)
    _scores, pks = node.search("c", q, 5, Metric.L2, guarantee)
    assert (pks[:, 0] == np.arange(3)).all()
    assert (pks < n).all()  # rows appended after the view are never served
    if deletes:
        assert not np.isin(pks, [3, 150]).any()


# -------------------------------------------- (b) threaded ingest under STRONG reads
def test_strong_searches_under_streaming_writes_match_reference(rng):
    centres = rng.standard_normal((8, DIM)).astype(np.float32) * 4
    total = 24_000
    ref = Reference(clustered(rng, total, centres))
    system = threaded_system()
    coll = system.create_collection("c", dim=DIM)
    coll.create_index("vector", kind="flat")
    batch, k = 100, 10
    tickets, errors, deleted_at, deleted = [], [], [0], []
    stop = threading.Event()

    def writer():
        drng = np.random.default_rng(1)
        try:
            for a in range(0, total, batch):
                if stop.is_set():
                    return
                pks = np.arange(a, a + batch)
                tickets.append((pks, coll.insert_async(
                    {"pk": pks, "vector": ref.vectors[pks]})))
                if a and a % (10 * batch) == 0:
                    acked = [p for p, t in tickets if t.done]
                    if acked:
                        doomed = drng.choice(np.concatenate(acked), 8, replace=False)
                        doomed = doomed[ref.del_ts[doomed] == NEVER]
                        res = coll.delete(_delete(doomed))
                        ref.del_ts[doomed] = res.watermark_ts
                        deleted_at.append(res.watermark_ts)
                        deleted.extend(doomed.tolist())
                time.sleep(0.015)
        except Exception as e:  # reported below, not lost in the thread
            errors.append(repr(e))

    def acked_ts() -> int:
        """The newest timestamp of a write acknowledged so far."""
        done = [t.result(0).watermark_ts for _p, t in list(tickets) if t.done]
        return max(done + deleted_at)

    answers, stale, probes = [], [], []
    w = threading.Thread(target=writer)
    try:
        w.start()
        t_end = time.monotonic() + 5.0
        while time.monotonic() < t_end and w.is_alive():
            q = clustered(rng, 2, centres)
            done = [p for p, t in list(tickets) if t.done]
            probe = bool(len(probes) < len(answers) and done and deleted)
            if probe:
                # Read-your-writes: a search for the newest acknowledged
                # row finds it, one for the newest deleted row does not.
                rows = [int(done[-1][-1]), deleted[-1]]
                q = ref.vectors[rows]
            try:
                floor = acked_ts()
                res = coll.search(strong(q, k))
                if res.query_ts < floor:  # STRONG reads every acknowledged write
                    stale.append((int(res.query_ts), floor))
                if probe:
                    probes.append((rows, np.asarray(res.pks)))
                    continue
                answers.append((q, int(res.query_ts), np.asarray(res.pks),
                                np.asarray(res.scores)))
            except Exception as e:
                errors.append(repr(e))
        stop.set()
        w.join()
        for pks, t in tickets:
            ref.ins_ts[pks] = t.result(10.0).watermark_ts
    finally:
        stop.set()
        system.stop_threads()

    assert not errors, errors[:3]
    assert not stale, stale[:3]
    assert len(answers) >= 20 and len(probes) >= 5
    missed = [(ins, dead) for (ins, dead), pks in probes
              if pks[0, 0] != ins or dead in pks[1]]
    assert not missed, missed[:3]
    assert system.telemetry.counter_value("data_node_segments_sealed_total") >= 2
    out = judge(ref, answers, k)
    assert out["bad"] == 0, out
    assert out["score_err"] < 1e-4, out
    assert out["recall"] >= 0.9, out


# ------------------------------------------------ (c) ticks ordered with writes
def test_ticks_and_mutations_from_two_threads_stay_ordered(rng):
    system = ManuSystem(ManuConfig(manual_clock=False, num_loggers=2, num_shards=2))
    coll = system.create_collection("c", dim=DIM)
    channels = system.broker.channels("dml/")
    stop = threading.Event()
    errors = []

    def ticker():
        try:
            while not stop.is_set():
                for lg in system.loggers:
                    lg.tick(channels, force=True)
        except Exception as e:
            errors.append(repr(e))

    t = threading.Thread(target=ticker)
    t.start()
    try:
        pk = 0
        t_end = time.monotonic() + 1.0
        while time.monotonic() < t_end and not errors:
            # both routing shards, so both loggers write while both tick
            pks = np.arange(pk, pk + 4)
            system.proxy.mutate(coll.info, _insert(pks, rng))
            system.proxy.mutate(coll.info, _delete(pks[:2]))
            pk += 4
    finally:
        stop.set()
        t.join()
    assert not errors, errors
    for ch in channels:
        ts = [e.ts for e in system.broker.read(ch, 0)]
        assert ts == sorted(ts)
        types = {e.type for e in system.broker.read(ch, 0)}
        assert {EntryType.TIME_TICK, EntryType.INSERT, EntryType.DELETE} <= types


def _insert(pks, rng):
    from repro.core.request import InsertRequest

    return InsertRequest({"pk": pks, "vector": rng.standard_normal((len(pks), DIM))
                          .astype(np.float32)})


def _delete(pks):
    from repro.core.request import DeleteRequest

    return DeleteRequest(pks)


# ---------------------------------------------- (d) builds leave the read path
def test_strong_search_is_not_held_by_an_index_build(rng, monkeypatch):
    started = threading.Event()
    orig = IndexNode._try_build

    def slow_build(self, task):
        started.set()
        time.sleep(3.0)
        return orig(self, task)

    monkeypatch.setattr(IndexNode, "_try_build", slow_build)
    system = threaded_system(seal_rows=100_000)
    try:
        coll = system.create_collection("c", dim=DIM)
        coll.create_index("vector", kind="flat")
        x = rng.standard_normal((500, DIM)).astype(np.float32)
        ticket = coll.insert_async({"pk": np.arange(500), "vector": x})
        assert ticket.wait(5.0)
        system.data_coord.flush("c")  # seal: the build thread takes the task
        assert started.wait(5.0)
        t0 = time.monotonic()
        res = coll.search(strong(x[:1]))
        assert time.monotonic() - t0 < 1.0
        assert res.pks[0, 0] == 0
    finally:
        system.stop_threads()


def test_strong_search_is_not_held_by_a_sealed_segment_load(rng, monkeypatch):
    """The sealed copy is read and decoded on the loader: its growing copy
    answers meanwhile, ticks keep flowing, and the other nodes hear of the
    load only once the copy is installed."""
    from repro.core import query_node as qn_mod

    started = threading.Event()
    orig = qn_mod.load_segment

    def slow_load(*a, **kw):
        started.set()
        time.sleep(2.0)
        return orig(*a, **kw)

    monkeypatch.setattr(qn_mod, "load_segment", slow_load)
    system = threaded_system(seal_rows=100_000)
    try:
        coll = system.create_collection("c", dim=DIM)
        coll.create_index("vector", kind="flat")
        x = rng.standard_normal((500, DIM)).astype(np.float32)
        ticket = coll.insert_async({"pk": np.arange(500), "vector": x})
        assert ticket.wait(5.0)
        # The growing copies hold the rows before the seal, as under a
        # steady stream.
        assert coll.search(strong(x[:1])).pks[0, 0] == 0
        system.broker.take_tick_gaps()
        system.data_coord.flush("c")
        assert started.wait(5.0)
        t0 = time.monotonic()
        res = coll.search(strong(x[:1]))
        assert time.monotonic() - t0 < 1.0
        assert res.pks[0, 0] == 0
        assert not any(qn.sealed for qn in system.query_nodes.values())
        deadline = time.monotonic() + 10.0
        while not any(qn.sealed for qn in system.query_nodes.values()):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert max(system.broker.take_tick_gaps().values()) < 1_000
        (sid, handle), *_ = [(key[1], h) for qn in system.query_nodes.values()
                             for key, h in list(qn.sealed.items())]
        (loaded,) = [e for e in system.broker.read("coord", 0)
                     if e.payload.get("msg") == "segment_loaded"
                     and e.payload["segment_id"] == sid]
        assert loaded.ts > handle.loaded_ts
        assert coll.search(strong(x[:1])).pks[0, 0] == 0
    finally:
        system.stop_threads()


def test_search_waits_for_a_survivor_loading_a_lost_copy(rng, monkeypatch):
    """A lost node's sealed copies are read again on a survivor's loader,
    which takes longer than the failover's pump rounds: the search waits
    for the load instead of failing with no live replica."""
    from repro.core import query_node as qn_mod

    system = threaded_system(seal_rows=100_000)
    try:
        coll = system.create_collection("c", dim=DIM)
        coll.create_index("vector", kind="flat")
        x = rng.standard_normal((500, DIM)).astype(np.float32)
        assert coll.insert_async({"pk": np.arange(500), "vector": x}).wait(5.0)
        assert coll.search(strong(x[:1])).pks[0, 0] == 0
        system.data_coord.flush("c")
        deadline = time.monotonic() + 10.0
        while sum(len(qn.sealed) for qn in system.query_nodes.values()) < 2:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        orig = qn_mod.load_segment

        def slow_load(*a, **kw):
            time.sleep(1.5)
            return orig(*a, **kw)

        monkeypatch.setattr(qn_mod, "load_segment", slow_load)
        victim = next(n for n, qn in system.query_nodes.items() if qn.sealed)
        system.kill_query_node(victim)
        res = coll.search(strong(x[:3]))
        np.testing.assert_array_equal(res.pks[:, 0], [0, 1, 2])
    finally:
        system.stop_threads()


# ------------------------------------ (e) a STRONG read never returns stale
def test_threaded_wait_raises_when_ticks_stop(rng, monkeypatch):
    monkeypatch.setattr(manu_mod, "THREADED_WAIT_S", 0.5)
    system = threaded_system()
    try:
        coll = system.create_collection("c", dim=DIM)
        coll.insert_async({"pk": np.arange(10),
                           "vector": rng.standard_normal((10, DIM)).astype(np.float32)})
        coll.search(strong(np.zeros((1, DIM), np.float32)))  # ticks flow
        for lg in system.loggers:
            system.kill_logger(lg.logger_id)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError, match="time-tick"):
            coll.search(strong(np.zeros((1, DIM), np.float32)))
        assert time.monotonic() - t0 < 3.0
    finally:
        system.stop_threads()


def test_a_dead_background_thread_fails_the_next_wait(rng, monkeypatch):
    system = threaded_system()
    try:
        coll = system.create_collection("c", dim=DIM)
        coll.search(strong(np.zeros((1, DIM), np.float32)))

        def broken():
            raise ValueError("coordinator fault")

        monkeypatch.setattr(system.index_coord, "step", broken)
        deadline = time.monotonic() + 5.0
        while system._threads[0].is_alive() and time.monotonic() < deadline:
            time.sleep(0.01)
        with pytest.raises(RuntimeError, match="pump") as info:
            coll.search(strong(np.zeros((1, DIM), np.float32)))
        assert isinstance(info.value.__cause__, ValueError)
    finally:
        system.stop_threads()


# --------------------------------------------- (f) start_threads is idempotent
def test_second_start_threads_starts_nothing():
    system = threaded_system()
    try:
        before = threading.active_count()
        system.start_threads()
        assert threading.active_count() == before
    finally:
        system.stop_threads()


# ------------------------------------- (g) background work and ticks are traced
def test_background_steps_are_counted_and_annotated(rng, tmp_path):
    import glob

    import jax

    system = ManuSystem(ManuConfig(num_query_nodes=1, seal_rows=300, slice_rows=64))
    coll = system.create_collection("c", dim=DIM)
    coll.create_index("vector", kind="ivf_flat", params={"nlist": 4, "nprobe": 4})
    with jax.profiler.trace(str(tmp_path)):
        # Growing rows first (full slices get temporary indexes), then a
        # flush: seals, index builds and the hand-off of the sealed copies.
        for n in (200, 500):
            coll.insert({"vector": rng.standard_normal((n, DIM)).astype(np.float32)})
            system.run_until_idle()
        coll.flush()
        system.run_until_idle()
    steps = ("seal", "index_build", "slice_build", "handoff")
    for step in steps:
        labels = {"step": step}
        assert system.telemetry.counter_value("background_steps_total", labels) >= 1
        assert system.telemetry.counter_value("background_us_total", labels) > 0
    assert system.telemetry.counter_value("index_builds_total") >= 2
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    names = {
        ev.name
        for plane in jax.profiler.ProfileData.from_file(path).planes
        for line in plane.lines
        for ev in line.events
    }
    assert {"manu." + step for step in steps} <= names


def test_plan_span_counts_growing_rows(rng):
    system = ManuSystem(ManuConfig(num_query_nodes=2, seal_rows=300))
    coll = system.create_collection("c", dim=DIM)
    coll.insert({"vector": rng.standard_normal((600, DIM)).astype(np.float32)})
    coll.flush()
    coll.insert({"vector": rng.standard_normal((130, DIM)).astype(np.float32)})
    res = coll.search(SearchRequest.single(
        rng.standard_normal((1, DIM)).astype(np.float32), k=5,
        staleness_ms=0.0, trace=True))
    plans = res.trace.spans_named("plan_search")
    assert len(plans) == 2
    assert sum(sp.growing_rows for sp in plans) == 130


def test_tick_gaps_are_kept_per_dml_channel_and_taken_per_window():
    broker = LogBroker()
    for ch in ("dml/c/0", "dml/c/1", "coord"):
        broker.create_channel(ch)
    ts = iter(range(1, 100))

    def tick(ch):
        broker.publish(ch, LogEntry(ts=next(ts), type=EntryType.TIME_TICK, payload={}))

    tick("dml/c/0")
    tick("coord")
    time.sleep(0.12)
    tick("dml/c/0")
    tick("dml/c/0")
    gaps = broker.take_tick_gaps()
    assert set(gaps) == {"dml/c/0"}  # a channel never ticked has no gap yet
    assert 120 <= gaps["dml/c/0"] < 1_000
    # The next window starts afresh: only the gap still open counts.
    time.sleep(0.03)
    assert 30 <= broker.take_tick_gaps()["dml/c/0"] < 120


# ------------------------- the growing path at a deployment's segment size
def test_brute_tail_holds_only_the_rows_no_slice_index_covers(rng):
    node = QueryNode("qn", LogBroker(), MemoryObjectStore(), slice_rows=64)
    seg = Segment(7, "c", 0, DIM, slice_rows=64)
    n = 64 * 5 + 30
    seg.append(np.arange(n), rng.standard_normal((n, DIM)).astype(np.float32),
               np.arange(1, n + 1, dtype=np.int64))
    node.growing[("c", 7)] = GrowingState(seg)
    node._build_slice_indexes()

    plan = node.plan_search("c", 10_000)

    assert len(plan.growing_slice) == 5
    (tail,) = plan.brute_tail
    # The scan reads the 30 rows after the last full slice, not all 350.
    assert len(tail.vectors) == len(tail.pks) == len(tail.mask) == 30
    np.testing.assert_array_equal(tail.pks, np.arange(320, n))


def test_slice_indexes_probe_on_the_host(rng, monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    system = ManuSystem(ManuConfig(num_query_nodes=1, num_shards=1,
                                   seal_rows=100_000, slice_rows=256))
    coll = system.create_collection("c", dim=DIM)
    coll.create_index("vector", kind="ivf_flat", params={"nlist": 8, "nprobe": 8})
    coll.insert({"vector": rng.standard_normal((600, DIM)).astype(np.float32)})
    coll.flush()
    coll.insert({"vector": rng.standard_normal((3 * 256, DIM)).astype(np.float32)})
    res = coll.search(SearchRequest.single(
        rng.standard_normal((1, DIM)).astype(np.float32), k=5,
        staleness_ms=0.0, trace=True))
    (slices,) = res.trace.spans_named("scan_growing_slice")
    (indexed,) = res.trace.spans_named("scan_indexed")
    # Three slice indexes answer without a kernel call; the sealed index
    # probes its centroids on the device.
    assert slices.rows_scanned == 3 * 256
    assert not [s for s in slices.children if s.name.startswith("kernel_")]
    assert [s.name for s in indexed.children] == ["kernel_l2_topk"]


@pytest.mark.parametrize("kernel", ["topk_scan", "merge_topk"])
def test_a_new_length_within_a_tile_compiles_nothing(rng, monkeypatch, kernel):
    """A growing tail's row count and a node's pool width change with every
    few inserts; only the padded shapes may reach the device."""
    import jax

    from repro.kernels import ops

    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    compiles = []

    def listen(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    def call(n):
        if kernel == "topk_scan":
            x = rng.standard_normal((n, DIM)).astype(np.float32)
            ops.topk_scan(x[:1], x, 5, valid=np.ones(n, bool))
        else:
            ops.merge_topk(rng.standard_normal((1, n)).astype(np.float32),
                           np.arange(n)[None, :], 5)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        first, second = (300, 301) if kernel == "topk_scan" else (600, 700)
        call(first)  # compiles what this padded shape needs
        before = len(compiles)
        call(second)  # same padded shape: nothing new
        assert len(compiles) == before
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def test_a_plan_pinned_before_a_load_serves_the_new_sealed_copy(rng):
    """A request pinned before the coordinator placed a freshly sealed
    segment leaves it out of every node's scope; the node that loaded it
    (dropping its growing copy) serves it anyway, and only then."""
    system = ManuSystem(ManuConfig(num_query_nodes=1, num_shards=1,
                                   seal_rows=100_000))
    coll = system.create_collection("c", dim=DIM)
    x = rng.standard_normal((300, DIM)).astype(np.float32)
    coll.insert({"pk": np.arange(300), "vector": x})
    system.run_until_idle()
    pinned = system.tso.next()  # a request pinned while the rows grow
    coll.flush()
    system.run_until_idle()
    (node,) = system.query_nodes.values()
    ((key, handle),) = node.sealed.items()
    assert not node.growing and handle.loaded_ts > pinned

    stale = node.plan_search("c", pinned, segments=())
    assert [u.segment_id for u in stale.units()] == [key[1]]
    fresh = node.plan_search("c", system.tso.next(), segments=())
    assert not fresh.units()  # a plan made after the load knows its owner
