"""Index nodes: asynchronous index builders (paper §3.5).

Index nodes receive build tasks from the index coordinator over the
coordination channel, claim them with a meta-store CAS (so concurrent index
nodes never duplicate work), read **only the vector column** of the binlog
(no read amplification), build the index, persist it to the object store,
and announce ``index_built``.
"""

from __future__ import annotations

from ..index.base import IndexSpec
from ..index.registry import create_index
from .binlog import index_key, read_binlog_column
from .collection import Metric
from .log import COORD_CHANNEL, EntryType, LogBroker, LogEntry, Subscription
from .meta_store import MetaStore
from .object_store import ObjectStore
from .telemetry import MetricsRegistry, background_step
from .timestamp import TSO


class IndexNode:
    def __init__(
        self,
        node_id: str,
        broker: LogBroker,
        store: ObjectStore,
        meta: MetaStore,
        tso: TSO,
        metrics: MetricsRegistry | None = None,
    ):
        self.node_id = node_id
        self.broker = broker
        self.store = store
        self.meta = meta
        self.tso = tso
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.sub = Subscription(broker, COORD_CHANNEL)
        self.alive = True
        self.builds_completed = 0
        self.busy_fraction = 0.0  # bookkeeping for the idle-shutdown policy

    def step(self) -> bool:
        if not self.alive:
            return False
        progress = False
        for entry in self.sub.poll():
            if entry.type is not EntryType.COORD:
                continue
            p = entry.payload
            if p.get("msg") != "index_build_task":
                continue
            progress |= self._try_build(p)
        return progress

    def _try_build(self, task: dict) -> bool:
        coll = task["collection"]
        sid = task["segment_id"]
        kind = task["index_kind"]
        # Per-field builds: the task names the schema field and the binlog
        # column backing it (the first vector field is stored as "vector").
        field = task.get("field", "vector")
        column = task.get("column", field)
        # Replay safety: a task re-read from the coord channel after a crash
        # may name a segment GC already reclaimed — nothing to build.
        if not self.store.exists(f"binlog/{coll}/{sid}/meta"):
            return False
        claim_key = f"index_claim/{coll}/{sid}/{field}/{kind}"
        # CAS claim: only one index node builds a given task.
        if not self.meta.cas(claim_key, None, {"owner": self.node_id}):
            return False

        import time as _t

        t0 = _t.perf_counter()
        try:
            with background_step(self.metrics, "index_build"):
                vectors = read_binlog_column(self.store, coll, sid, column)
                spec = IndexSpec(
                    kind=kind,
                    metric=Metric(task.get("metric", "l2")),
                    params=task.get("params") or {},
                    field=field,
                )
                index = create_index(spec)
                index.build(vectors)
                key = index_key(coll, sid, field, kind)
                self.store.put(key, index.save())
        except Exception:
            # Release the claim so the task stays takeable.  (A simulated
            # Crash is a BaseException: the claim leaks, as with a real
            # kill — IndexCoordinator.recover_state clears it.)
            self.meta.delete(claim_key)
            raise
        self.builds_completed += 1
        self.metrics.observe(
            "index_build_us", (_t.perf_counter() - t0) * 1e6,
            labels={"kind": kind},
        )
        self.metrics.inc("index_builds_total")

        with self.broker.order_lock:
            self.broker.publish(
                COORD_CHANNEL,
                LogEntry(
                    ts=self.tso.next(),
                    type=EntryType.COORD,
                    payload={
                        "msg": "index_built",
                        "collection": coll,
                        "segment_id": sid,
                        "field": field,
                        "column": column,
                        "index_kind": kind,
                        "index_key": key,
                        "built_by": self.node_id,
                    },
                ),
            )
        return True
