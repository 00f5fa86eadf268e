"""Query nodes: the search workers (paper §3.6).

A query node gets data from three sources:

* **WAL** — it subscribes to DML channels and keeps its own growing
  segments so freshly inserted rows are searchable within one time-tick;
  full slices get a light-weight temporary index (IVF-FLAT), the tail is
  brute-force scanned.
* **index files** — sealed segments' indexes, loaded from the object store
  when the query coordinator assigns the segment to this node.
* **binlog** — sealed segment columns, loaded on assignment/failover.

Searches run under MVCC: a query pinned at ``ts`` sees exactly the rows
with LSN <= ts that are not deleted as of ts.  Node-level results are the
node-wise top-k of the two-phase reduce; the proxy performs the global
merge and pk-dedup.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ..index.base import IndexSpec, VectorIndex, normalize_if_cosine
from ..index.flat import FlatIndex
from ..index.ivf import IVFFlatIndex
from ..index.registry import create_index
from .binlog import load_segment
from .collection import Metric
from .consistency import GuaranteeTs
from .log import EntryType, LogBroker, LogEntry, Subscription, shard_of_channel
from .object_store import ObjectStore
from .request import PRIMARY_VECTOR_COLUMN, AnnsQuery, NodeSearchRequest
from .segment import DEFAULT_PARTITION, Segment, add_tombstone, flatten_tombstones
from .telemetry import UNTRACED, MetricsRegistry

TEMP_INDEX_SLICE_ROWS = 2_048  # scaled-down default of the paper's 10k

# Selectivity-adaptive filtered-search thresholds.  ``n_comb`` is the
# surviving row count (visible AND matching), ``n_vis`` the visible count.
# brute (gather survivors, per-unit scan) wins while the survivor set is a
# small fraction of the segment — its per-row cost is higher (gather copy +
# unfused dispatch) but it touches only the survivors; the crossover vs the
# fused masked full scan sits around 1/3, so 0.25 leaves margin.  post
# (visibility-only scan at inflated k, cut after) is only ever chosen for
# index-backed units: a loose filter barely inflates k there and the index
# keeps its recall, while a tight mask fed INTO an ANN index starves its
# candidate pools.  Everything else pre-filters: bitmap ∧ visibility as the
# scan's validity mask.
FILTER_BRUTE_FRAC = 0.25
FILTER_BRUTE_MIN_ROWS = 64
FILTER_POST_FRAC = 0.5


def choose_filter_strategy(
    override: str | None, n_vis: int, n_comb: int, k: int, has_index: bool
) -> str:
    if override is not None:
        return override
    if n_comb <= max(2 * k, FILTER_BRUTE_MIN_ROWS) or n_comb <= FILTER_BRUTE_FRAC * n_vis:
        return "brute"
    if has_index and n_comb >= FILTER_POST_FRAC * n_vis:
        return "post"
    return "pre"


class StalePlanError(Exception):
    """The dispatch plan references segments this node can no longer serve
    at the request timestamp — a compaction swap or placement change landed
    between the proxy's planning and this scan.  The proxy catches this and
    re-plans from fresh placement (never a node failure)."""


def _seg_column(seg: Segment, column: str) -> np.ndarray | None:
    """A segment's stored column for one vector field (None if absent)."""
    if column == PRIMARY_VECTOR_COLUMN:
        return seg.vectors()
    if column in seg.extra_fields:
        return seg.extra(column)
    return None


def _scalar_columns(seg: Segment) -> dict[str, np.ndarray]:
    """The segment's filterable columns (pk + 1-D extras) for row-wise
    FilterExpr evaluation; vector extras are not filterable."""
    cols: dict[str, np.ndarray] = {"pk": seg.pks()}
    for f in seg.extra_fields:
        arr = np.asarray(seg.extra(f))
        if arr.ndim == 1:
            cols[f] = arr
    return cols


@dataclass
class SealedHandle:
    segment: Segment
    index: VectorIndex | None = None  # index on the primary vector column
    index_kind: str | None = None
    # Segment-map epoch gating (compaction hot-swap): a handle serves the
    # MVCC window [visible_from_ts, retired_at_ts).  Freshly sealed segments
    # cover everything; a compacted replacement starts at its compact_ts and
    # the sources it replaces end there, so a query pinned before the swap
    # keeps reading the old version until the retention horizon releases it.
    visible_from_ts: int = 0
    retired_at_ts: int | None = None
    # Indexes on additional vector columns (multi-vector schemas), keyed by
    # segment column name.
    extra_indexes: dict[str, VectorIndex] = field(default_factory=dict)
    extra_index_kinds: dict[str, str] = field(default_factory=dict)
    # Attribute indexes over scalar columns (pk + 1-D extras), loaded from
    # the segment's attr satellites (or rebuilt locally when absent); the
    # filtered-search planner resolves FilterExpr bitmaps through these.
    attr_indexes: dict[str, object] = field(default_factory=dict)

    def covers_ts(self, ts: int) -> bool:
        if ts < self.visible_from_ts:
            return False
        return self.retired_at_ts is None or ts < self.retired_at_ts

    def index_for(self, column: str) -> VectorIndex | None:
        if column == PRIMARY_VECTOR_COLUMN:
            return self.index
        return self.extra_indexes.get(column)

    def set_index(self, column: str, index: VectorIndex, kind: str) -> None:
        if column == PRIMARY_VECTOR_COLUMN:
            self.index, self.index_kind = index, kind
        else:
            self.extra_indexes[column] = index
            self.extra_index_kinds[column] = kind


@dataclass
class ScanUnit:
    """One plannable piece of search work: rows, masks, and how to run them.

    ``index`` set -> the unit executes through that index; otherwise
    ``vectors`` holds the rows for a brute-force scan.  ``pks`` maps the
    unit's local row indices back to primary keys.
    """

    segment_id: int
    pks: np.ndarray
    mask: np.ndarray  # visibility & delta-delete & attribute filter
    index: VectorIndex | None = None
    vectors: np.ndarray | None = None
    # Post-filter strategy state: ``post_mask`` is the attribute-filter
    # bitmap applied AFTER the scan (``mask`` then carries visibility only)
    # and ``k_extra`` is this unit's worst-case interloper count — rows
    # that pass visibility but fail the filter — so scanning top
    # (k + k_extra) provably contains the filtered top-k.
    post_mask: np.ndarray | None = None
    k_extra: int = 0


@dataclass
class SearchPlan:
    """Planner output: candidate units grouped by execution class.

    The two brute classes run as ONE fused scan each
    (``ops.topk_scan_segmented``); index-backed classes dispatch per
    unit since every index owns its own structure.
    """

    indexed: list[ScanUnit] = field(default_factory=list)  # sealed, index loaded
    brute_sealed: list[ScanUnit] = field(default_factory=list)  # sealed, no index
    growing_slice: list[ScanUnit] = field(default_factory=list)  # temp slice index
    brute_tail: list[ScanUnit] = field(default_factory=list)  # growing tail rows
    # Filtered-search strategy classes (empty without a filter):
    # post-filter units scan with visibility-only masks at an inflated k
    # and cut failing candidates afterwards; brute_filtered units gather
    # the surviving rows and scan just those, one dispatch per unit.
    post_indexed: list[ScanUnit] = field(default_factory=list)
    post_brute: list[ScanUnit] = field(default_factory=list)
    brute_filtered: list[ScanUnit] = field(default_factory=list)
    # Per-unit planning report for observability: dicts with segment_id,
    # strategy, estimated and actual selectivity.
    filter_info: list = field(default_factory=list)

    def units(self) -> "list[ScanUnit]":
        return (
            self.indexed + self.brute_sealed + self.growing_slice
            + self.brute_tail + self.post_indexed + self.post_brute
            + self.brute_filtered
        )


def _map_pks(idx: np.ndarray, pks: np.ndarray) -> np.ndarray:
    """Local row indices -> primary keys; -1 slots pass through."""
    pks = np.asarray(pks)
    return np.where(idx >= 0, pks[np.clip(idx, 0, len(pks) - 1)], -1)


@dataclass
class GrowingState:
    segment: Segment
    slice_index_built: dict[int, VectorIndex] = field(default_factory=dict)


class QueryNode:
    def __init__(
        self,
        node_id: str,
        broker: LogBroker,
        store: ObjectStore,
        tso=None,
        slice_rows: int = TEMP_INDEX_SLICE_ROWS,
        metrics: MetricsRegistry | None = None,
    ):
        self.node_id = node_id
        self.broker = broker
        self.store = store
        self.tso = tso
        self.slice_rows = slice_rows
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.subscriptions: dict[str, Subscription] = {}
        self.coord_sub = Subscription(broker, "coord") if broker.has_channel("coord") else None
        # LSN-keyed dedup: highest applied position per channel ("coord"
        # included).  The broker is at-least-once — duplicate delivery is an
        # injectable fault — so re-delivered entries must be no-ops.
        self._applied_pos: dict[str, int] = {}
        self.sealed: dict[tuple[str, int], SealedHandle] = {}
        self.growing: dict[tuple[str, int], GrowingState] = {}
        # Delta deletes for rows living in sealed segments:
        # coll -> pk -> delete ts (or a sorted ts list when the same pk was
        # deleted/upserted more than once).  Tombstones are row-ts aware: a
        # (pk, dts) pair kills only versions with row_ts < dts, so the
        # insert half of an upsert at the same LSN survives its own delete.
        self.delta_deletes: dict[str, dict[object, object]] = {}
        # Partitions dropped while this node serves the collection: WAL
        # replays must not resurrect their rows into growing segments.
        self.dropped_partitions: set[tuple[str, str]] = set()
        # Tombstones folded into compacted segments, pending removal from
        # ``delta_deletes`` once the retention horizon passes (the old
        # segment versions still need them until then).
        self._pending_prunes: list[dict] = []
        self.alive = True
        self.search_count = 0
        # Hedge-aware accounting: hedged duplicates are booked separately so
        # least-loaded replica picks (and the admin API) see primary load
        # only — a straggler's bail-out copy is not organic demand.
        self.searches_primary = 0
        self.searches_hedged = 0
        self.inflight = 0  # concurrent search_request count (any kind)
        self.inflight_primary = 0  # dispatch-load key used by the picker
        self.inject_delay_s = 0.0  # straggler fault injection (tests/benches)

    # --------------------------------------------------------- subscriptions
    def subscribe(self, channel: str, from_position: int = 0) -> None:
        if channel not in self.subscriptions:
            self.subscriptions[channel] = Subscription(self.broker, channel, from_position)
            # A (re-)subscription is an intentional replay: accept entries
            # from its start position even if we consumed further before.
            self._applied_pos[channel] = from_position - 1

    def unsubscribe(self, channel: str) -> None:
        self.subscriptions.pop(channel, None)
        self._applied_pos.pop(channel, None)

    def watermark(self, collection: str) -> int:
        """Min last-time-tick over this node's channels for the collection."""
        marks = [
            sub.last_tick_seen
            for ch, sub in self.subscriptions.items()
            if ch.startswith(f"dml/{collection}/")
        ]
        return min(marks) if marks else 0

    # ----------------------------------------------------------------- step
    def step(self) -> bool:
        if not self.alive:
            return False
        progress = False
        if self.coord_sub is not None:
            watermark = self._applied_pos.get("coord", -1)
            for entry in self.coord_sub.poll():
                if entry.position <= watermark:
                    self.metrics.inc("log_dedup_skipped_total",
                                     labels={"node": self.node_id})
                    continue
                progress |= self._handle_coord(entry)
                watermark = entry.position
            self._applied_pos["coord"] = watermark
        for sub in list(self.subscriptions.values()):
            watermark = self._applied_pos.get(sub.channel, -1)
            for entry in sub.poll():
                if entry.position <= watermark:
                    self.metrics.inc("log_dedup_skipped_total",
                                     labels={"node": self.node_id})
                    continue
                progress |= self._consume(entry)
                watermark = entry.position
            if sub.channel in self.subscriptions:
                self._applied_pos[sub.channel] = watermark
        progress |= self._build_slice_indexes()
        return progress

    def _handle_coord(self, entry: LogEntry) -> bool:
        if entry.type is not EntryType.COORD:
            return False
        p = entry.payload
        msg = p.get("msg")
        if msg == "segment_loaded":
            # Another node owns the sealed copy now: hand off our growing rows.
            if p.get("node_id") != self.node_id:
                self.drop_growing(p["collection"], p["segment_id"])
            return True
        if msg == "tombstones":
            # Broadcast mirror of a delete/upsert-delete-half (same LSN as
            # the per-shard DML entries): placement is not shard-affine, so
            # a node can serve a sealed segment of a shard whose DML channel
            # it does not own — this is how it still learns about the kills.
            # add_tombstone/Segment.delete dedup (pk, ts), so nodes that DO
            # own the channel apply the pair of deliveries idempotently.
            self._apply_delete(p["collection"], p["pk"], entry.ts)
            return True
        if msg == "tombstones_folded":
            # Broadcast: a compaction folded these tombstones into a rewritten
            # segment.  Every node remembers them for pruning at the horizon.
            self._pending_prunes.append(
                {
                    "collection": p["collection"],
                    "folded_pks": np.asarray(p["folded_pks"]),
                    "compact_ts": p["compact_ts"],
                }
            )
            return True
        if msg == "retention_advance":
            return self.apply_retention(p["horizon_ts"], p.get("collection"))
        if msg == "partition_dropped":
            # Broadcast: drop the partition's segments everywhere at once
            # (sealed copies by id, growing copies by tag) and remember the
            # drop so later WAL replays don't resurrect its rows.
            coll, part = p["collection"], p["partition"]
            self.dropped_partitions.add((coll, part))
            for sid in p.get("segment_ids", ()):
                self.sealed.pop((coll, sid), None)
                self.growing.pop((coll, sid), None)
            for key, gs in list(self.growing.items()):
                if key[0] == coll and gs.segment.partition == part:
                    del self.growing[key]
            for key, handle in list(self.sealed.items()):
                if key[0] == coll and handle.segment.partition == part:
                    del self.sealed[key]
            return True
        if p.get("node_id") != self.node_id:
            return False
        if msg == "load_segment":
            self.load_sealed(
                p["collection"], p["segment_id"],
                visible_from_ts=p.get("visible_from_ts", 0),
            )
            if self.tso is not None:
                self.broker.publish(
                    "coord",
                    LogEntry(
                        ts=self.tso.next(),
                        type=EntryType.COORD,
                        payload={
                            "msg": "segment_loaded",
                            "node_id": self.node_id,
                            "collection": p["collection"],
                            "segment_id": p["segment_id"],
                        },
                    ),
                )
            return True
        if msg == "load_index":
            self.load_index(
                p["collection"], p["segment_id"], p["index_kind"], p["index_key"],
                column=p.get("column", PRIMARY_VECTOR_COLUMN),
            )
            return True
        if msg == "release_segment":
            self.release_segment(p["collection"], p["segment_id"])
            return True
        if msg == "retire_segment":
            self.retire_segment(
                p["collection"], p["segment_id"], p["retired_at_ts"]
            )
            return True
        if msg == "subscribe_channel":
            self.subscribe(p["channel"], p.get("from_position", 0))
            return True
        if msg == "unsubscribe_channel":
            self.unsubscribe(p["channel"])
            return True
        return False

    def _apply_delete(self, collection: str, pks, ts: int) -> None:
        """Record tombstones for sealed rows and growing copies alike."""
        dd = self.delta_deletes.setdefault(collection, {})
        for pk in np.asarray(pks).tolist():
            add_tombstone(dd, pk, ts)
        for (c, _sid), gs in self.growing.items():
            if c == collection:
                gs.segment.delete(pks, ts)

    def _consume(self, entry: LogEntry) -> bool:
        if entry.type in (EntryType.INSERT, EntryType.UPSERT):
            p = entry.payload
            if entry.type is EntryType.UPSERT:
                # Delete half of the atomic record: older versions of these
                # pks die at this LSN; the insert half below lands at the
                # SAME LSN, so visibility flips in one step.
                self._apply_delete(p["collection"], p["pk"], entry.ts)
            key = (p["collection"], p["segment_id"])
            partition = p.get("partition", DEFAULT_PARTITION)
            if (p["collection"], partition) in self.dropped_partitions:
                return True  # replay of a dropped partition: insert half void
            if key in self.sealed:
                # already have the sealed (authoritative) copy of the rows;
                # the upsert's delete half above still applies
                return entry.type is EntryType.UPSERT
            gs = self.growing.get(key)
            if gs is None:
                extra_fields = tuple(sorted(p.get("extras", {})))
                seg = Segment(
                    p["segment_id"], p["collection"], p["shard"],
                    p["vector"].shape[1], slice_rows=self.slice_rows,
                    extra_fields=extra_fields, partition=partition,
                )
                gs = GrowingState(seg)
                self.growing[key] = gs
            n = len(p["pk"])
            gs.segment.append(
                p["pk"], p["vector"], np.full(n, entry.ts, np.int64), p.get("extras")
            )
            return True
        if entry.type is EntryType.DELETE:
            p = entry.payload
            self._apply_delete(p["collection"], p["pk"], entry.ts)
            return True
        return False

    def _build_slice_indexes(self) -> bool:
        """Temporary IVF-FLAT per full slice of growing segments (paper §3.6).

        Built L2 (the WAL carries no collection metric); the planner only
        uses a temp index whose metric matches the request and leaves
        mismatched slices to the brute tail, so IP/cosine growing reads
        stay exact."""
        progress = False
        for gs in self.growing.values():
            for s in gs.segment.full_slices():
                if s in gs.slice_index_built:
                    continue
                lo, hi = gs.segment.slice_bounds(s)
                idx = IVFFlatIndex(metric=Metric.L2, nlist=16, nprobe=4)
                idx.build(gs.segment.vectors()[lo:hi])
                gs.slice_index_built[s] = idx
                progress = True
        return progress

    # ---------------------------------------------------------- assignments
    def load_sealed(
        self, collection: str, segment_id: int, visible_from_ts: int = 0
    ) -> None:
        key = (collection, segment_id)
        if key in self.sealed:
            return
        seg = load_segment(self.store, collection, segment_id)
        self.sealed[key] = SealedHandle(
            seg,
            visible_from_ts=visible_from_ts,
            attr_indexes=self._attr_indexes_for(seg),
        )
        # Hand-off: drop our growing copy of the same segment.
        self.growing.pop(key, None)

    def _attr_indexes_for(self, seg: Segment) -> dict[str, object]:
        """Attribute indexes for a sealed segment's scalar columns.

        Satellites are loaded from the object store; a missing satellite or
        one whose row count disagrees with the loaded segment (a stale
        bitmap must never serve a filtered read) is rebuilt locally from
        the columns — query nodes never write the store, so the healed
        copy stays node-local until recovery repairs the satellite.
        """
        from ..index.attribute import build_attribute_index
        from .binlog import load_attr_satellites

        columns: dict[str, np.ndarray] = {"pk": seg.pks()}
        for f in seg.extra_fields:
            arr = np.asarray(seg.extra(f))
            if arr.ndim == 1:
                columns[f] = arr
        loaded = load_attr_satellites(
            self.store, seg.collection, seg.segment_id, columns
        )
        out: dict[str, object] = {}
        for f, col in columns.items():
            idx = loaded.get(f)
            if idx is None or idx.n != seg.num_rows:
                idx = build_attribute_index(col)
                self.metrics.inc(
                    "query_node_attr_local_builds_total",
                    labels={"node": self.node_id},
                )
            out[f] = idx
        return out

    def load_index(
        self,
        collection: str,
        segment_id: int,
        kind: str,
        index_key: str,
        column: str = PRIMARY_VECTOR_COLUMN,
    ) -> None:
        handle = self.sealed.get((collection, segment_id))
        if handle is None:
            self.load_sealed(collection, segment_id)
            handle = self.sealed[(collection, segment_id)]
        index = VectorIndex.load(self.store.get(index_key))
        handle.set_index(column, index, kind)

    def release_segment(self, collection: str, segment_id: int) -> None:
        self.sealed.pop((collection, segment_id), None)
        self.growing.pop((collection, segment_id), None)

    def retire_segment(
        self, collection: str, segment_id: int, retired_at_ts: int
    ) -> None:
        """MVCC retirement: the segment was replaced by a compacted rewrite.

        The handle keeps serving queries pinned before ``retired_at_ts``
        until ``apply_retention`` drops it at the retention horizon.
        """
        handle = self.sealed.get((collection, segment_id))
        if handle is not None and handle.retired_at_ts is None:
            handle.retired_at_ts = retired_at_ts
        self.growing.pop((collection, segment_id), None)

    def apply_retention(
        self, horizon_ts: int, collection: str | None = None
    ) -> bool:
        """Drop retired segment versions and fold-pruned tombstones whose
        compaction timestamp fell behind the retention horizon
        (``collection=None`` applies to every collection)."""
        changed = False
        for key, handle in list(self.sealed.items()):
            if collection is not None and key[0] != collection:
                continue
            if handle.retired_at_ts is not None and handle.retired_at_ts <= horizon_ts:
                del self.sealed[key]
                changed = True
        still_pending: list[dict] = []
        for prune in self._pending_prunes:
            if (collection is not None and prune["collection"] != collection) or (
                prune["compact_ts"] > horizon_ts
            ):
                still_pending.append(prune)
                continue
            from .compaction import prune_folded

            pruned = prune_folded(
                self.delta_deletes.get(prune["collection"]) or {},
                prune["folded_pks"],
                prune["compact_ts"],
            )
            if pruned is not None:
                self.delta_deletes[prune["collection"]] = pruned
                changed = True
        self._pending_prunes = still_pending
        return changed

    def drop_growing(self, collection: str, segment_id: int) -> None:
        """Hand-off after another node loaded the sealed copy."""
        self.growing.pop((collection, segment_id), None)

    def held_segments(self, collection: str) -> list[int]:
        return sorted(sid for (c, sid) in self.sealed if c == collection)

    def memory_rows(self, collection: str | None = None) -> int:
        rows = sum(
            h.segment.num_rows
            for (c, _sid), h in self.sealed.items()
            if collection is None or c == collection
        )
        rows += sum(
            g.segment.num_rows
            for (c, _sid), g in self.growing.items()
            if collection is None or c == collection
        )
        return rows

    def segment_rows(self, collection: str) -> "dict[tuple[str, int, bool], int]":
        """(collection, segment_id, is_sealed) -> live row count; used by
        the per-collection entity count (replicated segments dedup upstream)."""
        out: dict[tuple[str, int, bool], int] = {}
        for (c, sid), h in self.sealed.items():
            if c == collection and h.retired_at_ts is None:
                out[(c, sid, True)] = h.segment.num_rows
        for (c, sid), g in self.growing.items():
            if c == collection:
                out[(c, sid, False)] = g.segment.num_rows
        return out

    # --------------------------------------------------------------- search
    def _request_doomed_pks(
        self, collection: str, ts: int
    ) -> "tuple[np.ndarray, np.ndarray] | None":
        """Materialize the delta-delete tombstone set ONCE per request.

        Returns ``(sorted pks, per-pk effective delete ts at query time)``
        or None.  Every segment then probes it with one vectorized binary
        search (``ops.tombstone_mask``) instead of rebuilding and
        re-sorting the set once per segment per query.  The effective-ts
        half makes the kill row-version aware: rows written at or after
        their pk's delete (upsert insert halves, re-inserts) survive.
        """
        dd = self.delta_deletes.get(collection)
        if not dd:
            return None
        from ..kernels import ops

        pks, dts = flatten_tombstones(dd)
        return ops.eff_tombstones(pks, dts, ts)

    _DOOMED_UNSET = object()  # sentinel: standalone call, derive the set here

    def _visible(
        self,
        collection: str,
        seg: Segment,
        ts: int,
        doomed=_DOOMED_UNSET,
    ) -> np.ndarray:
        from ..kernels import ops

        if doomed is QueryNode._DOOMED_UNSET:
            doomed = self._request_doomed_pks(collection, ts)
        mask = seg.visible_mask(ts)
        if doomed is not None:
            mask &= ~ops.tombstone_mask(
                seg.pks(), seg.timestamps(), doomed[0], doomed[1]
            )
        return mask

    def plan_search(
        self,
        collection: str,
        ts: int,
        filter_masks: "dict[int, np.ndarray] | None" = None,
        column: str = PRIMARY_VECTOR_COLUMN,
        metric: Metric | None = None,
        doomed=_DOOMED_UNSET,
        partitions: "tuple[str, ...] | None" = None,
        segments: "tuple[int, ...] | None" = None,
        shards: "tuple[int, ...] | None" = None,
        filter=None,
        filter_strategy: str | None = None,
        k: int = 10,
    ) -> SearchPlan:
        """Gather every candidate (segment, visibility, filter) unit for a
        request pinned at ``ts`` and group it by execution class.

        ``column`` selects the vector column being searched (multi-vector
        schemas); temporary slice indexes only exist for the primary
        column, so other columns scan growing segments brute-force.  For
        cosine requests pass ``metric`` so brute units take the segments'
        cached row-normalized columns (indexes normalize at build).
        ``doomed`` lets multi-field requests share one materialized
        delta-delete set across sub-requests.  ``partitions`` prunes the
        plan to segments tagged with one of the named partitions BEFORE
        any distance work happens (None = no pruning).  ``segments``
        scopes the *live* sealed scan to a replica-dispatch plan unit
        (None = everything the node holds); retired MVCC versions are
        exempt — they only exist on the nodes that served the pre-swap
        epoch, so pinned queries must always reach them.  ``shards``
        scopes the *growing* scan the same way: only growing segments fed
        by those shards' DML channels enter the plan (None = all, () =
        sealed data only) — a replica-aware dispatch must not serve a
        lagging growing copy of a channel routed to a fresher node.

        ``filter`` is the compiled :class:`FilterExpr`: sealed units
        resolve it through their attribute-index satellites and pick a
        selectivity-adaptive strategy per (segment, filter) unit —
        pre-filter / post-filter / brute (``filter_strategy`` forces one;
        ``k`` feeds the brute threshold and post inflation).  Growing rows
        have no satellites and always pre-filter via row-wise evaluation.
        """
        plan = SearchPlan()
        if doomed is QueryNode._DOOMED_UNSET:
            doomed = self._request_doomed_pks(collection, ts)
        prune = set(partitions) if partitions is not None else None
        scope = set(segments) if segments is not None else None
        unit_cols = metric is Metric.COSINE

        def brute_column(seg: Segment) -> np.ndarray | None:
            raw = _seg_column(seg, column)
            if raw is None:
                return None
            return seg.unit_column(column) if unit_cols else raw

        # ---- sealed segments: indexed or brute ----
        served: set[int] = set()
        for (coll, sid), handle in self.sealed.items():
            if coll != collection:
                continue
            if scope is not None and sid in scope:
                # A scoped live handle serves its unit even when it does
                # not cover ``ts`` (a rewrite pinned after this query: the
                # exempt retired sources carry the rows).  A scoped retired
                # handle only serves queries pinned before its swap.
                if handle.retired_at_ts is None or handle.covers_ts(ts):
                    served.add(sid)
            if not handle.covers_ts(ts):
                continue  # wrong segment-map epoch for this MVCC timestamp
            if (
                scope is not None
                and handle.retired_at_ts is None
                and sid not in scope
            ):
                continue  # another replica owns this plan unit
            seg = handle.segment
            if prune is not None and seg.partition not in prune:
                continue  # partition pruning: skip before any scan work
            if seg.num_rows == 0:
                continue
            mask = self._visible(collection, seg, ts, doomed)
            if filter_masks and sid in filter_masks:
                mask = mask & filter_masks[sid]
            if not mask.any():
                continue
            index = handle.index_for(column)
            if filter is not None:
                self._plan_filtered_unit(
                    plan, sid, seg, handle.attr_indexes, mask, index,
                    filter, filter_strategy, k, brute_column,
                )
                continue
            if index is not None:
                plan.indexed.append(
                    ScanUnit(sid, seg.pks(), mask, index=index)
                )
            else:
                vectors = brute_column(seg)
                if vectors is None:
                    continue  # segment predates the field; nothing to scan
                plan.brute_sealed.append(
                    ScanUnit(sid, seg.pks(), mask, vectors=vectors)
                )
        if scope is not None and scope - served:
            raise StalePlanError(
                f"{self.node_id}: scoped segments {sorted(scope - served)} "
                f"of '{collection}' are not serveable at ts={ts} "
                "(placement changed between plan and scan)"
            )

        # ---- growing segments: temp slice indexes + brute tail ----
        shard_scope = set(shards) if shards is not None else None
        for (coll, sid), gs in self.growing.items():
            if coll != collection:
                continue
            seg = gs.segment
            if shard_scope is not None and seg.shard not in shard_scope:
                continue  # another replica serves this channel's rows
            if prune is not None and seg.partition not in prune:
                continue
            if seg.num_rows == 0:
                continue
            mask = self._visible(collection, seg, ts, doomed)
            if filter_masks and sid in filter_masks:
                mask = mask & filter_masks[sid]
            pks = seg.pks()
            if filter is not None:
                fmask = np.asarray(
                    filter.evaluate(_scalar_columns(seg), seg.num_rows), bool
                )
                n_vis = int(mask.sum())
                mask = mask & fmask
                plan.filter_info.append({
                    "segment_id": sid, "strategy": "pre",
                    "est": float(fmask.mean()) if seg.num_rows else 0.0,
                    "actual": (int(mask.sum()) / n_vis) if n_vis else 0.0,
                })
                self.metrics.inc(
                    "filter_strategy_total", labels={"strategy": "pre"}
                )
            vectors = brute_column(seg)
            if vectors is None:
                continue
            covered = np.zeros(seg.num_rows, dtype=bool)
            if column == PRIMARY_VECTOR_COLUMN:
                for s_idx, temp in gs.slice_index_built.items():
                    if metric is not None and temp.metric is not metric:
                        # metric-mismatched temp index (built L2 off the
                        # WAL): leave the slice in the brute tail so the
                        # request's metric stays exact
                        continue
                    lo, hi = seg.slice_bounds(s_idx)
                    covered[lo:hi] = True
                    if not mask[lo:hi].any():
                        continue
                    plan.growing_slice.append(
                        ScanUnit(sid, pks[lo:hi], mask[lo:hi], index=temp)
                    )
            # tail = rows not covered by any temp index yet
            tail_mask = mask & ~covered
            if tail_mask.any():
                plan.brute_tail.append(
                    ScanUnit(sid, pks, tail_mask, vectors=vectors)
                )
        return plan

    def _plan_filtered_unit(
        self,
        plan: SearchPlan,
        sid: int,
        seg: Segment,
        attr_indexes: dict,
        mask: np.ndarray,
        index: VectorIndex | None,
        fexpr,
        override: str | None,
        k: int,
        brute_column,
    ) -> None:
        """Resolve the filter bitmap for one sealed unit and place it in
        the strategy class the selectivity estimate calls for."""
        from ..kernels import ops

        n = seg.num_rows
        try:
            fmask = fexpr.bitmap(attr_indexes, n)
            est = fexpr.estimate_selectivity(attr_indexes, n)
        except KeyError:
            # A filter field without a satellite (late-added schema field):
            # row-wise fallback keeps semantics identical.
            fmask = np.asarray(fexpr.evaluate(_scalar_columns(seg), n), bool)
            est = float(fmask.mean()) if n else 0.0
        n_vis = int(mask.sum())
        combined = ops.mask_intersect(mask, fmask)
        n_comb = int(combined.sum())
        actual = (n_comb / n_vis) if n_vis else 0.0
        strategy = choose_filter_strategy(
            override, n_vis, n_comb, k, index is not None
        )
        plan.filter_info.append({
            "segment_id": sid, "strategy": strategy,
            "est": est, "actual": actual, "rows": n_comb,
        })
        self.metrics.inc("filter_strategy_total", labels={"strategy": strategy})
        self.metrics.set_gauge(
            "filter_selectivity_est", est,
            labels={"collection": seg.collection, "segment": str(sid)},
        )
        self.metrics.set_gauge(
            "filter_selectivity_actual", actual,
            labels={"collection": seg.collection, "segment": str(sid)},
        )
        if n_comb == 0:
            return
        pks = seg.pks()
        if strategy == "brute":
            vectors = brute_column(seg)
            if vectors is None:
                return
            rows = np.nonzero(combined)[0]
            plan.brute_filtered.append(
                ScanUnit(
                    sid, pks[rows], np.ones(len(rows), dtype=bool),
                    vectors=np.ascontiguousarray(vectors[rows]),
                )
            )
        elif strategy == "post":
            unit = ScanUnit(
                sid, pks, mask, post_mask=fmask, k_extra=n_vis - n_comb
            )
            if index is not None:
                unit.index = index
                plan.post_indexed.append(unit)
            else:
                vectors = brute_column(seg)
                if vectors is None:
                    return
                unit.vectors = vectors
                plan.post_brute.append(unit)
        else:  # pre
            unit = ScanUnit(sid, pks, combined)
            if index is not None:
                unit.index = index
                plan.indexed.append(unit)
            else:
                vectors = brute_column(seg)
                if vectors is None:
                    return
                unit.vectors = vectors
                plan.brute_sealed.append(unit)

    def _execute_plan(
        self,
        plan: SearchPlan,
        queries: np.ndarray,
        k: int,
        metric: Metric,
        trace: tuple | None = None,
    ) -> tuple["list[np.ndarray]", "list[np.ndarray]"]:
        """Run a plan's units and return per-unit top-k candidate pools.

        ``trace`` is the optional ``(TraceContext, parent Span)`` pair: one
        child span per execution-class dispatch, carrying the segment ids
        and live-row count it actually scanned.
        """
        import time as _t

        from ..kernels import ops

        metric_str = "l2" if metric is Metric.L2 else "ip"
        pool_s: list[np.ndarray] = []
        pool_p: list[np.ndarray] = []

        def open_class(cls: str, units):
            """The class's ``scan_<cls>`` span timer, open while the class
            runs so that kernel spans nest under it (a no-op untraced)."""
            if trace is None:
                return UNTRACED
            ctx, parent = trace
            return ctx.timed(ctx.span(
                f"scan_{cls}", parent=parent, node_id=self.node_id,
                segment_ids=sorted({u.segment_id for u in units}),
            ))

        def record_class(cls: str, units, t0: float, span) -> None:
            elapsed_us = (_t.perf_counter() - t0) * 1e6
            rows = int(sum(int(u.mask.sum()) for u in units))
            self.metrics.observe(
                "query_node_scan_us", elapsed_us, labels={"class": cls}
            )
            self.metrics.inc(
                "query_node_rows_scanned_total", rows, labels={"class": cls}
            )
            if span is not None:
                span.rows_scanned = rows

        # Index-backed units group by spec: all co-located segments sharing
        # an index configuration execute as ONE batched candidate-pool
        # dispatch (IVF runs its vectorized probe-gather-scan across the
        # group; other kinds fall back to per-index search inside).
        indexed_ids = {id(u) for u in plan.indexed}
        index_groups: dict = {}
        for unit in plan.indexed + plan.growing_slice:
            index_groups.setdefault(unit.index.batch_spec(), []).append(unit)
        for units in index_groups.values():
            cls = "indexed" if id(units[0]) in indexed_ids else "growing_slice"
            t0 = _t.perf_counter()
            with open_class(cls, units) as span:
                s, i, splits = type(units[0].index).search_batched(
                    [u.index for u in units],
                    queries,
                    k,
                    valids=[u.mask for u in units],
                )
                for j, unit in enumerate(units):
                    blk = slice(splits[j], splits[j + 1])
                    pool_s.append(s[:, blk])
                    pool_p.append(_map_pks(i[:, blk], unit.pks))
            record_class(cls, units, t0, span)
        # Brute classes run as one fused scan per class: a single shared
        # distance contraction, per-segment top-k extracted from it.
        # Cosine scans normalize both sides: the planner handed us the
        # segments' cached unit columns, only the queries normalize here
        # (indexes normalize at build and take raw queries).
        q_brute = normalize_if_cosine(metric, np.asarray(queries, np.float32))
        for cls, units in (
            ("brute_sealed", plan.brute_sealed),
            ("brute_tail", plan.brute_tail),
        ):
            if not units:
                continue
            t0 = _t.perf_counter()
            with open_class(cls, units) as span:
                s, i = ops.topk_scan_segmented(
                    q_brute,
                    [u.vectors for u in units],
                    k,
                    metric=metric_str,
                    valids=[u.mask for u in units],
                )
                for j, unit in enumerate(units):
                    blk = slice(j * k, (j + 1) * k)
                    pool_s.append(s[:, blk])
                    pool_p.append(_map_pks(i[:, blk], unit.pks))
            record_class(cls, units, t0, span)
        # Post-filter classes scan with VISIBILITY-only valids at the
        # inflated class width k' = k + max(k_extra): each unit's k_extra is
        # its worst-case interloper count (visible rows failing the filter),
        # so the widened top-k' provably contains the filtered top-k. The
        # cut zeroes interlopers to (fill, -1); merge_topk drops them.
        if plan.post_indexed:
            post_groups: dict = {}
            for unit in plan.post_indexed:
                post_groups.setdefault(unit.index.batch_spec(), []).append(unit)
            for units in post_groups.values():
                t0 = _t.perf_counter()
                with open_class("post_indexed", units) as span:
                    k_class = k + max(u.k_extra for u in units)
                    s, i, splits = type(units[0].index).search_batched(
                        [u.index for u in units],
                        queries,
                        k_class,
                        valids=[u.mask for u in units],
                    )
                    for j, unit in enumerate(units):
                        blk = slice(splits[j], splits[j + 1])
                        cs, ci = ops.post_filter_cut(
                            s[:, blk], i[:, blk], unit.post_mask, metric=metric_str
                        )
                        pool_s.append(cs)
                        pool_p.append(_map_pks(ci, unit.pks))
                record_class("post_indexed", units, t0, span)
        if plan.post_brute:
            units = plan.post_brute
            t0 = _t.perf_counter()
            with open_class("post_brute", units) as span:
                k_class = k + max(u.k_extra for u in units)
                s, i = ops.topk_scan_segmented(
                    q_brute,
                    [u.vectors for u in units],
                    k_class,
                    metric=metric_str,
                    valids=[u.mask for u in units],
                )
                for j, unit in enumerate(units):
                    blk = slice(j * k_class, (j + 1) * k_class)
                    cs, ci = ops.post_filter_cut(
                        s[:, blk], i[:, blk], unit.post_mask, metric=metric_str
                    )
                    pool_s.append(cs)
                    pool_p.append(_map_pks(ci, unit.pks))
            record_class("post_brute", units, t0, span)
        # Brute-filtered units already gathered their surviving rows: each
        # scans its own tiny vector block unfused (the gathers are ragged,
        # so a shared contraction buys nothing at these sizes).
        if plan.brute_filtered:
            t0 = _t.perf_counter()
            with open_class("brute_filtered", plan.brute_filtered) as span:
                for unit in plan.brute_filtered:
                    s, i = ops.topk_scan(
                        q_brute, unit.vectors, k, metric=metric_str
                    )
                    pool_s.append(s)
                    pool_p.append(_map_pks(i, unit.pks))
            record_class("brute_filtered", plan.brute_filtered, t0, span)
        return pool_s, pool_p

    def search_request(
        self, request: NodeSearchRequest
    ) -> "list[tuple[np.ndarray, np.ndarray]]":
        """Execute a node-level request: one planned pipeline per vector
        column, returning the node-wise top-k candidate list PER sub-request
        (fusion of hybrid sub-requests happens at the proxy, where global
        per-field ranks exist; the radius cut is applied there too — cutting
        node-local lists would make results depend on segment placement
        whenever an inner ``range_filter`` bound is set).

        Each returned pair is (scores [nq,k], pks [nq,k]; -1 = empty).
        """
        if not self.alive:
            raise RuntimeError(f"query node {self.node_id} is down")
        import time as _t

        if self.inject_delay_s > 0:
            _t.sleep(self.inject_delay_s)
        self.search_count += 1
        if request.hedged:
            self.searches_hedged += 1
        else:
            self.searches_primary += 1
            self.inflight_primary += 1
        self.inflight += 1
        t0 = _t.perf_counter()
        try:
            return self._search_request(request)
        finally:
            self.inflight -= 1
            if not request.hedged:
                self.inflight_primary -= 1
            self.metrics.observe(
                "query_node_search_latency_us",
                (_t.perf_counter() - t0) * 1e6,
                labels={"node": self.node_id},
            )

    def _search_request(
        self, request: NodeSearchRequest
    ) -> "list[tuple[np.ndarray, np.ndarray]]":
        from ..kernels import ops

        metric = request.metric
        metric_str = "l2" if metric is Metric.L2 else "ip"
        ts = request.guarantee.query_ts
        fill = np.inf if metric is Metric.L2 else -np.inf
        # Materialize the delta-delete set ONCE for the whole request; every
        # sub-request's plan probes the same sorted array.
        doomed = self._request_doomed_pks(request.collection, ts)
        shards = (
            None
            if request.channels is None
            else tuple(sorted({shard_of_channel(c) for c in request.channels}))
        )
        trace = request.trace  # (TraceContext, parent Span) | None
        results: list[tuple[np.ndarray, np.ndarray]] = []
        for a in request.anns:
            queries = a.queries
            nq = len(queries)
            if trace is not None:
                ctx, parent = trace
                pspan = ctx.span(
                    "plan_search", parent=parent, node_id=self.node_id,
                    detail=f"column={a.field}",
                )
                with ctx.timed(pspan):
                    plan = self.plan_search(
                        request.collection, ts, request.filter_masks,
                        column=a.field, metric=metric, doomed=doomed,
                        partitions=request.partitions,
                        segments=request.segments,
                        shards=shards,
                        filter=request.filter,
                        filter_strategy=request.filter_strategy,
                        k=request.k,
                    )
                pspan.segment_ids = tuple(
                    sorted({u.segment_id for u in plan.units()})
                )
                if request.filter is not None and plan.filter_info:
                    fspan = ctx.span(
                        "filter_plan", parent=parent, node_id=self.node_id,
                        detail=",".join(
                            f"{fi['segment_id']}:{fi['strategy']}"
                            f"@{fi['actual']:.3f}"
                            for fi in plan.filter_info
                        ),
                    )
                    fspan.segment_ids = tuple(
                        fi["segment_id"] for fi in plan.filter_info
                    )
            else:
                plan = self.plan_search(
                    request.collection, ts, request.filter_masks,
                    column=a.field, metric=metric, doomed=doomed,
                    partitions=request.partitions, segments=request.segments,
                    shards=shards,
                    filter=request.filter,
                    filter_strategy=request.filter_strategy,
                    k=request.k,
                )
            pool_s, pool_p = self._execute_plan(
                plan, queries, request.k, metric, trace=trace
            )
            if not pool_s:
                out = (
                    np.full((nq, request.k), fill, np.float32),
                    np.full((nq, request.k), -1, np.int64),
                )
            else:
                if trace is not None:
                    ctx, parent = trace
                    mspan = ctx.span(
                        "node_merge_topk", parent=parent, node_id=self.node_id
                    )
                    with ctx.timed(mspan):
                        out = ops.merge_topk(
                            np.concatenate(pool_s, axis=1),
                            np.concatenate(pool_p, axis=1),
                            request.k,
                            metric=metric_str,
                        )
                else:
                    out = ops.merge_topk(
                        np.concatenate(pool_s, axis=1),
                        np.concatenate(pool_p, axis=1),
                        request.k,
                        metric=metric_str,
                    )
            results.append(out)
        return results

    def search(
        self,
        collection: str,
        queries: np.ndarray,
        k: int,
        metric: Metric,
        guarantee: GuaranteeTs,
        filter_masks: "dict[int, np.ndarray] | None" = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Node-wise top-k over the primary vector column (the legacy
        kwarg surface).  Returns (scores [nq,k], pks [nq,k]; -1 = empty).

        This is a thin facade: the call is packed into a single-field
        :class:`NodeSearchRequest` and executed by :meth:`search_request`,
        so both surfaces share one planned pipeline.
        """
        request = NodeSearchRequest(
            collection=collection,
            k=k,
            metric=metric,
            guarantee=guarantee,
            anns=[AnnsQuery(PRIMARY_VECTOR_COLUMN, queries)],
            filter_masks=filter_masks,
        )
        return self.search_request(request)[0]

    # ----------------------------------------------------------- hydration
    def fetch_fields(
        self,
        collection: str,
        pks: np.ndarray,
        columns: "list[str]",
        ts: int,
    ) -> "dict[str, tuple[np.ndarray, np.ndarray]]":
        """Gather stored column values for result pks (output-field
        hydration).  ``columns`` holds segment column names ("pk", the
        primary "vector" column, or any extras column).  Returns
        column -> (found_pks [n], values [n, ...]) over the rows visible
        at ``ts`` on this node; the proxy assembles the [nq, k] view.
        """
        from ..kernels import ops

        want = np.unique(np.asarray(pks))
        want = want[want >= 0]
        # Per column: list of (pks_hit, values) pairs.  Collected per
        # column (not with one shared pk list) so a segment lacking a
        # column simply contributes nothing for it and the pk/value
        # alignment of the other columns stays intact.
        out: dict[str, list] = {c: [] for c in columns}
        if want.size:
            doomed = self._request_doomed_pks(collection, ts)
            sources: list[Segment] = [
                h.segment
                for (c, _sid), h in self.sealed.items()
                if c == collection and h.covers_ts(ts)
            ]
            sources += [
                g.segment for (c, _sid), g in self.growing.items() if c == collection
            ]
            for seg in sources:
                if seg.num_rows == 0:
                    continue
                hit = self._visible(collection, seg, ts, doomed)
                hit &= ops.isin_sorted(seg.pks(), want)
                if not hit.any():
                    continue
                hit_pks = seg.pks()[hit]
                for c in columns:
                    col = seg.pks() if c == "pk" else _seg_column(seg, c)
                    if col is None:
                        continue  # segment predates the column
                    out[c].append((hit_pks, np.asarray(col)[hit]))
        return {
            c: (
                (np.concatenate([p for p, _v in out[c]]),
                 np.concatenate([v for _p, v in out[c]]))
                if out[c]
                else (np.empty(0, np.int64), np.empty(0))
            )
            for c in columns
        }
