"""Segments: Manu's unit of data placement (paper §3.1, §3.6).

Entities from each shard are organized into segments.  A segment is
*growing* (accepts new rows) until it reaches ``seal_size`` rows or a
time limit, then *sealed* (read-only).  Growing segments are subdivided
into *slices* (10k vectors by default); once a slice fills up, a
light-weight temporary index (IVF-FLAT) is built for it so brute-force
scan cost stays bounded (paper reports up to 10x speedup).

MVCC: every row carries its LSN (HLC timestamp); deletes are recorded as
per-pk tombstone timestamps.  A tombstone ``(pk, dts)`` kills exactly the
row versions with ``row_ts < dts`` — so an upsert's delete half (published
at the same LSN as its insert half) retires the old version without
touching the new one, and visibility flips atomically at one timestamp.
``visible_mask(ts)`` gives the set of rows a query pinned at ``ts`` may
see — this one primitive yields delta consistency, repeatable reads, and
time travel.

Reads while a writer appends: columns grow in buffers that double when
full, so ``snapshot()`` hands out a ``SegmentView`` — one row count and
every column, tombstone set and slice bound cut to it — taken under the
segment's lock, which later appends never change.

Partitions: each segment carries the partition tag it was placed under
(paper §3.1: collection → shard → partition → segment); the query-node
planner prunes non-matching segments before any scan.
"""

from __future__ import annotations

import io
import threading
from dataclasses import dataclass, field
from enum import Enum
from typing import Any

import numpy as np

DEFAULT_SLICE_ROWS = 10_000
DEFAULT_SEAL_ROWS = 65_536
#: Every collection owns one implicit partition; unplaced writes land here.
DEFAULT_PARTITION = "_default"


# Tombstone maps (pk -> delete ts) keep the common single-delete case as a
# bare int and promote to a sorted list only when the same pk is deleted
# (upserted) again — the dict shape older tests poke directly stays valid.
def add_tombstone(dd: dict, pk, ts: int) -> bool:
    """Record one (pk, delete-ts) tombstone; returns False on duplicates."""
    cur = dd.get(pk)
    if cur is None:
        dd[pk] = int(ts)
        return True
    if isinstance(cur, list):
        if ts in cur:
            return False
        # A new list, not one grown in place: a reader on another thread
        # (``flatten_tombstones``) keeps the one it took.
        dd[pk] = sorted(cur + [int(ts)])
        return True
    if cur == ts:
        return False
    dd[pk] = sorted((cur, int(ts)))
    return True


def flatten_tombstones(dd: dict) -> tuple[np.ndarray, np.ndarray]:
    """Flatten a pk -> (ts | [ts, ...]) tombstone map into aligned
    (pks, dts) arrays — the shape ``ops.eff_tombstones`` consumes."""
    pks: list = []
    dts: list = []
    for pk, v in dd.items():
        if isinstance(v, list):
            pks.extend([pk] * len(v))
            dts.extend(v)
        else:
            pks.append(pk)
            dts.append(v)
    return np.asarray(pks), np.asarray(dts, np.int64)


class SegmentState(Enum):
    GROWING = "growing"
    SEALED = "sealed"
    DROPPED = "dropped"


@dataclass
class SegmentStats:
    num_rows: int
    num_deleted: int
    state: str
    min_ts: int
    max_ts: int


class _Column:
    """An append-only column: rows land in a buffer that doubles when it
    fills, so an append copies only its own rows and the first ``n`` rows
    read as a view that later appends never write into."""

    __slots__ = ("buf", "n")

    def __init__(self, empty: np.ndarray):
        self.buf = empty  # zero rows: the dtype and shape of an empty read
        self.n = 0

    def append(self, rows: np.ndarray) -> None:
        m = len(rows)
        dtype = np.promote_types(self.buf.dtype, rows.dtype) if self.n else rows.dtype
        if self.n + m > len(self.buf) or dtype != self.buf.dtype:
            grown = np.empty(
                (max(self.n + m, 2 * len(self.buf)),) + rows.shape[1:], dtype
            )
            if self.n:
                grown[: self.n] = self.buf[: self.n]
            self.buf = grown
        self.buf[self.n : self.n + m] = rows
        self.n += m

    def view(self) -> np.ndarray:
        return self.buf[: self.n]


@dataclass(frozen=True, eq=False)
class SegmentView:
    """A segment as one read sees it: its first ``num_rows`` rows, with
    the tombstones recorded when the view was taken.  It answers the read
    calls a ``Segment`` answers, so a planner reads a view where it would
    read the segment and every array it gets agrees in length."""

    segment: "Segment"
    num_rows: int
    columns: dict  # "pk", "vector", "ts" and each extra field, cut to num_rows
    tombstones: "tuple[np.ndarray, np.ndarray] | None"

    @property
    def segment_id(self) -> int:
        return self.segment.segment_id

    @property
    def shard(self) -> int:
        return self.segment.shard

    @property
    def partition(self) -> str:
        return self.segment.partition

    @property
    def extra_fields(self) -> tuple[str, ...]:
        return self.segment.extra_fields

    def pks(self) -> np.ndarray:
        return self.columns["pk"]

    def vectors(self) -> np.ndarray:
        return self.columns["vector"]

    def timestamps(self) -> np.ndarray:
        return self.columns["ts"]

    def extra(self, name: str) -> np.ndarray:
        return self.columns[name]

    def unit_column(self, name: str = "vector") -> np.ndarray:
        # Rows normalize one by one: the segment's cached column cut to
        # this view holds exactly its rows.
        return self.segment.unit_column(name)[: self.num_rows]

    def visible_mask(self, ts: int) -> np.ndarray:
        """MVCC visibility at query timestamp ``ts``: rows written at or
        before ``ts`` and not killed by any tombstone in ``(row_ts, ts]``."""
        from ..kernels import ops

        mask = self.columns["ts"] <= ts
        if self.tombstones is not None:
            eff = ops.eff_tombstones(self.tombstones[0], self.tombstones[1], ts)
            if eff is not None:
                mask &= ~ops.tombstone_mask(
                    self.columns["pk"], self.columns["ts"], eff[0], eff[1]
                )
        return mask

    def full_slices(self) -> list[int]:
        return list(range(self.num_rows // self.segment.slice_rows))

    def slice_bounds(self, slice_idx: int) -> tuple[int, int]:
        lo = slice_idx * self.segment.slice_rows
        return lo, min(lo + self.segment.slice_rows, self.num_rows)


class Segment:
    """Columnar in-memory segment with MVCC visibility.

    Storage is column-major (one numpy array per field) — the same layout
    as the binlog, so sealing = serializing columns verbatim.
    """

    def __init__(
        self,
        segment_id: int,
        collection: str,
        shard: int,
        dim: int,
        slice_rows: int = DEFAULT_SLICE_ROWS,
        extra_fields: tuple[str, ...] = (),
        partition: str = DEFAULT_PARTITION,
    ):
        self.segment_id = segment_id
        self.collection = collection
        self.shard = shard
        self.partition = partition
        self.dim = dim
        self.slice_rows = slice_rows
        self.state = SegmentState.GROWING
        self.extra_fields = tuple(extra_fields)

        self._cols: dict[str, _Column] = {
            "pk": _Column(np.empty(0, np.int64)),
            "vector": _Column(np.empty((0, dim), np.float32)),
            "ts": _Column(np.empty(0, np.int64)),
            **{f: _Column(np.empty(0)) for f in self.extra_fields},
        }
        self._num_rows = 0

        # The columns' views cut to ``num_rows``; invalidated on append.
        self._mat: dict[str, np.ndarray] | None = None
        # Row-normalized vector columns (cosine scans), cached per column
        # name; invalidated on append alongside ``_mat``.
        self._unit: dict[str, np.ndarray] = {}

        # Tombstones: pk -> delete ts (or a sorted list when the pk was
        # deleted more than once — repeated upserts).  The row-level kill
        # masks are derived lazily (and are what the scan kernels consume).
        self._deleted: dict[Any, Any] = {}
        self._del_flat: tuple[np.ndarray, np.ndarray] | None = None

        # Slice boundaries with a temporary index handle each (built by the
        # query node once a slice is full).
        self.slice_indexes: dict[int, Any] = {}

        self._lock = threading.RLock()
        self.checkpoint_pos: int = 0  # WAL position this segment has consumed up to

    # -------------------------------------------------------------- writes
    def append(
        self,
        pks: np.ndarray,
        vectors: np.ndarray,
        timestamps: np.ndarray,
        extras: dict[str, np.ndarray] | None = None,
    ) -> None:
        with self._lock:
            if self.state is not SegmentState.GROWING:
                raise RuntimeError(f"segment {self.segment_id} is {self.state}, not growing")
            if vectors.ndim != 2 or vectors.shape[1] != self.dim:
                raise ValueError(f"expected (n,{self.dim}) vectors, got {vectors.shape}")
            n = len(pks)
            if not (len(vectors) == len(timestamps) == n):
                raise ValueError("pks/vectors/timestamps length mismatch")
            missing = [f for f in self.extra_fields if (extras or {}).get(f) is None]
            if missing:
                raise ValueError(f"missing extra field '{missing[0]}'")
            self._cols["pk"].append(np.asarray(pks))
            self._cols["vector"].append(np.asarray(vectors, dtype=np.float32))
            self._cols["ts"].append(np.asarray(timestamps, dtype=np.int64))
            for name in self.extra_fields:
                self._cols[name].append(np.asarray(extras[name]))
            self._num_rows += n
            self._mat = None
            self._unit.clear()

    def delete(self, pks: np.ndarray, ts: int) -> int:
        """Tombstone primary keys as of ``ts``: row versions with
        ``row_ts < ts`` become invisible to queries pinned at or after
        ``ts``; versions written at or after ``ts`` (re-inserts, the
        insert half of an upsert at the same LSN) are untouched.
        Returns the number of tombstones recorded."""
        with self._lock:
            pks = np.asarray(pks)
            hits = 0
            for pk in pks[np.isin(pks, self.pks())].tolist():
                if add_tombstone(self._deleted, pk, ts):
                    hits += 1
            if hits:
                self._del_flat = None
            return hits

    def seal(self) -> None:
        with self._lock:
            self.state = SegmentState.SEALED

    # --------------------------------------------------------------- reads
    def _materialize(self) -> dict[str, np.ndarray]:
        with self._lock:
            if self._mat is None:
                self._mat = {name: c.view() for name, c in self._cols.items()}
            return self._mat

    def snapshot(self) -> SegmentView:
        """The segment as it stands now: its row count with every column,
        the tombstones and the slice bounds cut to it, read together under
        the lock (see ``SegmentView``)."""
        with self._lock:
            return SegmentView(
                self, self._num_rows, self._materialize(), self._tombstones_flat()
            )

    @property
    def num_rows(self) -> int:
        return self._num_rows

    def pks(self) -> np.ndarray:
        return self._materialize()["pk"]

    def vectors(self) -> np.ndarray:
        return self._materialize()["vector"]

    def timestamps(self) -> np.ndarray:
        return self._materialize()["ts"]

    def extra(self, name: str) -> np.ndarray:
        return self._materialize()[name]

    def unit_column(self, name: str = "vector") -> np.ndarray:
        """Row-normalized copy of a vector column, cached until the next
        append — cosine brute scans reuse it instead of renormalizing the
        whole column on every search."""
        with self._lock:
            cached = self._unit.get(name)
            if cached is None:
                col = self._materialize()[name]
                norms = np.linalg.norm(col, axis=1, keepdims=True)
                cached = col / np.maximum(norms, 1e-12)
                self._unit[name] = cached
            return cached

    def _tombstones_flat(self) -> tuple[np.ndarray, np.ndarray] | None:
        """(pks, dts) tombstone pairs, cached until the next delete."""
        with self._lock:
            if not self._deleted:
                return None
            if self._del_flat is None:
                self._del_flat = flatten_tombstones(self._deleted)
            return self._del_flat

    def delete_bitmap(self) -> np.ndarray:
        """Boolean mask of rows currently dead (killed at any timestamp)."""
        return ~self.visible_mask(np.iinfo(np.int64).max)

    def visible_mask(self, ts: int) -> np.ndarray:
        """MVCC visibility at query timestamp ``ts`` (``SegmentView``)."""
        return self.snapshot().visible_mask(ts)

    def min_ts(self) -> int:
        ts = self.timestamps()
        return int(ts.min()) if len(ts) else 0

    def max_ts(self) -> int:
        ts = self.timestamps()
        return int(ts.max()) if len(ts) else 0

    def stats(self) -> SegmentStats:
        return SegmentStats(
            num_rows=self.num_rows,
            num_deleted=len(self._deleted),
            state=self.state.value,
            min_ts=self.min_ts(),
            max_ts=self.max_ts(),
        )

    # -------------------------------------------------------------- slices
    def full_slices(self) -> list[int]:
        """Indices of completed slices (candidates for temporary indexes)."""
        return list(range(self._num_rows // self.slice_rows))

    def slice_bounds(self, slice_idx: int) -> tuple[int, int]:
        lo = slice_idx * self.slice_rows
        hi = min(lo + self.slice_rows, self._num_rows)
        return lo, hi

    def tail_rows(self) -> tuple[int, int]:
        """Row range not covered by any full slice (always brute-force)."""
        lo = (self._num_rows // self.slice_rows) * self.slice_rows
        return lo, self._num_rows

    # -------------------------------------------------- binlog (de)serialize
    def to_binlog(self) -> bytes:
        """Columnar serialization (the binlog format, paper §3.3).
        Tombstones flatten to aligned (pk, ts) pair arrays — one entry per
        delete event, so multi-delete histories round-trip exactly."""
        cols = dict(self._materialize())
        flat = self._tombstones_flat()
        if flat is not None:
            cols["__deleted_pks"], cols["__deleted_ts"] = flat
        else:
            cols["__deleted_pks"] = np.empty(0, cols["pk"].dtype)
            cols["__deleted_ts"] = np.empty(0, np.int64)
        buf = io.BytesIO()
        np.savez_compressed(
            buf,
            __meta=np.array(
                [self.segment_id, self.shard, self.dim, self.checkpoint_pos],
                dtype=np.int64,
            ),
            __partition=np.array(self.partition),
            **cols,
        )
        return buf.getvalue()

    @classmethod
    def from_binlog(
        cls, collection: str, data: bytes, slice_rows: int = DEFAULT_SLICE_ROWS
    ) -> "Segment":
        with np.load(io.BytesIO(data), allow_pickle=False) as z:
            meta = z["__meta"]
            segment_id, shard, dim, ckpt = (int(x) for x in meta)
            extra_names = tuple(
                k
                for k in z.files
                if k not in ("__meta", "__partition", "pk", "vector", "ts",
                             "__deleted_pks", "__deleted_ts")
            )
            partition = (
                str(z["__partition"]) if "__partition" in z.files else DEFAULT_PARTITION
            )
            seg = cls(segment_id, collection, shard, dim, slice_rows, extra_names,
                      partition=partition)
            n = len(z["pk"])
            if n:
                extras = {k: z[k] for k in extra_names}
                seg.append(z["pk"], z["vector"], z["ts"], extras)
            seg.checkpoint_pos = ckpt
            for pk, dts in zip(z["__deleted_pks"].tolist(), z["__deleted_ts"].tolist()):
                add_tombstone(seg._deleted, pk, dts)
            seg.seal()
            return seg

    def deleted_fraction(self) -> float:
        return len(self._deleted) / max(1, self.num_rows)


def merge_segments(new_id: int, segments: list[Segment]) -> Segment:
    """Compaction: merge small sealed segments into one, dropping rows whose
    delete tombstone is already present (paper §3.1 'merges small segments')."""
    if not segments:
        raise ValueError("nothing to merge")
    base = segments[0]
    out = Segment(
        new_id, base.collection, base.shard, base.dim, base.slice_rows,
        base.extra_fields, partition=base.partition,
    )
    for seg in segments:
        keep = ~seg.delete_bitmap()
        if keep.any():
            extras = {f: seg.extra(f)[keep] for f in seg.extra_fields}
            out.append(seg.pks()[keep], seg.vectors()[keep], seg.timestamps()[keep], extras)
    out.checkpoint_pos = max(s.checkpoint_pos for s in segments)
    out.seal()
    return out
