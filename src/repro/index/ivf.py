"""Inverted-file indexes: IVF-FLAT, IVF-SQ, IVF-PQ (paper Table 1).

Vectors are grouped into ``nlist`` k-means clusters; a query scans only the
``nprobe`` most promising lists.  Lists are stored contiguously (CSR-style)
so each probed list is one dense kernel scan — the TPU adaptation of the
cache-friendly layout Milvus uses on CPU.

Search is a fully vectorized batched pipeline (no per-query or per-list
Python loops):

1. **probe** — ``topk_scan`` over the centroids, as before;
2. **invert + gather-scan** — ``ops.ivf_probe_schedule`` inverts the probe
   matrix into a deduplicated (list -> query-group) schedule bucketed by
   padded size, and ``ops.ivf_gather_topk`` runs one fused scan per bucket
   (FLAT/SQ: batched shared contraction; PQ: a single batched residual-LUT
   ADC over all (query, list) pairs) and pools per-probe-slot top-k;
3. **reduce** — one ``ops.merge_topk`` call replaces the per-query
   ``np.argsort`` merges, and local offsets map to row ids with one
   vectorized take.

``search_batched`` extends the same pipeline across co-located segments
sharing an index spec: one dispatch returns every unit's candidate pool so
the query node merges exactly once.  Set ``REPRO_IVF_REFERENCE=1`` to run
the scalar per-list reference path instead (the equivalence oracle).

IVF-PQ encodes residuals (x - centroid) which materially improves recall at
the same code budget.
"""

from __future__ import annotations

import os

import numpy as np

from ..core.collection import Metric
from ..kernels import ops
from .base import VectorIndex, normalize_if_cosine, scan_metric, worst_score
from .kmeans import kmeans
from .pq import adc_tables, pq_encode, train_pq_codebooks


def use_reference() -> bool:
    """True when the scalar per-list oracle path is forced via env."""
    return os.environ.get("REPRO_IVF_REFERENCE") == "1"


class IVFBase(VectorIndex):
    def __init__(
        self, metric: Metric = Metric.L2, nlist: int = 64, nprobe: int = 8, **params
    ):
        super().__init__(metric, nlist=nlist, nprobe=nprobe, **params)
        self.nlist = nlist
        self.nprobe = nprobe
        self.centroids: np.ndarray | None = None
        # The probe's operand: ``centroids`` kept on the device from the
        # first probe on, for as long as this index lives (``ops.resident``).
        self._centroid_operand = None
        self.list_offsets: np.ndarray | None = None  # [nlist+1] CSR offsets
        self.row_ids: np.ndarray | None = None  # [n] permutation: list order -> original

    def _partition(self, x: np.ndarray) -> np.ndarray:
        """Cluster and build CSR layout; returns x permuted to list order."""
        self.centroids, assign = kmeans(x, min(self.nlist, max(1, len(x))), seed=0)
        self.nlist = len(self.centroids)
        self._centroid_operand = None
        order = np.argsort(assign, kind="stable")
        counts = np.bincount(assign, minlength=self.nlist)
        self.list_offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        self.row_ids = order.astype(np.int64)
        return x[order]

    def _probe_lists(self, q: np.ndarray, nprobe: int) -> np.ndarray:
        """[nq, nprobe] most promising list ids per query (-1 = padded)."""
        nprobe = min(nprobe, self.nlist)
        if self._centroid_operand is None:
            self._centroid_operand = ops.resident(self.centroids)
        # For IP, the best lists are by centroid similarity; for L2 by distance.
        vals, idx = ops.topk_scan(
            q, self._centroid_operand, nprobe, metric=scan_metric(self.metric)
        )
        return idx

    def _effective_nprobe(self) -> int:
        return int(self.params.get("nprobe", self.nprobe))

    # ------------------------------------------------- batched scan pipeline
    def _bucket_scorer(self, q: np.ndarray, valid_perm, sched):
        """Return ``(score_fn, q_offset)``: ``score_fn(bucket) -> [B, G, W]``
        min-semantics scores with dead slots at +inf, and an optional
        per-query additive constant ``q_offset [nq]`` the scan defers (it
        cannot change any per-query ranking, so it is added back to the
        pooled candidates in one cheap pass instead of per scanned cell)."""
        raise NotImplementedError

    def _row_bias(self, b: ops.IVFBucket, valid_perm, base=None):
        """Per-row additive bias [B, W] for a bucket's scan: ``base`` values
        (row norms etc., or zero) with +inf folded in for padding and
        masked-invisible rows — masking costs one [B, W] pass instead of a
        [B, G, W] one.  Returns None when there is nothing to add."""
        dead = None if b.full else ~b.wmask
        if valid_perm is not None:
            bad = ~valid_perm[b.rows]
            dead = bad if dead is None else (dead | bad)
        if base is None:
            if dead is None:
                return None
            bias = np.zeros(b.rows.shape, np.float32)
        else:
            bias = base[b.rows]  # fancy gather: already a fresh f32 array
        if dead is not None:
            np.copyto(bias, np.float32(np.inf), where=dead)
        return bias

    def _pool_candidates(
        self, q: np.ndarray, k: int, valid_perm: np.ndarray | None
    ) -> tuple[np.ndarray, np.ndarray]:
        """Probe + bucketed gather-scan; returns the candidate pool
        ``(scores [nq, nprobe*k], ids [nq, nprobe*k])`` in the metric's
        natural scale with original row ids (-1 = empty slot)."""
        probes = self._probe_lists(q, self._effective_nprobe())
        sched = ops.ivf_probe_schedule(probes, self.list_offsets)
        score_fn, q_offset = self._bucket_scorer(q, valid_perm, sched)
        pool_s, pool_rows = ops.ivf_gather_topk(sched, k, score_fn)
        if q_offset is not None:
            pool_s = pool_s + q_offset[:, None]  # fills stay +inf
        # local CSR offsets -> original row ids: one vectorized take
        ids = np.where(
            pool_rows >= 0,
            self.row_ids[np.clip(pool_rows, 0, len(self.row_ids) - 1)],
            -1,
        )
        if self.metric is not Metric.L2:  # back to descending similarity
            pool_s = np.where(ids >= 0, -pool_s, np.float32(-np.inf))
        return pool_s, ids

    def search(self, queries, k, valid=None):
        if use_reference():
            return self._search_reference(queries, k, valid)
        q = normalize_if_cosine(self.metric, np.asarray(queries, np.float32))
        valid_perm = None if valid is None else np.asarray(valid)[self.row_ids]
        pool_s, ids = self._pool_candidates(q, k, valid_perm)
        return ops.merge_topk(pool_s, ids, k, metric=scan_metric(self.metric))

    @classmethod
    def search_batched(cls, indexes, queries, k, valids=None):
        """All co-located IVF units of one spec in one dispatch: shared
        query prep, per-unit probe + bucketed gather-scan, raw candidate
        pools returned unreduced (the caller merges once)."""
        if use_reference() or not indexes:
            return super().search_batched(indexes, queries, k, valids)
        if valids is None:
            valids = [None] * len(indexes)
        q = normalize_if_cosine(
            indexes[0].metric, np.asarray(queries, np.float32)
        )
        ss, ii, splits = [], [], [0]
        for idx, v in zip(indexes, valids):  # per-segment, not per-list
            vp = None if v is None else np.asarray(v)[idx.row_ids]
            s, i = idx._pool_candidates(q, k, vp)
            ss.append(s)
            ii.append(i)
            splits.append(splits[-1] + s.shape[1])
        return np.concatenate(ss, axis=1), np.concatenate(ii, axis=1), splits

    # ------------------------------------------------- scalar reference path
    # Subclasses implement one-list scan over the permuted storage.
    def _scan_range(
        self, q: np.ndarray, lo: int, hi: int, k: int, valid_perm: np.ndarray | None
    ) -> tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError

    def _search_reference(self, queries, k, valid=None):
        """The pre-vectorization per-list loop, kept as the equivalence
        oracle for the batched pipeline (``REPRO_IVF_REFERENCE=1``)."""
        q = normalize_if_cosine(self.metric, np.asarray(queries, np.float32))
        nq = len(q)
        probes = self._probe_lists(q, self._effective_nprobe())  # [nq, nprobe]
        valid_perm = None
        if valid is not None:
            valid_perm = np.asarray(valid)[self.row_ids]

        # Group queries by probed list so each list is scanned once per
        # batch — the paper's request batching at the segment level.
        out_s = np.full((nq, k), worst_score(self.metric), np.float32)
        out_i = np.full((nq, k), -1, np.int64)
        unique_lists = np.unique(probes)
        per_q_scores: list[list[np.ndarray]] = [[] for _ in range(nq)]
        per_q_ids: list[list[np.ndarray]] = [[] for _ in range(nq)]
        for lst in unique_lists:
            if lst < 0:
                continue
            qmask = (probes == lst).any(axis=1)
            lo, hi = int(self.list_offsets[lst]), int(self.list_offsets[lst + 1])
            if hi <= lo or not qmask.any():
                continue
            sub_q = q[qmask]
            s, i = self._scan_range(sub_q, lo, hi, min(k, hi - lo), valid_perm)
            # map local list offsets -> original row ids
            gi = np.where(i >= 0, self.row_ids[np.clip(i + lo, 0, len(self.row_ids) - 1)], -1)
            rows = np.nonzero(qmask)[0]
            for r_local, r in enumerate(rows):
                per_q_scores[r].append(s[r_local : r_local + 1])
                per_q_ids[r].append(gi[r_local : r_local + 1])
        for r in range(nq):
            if per_q_scores[r]:
                s = np.concatenate(per_q_scores[r], axis=1)
                i = np.concatenate(per_q_ids[r], axis=1)
                ms, mi = ops.merge_topk(s, i, k, metric=scan_metric(self.metric))
                out_s[r] = ms[0]
                out_i[r] = mi[0]
        return out_s, out_i

    def _base_state(self) -> dict[str, np.ndarray]:
        return {
            "centroids": self.centroids,
            "list_offsets": self.list_offsets,
            "row_ids": self.row_ids,
        }

    def _load_base_state(self, state) -> None:
        self.centroids = state["centroids"]
        self._centroid_operand = None
        self.list_offsets = state["list_offsets"]
        self.row_ids = state["row_ids"]
        self.nlist = len(self.centroids)


class IVFFlatIndex(IVFBase):
    KIND = "ivf_flat"

    def __init__(self, metric: Metric = Metric.L2, nlist: int = 64, nprobe: int = 8, **params):
        super().__init__(metric, nlist=nlist, nprobe=nprobe, **params)
        self.storage: np.ndarray | None = None  # permuted vectors
        self._row_norms: np.ndarray | None = None  # lazy, not serialized

    def build(self, vectors: np.ndarray) -> None:
        x = normalize_if_cosine(self.metric, np.asarray(vectors, np.float32))
        self.storage = self._partition(x)
        self._row_norms = None
        self.num_rows = len(x)

    def _bucket_scorer(self, q, valid_perm, sched):
        # L2 distance = qn - 2 q.x + rn: the -2 folds into the query operand,
        # rn (cached per row) joins the masking bias, and qn defers to the
        # pooled candidates — the scan is one gemm + one [B, W]-bias add.
        l2 = self.metric is Metric.L2
        if l2 and self._row_norms is None:
            self._row_norms = np.einsum("ij,ij->i", self.storage, self.storage)
        qs = -2.0 * q if l2 else -q
        base = self._row_norms if l2 else None
        storage = self.storage

        def score(b: ops.IVFBucket) -> np.ndarray:
            tile = storage[b.rows]  # [B, W, d]
            s = np.matmul(qs[b.q_idx], tile.transpose(0, 2, 1))  # [B, G, W]
            bias = self._row_bias(b, valid_perm, base)
            if bias is not None:
                s += bias[:, None, :]
            return s

        q_offset = np.einsum("ij,ij->i", q, q) if l2 else None
        return score, q_offset

    def _scan_range(self, q, lo, hi, k, valid_perm):
        v = None if valid_perm is None else valid_perm[lo:hi]
        return ops.topk_scan(
            q, self.storage[lo:hi], k, metric=scan_metric(self.metric), valid=v
        )

    def _state(self):
        return {**self._base_state(), "storage": self.storage}

    def _load_state(self, state):
        self._load_base_state(state)
        self.storage = state["storage"]
        self._row_norms = None
        self.num_rows = len(self.storage)


class IVFSQIndex(IVFBase):
    KIND = "ivf_sq"

    def __init__(self, metric: Metric = Metric.L2, nlist: int = 64, nprobe: int = 8, **params):
        super().__init__(metric, nlist=nlist, nprobe=nprobe, **params)
        self.codes: np.ndarray | None = None
        self.vmin: np.ndarray | None = None
        self.vmax: np.ndarray | None = None
        self._row_norms: np.ndarray | None = None  # decoded-row norms, lazy

    def build(self, vectors: np.ndarray) -> None:
        x = normalize_if_cosine(self.metric, np.asarray(vectors, np.float32))
        xp = self._partition(x)
        self.vmin, self.vmax = xp.min(axis=0), xp.max(axis=0)
        self.codes = ops.sq_encode(xp, self.vmin, self.vmax)
        self._row_norms = None
        self.num_rows = len(x)

    def _decode_params(self):
        vmin = np.asarray(self.vmin, np.float32)
        return vmin, ops.sq_scale(vmin, self.vmax)

    def _decoded_norms(self) -> np.ndarray:
        """||decode(code)||^2 per row, computed once (chunked decode)."""
        if self._row_norms is None:
            vmin, scale = self._decode_params()
            out = np.empty(len(self.codes), np.float32)
            for lo in range(0, len(self.codes), 65536):
                y = self.codes[lo : lo + 65536].astype(np.float32) * scale + vmin
                out[lo : lo + 65536] = np.einsum("ij,ij->i", y, y)
            self._row_norms = out
        return self._row_norms

    def _bucket_scorer(self, q, valid_perm, sched):
        # Fused dequantization: with y = code*scale + vmin, the distance
        # q.y contraction runs directly on the CASTED codes by folding the
        # scale into the query operand and q.vmin into the deferred
        # per-query constant; decoded-row norms are a cached [n] bias.
        vmin, scale = self._decode_params()
        l2 = self.metric is Metric.L2
        qs = (-2.0 * q if l2 else -q) * scale
        base = self._decoded_norms() if l2 else None
        codes = self.codes

        def score(b: ops.IVFBucket) -> np.ndarray:
            tile = codes[b.rows].astype(np.float32)  # [B, W, d]
            s = np.matmul(qs[b.q_idx], tile.transpose(0, 2, 1))
            bias = self._row_bias(b, valid_perm, base)
            if bias is not None:
                s += bias[:, None, :]
            return s

        qv = q @ vmin
        q_offset = np.einsum("ij,ij->i", q, q) - 2.0 * qv if l2 else -qv
        return score, q_offset

    def _scan_range(self, q, lo, hi, k, valid_perm):
        v = None if valid_perm is None else valid_perm[lo:hi]
        return ops.sq_topk_scan(
            q, self.codes[lo:hi], self.vmin, self.vmax, k,
            metric=scan_metric(self.metric), valid=v,
        )

    def _state(self):
        return {
            **self._base_state(),
            "codes": self.codes,
            "vmin": self.vmin,
            "vmax": self.vmax,
        }

    def _load_state(self, state):
        self._load_base_state(state)
        self.codes, self.vmin, self.vmax = state["codes"], state["vmin"], state["vmax"]
        self._row_norms = None
        self.num_rows = len(self.codes)


class IVFPQIndex(IVFBase):
    KIND = "ivf_pq"

    def __init__(
        self,
        metric: Metric = Metric.L2,
        nlist: int = 64,
        nprobe: int = 8,
        m: int = 8,
        ksub: int = 256,
        **params,
    ):
        super().__init__(metric, nlist=nlist, nprobe=nprobe, m=m, ksub=ksub, **params)
        self.m, self.ksub = m, ksub
        self.codebooks: np.ndarray | None = None
        self.codes: np.ndarray | None = None
        self._perm_assign: np.ndarray | None = None  # list id per permuted row
        self._scan_bias: np.ndarray | None = None  # per-row scan bias, lazy
        self._cb_flat: np.ndarray | None = None  # [m*ksub, dsub] flat codebook
        self._codes_off: np.ndarray | None = None  # codes + j*ksub offsets

    def build(self, vectors: np.ndarray) -> None:
        x = normalize_if_cosine(self.metric, np.asarray(vectors, np.float32))
        xp = self._partition(x)
        assign = np.repeat(
            np.arange(self.nlist), np.diff(self.list_offsets).astype(int)
        )
        residual = xp - self.centroids[assign]
        self.codebooks = train_pq_codebooks(residual, self.m, self.ksub)
        self.codes = pq_encode(residual, self.codebooks)
        self._perm_assign = assign.astype(np.int32)
        self._scan_bias = self._cb_flat = self._codes_off = None
        self.num_rows = len(x)

    def _decode_setup(self) -> None:
        """Flat-LUT decode state: the codebook as one [m*ksub, dsub] table
        and the stored codes pre-offset by j*ksub, so tile decode is a
        SINGLE np.take whose every looked-up element is a contiguous dsub
        block — streaming copies instead of per-float LUT probes."""
        m, ksub, _dsub = self.codebooks.shape
        self._cb_flat = np.ascontiguousarray(
            self.codebooks.reshape(m * ksub, -1), np.float32
        )
        # int32 suffices: offsets are bounded by m*ksub (and keep the
        # cached array at 4x the codes instead of 8x)
        self._codes_off = self.codes.astype(np.int32) + (
            np.arange(m, dtype=np.int32) * ksub
        )

    def _decode_rows(self, rows) -> np.ndarray:
        """Residual reconstructions for a row-index tile [...,] -> [..., d]."""
        if self._codes_off is None:
            self._decode_setup()
        dsub = self._cb_flat.shape[1]
        rec = np.take(self._cb_flat, self._codes_off[rows], axis=0)
        return rec.reshape(np.shape(rows) + (self.codes.shape[1] * dsub,))

    def _ensure_scan_bias(self) -> np.ndarray:
        """Per-row scan bias, computed once per loaded index (chunked).

        Residual ADC against list ``l`` scores, for row reconstruction
        r = decode(code): L2 -> ||(q-c_l) - r||^2 = ||q - (c_l+r)||^2 =
        qn - 2 q.(c_l+r) + ||c_l+r||^2;  IP (negated) -> -(q-c_l).r =
        -q.r + c_l.r.  Both decompose into a gemm against the
        reconstruction tile plus a bias that depends only on the ROW
        (||c_l + r||^2, resp. c_l.r) — precomputed here — plus (L2) a
        per-pair -2 q.c_l constant and the deferred qn.
        """
        if self._scan_bias is None:
            cents = self.centroids[self._perm_assign]  # [n, d]
            out = np.empty(len(self.codes), np.float32)
            l2 = self.metric is Metric.L2
            for lo in range(0, len(self.codes), 65536):
                hi = min(lo + 65536, len(self.codes))
                rec = self._decode_rows(np.arange(lo, hi))
                c = cents[lo : lo + 65536]
                if l2:
                    y = c + rec
                    out[lo : lo + 65536] = np.einsum("ij,ij->i", y, y)
                else:
                    out[lo : lo + 65536] = np.einsum("ij,ij->i", c, rec)
            self._scan_bias = out
        return self._scan_bias

    def _bucket_scorer(self, q, valid_perm, sched):
        # Batched residual ADC via the reconstruction identity (see
        # _ensure_scan_bias): every (query, probed list) pair's LUT work
        # collapses into one gemm per bucket over decoded code tiles, a
        # precomputed per-row bias, and a vectorized per-pair constant.
        l2 = self.metric is Metric.L2
        base = self._ensure_scan_bias()
        qs = -2.0 * q if l2 else -q
        pair_const = None
        if l2:
            pc = self.centroids[sched.pair_list]
            pair_const = -2.0 * np.einsum(
                "ij,ij->i", q[sched.pair_q], pc
            ).astype(np.float32)

        def score(b: ops.IVFBucket) -> np.ndarray:
            rec = self._decode_rows(b.rows)  # [B, W, d]
            s = np.matmul(qs[b.q_idx], rec.transpose(0, 2, 1))
            if pair_const is not None:
                s += pair_const[b.pair_idx][:, :, None]
            bias = self._row_bias(b, valid_perm, base)
            if bias is not None:
                s += bias[:, None, :]
            return s

        q_offset = np.einsum("ij,ij->i", q, q) if l2 else None
        return score, q_offset

    def _search_reference(self, queries, k, valid=None):
        # Residual ADC oracle: LUTs recomputed per (query, probed list) on
        # q - centroid, scanning per list with shifted queries.
        q = normalize_if_cosine(self.metric, np.asarray(queries, np.float32))
        nq = len(q)
        probes = self._probe_lists(q, self._effective_nprobe())
        valid_perm = None if valid is None else np.asarray(valid)[self.row_ids]
        pools_s: list[list[np.ndarray]] = [[] for _ in range(nq)]
        pools_i: list[list[np.ndarray]] = [[] for _ in range(nq)]
        for lst in np.unique(probes):
            if lst < 0:
                continue
            lo, hi = int(self.list_offsets[lst]), int(self.list_offsets[lst + 1])
            qmask = (probes == lst).any(axis=1)
            if hi <= lo or not qmask.any():
                continue
            sub_q = q[qmask] - self.centroids[lst][None, :]
            luts = adc_tables(sub_q, self.codebooks, self.metric)
            v = None if valid_perm is None else valid_perm[lo:hi]
            s, i = ops.pq_adc_topk(luts, self.codes[lo:hi], min(k, hi - lo), valid=v)
            if self.metric is not Metric.L2:
                s = -s
            gi = np.where(i >= 0, self.row_ids[np.clip(i + lo, 0, len(self.row_ids) - 1)], -1)
            rows = np.nonzero(qmask)[0]
            for r_local, r in enumerate(rows):
                pools_s[r].append(s[r_local : r_local + 1])
                pools_i[r].append(gi[r_local : r_local + 1])
        out_s = np.full((nq, k), worst_score(self.metric), np.float32)
        out_i = np.full((nq, k), -1, np.int64)
        for r in range(nq):
            if pools_s[r]:
                s = np.concatenate(pools_s[r], axis=1)
                i = np.concatenate(pools_i[r], axis=1)
                ms, mi = ops.merge_topk(s, i, k, metric=scan_metric(self.metric))
                out_s[r] = ms[0]
                out_i[r] = mi[0]
        return out_s, out_i

    def _state(self):
        return {
            **self._base_state(),
            "codebooks": self.codebooks,
            "codes": self.codes,
            "perm_assign": self._perm_assign,
        }

    def _load_state(self, state):
        self._load_base_state(state)
        self.codebooks = state["codebooks"]
        self.codes = state["codes"]
        self._perm_assign = state["perm_assign"]
        self._scan_bias = self._cb_flat = self._codes_off = None
        self.m, self.ksub = self.codebooks.shape[0], self.codebooks.shape[1]
        self.num_rows = len(self.codes)
