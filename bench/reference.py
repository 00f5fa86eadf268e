"""The plain reference search and the comparison that decides ``correct``.

The reference imports nothing of the program.  It finds each query's
exact top-k over the rows visible at the query's timestamp in two stages:
float32 candidates (the k + ``EXTRA`` best by a blocked scan), then an
exact float64 re-rank of those candidates without the L2 expansion, so no
cancellation touches the answer.

Visibility is the MVCC rule the deployment guarantees: a row is visible at
``query_ts`` when the write that carried it has a timestamp at or below
``query_ts`` and no delete of it has.  Rows loaded in set-up carry
timestamp 0; rows never written carry ``NEVER``.

``control_topk`` is the same search with its distance contraction at the
next precision below the deployment's: float32 at ``Precision.HIGH``
(three bf16 passes), emulated exactly on any backend by splitting each
float32 into a bf16 high part and a bf16 low part.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

NEVER = np.iinfo(np.int64).max
EXTRA = 32
BLOCK_ROWS = 1 << 15
QUERY_CHUNK = 512


def _unit(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)


class Corpus:
    """Every row the run may write, by pk (pk == row), with its visibility."""

    def __init__(self, vectors: np.ndarray, metric: str):
        if metric not in ("l2", "cosine"):
            raise ValueError(f"unknown metric {metric!r}")
        self.metric = metric
        self.vectors = vectors
        n = len(vectors)
        self.ins_ts = np.full(n, NEVER, np.int64)
        self.del_ts = np.full(n, NEVER, np.int64)
        self._scan = None

    def append(self, vectors: np.ndarray) -> np.ndarray:
        """Add rows that no write has carried yet; returns their pks.  A
        loop that writes them sets ``ins_ts`` from each acknowledgement."""
        lo = len(self.vectors)
        self.vectors = np.concatenate([self.vectors, vectors.astype(np.float32)])
        fresh = np.full(len(vectors), NEVER, np.int64)
        self.ins_ts = np.concatenate([self.ins_ts, fresh])
        self.del_ts = np.concatenate([self.del_ts, fresh])
        self._scan = None
        return np.arange(lo, len(self.vectors), dtype=np.int64)

    def scan_rows(self) -> np.ndarray:
        """Float32 rows the candidate scan reads (unit rows for cosine)."""
        if self._scan is None:
            self._scan = (_unit(self.vectors).astype(np.float32)
                          if self.metric == "cosine" else self.vectors)
        return self._scan

    def visible(self, lo: int, hi: int, qts: np.ndarray) -> np.ndarray:
        """[Q, hi-lo] visibility of rows lo:hi at each query timestamp."""
        t = qts[:, None]
        return (self.ins_ts[None, lo:hi] <= t) & (self.del_ts[None, lo:hi] > t)

    def exact_scores(self, queries: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Float64 distance (L2) or similarity (cosine) of each query to each
        of its rows; ``rows`` is [Q, m] of pks, -1 gives NaN."""
        safe = np.clip(rows, 0, len(self.vectors) - 1)
        x = self.vectors[safe].astype(np.float64)  # [Q, m, d]
        q = queries.astype(np.float64)[:, None, :]
        if self.metric == "l2":
            out = ((x - q) ** 2).sum(-1)
        else:
            out = (_unit_f64(x) * _unit_f64(q)).sum(-1)
        return np.where(rows >= 0, out, np.nan)


def _unit_f64(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-300)


def _blocked_min(corpus: Corpus, queries: np.ndarray, qts: np.ndarray,
                 c: int, score_block):
    """(scores, pks) of the ``c`` smallest scores per query over the
    visible rows, unordered; empty slots carry (inf, -1)."""
    nq = len(queries)
    best_s = np.full((nq, 0), np.inf, np.float32)
    best_i = np.full((nq, 0), -1, np.int64)
    n = len(corpus.vectors)
    for lo in range(0, n, BLOCK_ROWS):
        hi = min(lo + BLOCK_ROWS, n)
        s = score_block(lo, hi)
        s[~corpus.visible(lo, hi, qts)] = np.inf
        s = np.concatenate([best_s, s], axis=1)
        ids = np.concatenate(
            [best_i, np.broadcast_to(np.arange(lo, hi), (nq, hi - lo))], axis=1)
        if s.shape[1] > c:
            part = np.argpartition(s, c - 1, axis=1)[:, :c]
            s = np.take_along_axis(s, part, 1)
            ids = np.take_along_axis(ids, part, 1)
        best_s, best_i = s, ids
    return best_s, np.where(np.isfinite(best_s), best_i, -1)


def exact_topk(corpus: Corpus, queries: np.ndarray, qts: np.ndarray, k: int):
    """(pks [Q,k], scores [Q,k] float64) of the exact answer, best first:
    ascending distance for L2, descending similarity for cosine."""
    out_i = np.empty((len(queries), k), np.int64)
    out_s = np.empty((len(queries), k), np.float64)
    rows = corpus.scan_rows()
    for a in range(0, len(queries), QUERY_CHUNK):
        q = queries[a:a + QUERY_CHUNK]
        t = qts[a:a + QUERY_CHUNK]
        if corpus.metric == "l2":
            q32 = q.astype(np.float32)

            def score(lo, hi):
                x = rows[lo:hi]
                return (x * x).sum(1)[None, :] - 2.0 * (q32 @ x.T)
        else:
            q32 = _unit(q.astype(np.float32)).astype(np.float32)

            def score(lo, hi):
                return -(q32 @ rows[lo:hi].T)
        _, cand = _blocked_min(corpus, q, t, k + EXTRA, score)
        exact = corpus.exact_scores(q, cand)
        key = np.where(cand >= 0, exact if corpus.metric == "l2" else -exact,
                       np.inf)
        order = np.argsort(key, axis=1, kind="stable")[:, :k]
        out_i[a:a + len(q)] = np.take_along_axis(cand, order, 1)
        out_s[a:a + len(q)] = np.take_along_axis(exact, order, 1)
    return out_i, out_s


def _split_bf16(x: np.ndarray):
    hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, lo


def _dot_high(q: np.ndarray, x: np.ndarray) -> np.ndarray:
    """q @ x.T as the MXU computes it at ``Precision.HIGH``: three bf16
    products (the low x low term dropped) accumulated in float32."""
    qh, ql = _split_bf16(q)
    xh, xl = _split_bf16(x)
    return qh @ xh.T + (qh @ xl.T + ql @ xh.T)


def control_topk(corpus: Corpus, queries: np.ndarray, qts: np.ndarray, k: int):
    """The reference in the program's place at ``Precision.HIGH``: returns
    (scores [Q,k] float32, pks [Q,k]) in the program's own form."""
    out_i = np.empty((len(queries), k), np.int64)
    out_s = np.empty((len(queries), k), np.float32)
    rows = corpus.scan_rows()
    for a in range(0, len(queries), QUERY_CHUNK):
        q = queries[a:a + QUERY_CHUNK].astype(np.float32)
        t = qts[a:a + QUERY_CHUNK]
        if corpus.metric == "l2":
            qn = (q * q).sum(1)[:, None]

            def score(lo, hi):
                x = rows[lo:hi]
                return qn - 2.0 * _dot_high(q, x) + (x * x).sum(1)[None, :]
        else:
            qu = _unit(q).astype(np.float32)

            def score(lo, hi):
                return -_dot_high(qu, rows[lo:hi])
        got, cand = _blocked_min(corpus, q, t, k, score)
        if corpus.metric == "cosine":
            got = -got
        order = np.argsort(got if corpus.metric == "l2" else -got, axis=1,
                           kind="stable")
        out_i[a:a + len(q)] = np.take_along_axis(cand, order, 1)
        out_s[a:a + len(q)] = np.take_along_axis(got, order, 1)
    return out_s, out_i


def compare(corpus: Corpus, queries: np.ndarray, qts: np.ndarray,
            pks: np.ndarray, scores: np.ndarray, k: int) -> dict:
    """The numbers ``correct`` is decided on, for answers ``pks``/``scores``
    [Q, k] to ``queries`` at timestamps ``qts``.  Gaps are relative to the
    exact distance for L2 and absolute for cosine (similarities lie in
    [-1, 1]).

    - ``bad_answers``: slots that name a row not visible at the query's
      timestamp, name a row twice, or are empty while k rows are visible;
    - ``recall``: share of the exact top-k found;
    - ``score_err``: root mean square of the gap between each returned
      score and the exact score of the row it names: the precision the
      scan computes at, and whether a score belongs to its row;
    - ``rank_err``: root mean square of the gap between the score returned
      at rank i and the exact rank-i score: the precision, and whether the
      true top-k came back in order (an exact search only).
    """
    ref_i, ref_s = exact_topk(corpus, queries, qts, k)
    n = len(corpus.vectors)
    inside = (pks >= 0) & (pks < n)
    safe = np.clip(pks, 0, n - 1)
    t = qts[:, None]
    vis = inside & (corpus.ins_ts[safe] <= t) & (corpus.del_ts[safe] > t)
    srt = np.sort(np.where(inside, pks, -1), axis=1)
    dup_rows = ((srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)).sum(1)
    empty = (ref_i >= 0) & ~inside
    bad = int((inside & ~vis).sum()) + int(dup_rows.sum()) + int(empty.sum())
    got = corpus.exact_scores(queries, np.where(vis, pks, -1))
    scores = scores.astype(np.float64)
    if corpus.metric == "l2":
        err = np.abs(scores - got) / np.maximum(got, 1e-12)
        rank = np.abs(scores - ref_s) / np.maximum(ref_s, 1e-12)
    else:
        err = np.abs(scores - got)
        rank = np.abs(scores - ref_s)
    rank = np.where(ref_i >= 0, rank, np.nan)
    return {
        "bad_answers": bad,
        "recall": float(np.mean([len(set(a[a >= 0]) & set(b[b >= 0]))
                                 for a, b in zip(pks, ref_i)]) / k),
        "score_err": _rms(err),
        "rank_err": _rms(rank),
    }


def _rms(x: np.ndarray) -> float | None:
    x = x[np.isfinite(x)]
    return float(np.sqrt(np.mean(x ** 2))) if x.size else None
