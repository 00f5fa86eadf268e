"""Public jit'd entry points for the kernel layer.

Dispatch policy: on a TPU backend the Pallas kernels run compiled; on any
other backend the vectorized numpy host paths below run, and the Pallas
bodies are validated against the ``ref.py`` oracles in interpret mode by
the test suite.  The platform is the only selector.

All wrappers here accept un-padded shapes and handle the 128-alignment the
kernels require (pad rows, mask padding as invalid, strip outputs).  An input
is a host array, sent on every call, or, for ``topk_scan``'s base, a
``ResidentOperand`` (``resident``): an immutable array that stays on the
device, already padded, and is sent once for the life of its holder.  A
host array whose length follows the data (a growing tail's rows and mask,
a candidate pool's width) is padded on the host before it is sent: an
eager pad on the device compiles a program for every length it meets.

Under a traced request every kernel call records a ``kernel_<name>`` span
(``_KernelCall``) below the span open in the caller.
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ..core.telemetry import open_span
from . import ref
from .kmeans_assign import kmeans_assign_pallas
from .l2_topk import l2_topk_pallas
from .merge_topk import merge_topk_pallas
from .pq_adc import pq_adc_topk_pallas
from .sq_codec import sq_decode_pallas, sq_encode_pallas, sq_l2_topk_pallas

__all__ = [
    "topk_scan",
    "topk_scan_segmented",
    "merge_topk",
    "isin_sorted",
    "eff_tombstones",
    "tombstone_mask",
    "shard_split",
    "normalized_similarity",
    "hybrid_fuse",
    "range_cut",
    "mask_intersect",
    "post_filter_cut",
    "pq_adc_topk",
    "sq_scale",
    "sq_encode",
    "sq_decode",
    "sq_topk_scan",
    "kmeans_assign",
    "use_pallas",
    "resident",
    "ResidentOperand",
    "ivf_probe_schedule",
    "ivf_gather_topk",
    "IVFBucket",
    "IVFSchedule",
]


@lru_cache(maxsize=1)
def use_pallas() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Host fast paths.  On CPU the jnp oracles pay dispatch/compile overhead per
# ragged shape (IVF lists are ragged); BLAS + argpartition is the idiomatic
# host implementation and is bit-compatible with the oracle semantics.
# ---------------------------------------------------------------------------


def _np_topk_min(scores: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """K smallest along the LAST axis (any leading batch dims)."""
    n = scores.shape[-1]
    k = min(k, n)
    if k >= n:
        idx = np.argsort(scores, axis=-1, kind="stable")[..., :k]
    else:
        part = np.argpartition(scores, k - 1, axis=-1)[..., :k]
        sub = np.take_along_axis(scores, part, -1)
        order = np.argsort(sub, axis=-1, kind="stable")
        idx = np.take_along_axis(part, order, -1)
    return np.take_along_axis(scores, idx, -1), idx


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


class _KernelCall:
    """The ``kernel_<name>`` span of one kernel call in a traced request.

    Two children split it: ``h2d`` runs from the first host input sent
    (``put``) until every input is on the device (``block_until_ready``,
    so that the copy is timed by itself) and counts the bytes sent, and
    apart from them the bytes of the resident operands it read in place;
    ``result_wait`` runs from the kernel's return until its outputs are
    numpy arrays.  The rest is the eager pads, the slicing and the launch.
    """

    __slots__ = ("ctx", "span", "timer", "sent", "resident")

    def __init__(self, ctx, parent, name: str) -> None:
        self.ctx = ctx
        self.span = ctx.span("kernel_" + name, parent=parent)
        self.timer = ctx.timed(self.span)
        self.sent: list = []
        self.resident = 0

    def __enter__(self) -> "_KernelCall":
        self.timer.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self.timer.__exit__(*exc)

    def put(self, x, dtype=None):
        """``x`` on the device; a host array counts its bytes as sent
        (after the dtype conversion), a device array counts none, and a
        resident operand counts its own as resident."""
        if isinstance(x, ResidentOperand):
            self.resident += x.nbytes
            return x.array
        out = jnp.asarray(x, dtype)
        if not isinstance(x, jax.Array):
            self.sent.append(out)
        return out

    @contextmanager
    def h2d(self):
        span = self.ctx.span("h2d", parent=self.span)
        with self.ctx.timed(span):
            yield
            jax.block_until_ready(self.sent)
        span.bytes_h2d = int(sum(a.nbytes for a in self.sent))
        span.bytes_resident = self.resident

    def result_wait(self):
        return self.ctx.timed(self.ctx.span("result_wait", parent=self.span))


class _Untraced:
    """The kernel-call tracer of an untraced request: sends and times
    nothing beyond the call itself.  One shared instance, no allocation."""

    __slots__ = ()

    def __enter__(self) -> "_Untraced":
        return self

    def __exit__(self, *exc) -> None:
        return None

    @staticmethod
    def put(x, dtype=None):
        if isinstance(x, ResidentOperand):
            return x.array
        return jnp.asarray(x, dtype)

    def h2d(self) -> "_Untraced":
        return self

    def result_wait(self) -> "_Untraced":
        return self


_UNTRACED_CALL = _Untraced()


def _kernel_call(name: str):
    """The tracer of one kernel call: a ``_KernelCall`` under the span the
    request has open, or the shared no-op when it is not traced."""
    opened = open_span()
    return _UNTRACED_CALL if opened is None else _KernelCall(*opened, name)


def _pad_rows(arr: jnp.ndarray, multiple: int, fill=0) -> jnp.ndarray:
    n = arr.shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return arr
    widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, widths, constant_values=fill)


def _host_padded(x, multiple: int, dtype, fill=0, axis: int = 0):
    """A host array as ``dtype``, padded with ``fill`` along ``axis`` to a
    multiple of ``multiple``; a device array or ``ResidentOperand`` as it
    is (``_pad_rows`` pads a device array after it is put)."""
    if isinstance(x, (ResidentOperand, jax.Array)):
        return x
    x = np.asarray(x, dtype)
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    shape = list(x.shape)
    shape[axis] += pad
    out = np.full(shape, fill, dtype)
    out[(slice(None),) * axis + (slice(0, x.shape[axis]),)] = x
    return out


def _row_tile(n: int) -> int:
    return 512 if n >= 512 else max(128, 1 << (n - 1).bit_length())


def _choose_tiles(nq: int, n: int) -> tuple[int, int]:
    tq = 128 if nq >= 128 else max(8, 1 << (nq - 1).bit_length())
    return tq, _row_tile(n)


@dataclass(frozen=True, eq=False)
class ResidentOperand:
    """A host array held on the device for as long as this object lives:
    f32, its rows zero-padded to the row tile ``topk_scan`` uses for
    ``shape[0]`` rows, so that a call neither sends nor pads it."""

    array: jax.Array  # [rows padded to _row_tile(rows), ...] float32
    shape: tuple[int, ...]  # the host array's shape, before padding

    @property
    def nbytes(self) -> int:
        """The bytes a call would otherwise send: f32, unpadded."""
        return 4 * int(np.prod(self.shape))


def resident(x):
    """``x`` as a ``ResidentOperand`` for ``topk_scan``'s base where the
    Pallas path runs; elsewhere (and for an empty array) ``x`` itself, so
    the host paths read it as before."""
    if not use_pallas() or len(x) == 0:
        return x
    return ResidentOperand(
        _pad_rows(jnp.asarray(x, jnp.float32), _row_tile(len(x))), tuple(x.shape)
    )


def topk_scan(
    queries,
    base,
    k: int,
    metric: str = "l2",
    valid=None,
) -> tuple[np.ndarray, np.ndarray]:
    """Brute-force top-k scan (the growing-segment / FLAT search path).

    Returns (scores [nq,k], idx [nq,k]); ascending distance for L2,
    descending similarity for IP.  ``valid`` masks rows (MVCC visibility /
    delete bitmap).  Invalid or out-of-range results carry idx == -1.
    ``base`` is a host array or a ``ResidentOperand``, which the kernel
    reads where it lies (already padded: ``_pad_rows`` adds nothing).
    """
    n = base.shape[0]
    if n == 0:
        nq = len(queries)
        fill = np.inf if metric == "l2" else -np.inf
        return (
            np.full((nq, k), fill, np.float32),
            np.full((nq, k), -1, np.int64),
        )
    k_eff = min(k, n)

    if use_pallas():
        tq, tn = _choose_tiles(len(queries), n)
        with _kernel_call("l2_topk") as call:
            with call.h2d():
                queries = call.put(queries, jnp.float32)
                base = call.put(_host_padded(base, tn, np.float32), jnp.float32)
                v = (
                    jnp.ones(n, jnp.int32)
                    if valid is None
                    else call.put(_host_padded(valid, tn, bool, fill=False))
                )
            v = v.astype(jnp.int32)
            qp = _pad_rows(queries, tq)
            bp = _pad_rows(base, tn)
            vp = _pad_rows(v, tn, fill=0)
            vals, idx = l2_topk_pallas(
                qp, bp, vp, k_eff, metric=metric, tq=tq, tn=tn, interpret=_interpret()
            )
            vals, idx = vals[: queries.shape[0]], idx[: queries.shape[0]]
            with call.result_wait():
                vals, idx = np.asarray(vals), np.asarray(idx, np.int64)
    else:
        qn = np.asarray(queries, np.float32)
        bn = np.asarray(base, np.float32)
        if metric == "l2":
            scores = (
                np.sum(qn * qn, axis=1, keepdims=True)
                - 2.0 * qn @ bn.T
                + np.sum(bn * bn, axis=1)[None, :]
            )
        else:
            scores = -(qn @ bn.T)
        if valid is not None:
            scores = np.where(np.asarray(valid, bool)[None, :], scores, np.float32(np.inf))
        vals, idx = _np_topk_min(scores, k_eff)
        if metric == "ip":
            vals = -vals
        idx = idx.astype(np.int64)

    vals = np.asarray(vals, np.float32)
    bad = np.abs(vals) >= 1e38
    idx = np.where(bad, -1, idx)
    if k_eff < k:  # pad out to requested k
        fill = np.inf if metric == "l2" else -np.inf
        vals = np.concatenate(
            [vals, np.full((vals.shape[0], k - k_eff), fill, np.float32)], axis=1
        )
        idx = np.concatenate(
            [idx, np.full((idx.shape[0], k - k_eff), -1, np.int64)], axis=1
        )
    return vals, idx


def topk_scan_segmented(
    queries,
    bases: "list",
    k: int,
    metric: str = "l2",
    valids: "list | None" = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Fused brute-force scan over one execution class of segments.

    Computes a single distance evaluation over the row-concatenation of
    ``bases`` and extracts the per-segment top-k from column slices of
    the shared score matrix — replacing S separate ``topk_scan``
    dispatches (S small gemms + S top-k passes) with one large gemm.
    This is the batched-scan half of the fused search engine; the merge
    half is :func:`merge_topk`.

    Returns (scores [nq, S*k], idx [nq, S*k]) where block
    ``idx[:, s*k:(s+1)*k]`` holds row indices LOCAL to ``bases[s]``
    (-1 = invalid slot), each block BIT-IDENTICAL to
    ``topk_scan(queries, bases[s], k, metric, valid=valids[s])``: the
    per-segment gemm shapes are preserved (cache-blocked execution), and
    the score combine runs as in-place passes whose float semantics match
    the expression in ``topk_scan`` exactly.  The fusion removes the
    per-dispatch overheads instead: query norms are computed once, the
    masking/combine passes allocate no broadcast temporaries, and the
    outputs land directly in the pooled candidate arrays.
    """
    n_seg = len(bases)
    nq = len(queries)
    fill = np.inf if metric == "l2" else -np.inf
    if n_seg == 0:
        return (
            np.full((nq, 0), fill, np.float32),
            np.full((nq, 0), -1, np.int64),
        )
    if valids is None:
        valids = [None] * n_seg
    if use_pallas():
        # TPU path: the scan kernel already streams base tiles through
        # VMEM; per-segment kernel launches keep the same semantics.
        parts = [
            topk_scan(queries, b, k, metric=metric, valid=v)
            for b, v in zip(bases, valids)
        ]
        return (
            np.concatenate([p[0] for p in parts], axis=1),
            np.concatenate([p[1] for p in parts], axis=1),
        )

    qn = np.ascontiguousarray(np.asarray(queries, np.float32))
    q_norm = np.sum(qn * qn, axis=1, keepdims=True) if metric == "l2" else None
    out_v = np.full((nq, n_seg * k), fill, np.float32)
    out_i = np.full((nq, n_seg * k), -1, np.int64)

    def emit_block(s_idx: int, vals: np.ndarray, idx: np.ndarray) -> None:
        if metric == "ip":
            vals = -vals
        vals = np.asarray(vals, np.float32)
        idx = np.where(np.abs(vals) >= 1e38, -1, idx.astype(np.int64))
        lo = s_idx * k
        out_v[:, lo : lo + vals.shape[-1]] = vals
        out_i[:, lo : lo + vals.shape[-1]] = idx

    # Group segments with equal row counts (the common case: slices and
    # seal-sized segments are uniform) so each group runs as ONE batched
    # gemm + ONE batched top-k instead of a per-segment dispatch chain.
    # Per-gemm shapes are preserved, so results stay bit-identical to the
    # per-segment scan.
    groups: dict[int, list[int]] = {}
    for s_idx, b in enumerate(bases):
        groups.setdefault(b.shape[0], []).append(s_idx)

    for n_s, members in groups.items():
        if n_s == 0:
            continue
        k_eff = min(k, n_s)
        # The batched cube pays off while it stays cache-resident (many
        # tiny segments, where per-dispatch overhead dominates); past
        # that, streaming the whole [S,nq,n_s] cube through each pass is
        # DRAM-bound and cache-blocked per-segment execution wins.
        if len(members) == 1 or len(members) * nq * n_s > 1 << 17:
            for s_idx in members:
                bn = np.asarray(bases[s_idx], np.float32)
                scores = qn @ bn.T
                if metric == "l2":
                    # In-place equivalent of q_norm - 2*q@b.T + b_norm (the
                    # float ops commute bitwise with topk_scan's expression).
                    scores *= -2.0
                    scores += q_norm
                    scores += np.sum(bn * bn, axis=1)[None, :]
                else:
                    np.negative(scores, out=scores)
                v = valids[s_idx]
                if v is not None:
                    scores[:, ~np.asarray(v, bool)] = np.float32(np.inf)
                vals, idx = _np_topk_min(scores, k_eff)
                emit_block(s_idx, vals, idx)
            continue
        # Per-slice BLAS gemms into one preallocated cube (numpy's stacked
        # matmul bypasses BLAS); all later passes are batched over [S,nq,n_s].
        n_grp = len(members)
        scores = np.empty((n_grp, nq, n_s), np.float32)
        b_norm = np.empty((n_grp, n_s), np.float32) if metric == "l2" else None
        for g, i in enumerate(members):
            bn = np.asarray(bases[i], np.float32)
            np.matmul(qn, bn.T, out=scores[g])
            if metric == "l2":
                b_norm[g] = np.sum(bn * bn, axis=1)
        if metric == "l2":
            scores *= -2.0
            scores += q_norm[None, :, :]
            scores += b_norm[:, None, :]
        else:
            np.negative(scores, out=scores)
        if any(valids[i] is not None for i in members):
            vstack = np.stack(
                [
                    np.ones(n_s, bool)
                    if valids[i] is None
                    else np.asarray(valids[i], bool)
                    for i in members
                ]
            )
            np.copyto(scores, np.float32(np.inf), where=~vstack[:, None, :])
        vals, idx = _np_topk_min(scores, k_eff)  # [S, nq, k_eff]
        for g, s_idx in enumerate(members):
            emit_block(s_idx, vals[g], idx[g])
    return out_v, out_i


def merge_topk(scores, pks, k: int, metric: str = "l2") -> tuple[np.ndarray, np.ndarray]:
    """Segmented k-way top-k merge with pk-dedup (two-phase reduce, §3.6).

    Merges pooled per-segment / per-node top-k candidates
    (scores [nq, m], pks [nq, m], -1 = empty slot) into the final
    per-query top-k, keeping the best occurrence of each pk.  Candidates
    with pk < 0 or a non-finite score are ignored.  Output slots beyond
    the number of distinct live pks carry pk == -1 and the metric's fill
    score (+inf for L2, -inf for IP).  Ties break by pool column order —
    bit-identical to a stable per-row selection over the pools.
    """
    s = np.asarray(scores, np.float32)
    p = np.asarray(pks)
    nq, m = s.shape
    fill = np.inf if metric == "l2" else -np.inf
    if m == 0 or nq == 0:
        return (
            np.full((nq, k), fill, np.float32),
            np.full((nq, k), -1, np.int64),
        )

    if use_pallas() and (p.size == 0 or np.abs(p).max() < 2**31 - 1):
        # Pools wider than 512 round up to a power of two: a node's pool
        # grows with every temporary slice index, and each width compiles
        # the kernel anew.
        width = 128 if m <= 512 else 1 << (m - 1).bit_length()
        with _kernel_call("merge_topk") as call:
            with call.h2d():
                sp = call.put(_host_padded(s, width, np.float32, fill, axis=1), jnp.float32)
                pp = call.put(_host_padded(p, width, np.int32, -1, axis=1), jnp.int32)
            tq = 128 if nq >= 128 else max(8, 1 << (nq - 1).bit_length())
            tm = next(t for t in (512, 256, 128) if sp.shape[1] % t == 0)
            sp = _pad_rows(sp, tq)
            pp = _pad_rows(pp, tq, fill=-1)
            k_eff = min(k, m)
            vals, opk = merge_topk_pallas(
                sp, pp, k_eff, metric=metric, tq=tq, tm=tm, interpret=_interpret()
            )
            vals, opk = vals[:nq], opk[:nq]
            with call.result_wait():
                vals = np.asarray(vals, np.float32)
                opk = np.asarray(opk, np.int64)
        bad = np.abs(vals) >= 1e38
        vals = np.where(bad, np.float32(fill), vals)
        opk = np.where(bad, -1, opk)
        if k_eff < k:
            vals = np.concatenate(
                [vals, np.full((nq, k - k_eff), fill, np.float32)], axis=1
            )
            opk = np.concatenate([opk, np.full((nq, k - k_eff), -1, np.int64)], axis=1)
        return vals, opk

    # Host fast path: optimistic top-k by packed integer key, then full
    # dedup only for the rows whose top-k actually contains a duplicate
    # pk.  The common case (disjoint pks across segments/nodes) never
    # pays for grouping sorts: one argpartition + one k-wide sort.
    p = p.astype(np.int64, copy=False)
    alive = (p >= 0) & np.isfinite(s)
    key = np.where(alive, s if metric == "l2" else -s, np.float32(np.inf))
    key += np.float32(0.0)  # canonicalize -0.0 -> +0.0 (they compare equal)
    # Order-preserving f32 -> uint32 bit twiddle (IEEE trick: flip all
    # bits of negatives, flip just the sign bit of non-negatives), with
    # the column index in the low bits so a NON-stable uint64 sort
    # reproduces the stable column tie-break.
    u = key.view(np.uint32)
    ub = (u ^ (np.uint32(0x80000000) | (u >> 31) * np.uint32(0x7FFFFFFF))).astype(
        np.uint64
    )
    if m >= 1 << 20:  # column bits would overflow the compound
        return _merge_topk_host_dedup(s, p, key, alive, ub, k, fill, metric)
    cc = (ub << np.uint64(20)) | np.arange(m, dtype=np.uint64)[None, :]
    order = _topk_by_compound(cc, min(k, m))
    out_s, out_p = _merge_gather(s, p, alive, order, nq, m, k, fill)
    # pk-dedup check: rows whose optimistic top-k holds a repeated pk
    # must re-merge with grouping (dropping a duplicate pulls in
    # candidates from beyond the cut).
    p_sorted = np.sort(out_p, axis=1)
    dup_rows = ((p_sorted[:, 1:] == p_sorted[:, :-1]) & (p_sorted[:, 1:] >= 0)).any(1)
    if dup_rows.any():
        r = np.nonzero(dup_rows)[0]
        out_s[r], out_p[r] = _merge_topk_host_widen(
            s[r], p[r], key[r], alive[r], ub[r], cc[r], k, fill, metric
        )
    return out_s, out_p


def _merge_topk_host_widen(s, p, key, alive, ub, cc, k: int, fill, metric: str):
    """Dedup'd merge via iterative widening: gather the k' globally-best
    candidates per row (cc order), dedup inside that small slice, and
    widen k' until every row has k distinct pks (or the pool is spent).

    The slice is gathered in (key, col) order, so positions inside it
    preserve the stable tie-break, and any candidate left outside has a
    strictly worse compound than everything inside — it can neither
    displace a survivor nor improve a kept score.
    """
    nq, m = s.shape
    k_pr = min(m, max(2 * k, k + 8))
    while True:
        order = _topk_by_compound(cc, k_pr)
        out_s, out_p = _merge_topk_host_dedup(
            np.take_along_axis(s, order, 1),
            np.take_along_axis(p, order, 1),
            np.take_along_axis(key, order, 1),
            np.take_along_axis(alive, order, 1),
            np.take_along_axis(ub, order, 1),
            k,
            fill,
            metric,
        )
        if k_pr >= m or not ((out_p >= 0).sum(1) < k).any():
            return out_s, out_p
        k_pr = min(m, 4 * k_pr)


def _merge_topk_host_dedup(s, p, key, alive, ub, k: int, fill, metric: str):
    """Full grouping merge: kill all but the best occurrence of each pk,
    then take the top-k of the survivors."""
    nq, m = s.shape
    if p.min() >= -1 and p.max() < 2**31 - 1:
        # Group by (pk, key): pk+1 in the high 32 bits, key bits low.  The
        # stable sort keeps equal (pk, key) pairs in column order, so the
        # surviving occurrence is the seed merge's (its column position
        # decides later equal-score tie-breaks across pks).
        perm = np.argsort(
            ((p + 1).astype(np.uint64) << np.uint64(32)) | ub, axis=1, kind="stable"
        )
    else:  # pks outside the packable range: two stable float/int sorts
        ord_key = np.argsort(key, axis=1, kind="stable")
        ord_pk = np.argsort(np.take_along_axis(p, ord_key, 1), axis=1, kind="stable")
        perm = np.take_along_axis(ord_key, ord_pk, 1)
    p_grouped = np.take_along_axis(p, perm, 1)
    dup = np.zeros((nq, m), bool)
    dup[:, 1:] = p_grouped[:, 1:] == p_grouped[:, :-1]
    killed = np.empty((nq, m), bool)
    np.put_along_axis(killed, perm, dup, axis=1)  # scatter to column order
    k_take = min(k, m)
    if m < 1 << 20:
        # survivors' top-k by (key, col) compound — argpartition beats a
        # stable float sort and the column bits keep the tie-break stable
        cc = np.where(killed, np.uint64(0xFFFFFFFF), ub)
        cc = (cc << np.uint64(20)) | np.arange(m, dtype=np.uint64)[None, :]
        order = _topk_by_compound(cc, k_take)
    else:
        key = np.where(killed, np.float32(np.inf), key)
        order = np.argsort(key, axis=1, kind="stable")[:, :k_take]
    return _merge_gather(s, p, alive & ~killed, order, nq, m, k, fill)


def _topk_by_compound(cc: np.ndarray, k_take: int) -> np.ndarray:
    """Row-wise indices of the k_take smallest compound keys, ascending.
    Compounds are unique per row (column bits), so the non-stable
    partition+sort reproduces the stable (key, col) order."""
    m = cc.shape[1]
    if k_take >= m:
        return np.argsort(cc, axis=1)
    part = np.argpartition(cc, k_take - 1, axis=1)[:, :k_take]
    sub = np.take_along_axis(cc, part, 1)
    return np.take_along_axis(part, np.argsort(sub, axis=1), 1)


def _merge_gather(s, p, live, order, nq, m, k, fill):
    sel_alive = np.take_along_axis(live, order, 1)
    out_s = np.where(sel_alive, np.take_along_axis(s, order, 1), fill).astype(np.float32)
    out_p = np.where(sel_alive, np.take_along_axis(p, order, 1), -1)
    if m < k:
        out_s = np.concatenate(
            [out_s, np.full((nq, k - m), fill, np.float32)], axis=1
        )
        out_p = np.concatenate([out_p, np.full((nq, k - m), -1, np.int64)], axis=1)
    return out_s, out_p


def isin_sorted(values, sorted_haystack) -> np.ndarray:
    """Vectorized membership of ``values`` in a SORTED 1-D haystack.

    The searchsorted probe replaces ``np.isin``'s internal sort of the
    haystack on every call — the hot shape is one doomed-pk set shared by
    many per-segment pk columns (delta-delete visibility masks, compaction
    rewrites), so the sort is paid once by the caller and each probe is a
    single binary-search pass.
    """
    v = np.asarray(values)
    hay = np.asarray(sorted_haystack)
    if hay.size == 0 or v.size == 0:
        return np.zeros(v.shape, bool)
    idx = np.searchsorted(hay, v)
    return hay[np.minimum(idx, hay.size - 1)] == v


def eff_tombstones(pks, dts, ts: int):
    """Reduce (pk, delete-ts) tombstone pairs to the per-pk *effective*
    delete timestamp at query time ``ts``.

    A row version ``(pk, row_ts)`` is dead at ``ts`` iff some tombstone of
    its pk has ``row_ts < dts <= ts``; that is equivalent to comparing
    against ``max(dts | dts <= ts)``, so one (sorted-unique pks, eff-dts)
    pair per pk captures the whole tombstone history for one query — the
    shape every segment then probes with :func:`tombstone_mask`.  Returns
    ``(pks_sorted, eff_dts)`` or ``None`` when no tombstone applies.
    """
    pks = np.asarray(pks)
    dts = np.asarray(dts, np.int64)
    sel = dts <= ts
    if not sel.any():
        return None
    p, d = pks[sel], dts[sel]
    order = np.lexsort((d, p))
    p, d = p[order], d[order]
    last = np.r_[p[1:] != p[:-1], True] if p.size > 1 else np.ones(1, bool)
    return p[last], d[last]


def tombstone_mask(seg_pks, seg_ts, doomed_pks, doomed_eff) -> np.ndarray:
    """Rows of a segment killed by a materialized tombstone set.

    ``doomed_pks`` is sorted with ``doomed_eff`` aligned (the output of
    :func:`eff_tombstones`); a row dies iff its pk is doomed AND its row
    timestamp predates the effective delete — so a row re-inserted (or
    upserted) after the delete survives, which is what makes one-LSN
    upserts possible.  One binary-search probe per segment, no re-sorts.
    """
    seg_pks = np.asarray(seg_pks)
    if seg_pks.size == 0 or np.asarray(doomed_pks).size == 0:
        return np.zeros(seg_pks.shape, bool)
    idx = np.searchsorted(doomed_pks, seg_pks)
    idx = np.minimum(idx, len(doomed_pks) - 1)
    hit = doomed_pks[idx] == seg_pks
    return hit & (np.asarray(seg_ts, np.int64) < doomed_eff[idx])


def shard_split(shards: np.ndarray, num_shards: int) -> tuple[np.ndarray, np.ndarray]:
    """Group a batch by shard id in one pass: hash once upstream, then
    ``bincount`` + stable ``argsort`` here — no per-row Python loops.

    Returns ``(order, offsets)``: ``order[offsets[s]:offsets[s+1]]`` are
    the original row indices of shard ``s``, in arrival order (the stable
    sort preserves WAL ordering within a shard).
    """
    shards = np.asarray(shards)
    counts = np.bincount(shards, minlength=num_shards)
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    order = np.argsort(shards, kind="stable")
    return order, offsets


def normalized_similarity(scores, metric: str = "l2") -> np.ndarray:
    """Map raw metric scores onto a shared (0, 1] similarity scale.

    Hybrid fusion sums contributions across vector fields, so per-field
    scores must be commensurable and higher-is-better: L2 distances map
    through ``1/(1+d)`` (d clipped at 0 — the gemm expansion can go a few
    ulp negative), cosine through ``(1+s)/2``, and unbounded IP through
    the logistic ``1/(1+exp(-s))``.
    """
    s = np.asarray(scores, np.float32)
    if metric == "l2":
        return 1.0 / (1.0 + np.maximum(s, 0.0))
    if metric == "cosine":
        return (1.0 + s) / 2.0
    return 1.0 / (1.0 + np.exp(-s))


def hybrid_fuse(
    scores_list,
    pks_list,
    k: int,
    metrics,
    weights=None,
    kind: str = "weighted",
    rrf_k: float = 60.0,
) -> tuple[np.ndarray, np.ndarray]:
    """Fuse per-field candidate lists into the hybrid top-k (one shot).

    ``scores_list[f]`` / ``pks_list[f]`` is vector field ``f``'s global
    result (best-first, [nq, m_f], pk < 0 = empty slot).  ``kind`` is
    ``"weighted"`` (weight-scaled sum of :func:`normalized_similarity`)
    or ``"rrf"`` (``w_f / (rrf_k + rank)``, 1-based ranks).  A pk absent
    from a field's list contributes nothing for that field.

    Returns (fused_scores [nq, k] descending, pks [nq, k]); slots beyond
    the number of distinct candidates carry pk == -1 and -inf.  The whole
    reduce is vectorized: per-field contributions are computed in one
    pass, per-(row, pk) sums via one flat-key ``bincount``, and the final
    ranking is one stable argsort — no per-row Python loops.
    """
    n_fields = len(scores_list)
    if n_fields == 0:
        raise ValueError("hybrid_fuse needs at least one field result")
    if weights is None:
        weights = [1.0] * n_fields
    if isinstance(metrics, str):
        metrics = [metrics] * n_fields
    nq = np.asarray(scores_list[0]).shape[0]

    contribs = []
    for f in range(n_fields):
        s = np.asarray(scores_list[f], np.float32)
        p = np.asarray(pks_list[f])
        live = (p >= 0) & np.isfinite(s)
        if kind == "rrf":
            ranks = np.arange(1, s.shape[1] + 1, dtype=np.float64)[None, :]
            c = np.float64(weights[f]) / (np.float64(rrf_k) + ranks)
            c = np.broadcast_to(c, s.shape).copy()
        elif kind == "weighted":
            c = np.float64(weights[f]) * normalized_similarity(s, metrics[f]).astype(
                np.float64
            )
        else:
            raise ValueError(f"unknown fusion kind '{kind}'")
        c[~live] = 0.0
        contribs.append(c)

    P = np.concatenate([np.asarray(p) for p in pks_list], axis=1).astype(np.int64)
    C = np.concatenate(contribs, axis=1)
    m = P.shape[1]
    if nq == 0 or m == 0:
        return (
            np.full((nq, k), -np.inf, np.float32),
            np.full((nq, k), -1, np.int64),
        )
    live = P >= 0
    # Per-(row, pk) sum: one flat-key bincount over all live slots.
    stride = np.int64(max(int(P.max()) + 1, 1))
    rows = np.repeat(np.arange(nq, dtype=np.int64)[:, None], m, axis=1)
    key = rows * stride + np.where(live, P, 0)
    flat_live = live.ravel()
    fused = np.full(nq * m, -np.inf, np.float64)
    if flat_live.any():
        uniq, inv = np.unique(key.ravel()[flat_live], return_inverse=True)
        sums = np.bincount(inv, weights=C.ravel()[flat_live], minlength=len(uniq))
        # Scatter each candidate's sum onto its FIRST occurrence slot; the
        # duplicates stay -inf and sort behind every live candidate.
        pos = np.nonzero(flat_live)[0]
        first = np.full(len(uniq), np.iinfo(np.int64).max, np.int64)
        np.minimum.at(first, inv, pos)
        fused[first] = sums
    fused = fused.reshape(nq, m)
    order = np.argsort(-fused, axis=1, kind="stable")[:, :k]
    out_s = np.take_along_axis(fused, order, axis=1)
    out_p = np.take_along_axis(P, order, axis=1)
    dead = ~np.isfinite(out_s)
    out_p[dead] = -1
    pad = k - out_s.shape[1]
    if pad > 0:
        out_s = np.concatenate([out_s, np.full((nq, pad), -np.inf)], axis=1)
        out_p = np.concatenate([out_p, np.full((nq, pad), -1, np.int64)], axis=1)
    return out_s.astype(np.float32), out_p


def range_cut(
    scores,
    pks,
    metric: str = "l2",
    radius=None,
    range_filter=None,
) -> tuple[np.ndarray, np.ndarray]:
    """Post-scan radius cut (range search).

    Milvus-convention bounds: for L2 (ascending) keep
    ``range_filter <= d < radius``; for IP/cosine (descending) keep
    ``radius < s <= range_filter``.  Either bound may be None.  Cut slots
    become (fill, -1); they are NOT compacted — downstream ``merge_topk``
    drops them.
    """
    s = np.asarray(scores, np.float32)
    p = np.asarray(pks)
    keep = (p >= 0) & np.isfinite(s)
    if metric == "l2":
        fill = np.float32(np.inf)
        if radius is not None:
            keep &= s < radius
        if range_filter is not None:
            keep &= s >= range_filter
    else:
        fill = np.float32(-np.inf)
        if radius is not None:
            keep &= s > radius
        if range_filter is not None:
            keep &= s <= range_filter
    return np.where(keep, s, fill), np.where(keep, p, -1)


def mask_intersect(*masks) -> np.ndarray | None:
    """Fold row bitmaps (filter ∩ visibility ∩ ...) into one validity mask.

    ``None`` operands mean all-visible and are skipped; returns ``None``
    when every operand is ``None``.  Single memory-bound boolean pass —
    the planner combines attribute-filter bitmaps with MVCC/tombstone
    masks through this one op so the cost is bookable.
    """
    out = None
    for m in masks:
        if m is None:
            continue
        m = np.asarray(m, bool)
        out = m.copy() if out is None else (out & m)
    return out


def post_filter_cut(scores, idx, keep, metric: str = "l2") -> tuple[np.ndarray, np.ndarray]:
    """Cut post-filter candidates whose row fails the filter bitmap.

    ``scores/idx [nq, m]`` are a candidate pool with segment-local row ids
    (-1 = empty slot); ``keep [n]`` is the filter bitmap over the segment's
    rows.  Failing slots become (fill, -1) — not compacted; downstream
    ``merge_topk`` drops them, mirroring ``range_cut``.
    """
    s = np.asarray(scores, np.float32)
    i = np.asarray(idx, np.int64)
    km = np.asarray(keep, bool)
    fill = np.float32(np.inf if metric == "l2" else -np.inf)
    alive = i >= 0
    ok = np.zeros(i.shape, bool)
    if km.size and alive.any():
        ok[alive] = km[i[alive]]
    dead = alive & ~ok
    return np.where(dead, fill, s), np.where(dead, -1, i)


def pq_adc_topk(luts, codes, k: int, valid=None) -> tuple[np.ndarray, np.ndarray]:
    """ADC top-k over PQ codes.  luts: [nq, m, ksub]; codes: [n, m]."""
    n = codes.shape[0]
    nq = luts.shape[0]
    if n == 0:
        return np.full((nq, k), np.inf, np.float32), np.full((nq, k), -1, np.int64)
    k_eff = min(k, n)
    if use_pallas():
        with _kernel_call("pq_adc_topk") as call:
            with call.h2d():
                luts = call.put(luts, jnp.float32)
                codes = call.put(codes, jnp.int32)
                v = jnp.ones(n, jnp.int32) if valid is None else call.put(valid)
            v = v.astype(jnp.int32)
            tn = 512 if n >= 512 else max(128, 1 << (n - 1).bit_length())
            cp = _pad_rows(codes, tn)
            vp = _pad_rows(v, tn, fill=0)
            vals, idx = pq_adc_topk_pallas(luts, cp, vp, k_eff, tn=tn, interpret=_interpret())
            with call.result_wait():
                vals, idx = np.asarray(vals), np.asarray(idx, np.int64)
    else:
        ln = np.asarray(luts, np.float32)
        cn = np.asarray(codes, np.int64)
        m = cn.shape[1]
        scores = np.zeros((nq, n), np.float32)
        for j in range(m):
            scores += ln[:, j, cn[:, j]]
        if valid is not None:
            scores = np.where(np.asarray(valid, bool)[None, :], scores, np.float32(np.inf))
        vals, idx = _np_topk_min(scores, k_eff)
        idx = idx.astype(np.int64)
    idx = np.where(np.abs(vals) >= 1e38, -1, idx)
    if k_eff < k:
        vals = np.concatenate(
            [vals, np.full((nq, k - k_eff), np.inf, np.float32)], axis=1
        )
        idx = np.concatenate([idx, np.full((nq, k - k_eff), -1, np.int64)], axis=1)
    return vals, idx


def sq_scale(vmin, vmax) -> np.ndarray:
    """The SQ codec's per-dimension quantization step (single source of
    truth for encode/decode and any fused-dequant scan)."""
    vmin = np.asarray(vmin, np.float32)
    vmax = np.asarray(vmax, np.float32)
    return np.maximum(vmax - vmin, 1e-12) / 255.0


def sq_encode(x, vmin, vmax) -> np.ndarray:
    if use_pallas():
        with _kernel_call("sq_encode") as call:
            with call.h2d():
                x = call.put(x, jnp.float32)
                lo, hi = call.put(vmin), call.put(vmax)
            n = x.shape[0]
            tn = 512 if n >= 512 else max(128, 1 << max(0, (n - 1)).bit_length())
            xp = _pad_rows(x, tn)
            out = sq_encode_pallas(xp, lo, hi, tn=tn, interpret=_interpret())[:n]
            with call.result_wait():
                return np.asarray(out, np.uint8)
    xn = np.asarray(x, np.float32)
    vmin = np.asarray(vmin, np.float32)
    scale = sq_scale(vmin, vmax)
    q = np.round((xn - vmin[None, :]) / scale[None, :])
    return np.clip(q, 0, 255).astype(np.uint8)


def sq_decode(codes, vmin, vmax) -> np.ndarray:
    if use_pallas():
        with _kernel_call("sq_decode") as call:
            with call.h2d():
                codes = call.put(codes)
                lo, hi = call.put(vmin), call.put(vmax)
            n = codes.shape[0]
            tn = 512 if n >= 512 else max(128, 1 << max(0, (n - 1)).bit_length())
            cp = _pad_rows(codes.astype(jnp.int32), tn)
            out = sq_decode_pallas(cp, lo, hi, tn=tn, interpret=_interpret())[:n]
            with call.result_wait():
                return np.asarray(out)
    vmin = np.asarray(vmin, np.float32)
    scale = sq_scale(vmin, vmax)
    return np.asarray(codes, np.float32) * scale[None, :] + vmin[None, :]


def sq_topk_scan(
    queries, codes, vmin, vmax, k: int, metric: str = "l2", valid=None
) -> tuple[np.ndarray, np.ndarray]:
    """Top-k against SQ-compressed base with fused dequantization."""
    n = codes.shape[0]
    nq = len(queries)
    if n == 0:
        fill = np.inf if metric == "l2" else -np.inf
        return np.full((nq, k), fill, np.float32), np.full((nq, k), -1, np.int64)
    k_eff = min(k, n)
    if use_pallas():
        with _kernel_call("sq_l2_topk") as call:
            with call.h2d():
                queries = call.put(queries, jnp.float32)
                codes = call.put(codes)
                lo, hi = call.put(vmin), call.put(vmax)
                v = jnp.ones(n, jnp.int32) if valid is None else call.put(valid)
            v = v.astype(jnp.int32)
            tq, tn = _choose_tiles(nq, n)
            qp = _pad_rows(queries, tq)
            cp = _pad_rows(codes.astype(jnp.int32), tn)
            vp = _pad_rows(v, tn, fill=0)
            vals, idx = sq_l2_topk_pallas(
                qp, cp, lo, hi, vp, k_eff,
                metric=metric, tq=tq, tn=tn, interpret=_interpret(),
            )
            vals, idx = vals[:nq], idx[:nq]
            with call.result_wait():
                vals, idx = np.asarray(vals), np.asarray(idx, np.int64)
    else:
        decoded = sq_decode(np.asarray(codes), vmin, vmax)
        return topk_scan(np.asarray(queries), decoded, k, metric=metric, valid=valid)
    vals, idx = np.asarray(vals), np.asarray(idx, np.int64)
    idx = np.where(np.abs(vals) >= 1e38, -1, idx)
    if k_eff < k:
        fill = np.inf if metric == "l2" else -np.inf
        vals = np.concatenate([vals, np.full((nq, k - k_eff), fill, np.float32)], axis=1)
        idx = np.concatenate([idx, np.full((nq, k - k_eff), -1, np.int64)], axis=1)
    return vals, idx


# ---------------------------------------------------------------------------
# Batched IVF execution: probe inversion + size-bucketed gather-scan.
#
# ``ivf_probe_schedule`` inverts a ``probes [nq, nprobe]`` matrix into a
# deduplicated (list -> query-group) schedule over the index's CSR layout:
# each probed list is scanned ONCE against the padded group of queries that
# probe it.  Scheduled lists are bucketed by quantized (list length, group
# size) levels so the number of distinct padded shapes stays bounded (the
# JIT recompilation budget on TPU; on host it bounds the number of batched
# dispatches), while the actual padded extents are the in-bucket maxima so
# no flops are wasted on the quantization ceiling.  ``ivf_gather_topk`` then
# runs one fused scan per bucket through a caller-supplied scorer and
# scatters per-(query, probe-slot) top-k candidates into a dense pool that
# feeds straight into ``merge_topk``.
# ---------------------------------------------------------------------------


@dataclass
class IVFBucket:
    """One fused-scan work item: B probed lists padded to a common
    (group G, width W) tile.  ``rows`` are absolute row indices into the
    permuted CSR storage (padding clipped to each list's first row, masked
    dead by ``wmask``); ``q_idx``/``slot_idx`` address the candidate pool,
    ``pair_idx`` addresses the schedule's flat pair arrays (per-pair state
    such as PQ residual LUTs)."""

    lists: np.ndarray  # [B] list ids
    lo: np.ndarray  # [B] CSR start offset per list
    lengths: np.ndarray  # [B] rows per list
    rows: np.ndarray  # [B, W] absolute (clipped) storage rows
    wmask: np.ndarray  # [B, W] True = real row
    q_idx: np.ndarray  # [B, G] query index per group slot
    slot_idx: np.ndarray  # [B, G] probe slot per group slot
    pair_idx: np.ndarray  # [B, G] index into schedule pair arrays
    gmask: np.ndarray  # [B, G] True = real (query, list) pair
    full: bool  # True when every [B, W] slot is a real row (no padding)


@dataclass
class IVFSchedule:
    """Inverted probe schedule: flat (query, list) pairs sorted by list id
    plus the bucketed scan work items derived from them."""

    buckets: "list[IVFBucket]"
    pair_q: np.ndarray  # [P] query index per kept pair (list-sorted order)
    pair_list: np.ndarray  # [P] list id per kept pair (sorted)
    nq: int
    nprobe: int


def _pow2_ceil(x: np.ndarray) -> np.ndarray:
    """Elementwise smallest power of two >= x (x >= 1)."""
    e = np.ceil(np.log2(np.maximum(np.asarray(x, np.int64), 1))).astype(np.int64)
    return np.left_shift(np.int64(1), e)


def _bucket_quantum(x: np.ndarray) -> np.ndarray:
    """Quantize up to {1, 2, 3, 4, 6, 8, 12, 16, ...}: powers of two plus
    their midpoints.  Finer than pow2-only buckets (padding waste <= 1.33x
    instead of 2x on the scan's hot axes) while the level count stays
    logarithmic, keeping the distinct padded shapes bounded."""
    p2 = _pow2_ceil(x)
    mid = (p2 >> 1) + (p2 >> 2)  # 0.75 * p2
    return np.where(np.asarray(x) <= mid, np.maximum(mid, 1), p2)


_SMALL_TILE_W = 128  # lists at or below this width share one tile class
_COARSE_W = 512  # below this width, padding is cheaper than dispatches


def _pow4_ceil(x: np.ndarray) -> np.ndarray:
    """Smallest power of FOUR >= x: the coarse group-size ladder."""
    e = np.ceil(np.log2(np.maximum(np.asarray(x, np.int64), 1))).astype(np.int64)
    return np.left_shift(np.int64(1), (e + 1) >> 1 << 1)


def ivf_probe_schedule(
    probes, list_offsets, max_tile_rows: int = 1 << 17
) -> IVFSchedule:
    """Invert ``probes [nq, nprobe]`` into a bucketed gather-scan schedule.

    Padded probe slots (id -1, emitted by ``topk_scan`` when fewer than
    ``nprobe`` lists exist) and empty lists are dropped up front — the
    corresponding pool slots simply stay at their fill value.  All steps
    are vectorized; the only Python iteration is over the bounded set of
    (length-bucket, group-bucket) keys and their ``max_tile_rows`` chunks.
    """
    probes = np.asarray(probes)
    offsets = np.asarray(list_offsets, np.int64)
    nq, nprobe = probes.shape
    lengths_all = np.diff(offsets)
    nlist = len(lengths_all)

    pair_list = probes.reshape(-1).astype(np.int64)
    pair_q = np.repeat(np.arange(nq, dtype=np.int64), nprobe)
    pair_slot = np.tile(np.arange(nprobe, dtype=np.int64), nq)
    ok = (pair_list >= 0) & (pair_list < nlist)
    if nlist:
        ok &= lengths_all[np.clip(pair_list, 0, nlist - 1)] > 0
    pair_list, pair_q, pair_slot = pair_list[ok], pair_q[ok], pair_slot[ok]

    order = np.argsort(pair_list, kind="stable")
    pl, pq, ps = pair_list[order], pair_q[order], pair_slot[order]
    sched = IVFSchedule([], pq, pl, nq, nprobe)
    if pl.size == 0:
        return sched

    ulists, starts, counts = np.unique(pl, return_index=True, return_counts=True)
    ulen = lengths_all[ulists]
    # Adaptive bucket granularity: for big tiles padding waste is the cost
    # (fine levels); for small ones the per-bucket dispatch is (coarse
    # levels + a shared width floor), keeping cells AND bucket count low.
    wq = np.maximum(_bucket_quantum(ulen), _SMALL_TILE_W)
    gq = np.where(
        wq <= _COARSE_W, _pow4_ceil(counts), _bucket_quantum(counts)
    )
    bkey = wq << np.int64(32) | gq
    for key in np.unique(bkey):  # bounded: one per (W, G) power-of-2 pair
        mem = np.nonzero(bkey == key)[0]
        w = int(ulen[mem].max())
        g = int(counts[mem].max())
        chunk = max(1, max_tile_rows // max(w, 1))
        for c0 in range(0, len(mem), chunk):
            mm = mem[c0 : c0 + chunk]
            lo = offsets[ulists[mm]]
            ln = ulen[mm]
            wmask = np.arange(w)[None, :] < ln[:, None]
            rows = np.where(wmask, lo[:, None] + np.arange(w)[None, :], lo[:, None])
            gpos = starts[mm][:, None] + np.arange(g)[None, :]
            gmask = np.arange(g)[None, :] < counts[mm][:, None]
            gpos = np.minimum(gpos, pl.size - 1)
            sched.buckets.append(
                IVFBucket(
                    lists=ulists[mm],
                    lo=lo,
                    lengths=ln,
                    rows=rows,
                    wmask=wmask,
                    q_idx=pq[gpos],
                    slot_idx=ps[gpos],
                    pair_idx=gpos,
                    gmask=gmask,
                    full=bool(ln.min() == w),
                )
            )
    return sched


def ivf_gather_topk(
    schedule: IVFSchedule, k: int, score_bucket
) -> tuple[np.ndarray, np.ndarray]:
    """Run a probe schedule's fused scans and pool per-pair top-k.

    ``score_bucket(bucket) -> scores [B, G, W]`` must return min-semantics
    scores (L2 distance, or negated similarity for IP) with dead slots
    (padding, invisible rows) at +inf.  Returns
    ``(pool_scores [nq, nprobe*k], pool_rows [nq, nprobe*k])`` where block
    ``[:, j*k:(j+1)*k]`` holds probe slot j's candidates: min-semantics
    scores (+inf fill) and absolute rows into the permuted CSR storage
    (-1 fill) — ready for a ``merge_topk`` reduce after the caller maps
    rows to ids and flips sign for descending metrics.
    """
    nq, nprobe = schedule.nq, schedule.nprobe
    pool_s = np.full((nq, nprobe, k), np.inf, np.float32)
    pool_r = np.full((nq, nprobe, k), -1, np.int64)
    for b in schedule.buckets:
        scores = score_bucket(b)  # [B, G, W]
        k_eff = min(k, scores.shape[2])
        vals, idx = _np_topk_min(scores, k_eff)
        idx += b.lo[:, None, None]  # local offsets -> absolute rows
        np.copyto(idx, -1, where=vals >= np.float32(1e38))  # dead slots
        sel = b.gmask
        qi, si = b.q_idx[sel], b.slot_idx[sel]
        pool_s[qi, si, :k_eff] = vals[sel]
        pool_r[qi, si, :k_eff] = idx[sel]
    return pool_s.reshape(nq, nprobe * k), pool_r.reshape(nq, nprobe * k)


def kmeans_assign(x, centroids) -> tuple[np.ndarray, np.ndarray]:
    """Nearest-centroid assignment: returns (assign [n] int, sqdist [n])."""
    n, ncent = x.shape[0], centroids.shape[0]
    if use_pallas():
        with _kernel_call("kmeans_assign") as call:
            with call.h2d():
                x = call.put(x, jnp.float32)
                c = call.put(centroids, jnp.float32)
            tn = 512 if n >= 512 else max(128, 1 << (max(n, 2) - 1).bit_length())
            tc = 512 if ncent >= 512 else max(128, 1 << (max(ncent, 2) - 1).bit_length())
            xp = _pad_rows(x, tn)
            # pad centroids with far-away sentinels so they never win
            pad = (-ncent) % tc
            if pad:
                c = jnp.concatenate([c, jnp.full((pad, c.shape[1]), 1e18, jnp.float32)])
            a, d = kmeans_assign_pallas(xp, c, tn=tn, tc=tc, interpret=_interpret())
            a, d = a[:n], d[:n]
            with call.result_wait():
                return np.asarray(a, np.int64), np.asarray(d)
    xn = np.asarray(x, np.float32)
    cn = np.asarray(centroids, np.float32)
    d2 = (
        np.sum(xn * xn, axis=1, keepdims=True)
        - 2.0 * xn @ cn.T
        + np.sum(cn * cn, axis=1)[None, :]
    )
    return np.argmin(d2, axis=1).astype(np.int64), np.min(d2, axis=1)
