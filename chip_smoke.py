"""Bring-up smoke run: the vector store's main path on a TPU.

    python chip_smoke.py             # one chip: FLAT and IVF_FLAT collections
    python chip_smoke.py --chips 4   # four chips: segment-parallel mesh search only

One chip.  A SIFT1M-shaped deployment (ANN-Benchmarks ``sift-128-euclidean``:
1,000,000 rows of 128-d float32 under L2, generated from a seed) is loaded
through ``ManuSystem`` into two collections, one FLAT and one IVF_FLAT
(nlist=1024, nprobe=32), on a cluster of two query nodes that seals
segments at 131,072 rows.  Rows go in as 10k-row inserts; after sealing and
index builds, 10k more rows leave a growing tail and 1% of the pks are
deleted.  Three batches of 128 queries at k=10 and one at k=100 run at
STRONG consistency and are checked against exact search over the visible
rows: FLAT recall@k >= 0.999 with every distance within 1e-3 relative of
the reference at its rank, IVF_FLAT recall@10 >= 0.95, no deleted pk
returned, and a compiled variant of each main-path kernel.

Four chips.  ``distributed_search_host`` (the base row-sharded over a
4-chip mesh, an all-gather of per-shard top-k) over the same 1M x 128 base,
compared with the single-chip ``ops.topk_scan``: ids equal except at ties.

Every output line but the last is one JSON object for one phase; wall
times in it are set-up times, not speed.  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU the script exits 2 before
doing any work; a failed check exits 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import (  # noqa: E402
    queries_from,
    recall_of,
    sift_like,
    use_compile_cache,
)
from repro.core import (  # noqa: E402
    ConsistencyLevel,
    ManuConfig,
    ManuSystem,
    Metric,
    SearchRequest,
)
from repro.kernels import ops  # noqa: E402
from repro.kernels.kmeans_assign import kmeans_assign_pallas  # noqa: E402
from repro.kernels.l2_topk import l2_topk_pallas  # noqa: E402
from repro.kernels.merge_topk import merge_topk_pallas  # noqa: E402

DIM = 128
FLAT_RECALL_FLOOR = 0.999
FLAT_DIST_RTOL = 1e-3
IVF_RECALL_FLOOR = 0.95
MAIN_PATH_KERNELS = {
    "l2_topk": l2_topk_pallas,
    "merge_topk": merge_topk_pallas,
    "kmeans_assign": kmeans_assign_pallas,
}


def exact_topk(base, visible, queries, k):
    """Exact L2 top-k over the visible rows, in float64: (dist, row)."""
    q = queries.astype(np.float64)
    x = base.astype(np.float64)
    d = (q * q).sum(1)[:, None] - 2.0 * q @ x.T + (x * x).sum(1)[None, :]
    d[:, ~visible] = np.inf
    part = np.argpartition(d, k - 1, axis=1)[:, :k]
    order = np.argsort(np.take_along_axis(d, part, 1), axis=1, kind="stable")
    rows = np.take_along_axis(part, order, 1)
    return np.take_along_axis(d, rows, 1), rows


def run_store_phase(
    *,
    rows: int,
    tail_rows: int,
    batch_rows: int,
    seal_rows: int,
    nlist: int,
    nprobe: int,
    nq: int,
    seed: int,
    emit,
) -> list[str]:
    """Load, index and search the FLAT and IVF_FLAT collections through
    ``ManuSystem`` and check every answer; returns the failed checks."""
    failures: list[str] = []
    t0 = time.perf_counter()
    base = sift_like(rows + tail_rows, DIM, seed)
    pks = np.arange(len(base), dtype=np.int64)  # pk == row of ``base``
    emit({"phase": "data", "rows": len(base), "dim": DIM,
          "setup_wall_s": time.perf_counter() - t0})

    manu = ManuSystem(ManuConfig(num_query_nodes=2, seal_rows=seal_rows))
    specs = {
        "flat": ("flat", {}),
        "ivf_flat": ("ivf_flat", {"nlist": nlist, "nprobe": nprobe}),
    }
    colls = {}
    for name, (kind, params) in specs.items():
        t0 = time.perf_counter()
        coll = manu.create_collection(f"smoke_{name}", dim=DIM, metric=Metric.L2)
        coll.create_index("vector", kind=kind, params=params)
        for lo in range(0, rows, batch_rows):
            hi = min(lo + batch_rows, rows)
            coll.insert({"pk": pks[lo:hi], "vector": base[lo:hi]})
        t_ingest = time.perf_counter() - t0
        t0 = time.perf_counter()
        coll.flush()  # seal everything, wait for binlogs and index builds
        emit({"phase": "load", "collection": name, "index": kind, **params,
              "rows": rows,
              "sealed_segments": len(manu.data_coord.sealed_segments(coll.name)),
              "ingest_setup_wall_s": t_ingest,
              "seal_index_setup_wall_s": time.perf_counter() - t0})
        colls[name] = coll

    rng = np.random.default_rng(seed + 1)
    deleted = np.sort(rng.choice(len(base), len(base) // 100, replace=False))
    for name, coll in colls.items():
        t0 = time.perf_counter()
        coll.insert({"pk": pks[rows:], "vector": base[rows:]})  # growing tail
        coll.delete(deleted)
        emit({"phase": "tail_and_delete", "collection": name,
              "tail_rows": tail_rows, "deleted": len(deleted),
              "entities": coll.num_entities(),
              "setup_wall_s": time.perf_counter() - t0})
    visible = np.ones(len(base), bool)
    visible[deleted] = False

    batches = [(10, seed + 10 + b) for b in range(3)] + [(100, seed + 20)]
    for k, qseed in batches:
        queries = queries_from(base, nq, seed=qseed)
        ref_d, ref_rows = exact_topk(base, visible, queries, k)
        for name, coll in colls.items():
            t0 = time.perf_counter()
            res = coll.search(SearchRequest.single(
                queries, k=k, consistency=ConsistencyLevel.STRONG))
            wall = time.perf_counter() - t0
            got = np.asarray(res.pks)
            rec = recall_of(got, ref_rows)
            n_deleted = int(np.isin(got, deleted).sum())
            line = {"phase": "search", "collection": name, "k": k, "nq": nq,
                    "recall": rec, "deleted_returned": n_deleted,
                    "setup_wall_s": wall}
            if n_deleted:
                failures.append(f"{name} k={k}: {n_deleted} deleted pks returned")
            if name == "flat":
                close = np.abs(np.asarray(res.scores) - ref_d) <= FLAT_DIST_RTOL * ref_d
                line["dist_within_rtol"] = float(close.mean())
                if rec < FLAT_RECALL_FLOOR:
                    failures.append(f"flat k={k}: recall {rec} < {FLAT_RECALL_FLOOR}")
                if not close.all():
                    failures.append(
                        f"flat k={k}: {int((~close).sum())} distances off by "
                        f"more than {FLAT_DIST_RTOL} relative")
            elif k == 10 and rec < IVF_RECALL_FLOOR:
                failures.append(f"ivf_flat k=10: recall {rec} < {IVF_RECALL_FLOOR}")
            emit(line)

    variants = {name: fn._cache_size() for name, fn in MAIN_PATH_KERNELS.items()}
    emit({"phase": "kernels", "compiled_variants": variants})
    failures += [f"kernel {n} never compiled" for n, c in variants.items() if c < 1]
    return failures


def run_mesh_phase(*, rows: int, nq: int, k: int, seed: int, emit) -> list[str]:
    """Segment-parallel search over every chip vs the single-chip scan."""
    from repro.distributed.search import distributed_search_host

    base = sift_like(rows, DIM, seed)
    queries = queries_from(base, nq, seed=seed + 10)
    t0 = time.perf_counter()
    mesh_d, mesh_i = distributed_search_host(queries, base, k)
    t_mesh = time.perf_counter() - t0
    t0 = time.perf_counter()
    one_d, one_i = ops.topk_scan(queries, base, k)
    t_one = time.perf_counter() - t0
    ref_d, ref_rows = exact_topk(base, np.ones(rows, bool), queries, k)

    # A differing id is a tie only if both ids lie at the same exact distance
    # (to f32 resolution): two correct scans may order such rows differently.
    q = queries.astype(np.float64)

    def exact(rows_):
        x = base[rows_].astype(np.float64)
        return ((x - q[:, None, :]) ** 2).sum(-1)

    differ = mesh_i != one_i
    tie = np.isclose(exact(mesh_i), exact(one_i), rtol=1e-6, atol=0)
    untied = int((differ & ~tie).sum())
    emit({"phase": "mesh_search", "devices": jax.device_count(), "rows": rows,
          "nq": nq, "k": k, "ids_differ": int(differ.sum()),
          "ids_differ_untied": untied,
          "recall_mesh": recall_of(mesh_i, ref_rows),
          "recall_one_chip": recall_of(one_i, ref_rows),
          "mesh_setup_wall_s": t_mesh, "one_chip_setup_wall_s": t_one})
    failures = []
    if untied:
        failures.append(f"mesh and one-chip ids differ at {untied} untied slots")
    if not np.allclose(mesh_d, one_d, rtol=FLAT_DIST_RTOL, atol=0):
        failures.append("mesh and one-chip distances disagree")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 2
    use_compile_cache()

    def emit(obj):
        print(json.dumps(obj), flush=True)

    emit({"phase": "device", "platform": platform,
          "kind": devices[0].device_kind, "count": len(devices)})
    if args.chips == 4:
        failures = run_mesh_phase(rows=1_000_000, nq=128, k=10, seed=args.seed,
                                  emit=emit)
    else:
        failures = run_store_phase(
            rows=1_000_000, tail_rows=10_000, batch_rows=10_000,
            seal_rows=131_072, nlist=1024, nprobe=32, nq=128, seed=args.seed,
            emit=emit)
    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
