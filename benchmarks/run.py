# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV and writes the same rows to a ``BENCH_results.json`` trajectory file
# (per-row name/value/units) so CI and future PRs have a perf baseline to
# diff against.

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import traceback

from . import (
    fig6_mixed_workload,
    fig8_recall_throughput,
    fig9_elasticity,
    fig10_scaling_nodes,
    fig11_scaling_data,
    fig12_grace_time,
    fig13_index_build,
    fig_compaction,
    fig_filtered,
    fig_ingest,
    fig_recovery,
    kernels_micro,
)
from .common import emit, use_compile_cache

MODULES = [
    ("fig6", fig6_mixed_workload),
    ("fig8", fig8_recall_throughput),
    ("fig9", fig9_elasticity),
    ("fig10", fig10_scaling_nodes),
    ("fig11", fig11_scaling_data),
    ("fig12", fig12_grace_time),
    ("fig13", fig13_index_build),
    ("fig_compaction", fig_compaction),
    ("fig_filtered", fig_filtered),
    ("fig_ingest", fig_ingest),
    ("fig_recovery", fig_recovery),
    ("kernels", kernels_micro),
]


def run_metadata() -> dict:
    """Environment fingerprint stamped on every results file so a perf
    diff across runs can tell code changes from environment drift."""
    meta: dict = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "hostname": socket.gethostname(),
        "python": sys.version.split()[0],
    }
    try:
        meta["git_commit"] = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=5,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or None
    except Exception:
        meta["git_commit"] = None
    try:
        import numpy as _np

        meta["numpy"] = _np.__version__
    except Exception:
        meta["numpy"] = None
    try:
        import jax as _jax

        meta["jax"] = _jax.__version__
    except Exception:
        meta["jax"] = None
    return meta


def write_results(all_rows: "list[tuple[str, float, str]]", path: str) -> None:
    """Persist the benchmark trajectory: one entry per emitted row."""
    payload = {
        "schema": "repro-bench/v1",
        "smoke": os.environ.get("REPRO_BENCH_SMOKE") == "1",
        "meta": run_metadata(),
        "rows": [
            {"name": name, "value": round(us, 3), "units": "us_per_call",
             "derived": derived}
            for name, us, derived in all_rows
        ],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")


def main() -> None:
    only = set(sys.argv[1:])
    unknown = only - {tag for tag, _ in MODULES}
    if unknown:  # a typo'd tag must not pass as an empty (green) run
        print(f"# unknown benchmark tags: {sorted(unknown)}", file=sys.stderr)
        sys.exit(2)
    use_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    all_rows: list[tuple[str, float, str]] = []
    for tag, mod in MODULES:
        if only and tag not in only:
            continue
        t0 = time.time()
        try:
            rows = mod.main()
            emit(rows)
            all_rows += rows
            print(f"# {tag} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            failures += 1
            print(f"# {tag} FAILED", file=sys.stderr)
            traceback.print_exc()
    out = os.environ.get("REPRO_BENCH_OUT", "BENCH_results.json")
    write_results(all_rows, out)
    print(f"# wrote {out} ({len(all_rows)} rows)", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
