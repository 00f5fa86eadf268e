"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; see ``harness.py`` for what a run does.  Without a TPU, or
with fewer chips than the cell asks for, the run exits 2 before any work
and prints no result.  Every output line but the last is one JSON object
describing a phase (set-up, window); the last is the result.  The numbers
``correct`` was decided on are printed with their limits as the last lines
of standard error.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import spec  # noqa: E402


def use_compile_cache(jax) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``JAX_COMPILATION_CACHE_DIR`` wins when set), keeping every program,
    however quick to compile, so that only a cell's first run compiles."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_chips(jax, chips: int) -> str | None:
    """None when this host has a TPU with at least ``chips`` chips, else why
    not."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return f"no TPU found (platform {devices[0].platform!r})"
    if len(devices) < chips:
        return f"{chips} chips asked, {len(devices)} found"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds < 1:
        ap.error("--seconds must be at least 1")

    cell = spec.load_cell(args.workload)
    import jax

    why = find_chips(jax, cell.chips)
    if why:
        print(f"bench: {why}", file=sys.stderr)
        return 2
    use_compile_cache(jax)

    import harness

    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              PROCESS_START)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
