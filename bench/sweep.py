"""Find a cell's knee: load once, then offer open-loop search traffic at
each rate in turn and report latency and backlog per rate.
A rate given twice runs twice, with other queries: the spread between
windows of one process.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 10,20,40

The knee is the highest offered rate at which completions keep pace: the
backlog at the end of the window (how late the last request was sent) does
not grow with the window.  Cells run at a fixed rate below it; this tool
is how that rate was found, not part of a run.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run  # noqa: E402
import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax

    why = run.find_chips(jax, cell.chips)
    if why:
        print(f"sweep: {why}", file=sys.stderr)
        return 2
    run.use_compile_cache(jax)
    import data
    import harness

    clock = time.perf_counter
    config, traffic = cell.config, cell.traffic
    corpus = harness.make_corpus(config, args.seed)
    manu, coll = harness.build_system(config)
    harness.load(manu, coll, corpus, config)
    ctx = harness.Context(traffic=traffic, seconds=args.seconds, seed=args.seed,
                          corpus=corpus, coll=coll,
                          client=harness.Client(coll, traffic, traced=False))
    loop = spec.loop_module("open")
    harness.warm_up(ctx)
    print(json.dumps({"phase": "setup", "setup_s": clock() - PROCESS_START}),
          flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        t = dict(traffic, rate_per_s=rate)
        due = harness.schedule(t, args.seconds)
        reqs = harness.make_requests(t, corpus, len(due),
                                     data.rng_for(args.seed + i, "queries"))
        win = loop.window(dataclasses.replace(ctx, traffic=t), (due, reqs))
        last = win.searches[-1]
        print(json.dumps({
            "rate_per_s": rate, "offered": len(due), "answered": len(win.answered),
            "unsent": win.unsent, "elapsed_s": win.t1 - win.t0,
            "last_send_lag_s": last.send - last.due,
            **harness.percentiles_ms(win)}), flush=True)
    manu.stop_threads()
    return 0


if __name__ == "__main__":
    sys.exit(main())
