"""Find a streaming cell's knee: load once, then run the cell's own
``streaming`` window at each search rate in turn, each window holding a
seal, an index build and a hand-off per shard as the cell's does.

    python bench/sweep_streaming.py --workload <cell> --seed <n> --seconds 51 --rates 3,4,5

Before each window the growing tail is topped up (``grow_tail``) so that
every shard's growing segment is as many rows short of ``seal_rows`` as
the window's inserts bring a shard in half the window, the state the
cell's own set-up leaves.  One line per rate: latency, how late the last
event was sent and the most any was (a backlog that grows with the window,
or one the seal, build and hand-off leave), seals and builds in the window,
the longest tick gap, and failures.  The knee is the highest rate whose
last search is sent less than ``OVERLOAD_LAG_S`` late, with nothing cut:
the backlog the window built has drained by its end.  The cell runs at
about four fifths of it.  The sweep stops after the first rate past the
knee: higher rates only pile on.  This
tool is how that rate was found, not part of a run.
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

OVERLOAD_LAG_S = 1.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "streaming":
        print(f"sweep_streaming: {args.workload} runs the "
              f"'{cell.traffic['loop']}' loop", file=sys.stderr)
        return 2
    import jax

    why = run.find_chips(jax, cell.chips)
    if why:
        print(f"sweep_streaming: {why}", file=sys.stderr)
        return 2
    run.use_compile_cache(jax)
    import harness

    clock = time.perf_counter
    config, traffic = cell.config, cell.traffic
    loop = spec.loop_module("streaming")
    corpus = harness.make_corpus(config, args.seed)
    n_base = len(corpus.vectors)
    manu, coll = harness.build_system(config)
    harness.load(manu, coll, corpus, config)
    ctx = harness.Context(traffic=traffic, seconds=args.seconds, seed=args.seed,
                          corpus=corpus, coll=coll,
                          client=harness.Client(coll, traffic, traced=False))
    shards, seal = int(manu.config.num_shards), int(manu.config.seal_rows)
    per_window = int(round(float(traffic["insert_rows_per_s"]) * args.seconds))
    fresh = 0  # rows inserted since the load, spread evenly over the shards
    t = clock()
    n = loop.tail_rows(ctx)
    loop.grow_tail(ctx, n)
    fresh += n
    harness.warm_up(ctx)
    loop.warm_shapes(ctx)
    print(json.dumps({"phase": "setup", "setup_s": clock() - PROCESS_START,
                      "tail_s": clock() - t}), flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        if i:
            # Each shard as far short of its seal as the set-up leaves it.
            want = loop.tail_rows(ctx) // shards
            n = shards * ((want - fresh // shards) % seal)
            loop.grow_tail(dataclasses.replace(ctx, seed=args.seed + i), n)
            fresh += n
        wctx = dataclasses.replace(ctx, traffic=dict(traffic, rate_per_s=rate),
                                   seed=args.seed + i)
        events = loop.draw(wctx, n_base)
        seals0 = harness.counter(manu, "data_node_segments_sealed_total")
        builds0 = harness.counter(manu, "index_builds_total")
        win = loop.window(wctx, events)
        fresh += per_window
        searches = win.searches
        last = searches[-1] if searches else None
        lag = last.send - last.due if last else None
        print(json.dumps({
            "rate_per_s": rate, "topped_up_rows": n,
            "offered": sum(1 for e in events if e[1] == 0),
            "answered": len(win.answered), "unsent": win.unsent,
            "search_errors": sorted({s.error for s in searches if s.error})[:3],
            "write_errors": win.write_errors[:3],
            "elapsed_s": win.t1 - win.t0,
            "last_send_lag_s": lag,
            "max_send_lag_s": max((s.send - s.due for s in searches), default=None),
            "seals": harness.counter(manu, "data_node_segments_sealed_total") - seals0,
            "builds": harness.counter(manu, "index_builds_total") - builds0,
            "tick_gap_max_ms": win.tick_gap_max_ms,
            **harness.percentiles_ms(win)}), flush=True)
        if (win.unsent or lag is None or lag > OVERLOAD_LAG_S
                or any(s.error for s in searches)):
            break
    manu.stop_threads()
    return 0


if __name__ == "__main__":
    sys.exit(main())
