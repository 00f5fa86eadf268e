"""Closed loop: one search of ``nq`` queries after another, cycling
through a pool of ``request_pool`` requests, until the window's seconds
have passed."""

import data
import harness


def setup(ctx: harness.Context):
    requests = harness.make_requests(ctx.traffic, ctx.corpus,
                                     int(ctx.traffic["request_pool"]),
                                     data.rng_for(ctx.seed, "queries"))
    harness.warm_up(ctx)
    return requests


def window(ctx: harness.Context, requests) -> harness.Window:
    client, clock = ctx.client, ctx.clock
    win = harness.Window(seconds=float(ctx.seconds), searches=[])
    t0 = clock()
    i = 0
    while clock() - t0 < ctx.seconds:
        rec = harness.Search(due=0.0, queries=requests[i % len(requests)])
        client.search(rec, clock)
        rec.due = rec.send - t0
        rec.send -= t0
        rec.done -= t0
        win.searches.append(rec)
        i += 1
    win.t0, win.t1 = t0, clock()
    win.seconds = win.t1 - t0
    return win
