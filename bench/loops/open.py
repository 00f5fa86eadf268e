"""Open loop: single searches due at the times ``harness.schedule`` draws
from the traffic's ``rate_per_s``, served first in first out by the run's
one client thread, each timed from its due time, so a stall charges every
search queued behind it.  Searches more than ``max_backlog_s`` late are
not sent and count as failed."""

import time

import data
import harness


def setup(ctx: harness.Context):
    due = harness.schedule(ctx.traffic, ctx.seconds)
    requests = harness.make_requests(ctx.traffic, ctx.corpus, len(due),
                                     data.rng_for(ctx.seed, "queries"))
    harness.warm_up(ctx)
    return due, requests


def window(ctx: harness.Context, state) -> harness.Window:
    due, requests = state
    client, clock = ctx.client, ctx.clock
    max_backlog = float(ctx.traffic["max_backlog_s"])
    win = harness.Window(seconds=float(ctx.seconds), searches=[])
    t0 = clock() + 0.01
    for i, d in enumerate(due):
        d = t0 + float(d)
        now = clock()
        slept = now < d
        if slept:
            with client.annotate("bench.wait"):
                time.sleep(d - now)
        elif now - d > max_backlog:
            win.unsent = len(due) - i
            break
        rec = harness.Search(due=d, queries=requests[i])
        client.search(rec, clock)
        if slept:  # the client was idle: any lateness is the generator's
            win.generator_late_s.append(rec.send - d)
        win.searches.append(rec)
    win.t0, win.t1 = t0, clock()
    for rec in win.searches:
        rec.due -= t0
        rec.send -= t0
        rec.done -= t0
    return win
