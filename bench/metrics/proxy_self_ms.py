"""Proxy and facade self time per search (ms): each request's wall time
on the client minus the time its query-node dispatches and the proxy's
global reduce took, from the request span trees."""


def read(run):
    total, n = 0.0, 0
    for s in run.answered:
        if s.trace is None:
            continue
        child_us = sum(sp.duration_us for sp in s.trace.root.children
                       if sp.name in ("dispatch", "hedge_dispatch", "merge_topk"))
        total += s.wall_s * 1e3 - child_us / 1e3
        n += 1
    return total / n if n else None
