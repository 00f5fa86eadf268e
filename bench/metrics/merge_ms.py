"""Reduce time per search (ms): the query nodes' ``node_merge_topk`` and
the proxy's global ``merge_topk`` spans."""


def read(run):
    traced = [s.trace for s in run.answered if s.trace is not None]
    if not traced:
        return None
    us = sum(sp.duration_us for t in traced for sp in t.walk()
             if sp.name in ("node_merge_topk", "merge_topk"))
    return us / 1e3 / len(traced)
