"""Query-node planning time per search (ms): the ``plan_search`` spans."""


def read(run):
    traced = [s.trace for s in run.answered if s.trace is not None]
    if not traced:
        return None
    us = sum(sp.duration_us for t in traced for sp in t.spans_named("plan_search"))
    return us / 1e3 / len(traced)
