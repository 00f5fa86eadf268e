"""Growing-segment rows a search planned over: the ``growing_rows`` count
of its ``plan_search`` spans, summed over its query nodes.  A program
whose spans have no such count reads nothing."""

import spans


def read(run):
    trees = spans.traced(run)
    if not trees or not hasattr(trees[0].root, "growing_rows"):
        return None
    return spans.per_request(
        run, lambda sp: sp.growing_rows if sp.name == "plan_search" else 0)
