"""Per-request totals over the program's own request span trees
(``SearchRequest(trace=True)``), for the readers in ``metrics/``.

A program that records no kernel-call spans (its ``Span`` has no
``bytes_h2d``) reads nothing: ``None``, where a program that records them
and had nothing to record in a window reads 0."""


def traced(run) -> list:
    """The span trees of the window's answered requests, or [] where the
    program records no kernel-call spans."""
    trees = [s.trace for s in run.answered if s.trace is not None]
    if not trees or not hasattr(trees[0].root, "bytes_h2d"):
        return []
    return trees


def per_request(run, value):
    """``value(span)`` summed over every span of a request, averaged over
    the window's traced requests; None where there is nothing to read."""
    trees = traced(run)
    if not trees:
        return None
    return sum(value(sp) for t in trees for sp in t.walk()) / len(trees)


def per_request_ms(run, named):
    """Milliseconds per request in the spans whose name ``named`` accepts."""
    us = per_request(run, lambda sp: sp.duration_us if named(sp.name) else 0.0)
    return None if us is None else us / 1e3
