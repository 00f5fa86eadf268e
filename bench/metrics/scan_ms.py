"""Query-node scan time per search (ms): every ``scan_<class>`` span
(indexed, growing_slice, brute_sealed, brute_tail, ...)."""


def read(run):
    traced = [s.trace for s in run.answered if s.trace is not None]
    if not traced:
        return None
    us = sum(sp.duration_us for t in traced for sp in t.walk()
             if sp.name.startswith("scan_"))
    return us / 1e3 / len(traced)
