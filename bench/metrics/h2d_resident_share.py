"""Share of the kernel calls' operand bytes that were already on the
device: the ``bytes_resident`` counters of the ``h2d`` spans over those
and the ``bytes_h2d`` sent, over the window's traced requests.  A program
whose spans have no ``bytes_resident`` reads nothing."""

import spans


def read(run):
    trees = spans.traced(run)
    if not trees or not hasattr(trees[0].root, "bytes_resident"):
        return None
    resident = spans.per_request(run, lambda sp: sp.bytes_resident)
    total = resident + spans.per_request(run, lambda sp: sp.bytes_h2d)
    return resident / total if total else 0.0
