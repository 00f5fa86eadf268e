"""Host-to-device bytes per search (MB, 1e6 bytes): the ``bytes_h2d``
counters of the kernel calls' ``h2d`` spans."""

import spans


def read(run):
    total = spans.per_request(run, lambda sp: sp.bytes_h2d)
    return None if total is None else total / 1e6
