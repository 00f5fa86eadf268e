"""Host-to-device copy time per batch request (ms): the ``h2d`` child of
every kernel call, from its first input sent until all are on the device."""

import spans


def read(run):
    return spans.per_request_ms(run, lambda name: name == "h2d")
