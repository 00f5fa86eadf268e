"""Result wait per search (ms): the ``result_wait`` child of every kernel
call, from the kernel's return until its outputs are numpy arrays (device
time still to run, the copy back, and the host thread's wait)."""

import spans


def read(run):
    return spans.per_request_ms(run, lambda name: name == "result_wait")
