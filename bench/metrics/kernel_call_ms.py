"""Kernel-call time per search (ms): every ``kernel_<name>`` span, from
the entry point's first input sent until its outputs are on the host."""

import spans


def read(run):
    return spans.per_request_ms(run, lambda name: name.startswith("kernel_"))
