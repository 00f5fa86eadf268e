"""Consistency-wait time per search (ms): the proxy's ``consistency_wait``
spans, one for each query node it waited on before dispatch."""

import spans


def read(run):
    return spans.per_request_ms(run, lambda name: name == "consistency_wait")
