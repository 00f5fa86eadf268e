"""Growing-segment scan time per search (ms): the ``scan_growing_slice``
spans (temporary slice indexes) and the ``scan_brute_tail`` spans (rows
no slice index covers yet)."""

import spans


def read(run):
    return spans.per_request_ms(
        run, lambda name: name in ("scan_growing_slice", "scan_brute_tail"))
