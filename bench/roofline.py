"""Operations and bytes of a kernel call from its shapes, and the peaks of
the chip the run found, for roofline shares.

A call's least time is the larger of its operations over the peak rate
and its bytes over the peak bandwidth.  A kernel's share of its roofline
is the sum of its calls' least times over the device time its programs
took in the trace.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """{"flops": FLOP/s, "bytes": bytes/s} of one chip; a kind not in the
    table is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE}")
    row = table[device_kind]
    return {"flops": float(row["flops_per_s"]), "bytes": float(row["bytes_per_s"])}


def l2_topk_cost(nq: int, n: int, d: int, k: int) -> tuple[float, float]:
    """(operations, bytes) of one ``l2_topk`` call on its padded shapes:
    the [nq, d] x [d, n] contraction, and reading the queries, the base,
    its validity row and writing the [nq, k] values and indices once."""
    ops = 2.0 * nq * n * d
    nbytes = 4.0 * (nq * d + n * d + n + 2 * nq * k)
    return ops, nbytes


COSTS = {"l2_topk": l2_topk_cost}


def least_time_s(kernel: str, calls: list, peaks: dict) -> float:
    cost = COSTS[kernel]
    total = 0.0
    for shape in calls:
        ops, nbytes = cost(*shape)
        total += max(ops / peaks["flops"], nbytes / peaks["bytes"])
    return total


def kernel_share(run, kernel: str, program: str) -> float | None:
    """Share (%) of its roofline that ``kernel`` reached in a traced run:
    the least time of its recorded calls over the device time its
    ``program`` took in the trace; None where it did not run."""
    calls = run.kernel_calls.get(kernel)
    d = run.device
    if not calls or d is None:
        return None
    busy = d.module_s.get(program, 0.0)
    if busy <= 0:
        return None
    return 100.0 * least_time_s(kernel, calls, run.peaks()) / busy
