"""Cells cut to a size a CPU test can hold: every code path of a run at a
few thousand rows."""

import time

import harness
import spec


def shrink(cell: spec.Cell) -> spec.Cell:
    c = cell.config
    c["dataset"]["rows"] = 6000
    c["system"]["seal_rows"] = 2048
    c["load"]["batch_rows"] = 1000
    if c["index"]["kind"] == "ivf_flat":
        # As at full size, a cluster spans some 16 lists and the probes
        # cover two clusters' worth: too few probes then lose neighbours.
        c["dataset"]["clusters"] = 4
        c["index"]["params"] = {"nlist": 64, "nprobe": 32}
    t = cell.traffic
    t["warmup_requests"] = 2
    if "rate_per_s" in t:
        t["rate_per_s"] = 4
    if "request_pool" in t:
        t["nq"], t["request_pool"] = 16, 4
    t["reference_sample"] = min(int(t["reference_sample"]), 50)
    return cell


def run_small(name: str, seed: int = 2**33 + 7, trace: bool = False,
              root: str = spec.ROOT, fault=None, **kw) -> dict:
    """One run of the cell ``name`` cut to size, for 2 s; ``fault(cell)``,
    where given, plants a fault in the cut cell first."""
    cell = shrink(spec.load_cell(name, root))
    if fault is not None:
        fault(cell)
    return harness.run_cell(cell, seed, 2, trace, time.perf_counter(), **kw)
