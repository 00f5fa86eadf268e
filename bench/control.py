"""Run a cell with the control beside it, or with a fault planted, to set
the limits of ``correct``.

    python bench/control.py --workload <cell> --seed <n> --seconds <s> [--fault <name>]

Without ``--fault``: a normal run of the cell (its result is the last
line, as from ``run.py``) that also puts the control in the program's
place on the same sampled requests: the plain reference with its distance
contraction at ``Precision.HIGH`` (``reference.control_topk``), the next
precision below the deployment's.  The ``control`` line gives its numbers
and its ``correct`` under the same limits beside the program's; a limit
lies between the two.

With ``--fault``: the run of the cell with that fault planted in the
program (``FAULTS``); its last line's ``correct`` has to read false.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import reference  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402


def fewer_probes(cell: spec.Cell) -> None:
    """The IVF index probes an eighth of the lists the configuration
    states: real rows with their true scores, picked from too few lists."""
    params = cell.config["index"]["params"]
    params["nprobe"] = max(1, int(params["nprobe"]) // 8)


FAULTS = {"fewer_probes": fewer_probes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if args.fault:
        FAULTS[args.fault](cell)
    import jax

    why = run.find_chips(jax, cell.chips)
    if why:
        print(f"control: {why}", file=sys.stderr)
        return 2
    run.use_compile_cache(jax)
    import harness

    controls = {} if args.fault else {"high": reference.control_topk}
    result = harness.run_cell(cell, args.seed, args.seconds, False, PROCESS_START,
                              controls=controls)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
