import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  Everything below is ordinary code.

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell against the production meshes and extract the roofline inputs.

For each cell we record:
  * ``memory_analysis()``     — per-device bytes (proves it fits HBM),
  * ``cost_analysis()``       — HLO FLOPs + bytes accessed,
  * collective bytes          — parsed from the optimized HLO: operand sizes
    of all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute instructions,
  * roofline terms at TPU v5e constants (197 TFLOP/s bf16, 819 GB/s HBM,
    ~50 GB/s/link ICI) and MODEL_FLOPS = 6·N·D (6·N_active·D for MoE).

Results stream into ``results/dryrun/<mesh>/<arch>--<shape>.json`` so the
sweep is resumable; EXPERIMENTS.md §Dry-run / §Roofline are generated from
these files.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only]
"""

import argparse
import json
import re
import sys
import time
import traceback

import jax

from ..configs import ARCHS, SHAPES, cells, get_arch, skipped_cells
from .mesh import make_production_mesh
from .steps import build_cell

# TPU v5e hardware constants (per chip)
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_BW_PER_LINK = 50e9

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|c64|c128)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def parse_collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum operand bytes of every collective instruction in optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    out["collective-ops"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # instruction lines look like:  %name = TYPE[dims] op-name(...)
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+)$", stripped)
        if not m:
            continue
        rhs = m.group(1)
        opm = re.search(r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)(?:-start|-done)?\(", rhs)
        if not opm:
            continue
        op = opm.group(1)
        if "-done(" in rhs:
            continue  # avoid double counting start/done pairs
        # operand bytes: shapes inside the parens; result shape(s) precede op
        paren = rhs[opm.end() :]
        operand_bytes = sum(
            _shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(paren)
        )
        if operand_bytes == 0:
            # fallback: use result shape (e.g. operands spelled as %refs only)
            pre = rhs[: opm.start()]
            operand_bytes = sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(pre))
        out[op] += operand_bytes
        out["collective-ops"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def roofline_terms(
    flops: float, bytes_accessed: float, collective_bytes: float
) -> dict[str, float]:
    """All inputs are PER-DEVICE (cost_analysis & the optimized HLO are the
    per-device SPMD module — calibrated empirically; see EXPERIMENTS.md)."""
    t_compute = flops / PEAK_FLOPS_BF16
    t_memory = bytes_accessed / HBM_BW
    t_collective = collective_bytes / ICI_BW_PER_LINK
    terms = {"compute_s": t_compute, "memory_s": t_memory, "collective_s": t_collective}
    terms["bound"] = max(terms, key=lambda k: terms[k] if k.endswith("_s") else -1).replace("_s", "")
    return terms


def _compile_cell(cfg, shape, mesh, kw):
    step, in_shardings, in_structs, donate = build_cell(cfg, shape, mesh, **kw)
    with mesh:
        jitted = jax.jit(step, in_shardings=in_shardings, donate_argnums=donate)
        lowered = jitted.lower(*in_structs)
        compiled = lowered.compile()
    return compiled


def _cost_of(compiled) -> dict[str, float]:
    cost = compiled.cost_analysis()
    coll = parse_collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(coll["total"]),
        "coll_breakdown": coll,
    }


def extrapolated_cost(cfg, shape, mesh, kw) -> dict:
    """Depth-extrapolated per-device cost.

    XLA's cost_analysis counts while-loop bodies once (no trip-count
    multiplication), so scan-structured models undercount by ~num_layers.
    We compile shallow variants (1 and 2 effective periods) with EVERY scan
    unrolled and extrapolate:  C(L) = C1 + (periods-1) * (C2 - C1).
    """
    import dataclasses

    from ..models import scan_util
    from ..models.model import effective_pattern, num_periods

    period = len(effective_pattern(cfg))
    periods = num_periods(cfg)
    scan_util.set_unroll(True)
    try:
        cfg1 = dataclasses.replace(cfg, num_layers=period)
        c1 = _cost_of(_compile_cell(cfg1, shape, mesh, kw))
        if periods == 1:
            return {**c1, "method": "exact-unrolled"}
        cfg2 = dataclasses.replace(cfg, num_layers=2 * period)
        c2 = _cost_of(_compile_cell(cfg2, shape, mesh, kw))
    finally:
        scan_util.set_unroll(False)
    # clamp: for near-zero-cost cells the linear fit can dip below C1
    out = {
        k: max(c1[k] + (periods - 1) * (c2[k] - c1[k]), c1[k], 0.0)
        for k in ("flops", "bytes", "coll")
    }
    out["coll_breakdown"] = {
        k: c1["coll_breakdown"].get(k, 0) + (periods - 1) * (
            c2["coll_breakdown"].get(k, 0) - c1["coll_breakdown"].get(k, 0)
        )
        for k in c1["coll_breakdown"]
    }
    out["method"] = f"extrapolated(1,2)x{periods}"
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str | None = None,
             flash_decode: bool = True, remat: bool = True,
             cost_pass: bool = True, variant: str = "",
             **cell_kw) -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    mesh_name = "2x16x16" if multi_pod else "16x16"

    kw = dict(cell_kw)
    if shape.kind == "decode":
        kw["flash_decode"] = flash_decode
    else:
        kw["remat"] = remat
    t0 = time.time()
    step, in_shardings, in_structs, donate = build_cell(cfg, shape, mesh, **kw)
    with mesh:
        jitted = jax.jit(step, in_shardings=in_shardings, donate_argnums=donate)
        lowered = jitted.lower(*in_structs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)

    if cost_pass:
        cost = extrapolated_cost(cfg, shape, mesh, kw)
        coll_total = cost["coll"]
        cost_method = cost["method"]
        coll_breakdown = cost["coll_breakdown"]
    else:
        raw = compiled.cost_analysis()
        cost = {
            "flops": float(raw.get("flops", 0.0)),
            "bytes": float(raw.get("bytes accessed", 0.0)),
        }
        coll_total = float(coll["total"])
        coll_breakdown = coll
        cost_method = "raw-rolled (loop bodies counted once)"

    flops = cost["flops"]  # per-device
    bytes_accessed = cost["bytes"]  # per-device
    model_flops_per_tok = 2.0 * cfg.active_params()  # fwd 2ND
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 3.0 * model_flops_per_tok * tokens  # fwd+bwd = 6ND
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = model_flops_per_tok * tokens
    else:
        tokens = shape.global_batch  # one token per sequence
        model_flops = model_flops_per_tok * tokens
    model_flops_per_device = model_flops / n_chips

    terms = roofline_terms(flops, bytes_accessed, coll_total)
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "n_chips": int(n_chips),
        "kind": shape.kind,
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes_per_device": int(
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
            ),
        },
        "cost_analysis": {
            "flops_per_device": flops,
            "bytes_accessed_per_device": bytes_accessed,
            "method": cost_method,
        },
        "collectives": coll_breakdown,
        "collectives_rolled_module": coll,
        "model_flops_global": model_flops,
        "model_flops_per_device": model_flops_per_device,
        "useful_flops_ratio": model_flops_per_device / flops if flops else 0.0,
        "roofline": terms,
        "step_time_bound_s": max(terms["compute_s"], terms["memory_s"], terms["collective_s"]),
        # roofline fraction = (ideal model-FLOP time) / (roofline-bound step
        # time): how close the compiled program is to the hardware ceiling.
        "roofline_fraction": (
            (model_flops_per_device / PEAK_FLOPS_BF16)
            / max(terms["compute_s"], terms["memory_s"], terms["collective_s"])
            if max(terms["compute_s"], terms["memory_s"], terms["collective_s"]) > 0
            else 0.0
        ),
    }
    if variant:
        result["variant"] = variant
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"--{variant}" if variant else ""
        path = os.path.join(out_dir, f"{arch}--{shape_name}{suffix}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--no-flash-decode", action="store_true",
                    help="baseline: dense decode attention (paper-faithful direct port)")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-cost-pass", action="store_true",
                    help="skip the unrolled shallow cost extrapolation")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--resume", action="store_true", help="skip cells with existing results")
    args = ap.parse_args()

    if args.all:
        todo = cells()
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        todo = [(args.arch, args.shape)]

    meshes = []
    if not args.multi_pod_only:
        meshes.append(False)
    if not args.single_pod_only:
        meshes.append(True)

    failures = []
    for multi_pod in meshes:
        mesh_name = "2x16x16" if multi_pod else "16x16"
        out_dir = os.path.join(args.out, mesh_name)
        for arch, shape_name in todo:
            tag = f"[{mesh_name}] {arch} x {shape_name}"
            path = os.path.join(out_dir, f"{arch}--{shape_name}.json")
            if args.resume and os.path.exists(path):
                print(f"{tag}: cached, skipping", flush=True)
                continue
            try:
                res = run_cell(
                    arch, shape_name, multi_pod, out_dir,
                    flash_decode=not args.no_flash_decode,
                    remat=not args.no_remat,
                    # roofline table is single-pod; multi-pod pass proves fit
                    cost_pass=not multi_pod and not args.no_cost_pass,
                )
                r = res["roofline"]
                print(
                    f"{tag}: OK compile={res['compile_s']:.0f}s "
                    f"mem/dev={res['memory_analysis']['peak_bytes_per_device']/2**30:.2f}GiB "
                    f"compute={r['compute_s']*1e3:.1f}ms memory={r['memory_s']*1e3:.1f}ms "
                    f"coll={r['collective_s']*1e3:.1f}ms bound={r['bound']} "
                    f"roofline_frac={res['roofline_fraction']:.2f}",
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001 - report and continue the sweep
                failures.append((tag, repr(e)))
                print(f"{tag}: FAIL {e!r}", flush=True)
                traceback.print_exc()
                os.makedirs(out_dir, exist_ok=True)
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape_name, "mesh": mesh_name,
                               "ok": False, "error": repr(e)}, f, indent=1)

    for arch, shape_name, reason in skipped_cells():
        print(f"[skip] {arch} x {shape_name}: {reason}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(f"  {tag}: {err}")
        sys.exit(1)
    print("\nALL CELLS PASS")


if __name__ == "__main__":
    main()
