"""Every cell's run at a tiny size on the CPU, the result line's shape,
and the refusal of a host without a TPU."""

import json
import os

import numpy as np
import pytest

import run
import spec
from small import run_small

CELLS = [w["name"] for w in spec.load_json(
    os.path.join(spec.ROOT, "BENCHMARK.json"))["workloads"]]


@pytest.fixture
def kernel_path(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "use_pallas", lambda: True)


def check_shape(out: dict, cell: spec.Cell, trace: bool) -> None:
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        assert key in out["device"]
    wanted = {m["name"] for m in (cell.per_layer if trace else cell.end_to_end)}
    assert set(out["metrics"]) <= wanted
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    json.dumps(out)


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_at_small_size(name, kernel_path):
    out = run_small(name)
    check_shape(out, spec.load_cell(name), trace=False)
    assert set(out["metrics"]) == {m["name"] for m in spec.load_cell(name).end_to_end}


def test_traced_run_reads_span_metrics(kernel_path):
    name = "nytimes256-flat.online"
    out = run_small(name, trace=True)
    check_shape(out, spec.load_cell(name), trace=True)
    # No device plane on the CPU: the trace-based readers report nothing.
    assert {"proxy_self_ms", "plan_ms", "scan_ms", "merge_ms",
            "compiles_in_window"} <= set(out["metrics"])
    assert "device_idle_share" not in out["metrics"]
    assert out["metrics"]["scan_ms"]["value"] > 0


def test_refuses_a_host_without_tpu(capsys):
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"]) == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_every_seed_offers_the_same_load():
    import harness

    traffic = {"rate_per_s": 11}
    a = harness.schedule(traffic, 51)
    b = harness.schedule(traffic, 51)
    assert len(a) == 561 and (a == b).all()
    gaps = np.diff(a, prepend=0.0)
    assert (gaps > 0).all() and a[-1] == pytest.approx(51)
    # Poisson-like: the gaps come in no sorted order, and spread as an
    # exponential's do (its quantiles at (i + 1/2) / n).
    assert not (np.diff(gaps) >= 0).all() and not (np.diff(gaps) <= 0).all()
    assert np.median(gaps) == pytest.approx(np.log(2) * np.mean(gaps), rel=0.01)
