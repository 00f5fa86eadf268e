"""The trace reduction, on a trace recorded on a TPU v5e and on a
synthetic one whose answer is known by hand."""

import gzip
import json
import os
from types import SimpleNamespace as NS

import pytest

import devtrace
import roofline

DATA = os.path.join(os.path.dirname(__file__), "data")


def planes_from(obj):
    return [NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[NS(name=n, start_ns=s, duration_ns=d)
                                    for n, s, d in ln["events"]])
        for ln in p["lines"]]) for p in obj["planes"]]


def test_recorded_tpu_trace():
    # The device and window lines of the first traced run of
    # nytimes256-flat.online on a TPU v5e (8 s window), kept as JSON.
    with gzip.open(os.path.join(DATA, "trace_nytimes_online.json.gz"), "rt") as f:
        d = devtrace.reduce_planes(planes_from(json.load(f)))
    assert d.n_devices == 1
    assert d.window_s == pytest.approx(7.938348267)
    assert d.busy_s == pytest.approx(0.180386981)
    assert d.module_s["jit_l2_topk_pallas"] == pytest.approx(0.120622383)
    assert max(d.op_s, key=d.op_s.get) == "jit_l2_topk_pallas/l2_topk_pallas.1"
    # Every idle moment of the window is named by a host activity.
    assert sum(d.idle_by_host.values()) == pytest.approx(d.window_s - d.busy_s)
    assert set(d.idle_by_host) == {"bench.search", "bench.wait"}


def test_synthetic_union_and_gaps():
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench.window", 0, 100], ["bench.search", 10, 40], ["bench.wait", 50, 50]]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_a(1)", 20, 20], ["jit_b(2)", 90, 20]]},
        {"name": "XLA Ops", "events": [["%x.1 = f32[] x()", 20, 15],
                                       ["%y.1 = f32[] y()", 30, 10],
                                       ["%z.1 = f32[] z()", 90, 20]]}]}
    other = {"name": "/device:CUSTOM:Megascale Trace", "lines": []}
    d = devtrace.reduce_planes(planes_from({"planes": [host, dev, other]}))
    # Ops [20,35) and [30,40) overlap; [90,110) is cut at the window's end.
    assert d.busy_s == pytest.approx(30e-9)
    assert d.window_s == pytest.approx(100e-9)
    assert d.op_s["jit_a/x.1"] == pytest.approx(15e-9)
    assert d.op_s["jit_b/z.1"] == pytest.approx(10e-9)
    assert d.idle_by_host == pytest.approx(
        {"bench.search": 20e-9, "bench.wait": 50e-9})


def test_no_device_plane_reads_nothing():
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench.window", 0, 100]]}]}
    assert devtrace.reduce_planes(planes_from({"planes": [host]})) is None


def test_l2_topk_cost_and_peaks():
    ops, nbytes = roofline.l2_topk_cost(8, 135168, 256, 10)
    assert ops == 2 * 8 * 135168 * 256
    assert nbytes == 4 * (8 * 256 + 135168 * 256 + 135168 + 2 * 8 * 10)
    peaks = roofline.peaks_for("TPU v5 lite")
    assert peaks == {"flops": 197e12, "bytes": 819e9}
    # Bandwidth-bound at nq 8: the least time is the bytes over 819 GB/s.
    t = roofline.least_time_s("l2_topk", [(8, 135168, 256, 10)], peaks)
    assert t == pytest.approx(nbytes / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")
