"""The ``h2d_resident_share`` reader: on a window whose bytes are known by
hand, on a program whose spans have no ``bytes_resident``, and on a small
traced run of the FLAT cell, whose sealed rows stay on the device."""

from types import SimpleNamespace as NS

import pytest

import spec
from repro.core.telemetry import RequestTrace, Span
from small import run_small


def read(run):
    return spec.metric_reader("h2d_resident_share")(run)


def window(*traces):
    return NS(answered=[NS(trace=t) for t in traces])


def search(*h2d):
    """One request whose scan holds a kernel call per ``h2d`` span."""
    calls = [Span("kernel_l2_topk", duration_us=100.0, children=[sp]) for sp in h2d]
    return RequestTrace(1, "search", Span("search", duration_us=900.0, children=[
        Span("scan_indexed", duration_us=500.0, children=calls)]))


def test_share_of_resident_bytes_over_a_known_window():
    a = search(Span("h2d", bytes_h2d=1_000, bytes_resident=3_000_000),
               Span("h2d", bytes_h2d=1_000, bytes_resident=3_000_000))
    b = search(Span("h2d", bytes_h2d=500_000))  # nothing resident
    resident, sent = 6_000_000, 502_000
    assert read(window(a, b, None)) == pytest.approx(resident / (resident + sent))
    assert read(window(search())) == 0.0  # no kernel call, nothing to share


def test_a_program_without_resident_counters_reads_nothing():
    # The spans of a program that counts bytes sent but none resident.
    def old(name, *children, sent=0):
        return NS(name=name, duration_us=1.0, bytes_h2d=sent, children=list(children))

    tree = RequestTrace(1, "search", old("search", old(
        "kernel_l2_topk", old("h2d", sent=4_000))))
    assert read(window(tree)) is None
    assert read(window()) is None


def test_small_flat_run_keeps_its_sealed_rows_on_the_device(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    out = run_small("nytimes256-flat.online", trace=True)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # Every one of the 6,000 rows is sealed under a FLAT index: its 256 f32
    # are read on the device, and only masks, queries and pools are sent.
    resident_mb = 6000 * 256 * 4 / 1e6
    assert 6000 / 1e6 <= m["h2d_mb"] < 0.02
    assert m["h2d_resident_share"] == pytest.approx(
        resident_mb / (resident_mb + m["h2d_mb"]))
