"""The streaming cell (``sift1m-ivf_flat.streaming``): a small-cut
rehearsal through ``run_small``, the tail that puts each shard's seal in
the middle of the window, and the four readers it adds, on known windows
and on a program without their spans and counters."""

import dataclasses
import json
from types import SimpleNamespace as NS

import pytest

import harness
import spec
from repro.core.telemetry import RequestTrace, Span
from small import run_small

CELL = "sift1m-ivf_flat.streaming"
NEW = ("growing_scan_ms.streaming", "growing_rows.streaming",
       "background_ms.streaming", "tick_gap_max_ms.streaming")


def read(name, run):
    return spec.metric_reader(name)(run)


def phase(out: str, name: str) -> dict:
    lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    return next(ln for ln in lines if ln.get("phase") == name)


def test_small_streaming_run(capsys):
    out = run_small(CELL, trace=True)
    window = phase(capsys.readouterr().out, "window")
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0
    # 2 s of 200-row inserts at 4,000 rows/s and one 40-pk delete a second
    assert window["writes"] == 40 + 2
    assert out["attempted"] == window["searches"] + window["writes"]
    # each shard's growing segment fills in the window: seal, build, hand-off
    assert window["seals_in_window"] >= 2
    assert window["index_builds_in_window"] >= 2
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW) <= set(m)
    assert m["growing_rows.streaming"] > 0
    assert m["background_ms.streaming"] > 0
    assert 0 < m["tick_gap_max_ms.streaming"] < 2_000


def test_a_strong_read_below_an_acknowledged_write_is_not_correct(monkeypatch):
    """A planted fault: STRONG reads served at the query nodes' last
    consumed tick, without a wait, as a BOUNDED read is."""
    from repro.core.manu import ManuSystem

    resolve = ManuSystem._resolve_guarantee

    def stale(self, request, session_ts=None):
        g = resolve(self, request, session_ts=session_ts)
        if g.staleness_ms > 0:
            return g
        last = min(qn.watermark(harness.COLLECTION)
                   for qn in self.query_nodes.values())
        return dataclasses.replace(g, query_ts=last)

    monkeypatch.setattr(ManuSystem, "_resolve_guarantee", stale)
    out = run_small(CELL)
    assert out["correct"] is False
    assert out["checks"]["errors"]["value"] > 0


def test_tail_puts_the_seal_mid_window():
    loop = spec.loop_module("streaming")
    cell = spec.load_cell(CELL)

    def ctx(seal_rows, seconds):
        config = NS(num_shards=cell.config["system"]["num_shards"],
                    seal_rows=seal_rows)
        return NS(coll=NS(system=NS(config=config)), traffic=cell.traffic,
                  seconds=seconds)

    # 2 x (131,072 - 4,000 / 2 x 51 / 2)
    assert loop.tail_rows(ctx(cell.config["system"]["seal_rows"], 51)) == 160_144
    # a small cut wraps: 2 x ((2,048 - 2,000) mod 2,048)
    assert loop.tail_rows(ctx(2048, 2)) == 96


def plan(growing, *scans):
    return Span("plan_search", duration_us=10.0, growing_rows=growing,
                children=list(scans))


def search(*nodes):
    return RequestTrace(1, "search", Span("search", duration_us=900.0,
                                          children=list(nodes)))


def test_readers_on_a_known_window():
    a = search(plan(1000), plan(3000),
               Span("scan_growing_slice", duration_us=2_000.0),
               Span("scan_brute_tail", duration_us=500.0),
               Span("scan_indexed", duration_us=9_000.0))
    b = search(plan(0), Span("scan_brute_tail", duration_us=1_500.0))
    run = NS(answered=[NS(trace=a), NS(trace=b), NS(trace=None)],
             background={"seal": {"us": 300_000.0, "steps": 2.0},
                         "index_build": {"us": 8_700_000.0, "steps": 2.0}},
             tick_gap_max_ms=61.5)
    assert read("growing_scan_ms.streaming", run) == pytest.approx(2.0)
    assert read("growing_rows.streaming", run) == pytest.approx(2000.0)
    assert read("background_ms.streaming", run) == pytest.approx(9_000.0 / 3)
    assert read("tick_gap_max_ms.streaming", run) == 61.5


def test_a_program_without_the_counters_reads_nothing():
    # Spans of a program that records neither kernel calls nor growing rows,
    # and a window whose loop found no background counters or tick record.
    def old(name, *children):
        return NS(name=name, duration_us=1.0, children=list(children))

    tree = RequestTrace(1, "search", old("search", old("plan_search"),
                                         old("scan_brute_tail")))
    run = NS(answered=[NS(trace=tree)], background=None, tick_gap_max_ms=None)
    for name in NEW:
        assert read(name, run) is None, name
    assert read("background_ms.streaming", NS(answered=[])) is None
    assert read("tick_gap_max_ms.streaming", NS(answered=[])) is None


def test_a_program_with_kernel_spans_but_no_growing_count():
    # The spans of the program before the growing count: the growing scan
    # reads, the planner's count does not.
    def span(name, *children, us=1.0):
        return NS(name=name, duration_us=us, bytes_h2d=0, children=list(children))

    tree = RequestTrace(1, "search", span("search", span("plan_search"),
                                          span("scan_brute_tail", us=4_000.0)))
    run = NS(answered=[NS(trace=tree)])
    assert read("growing_scan_ms.streaming", run) == pytest.approx(4.0)
    assert read("growing_rows.streaming", run) is None
