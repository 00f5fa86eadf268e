"""The readers of the program's kernel-call spans and counters, on a
synthetic window whose answers are known by hand, on a program that
records no such spans, and on a small traced run."""

from types import SimpleNamespace as NS

import pytest

import spec
from repro.core.telemetry import RequestTrace, Span
from small import run_small

NEW = ("wait_ms", "h2d_copy_ms", "result_wait_ms", "kernel_call_ms", "h2d_mb",
       "h2d_copy_ms.batch")


def read(name, run):
    return spec.metric_reader(name)(run)


def kernel(name, total_us, copy_us, wait_us, nbytes):
    return Span(f"kernel_{name}", duration_us=total_us, children=[
        Span("h2d", duration_us=copy_us, bytes_h2d=nbytes),
        Span("result_wait", duration_us=wait_us)])


def search(wait_us, scans, merges):
    """One request's tree: an optional wait, one dispatch whose scan holds
    the kernel spans ``scans``, a node merge and the proxy's merge."""
    node_merge, proxy_merge = merges
    dispatch = Span("dispatch", duration_us=5000.0, children=[
        Span("plan_search", duration_us=100.0),
        Span("scan_brute_sealed", duration_us=3000.0, children=scans),
        Span("node_merge_topk", duration_us=900.0, children=[node_merge])])
    children = [] if wait_us is None else [Span("consistency_wait", duration_us=wait_us)]
    children += [dispatch, Span("merge_topk", duration_us=800.0, children=[proxy_merge])]
    return RequestTrace(1, "search", Span("search", duration_us=9000.0,
                                          children=children))


def window(*traces):
    return NS(answered=[NS(trace=t) for t in traces])


@pytest.fixture
def two_searches():
    a = search(1500.0,
               [kernel("l2_topk", 1000.0, 600.0, 300.0, 3_000_000),
                kernel("l2_topk", 1200.0, 700.0, 400.0, 3_000_000)],
               (kernel("merge_topk", 800.0, 50.0, 700.0, 1_000),
                kernel("merge_topk", 700.0, 40.0, 600.0, 500)))
    b = search(None,
               [kernel("l2_topk", 900.0, 500.0, 300.0, 3_000_000)],
               (kernel("merge_topk", 600.0, 30.0, 500.0, 1_000),
                kernel("merge_topk", 500.0, 20.0, 400.0, 500)))
    return window(a, b, None)  # an untraced answer is left out


def test_readers_on_a_known_window(two_searches):
    got = {name: read(name, two_searches) for name in NEW}
    assert got == pytest.approx({
        "wait_ms": 1500.0 / 2 / 1e3,
        "h2d_copy_ms": (600 + 700 + 50 + 40 + 500 + 30 + 20) / 2 / 1e3,
        "result_wait_ms": (300 + 400 + 700 + 600 + 300 + 500 + 400) / 2 / 1e3,
        "kernel_call_ms": (1000 + 1200 + 800 + 700 + 900 + 600 + 500) / 2 / 1e3,
        "h2d_mb": (9_000_000 + 3_000) / 2 / 1e6,
        "h2d_copy_ms.batch": (600 + 700 + 50 + 40 + 500 + 30 + 20) / 2 / 1e3,
    })
    # The existing span readers read the same trees as before.
    assert read("scan_ms", two_searches) == pytest.approx(3.0)
    assert read("merge_ms", two_searches) == pytest.approx(1.7)


def test_no_wait_reads_zero():
    quiet = search(None, [kernel("l2_topk", 10.0, 5.0, 4.0, 100)],
                   (kernel("merge_topk", 3.0, 1.0, 1.0, 8),
                    kernel("merge_topk", 3.0, 1.0, 1.0, 8)))
    assert read("wait_ms", window(quiet)) == 0.0


def test_a_program_without_the_spans_reads_nothing():
    # The spans of a program older than the kernel-call spans: no start
    # times, no byte counters, no waits or kernel calls recorded.
    def old(name, us, *children):
        return NS(name=name, duration_us=us, children=list(children))

    tree = RequestTrace(1, "search", old("search", 900.0, old(
        "dispatch", 800.0, old("scan_brute_sealed", 500.0))))
    for name in NEW:
        assert read(name, window(tree)) is None
    assert read("scan_ms", window(tree)) == pytest.approx(0.5)
    for name in NEW:
        assert read(name, window()) is None


def test_small_traced_run_reads_every_new_metric(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    out = run_small("nytimes256-flat.online", trace=True)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"wait_ms", "h2d_copy_ms", "result_wait_ms", "kernel_call_ms",
            "h2d_mb"} <= set(m)
    assert m["h2d_copy_ms"] + m["result_wait_ms"] <= m["kernel_call_ms"]
    assert m["kernel_call_ms"] <= m["scan_ms"] + m["merge_ms"]
    # 6,000 rows of 256 f32 and their bool masks go to the device for
    # every search, with the query and the merge pools.
    assert m["h2d_mb"] == pytest.approx(6000 * (256 * 4 + 1) / 1e6, rel=0.01)
