"""One run of one cell, end to end, in this process.

Set-up makes the corpus from the seed and loads it through
``ManuSystem``.  The window then serves on the threaded runtime
(wall-clock time ticks, background pumping), driven by the traffic's loop:
the module ``bench/loops/<loop>.py`` that the traffic file names, found by
name.  A loop's ``setup(ctx)`` draws its requests and warms up their
shapes (counted as set-up); its ``window(ctx, state)`` drives the
collection for the window's seconds from the run's one client thread and
returns a ``Window``.  A mix that needs another shape of load (writes,
filters, bursts) adds a loop module and a traffic file; nothing here
changes.

After the window the program is stopped and freed, and the plain
reference judges a sample of the answers (``reference.compare``).  The
result is one dict in the shape the last output line carries.
"""

from __future__ import annotations

import contextlib
import gc
import json
import shutil
import statistics
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field

import numpy as np

import data
import devtrace
import reference
import roofline
import spec as specmod
from repro.core import ConsistencyLevel, ManuConfig, ManuSystem, Metric, SearchRequest

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
LOAD_WAIT_S = 900.0  # a bound on a hung load, far above its normal time
COLLECTION = "bench"


# --------------------------------------------------------------- compiles
class CompileLog:
    """Every program JAX had to compile or load from its persistent cache,
    with the host time it was obtained and how long that took."""

    def __init__(self):
        self.events: list = []
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.events.append((time.perf_counter(), float(duration)))

    def between(self, lo: float, hi: float) -> list:
        with self._lock:
            return [d for t, d in self.events if lo <= t <= hi]


# ---------------------------------------------------------------- records
@dataclass
class Search:
    due: float  # seconds after window start (closed loop: = send)
    queries: np.ndarray  # [nq, d]
    send: float = 0.0
    done: float = 0.0
    pks: np.ndarray | None = None
    scores: np.ndarray | None = None
    query_ts: int = 0
    trace: object = None
    error: str | None = None

    @property
    def latency_s(self) -> float:
        return self.done - self.due

    @property
    def wall_s(self) -> float:
        return self.done - self.send


@dataclass
class Window:
    """What the measured window did, for the metrics and their readers."""

    seconds: float
    searches: list
    t0: float = 0.0  # host clock at the window's start and end
    t1: float = 0.0
    unsent: int = 0  # searches cut when the backlog passed its limit
    writes: int = 0  # write requests sent, and those that failed
    write_errors: list = field(default_factory=list)
    generator_late_s: list = field(default_factory=list)
    compiles: list = field(default_factory=list)
    device: object = None  # devtrace.DeviceTrace in a traced run
    kernel_calls: dict = field(default_factory=dict)
    device_kind: str = ""

    @property
    def answered(self) -> list:
        return [s for s in self.searches if s.error is None and s.pks is not None]

    @property
    def compiles_in_window(self) -> int:
        return len(self.compiles)

    def peaks(self) -> dict:
        return roofline.peaks_for(self.device_kind)


# ---------------------------------------------------------------- set-up
def make_corpus(config: dict, seed: int) -> reference.Corpus:
    ds = config["dataset"]
    vectors = data.clustered(int(ds["rows"]), int(ds["dim"]),
                             data.rng_for(seed, "corpus"),
                             n_clusters=int(ds["clusters"]))
    return reference.Corpus(vectors, config["metric"])


def build_system(config: dict):
    manu = ManuSystem(ManuConfig(manual_clock=False, threaded=True,
                                 **config["system"]))
    coll = manu.create_collection(COLLECTION, dim=int(config["dataset"]["dim"]),
                                  metric=Metric(config["metric"]))
    idx = config["index"]
    coll.create_index("vector", kind=idx["kind"], params=dict(idx["params"]))
    return manu, coll


@contextlib.contextmanager
def heartbeats(manu, period_s: float = 0.05):
    """Keep the query nodes' leases alive while the background threads are
    stopped, as the runtime's watchdog thread does: one pump round that
    builds an index can outlast a lease."""
    stop = threading.Event()

    def beat():
        while not stop.wait(period_s):
            for node_id, qn in list(manu.query_nodes.items()):
                if qn.alive and node_id in manu.query_coord.nodes:
                    manu.query_coord.heartbeat(node_id)

    t = threading.Thread(target=beat, daemon=True)
    t.start()
    try:
        yield
    finally:
        stop.set()
        t.join(timeout=5.0)


def load(manu, coll, corpus, config: dict) -> None:
    """Insert every row, flush, and wait until the query nodes serve them
    all, with the client thread alone pumping the system.

    On the threaded runtime a logger's time tick (pump thread) and a write
    published from the client thread can reach one WAL channel out of
    timestamp order, which the broker refuses; loading with one thread
    pumping avoids that race.  The threads start again for the window."""
    rows = len(corpus.vectors)
    batch = int(config["load"]["batch_rows"])
    manu.stop_threads()
    with heartbeats(manu):
        for a in range(0, rows, batch):
            b = min(a + batch, rows)
            coll.insert({"pk": np.arange(a, b, dtype=np.int64),
                         "vector": corpus.vectors[a:b]})
            manu.pump()
        manu.data_coord.flush(coll.name)  # what ManuCollection.flush seals
        deadline = time.monotonic() + LOAD_WAIT_S
        while True:
            manu.run_until_idle(max_rounds=1_000_000)
            if coll.num_entities() == rows:
                break
            if time.monotonic() > deadline:
                raise TimeoutError(f"{coll.num_entities()} of {rows} rows served")
            time.sleep(0.01)
    corpus.ins_ts[:] = 0
    manu.start_threads()


# ---------------------------------------------------------------- traffic
def make_requests(traffic: dict, corpus, count: int,
                  rng: np.random.Generator) -> list:
    """``count`` requests of ``nq`` queries, each near a base row drawn
    uniformly."""
    nq = int(traffic["nq"])
    picks = rng.integers(0, len(corpus.vectors), count * nq)
    q = data.queries_near(corpus.vectors, picks, float(traffic["query_noise"]), rng)
    return [q[i * nq:(i + 1) * nq] for i in range(count)]


def schedule(traffic: dict, seconds: int) -> np.ndarray:
    """Open-loop due times (seconds from the window's start) of
    ``rate_per_s * seconds`` searches.

    The gap before each arrival is one of the exponential distribution's
    quantiles at (i + 1/2) / n, scaled so that the last is due as the
    window closes, in an order drawn from a fixed stream: Poisson-like
    arrivals that are the same for every seed, so that seeds change the
    data and the queries and not the load or its order."""
    n = int(round(float(traffic["rate_per_s"]) * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    order = data.rng_for(0, "arrivals").permutation(n)
    return np.cumsum(gaps[order] * (seconds / gaps.sum()))


class Client:
    """The run's one client thread: builds requests and calls the facade."""

    def __init__(self, coll, traffic: dict, traced: bool):
        self.coll = coll
        self.k = int(traffic["k"])
        self.level = ConsistencyLevel[traffic["consistency"]]
        self.traced = traced
        if traced:
            import jax

            self.annotate = jax.profiler.TraceAnnotation
        else:
            self.annotate = lambda _name: contextlib.nullcontext()

    def search(self, rec: Search, clock) -> None:
        rec.send = clock()
        try:
            with self.annotate("bench.search"):
                res = self.coll.search(SearchRequest.single(
                    rec.queries, k=self.k, consistency=self.level,
                    trace=self.traced))
            rec.pks = np.asarray(res.pks)
            rec.scores = np.asarray(res.scores)
            rec.query_ts = int(res.query_ts)
            rec.trace = res.trace
        except Exception as e:  # a request that fails is counted, not fatal
            rec.error = f"{type(e).__name__}: {e}"
        rec.done = clock()


@dataclass
class Context:
    """What a loop module works with."""

    traffic: dict
    seconds: int
    seed: int
    corpus: reference.Corpus
    coll: object
    client: Client
    clock: object = time.perf_counter


def warm_up(ctx: Context) -> None:
    """Serve the traffic's ``warmup_requests`` searches (queries from their
    own stream) so that every program the window's searches run is
    compiled or loaded before it starts."""
    for q in make_requests(ctx.traffic, ctx.corpus,
                           int(ctx.traffic["warmup_requests"]),
                           data.rng_for(ctx.seed, "warmup")):
        rec = Search(due=0.0, queries=q)
        ctx.client.search(rec, ctx.clock)
        if rec.error or (rec.pks < 0).any():
            raise RuntimeError(f"warm-up search failed: {rec.error or rec.pks}")


# ----------------------------------------------------------------- kernels
@contextlib.contextmanager
def record_kernel_shapes(calls: dict, active: threading.Event):
    """Record the padded shapes of every ``l2_topk`` call made while
    ``active`` is set, for its roofline (operations and bytes)."""
    from repro.kernels import ops

    orig = ops.l2_topk_pallas

    def recorded(queries, base, valid, k, *args, **kw):
        if active.is_set():
            calls.setdefault("l2_topk", []).append(
                (int(queries.shape[0]), int(base.shape[0]), int(base.shape[1]),
                 int(k)))
        return orig(queries, base, valid, k, *args, **kw)

    ops.l2_topk_pallas = recorded
    try:
        yield
    finally:
        ops.l2_topk_pallas = orig


# ------------------------------------------------------------------ run
def percentile(values: list, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def log(obj) -> None:
    print(json.dumps(obj), flush=True)


def counter(manu, name: str) -> int:
    return int(manu.telemetry.counter_value(name))


def run_cell(cell: specmod.Cell, seed: int, seconds: int, trace: bool,
             process_start: float, controls: dict | None = None) -> dict:
    """One run; ``controls`` maps a name to an ``answers`` function (see
    ``judge``) put in the program's place on the same sample: its numbers
    and its own ``correct`` under the same limits are logged on a
    ``control`` line, for setting the limits."""
    import jax

    clock = time.perf_counter
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    devices = jax.devices()
    config, traffic = cell.config, cell.traffic

    # ---- set-up: corpus, load, requests, warm-up
    corpus = make_corpus(config, seed)
    t_data = clock()
    manu, coll = build_system(config)
    load(manu, coll, corpus, config)
    t_loaded = clock()
    client = Client(coll, traffic, traced=trace)
    ctx = Context(traffic=traffic, seconds=seconds, seed=seed, corpus=corpus,
                  coll=coll, client=client, clock=clock)
    loop = specmod.loop_module(traffic["loop"], cell.root)
    state = loop.setup(ctx)
    # Set-up leaves some hundred thousand long-lived objects; without this
    # every full collection in the window walks them all (tens of ms with
    # the client thread stopped).  Objects made later are collected as usual.
    gc.collect()
    gc.freeze()
    setup_end = clock()
    setup_s = setup_end - process_start
    setup_compiles = compiles.between(0.0, setup_end)
    log({"phase": "setup", "setup_s": setup_s,
         "data_s": t_data - process_start, "load_s": t_loaded - t_data,
         "warmup_s": setup_end - t_loaded, "rows_loaded": len(corpus.vectors),
         "programs_obtained": len(setup_compiles),
         "compile_s": float(sum(setup_compiles))})

    # ---- window
    seals0 = counter(manu, "data_node_segments_sealed_total")
    builds0 = counter(manu, "index_builds_total")
    kernel_calls: dict = {}
    recording = threading.Event()
    tmp = None
    stack = contextlib.ExitStack()
    if trace:
        stack.enter_context(record_kernel_shapes(kernel_calls, recording))
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
    with stack:
        recording.set()
        with client.annotate("bench.window"):
            win = loop.window(ctx, state)
        recording.clear()
        if trace:
            jax.profiler.stop_trace()
    win.compiles = compiles.between(win.t0, win.t1)
    win.kernel_calls = kernel_calls
    win.device_kind = devices[0].device_kind
    seals = counter(manu, "data_node_segments_sealed_total") - seals0
    builds = counter(manu, "index_builds_total") - builds0

    # ---- memory, then stop and free the program
    peak = memory_peak(devices[: cell.chips])
    manu.stop_threads()
    del manu, coll, client, ctx, state
    gc.unfreeze()
    gc.collect()

    answered = win.answered
    late = win.generator_late_s
    lat_ms = [s.latency_s * 1e3 for s in answered]
    log({"phase": "window", "seconds": win.seconds, "searches": len(win.searches),
         "answered": len(answered), "unsent": win.unsent,
         "latency_ms_p95": percentile(lat_ms, 95) if lat_ms else None,
         "writes": win.writes, "write_errors": len(win.write_errors),
         "errors": sorted({s.error for s in win.searches if s.error}
                          | set(win.write_errors))[:3],
         "generator_late_ms_p50": percentile(late, 50) * 1e3 if late else None,
         "generator_late_ms_max": max(late) * 1e3 if late else None,
         "programs_obtained_in_window": win.compiles_in_window,
         "compile_s_in_window": float(sum(win.compiles)),
         "seals_in_window": seals, "index_builds_in_window": builds,
         "peak_bytes_in_use": peak})

    if trace:
        path = devtrace.find_xplane(tmp)
        win.device = devtrace.reduce_file(path) if path else None
        shutil.rmtree(tmp, ignore_errors=True)

    # ---- correctness, on a sample drawn from the seed
    t_ref = clock()
    numbers = judge(corpus, answered, traffic, seed)
    limits = config["correct"]["limits"]
    for name, fn in (controls or {}).items():
        ctl = check(judge(corpus, answered, traffic, seed, answers=fn), limits)
        log({"phase": "control", "control": name, "correct": passed(ctl),
             "checks": ctl, "program": check(numbers, limits)})
    log({"phase": "reference", "seconds": clock() - t_ref,
         "searches_compared": min(len(answered), int(traffic["reference_sample"]))})
    n_err = sum(1 for s in win.searches if s.error) + len(win.write_errors)
    checks = check({**numbers, "answered": len(answered), "errors": n_err},
                   {"answered": {"min": 1}, **limits, "errors": {"max": 0}})
    correct = passed(checks)

    # ---- metrics
    metrics = {}
    if not trace:
        values = {
            "search_p50_ms": percentile(lat_ms, 50) if lat_ms else None,
            "search_p95_ms": percentile(lat_ms, 95) if lat_ms else None,
            "search_qps": sum(len(s.queries) for s in answered) / win.seconds,
            "recall": numbers.get("recall"),
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = specmod.metric_reader(m["name"], cell.root)(win)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct,
           "attempted": len(win.searches) + win.unsent + win.writes,
           "failed": n_err + win.unsent,
           "metrics": metrics, "device": device}
    if trace and win.device is not None:
        device["busy_s"] = win.device.busy_s
        device["window_s"] = win.device.window_s
        out["breakdown"] = {"device_ops": win.device.top(win.device.op_s),
                            "idle_gaps": win.device.top(win.device.idle_by_host)}
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    for k, c in checks.items():
        op = ">=" if c["bound"] == "min" else "<="
        print(f"check {k}: {c['value']!r} (limit {op} {c['limit']!r})"
              f" {'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    return out


def check(numbers: dict, limits: dict) -> dict:
    """Each number that ``correct`` is decided on beside its limit
    (``{"min": x}`` or ``{"max": x}``), and whether it keeps it; a number
    that is missing keeps none."""
    out = {}
    for name, lim in limits.items():
        bound = "min" if "min" in lim else "max"
        v = numbers.get(name)
        ok = v is not None and (v >= lim[bound] if bound == "min"
                                else v <= lim[bound])
        out[name] = {"value": v, "limit": lim[bound], "bound": bound, "ok": ok}
    return out


def passed(checks: dict) -> bool:
    return all(c["ok"] for c in checks.values())


def sample(answered: list, traffic: dict, seed: int) -> list:
    n = min(int(traffic["reference_sample"]), len(answered))
    rng = data.rng_for(seed, "sample")
    return [answered[i] for i in np.sort(rng.choice(len(answered), n, replace=False))]


def judge(corpus, answered: list, traffic: dict, seed: int,
          answers=None) -> dict:
    """The comparison numbers for a seeded sample of the answered searches;
    ``answers(corpus, queries, qts, k) -> (scores, pks)`` puts another
    search (the control) in the program's place on the same requests."""
    picked = sample(answered, traffic, seed)
    if not picked:
        return {}
    k = int(traffic["k"])
    q = np.concatenate([s.queries for s in picked])
    qts = np.concatenate([np.full(len(s.queries), s.query_ts, np.int64)
                          for s in picked])
    if answers is None:
        pks = np.concatenate([s.pks for s in picked]).astype(np.int64)
        scores = np.concatenate([s.scores for s in picked]).astype(np.float32)
    else:
        scores, pks = answers(corpus, q, qts, k)
    return reference.compare(corpus, q, qts, pks, scores, k)


def percentiles_ms(win: Window) -> dict:
    lat = [s.latency_s * 1e3 for s in win.answered]
    if not lat:
        return {}
    return {"p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
            "mean_ms": statistics.fmean(lat), "n": len(lat)}
