"""Streaming ingest while serving: a steady stream of inserts, deletes of
base rows, and open-loop searches at the traffic's consistency level, all
on the run's one client thread.

Set-up grows a tail of fresh rows through ``insert_async`` on the running
threads and waits (bounded) until every row is acknowledged and served.
The tail leaves each shard's growing segment ``seal_rows`` short by what
the window's inserts bring it in half the window, so that its seal, its
index build and the hand-off of the sealed copy run under the load.  It
then compiles the kernel shapes the window meets first (``warm_shapes``),
so that nothing compiles on the client thread in the window, whatever
the compile cache holds.

The window merges three due-time schedules into one, first in first out:

- ``insert_rows_per_request`` fresh rows per ``insert_async`` at
  ``insert_rows_per_s``; tickets are collected, not awaited, and each is
  waited on (bounded) after the window;
- one sync ``delete`` of ``delete_pks_per_request`` base rows at
  ``delete_pks_per_s``, drawn from rows not yet deleted;
- single searches at ``harness.schedule``'s arrivals for ``rate_per_s``,
  each timed from its due time.

Events more than ``max_backlog_s`` late are not sent and count as failed.
Each acknowledged write's timestamp goes to the reference
(``Corpus.ins_ts``, ``Corpus.del_ts``), which judges visibility at every
answer's ``query_ts``.  A STRONG search answered at a ``query_ts`` below
the newest write acknowledged before it was sent (a delete's returned
timestamp, or an insert ticket already done) fails: it read a stale
snapshot, whatever its rows.

The program's background counters read at the window's edges ride on the
``Window`` for the readers: ``background`` (host time and count per step
of ``background_steps_total`` / ``background_us_total``) and
``tick_gap_max_ms`` (the longest gap between two time-ticks on any DML
channel, ``LogBroker.take_tick_gaps``).  A program without them leaves
each ``None``.
"""

import time

import numpy as np

import data
import harness
import reference


def tail_rows(ctx: harness.Context) -> int:
    """Rows that leave every shard's growing segment as many rows short of
    ``seal_rows`` as the window's inserts bring a shard in half the
    window (modulo ``seal_rows``)."""
    config = ctx.coll.system.config
    shards = int(config.num_shards)
    seal = int(config.seal_rows)
    per_shard = float(ctx.traffic["insert_rows_per_s"]) / shards * ctx.seconds / 2
    return shards * (int(round(seal - per_shard)) % seal)


def grow_tail(ctx: harness.Context, n: int) -> None:
    """Insert ``n`` fresh rows through ``insert_async`` and wait until all
    are acknowledged and served."""
    t, corpus, coll = ctx.traffic, ctx.corpus, ctx.coll
    base = len(corpus.vectors)
    rng = data.rng_for(ctx.seed, "tail")
    fresh = data.queries_near(corpus.vectors, rng.integers(0, base, n),
                              float(t["insert_noise"]), rng)
    pks = corpus.append(fresh)
    deadline = time.monotonic() + float(t["write_wait_s"])
    tickets = []
    step = int(t["tail_rows_per_request"])
    for a in range(0, n, step):
        p = pks[a:a + step]
        tickets.append((p, coll.insert_async({"pk": p, "vector": corpus.vectors[p]})))
    for p, ticket in tickets:
        corpus.ins_ts[p] = ticket.result(
            max(0.0, deadline - time.monotonic())).watermark_ts
    while coll.num_entities() < len(corpus.vectors):
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"tail: {coll.num_entities()} of {len(corpus.vectors)} rows served")
        time.sleep(0.01)


def draw(ctx: harness.Context, n_base: int) -> list:
    """The window's events ``(due_s, kind, payload)`` in due order: kind 0
    a search (its queries), 1 an insert (its pks), 2 a delete (its pks).
    Fresh rows are appended to the corpus here, unwritten."""
    t, corpus, seconds = ctx.traffic, ctx.corpus, ctx.seconds
    base = corpus.vectors[:n_base]
    nq = int(t["nq"])
    due = harness.schedule(t, seconds)
    rng = data.rng_for(ctx.seed, "queries")
    q = data.queries_near(base, rng.integers(0, n_base, len(due) * nq),
                          float(t["query_noise"]), rng)
    events = [(float(d), 0, q[i * nq:(i + 1) * nq]) for i, d in enumerate(due)]

    per = int(t["insert_rows_per_request"])
    n_ins = int(round(float(t["insert_rows_per_s"]) * seconds / per))
    rng = data.rng_for(ctx.seed, "inserts")
    fresh = data.queries_near(base, rng.integers(0, n_base, n_ins * per),
                              float(t["insert_noise"]), rng)
    pks = corpus.append(fresh).reshape(n_ins, per)
    gap = per / float(t["insert_rows_per_s"])
    events += [(i * gap, 1, pks[i]) for i in range(n_ins)]

    per = int(t["delete_pks_per_request"])
    n_del = int(round(float(t["delete_pks_per_s"]) * seconds / per))
    live = np.flatnonzero(corpus.del_ts[:n_base] == reference.NEVER)
    rng = data.rng_for(ctx.seed, "deletes")
    doomed = rng.choice(live, n_del * per, replace=False).reshape(n_del, per)
    gap = per / float(t["delete_pks_per_s"])
    events += [((i + 0.5) * gap, 2, doomed[i]) for i in range(n_del)]
    events.sort(key=lambda e: (e[0], e[1]))
    return events


# Node merge pools up to this wide are compiled in set-up: a node's sealed
# IVF pools plus 40 columns for each of its slice indexes (64 a full
# growing segment at the cell's seal and slice sizes, two while one hands
# off).
MERGE_WIDTH_MAX = 16_384


def warm_shapes(ctx: harness.Context) -> None:
    """Compile in set-up the kernel shapes the window meets first, which
    warm-up's searches over one state of the growing segments do not: the
    brute tail at every padded length below a slice, the brute scan of a
    freshly sealed copy until its index is installed, and the node merge
    at every pool width.  With a cold compile cache these otherwise
    compile inside the window, on the client thread."""
    from repro.kernels import ops

    config, t = ctx.coll.system.config, ctx.traffic
    k, dim = int(t["k"]), ctx.corpus.vectors.shape[1]
    metric = "l2" if ctx.coll.info.metric.value == "l2" else "ip"
    q = np.zeros((int(t["nq"]), dim), np.float32)
    seal, slice_rows = int(config.seal_rows), int(config.slice_rows)
    rows = {128, 256, seal, seal + 1}
    rows |= {512 * j for j in range(1, -(-slice_rows // 512) + 1)}
    for n in sorted(rows):
        ops.topk_scan(q, np.zeros((n, dim), np.float32), k, metric=metric,
                      valid=np.ones(n, bool))
    widths = [128, 256, 384, 512]
    while widths[-1] < MERGE_WIDTH_MAX:
        widths.append(widths[-1] * 2)
    for m in widths:
        ops.merge_topk(np.zeros((len(q), m), np.float32),
                       np.tile(np.arange(m), (len(q), 1)), k, metric=metric)


def setup(ctx: harness.Context):
    n_base = len(ctx.corpus.vectors)
    grow_tail(ctx, tail_rows(ctx))
    harness.warm_up(ctx)
    warm_shapes(ctx)
    return draw(ctx, n_base)


def background(manu) -> dict | None:
    """Host microseconds and count per background step so far, or None
    where the program keeps no such counters."""
    counters, _gauges, _hists = manu.telemetry.snapshot_rows()
    out = {}
    for key, v in counters.items():
        for name, field in (("background_us_total", "us"),
                            ("background_steps_total", "steps")):
            if key.startswith(name + "{"):
                step = key.split('step="', 1)[1].split('"', 1)[0]
                out.setdefault(step, {"us": 0.0, "steps": 0.0})[field] += v
    return out or None


def newest_ack(pending: list, acked: int) -> int:
    """``acked`` raised to the timestamp of each insert ticket in
    ``pending`` that is done; those leave ``pending`` (a failed one is
    counted when the tickets are waited on)."""
    still = []
    for ticket in pending:
        if not ticket.done:
            still.append(ticket)
            continue
        try:
            acked = max(acked, int(ticket.result(0).watermark_ts))
        except Exception:
            pass
    pending[:] = still
    return acked


def tick_gaps(manu) -> dict | None:
    take = getattr(manu.broker, "take_tick_gaps", None)
    return take() if take is not None else None


def window(ctx: harness.Context, events) -> harness.Window:
    client, clock, coll, corpus = ctx.client, ctx.clock, ctx.coll, ctx.corpus
    manu = coll.system
    max_backlog = float(ctx.traffic["max_backlog_s"])
    win = harness.Window(seconds=float(ctx.seconds), searches=[])
    strong = str(ctx.traffic["consistency"]).upper() == "STRONG"
    tickets, pending = [], []
    acked = 0  # the newest timestamp of a write acknowledged so far
    bg0 = background(manu)
    tick_gaps(manu)  # starts the window's period
    t0 = clock() + 0.01
    for i, (due, kind, payload) in enumerate(events):
        d = t0 + due
        now = clock()
        slept = now < d
        if slept:
            with client.annotate("bench.wait"):
                time.sleep(d - now)
        elif now - d > max_backlog:
            win.unsent = len(events) - i
            break
        if kind == 0:
            acked = newest_ack(pending, acked)
            rec = harness.Search(due=d, queries=payload)
            client.search(rec, clock)
            if strong and rec.error is None and rec.query_ts < acked:
                rec.error = (f"StaleRead: STRONG query_ts {rec.query_ts} below "
                             f"the acknowledged write at {acked}")
            if slept:  # the client was idle: any lateness is the generator's
                win.generator_late_s.append(rec.send - d)
            win.searches.append(rec)
            continue
        win.writes += 1
        try:
            if kind == 1:
                tickets.append((payload, coll.insert_async(
                    {"pk": payload, "vector": corpus.vectors[payload]})))
                pending.append(tickets[-1][1])
            else:
                ts = coll.delete(payload)
                corpus.del_ts[payload] = ts
                acked = max(acked, int(ts))
        except Exception as e:  # a write that fails is counted, not fatal
            win.write_errors.append(f"{type(e).__name__}: {e}")
    win.t0, win.t1 = t0, clock()
    gaps = tick_gaps(manu)
    bg1 = background(manu)

    deadline = time.monotonic() + float(ctx.traffic["write_wait_s"])
    for pks, ticket in tickets:
        try:
            corpus.ins_ts[pks] = ticket.result(
                max(0.0, deadline - time.monotonic())).watermark_ts
        except Exception as e:
            win.write_errors.append(f"{type(e).__name__}: {e}")

    win.tick_gap_max_ms = max(gaps.values()) if gaps else None
    win.background = None
    if bg1 is not None:
        zero = {"us": 0.0, "steps": 0.0}
        win.background = {
            step: {f: v[f] - (bg0 or {}).get(step, zero)[f] for f in v}
            for step, v in bg1.items()}
    harness.log({"phase": "streaming", "tail_rows": tail_rows(ctx),
                 "inserts_acked": len(tickets), "tick_gap_max_ms": win.tick_gap_max_ms,
                 "background": win.background})
    for rec in win.searches:
        rec.due -= t0
        rec.send -= t0
        rec.done -= t0
    return win
