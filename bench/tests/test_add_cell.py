"""Cells added from files alone, with no existing file edited: a new
configuration and traffic file for an existing loop, and a traffic mix
that needs code of its own (writes), as a new loop module."""

import json
import shutil
import textwrap

import spec
from small import run_small

# A loop that writes: each round inserts fresh rows with ``insert_async``,
# waits for the pump thread to acknowledge them, then searches at the
# traffic's consistency level next to one of them.
WRITE_LOOP = textwrap.dedent('''
    import data
    import harness


    def setup(ctx):
        t = ctx.traffic
        rng = data.rng_for(ctx.seed, "writes")
        base = ctx.corpus.vectors
        n = int(t["rounds"]) * int(t["write_rows"])
        fresh = data.queries_near(base, rng.integers(0, len(base), n), 0.5, rng)
        pks = ctx.corpus.append(fresh)
        harness.warm_up(ctx)
        return pks.reshape(int(t["rounds"]), -1)


    def window(ctx, batches):
        win = harness.Window(seconds=float(ctx.seconds), searches=[])
        win.t0 = ctx.clock()
        for pks in batches:
            win.writes += 1
            ticket = ctx.coll.insert_async(
                {"pk": pks, "vector": ctx.corpus.vectors[pks]})
            if not ticket.wait(10.0):
                win.write_errors.append("insert not acknowledged")
                continue
            ctx.corpus.ins_ts[pks] = ticket.result().watermark_ts
            rec = harness.Search(due=ctx.clock(),
                                 queries=ctx.corpus.vectors[pks[:1]])
            ctx.client.search(rec, ctx.clock)
            win.searches.append(rec)
        win.t1 = ctx.clock()
        for rec in win.searches:
            rec.due -= win.t0
            rec.send -= win.t0
            rec.done -= win.t0
        return win
''')


def checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH, root / spec.BENCH_DIR,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return root, spec.load_json(f"{spec.ROOT}/BENCHMARK.json")


def add_cell(root, bench: dict, name: str, config: str, traffic: str) -> None:
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "search_p50_ms":
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_cell_from_files(tmp_path):
    root, bench = checkout(tmp_path)
    config = spec.load_json(f"{spec.BENCH}/configs/sift1m-ivf_flat.json")
    config["metric"] = "cosine"
    (root / spec.BENCH_DIR / "configs" / "sift1m-ivf_flat-cosine.json").write_text(
        json.dumps(config))
    online = spec.load_cell("sift1m-ivf_flat.online").traffic_name
    traffic = spec.load_json(f"{spec.BENCH}/traffic/{online}.json")
    traffic["k"] = 20
    (root / spec.BENCH_DIR / "traffic" / "online_k20.json").write_text(
        json.dumps(traffic))
    bench["configs"].append({
        "name": "sift1m-ivf_flat-cosine", "source": "test",
        "file": f"{spec.BENCH_DIR}/configs/sift1m-ivf_flat-cosine.json",
        "reduced": [], "why": "test"})
    name = "sift1m-ivf_flat-cosine.online_k20"
    add_cell(root, bench, name, "sift1m-ivf_flat-cosine", "online_k20")

    cell = spec.load_cell(name, str(root))
    assert cell.traffic["k"] == 20 and cell.config["metric"] == "cosine"
    out = run_small(name, root=str(root))
    assert out["correct"] is True, out["checks"]
    assert {"search_p50_ms", "recall", "setup_s"} <= set(out["metrics"])


def test_new_loop_from_files(tmp_path):
    root, bench = checkout(tmp_path)
    (root / spec.BENCH_DIR / "loops" / "read_your_writes.py").write_text(WRITE_LOOP)
    traffic = {"loop": "read_your_writes", "nq": 1, "k": 10,
               "consistency": "STRONG", "query_noise": 0.3,
               "warmup_requests": 2, "reference_sample": 50,
               "rounds": 6, "write_rows": 20}
    (root / spec.BENCH_DIR / "traffic" / "ryw.json").write_text(json.dumps(traffic))
    name = "nytimes256-flat.ryw"
    add_cell(root, bench, name, "nytimes256-flat", "ryw")

    out = run_small(name, root=str(root))
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 12 and out["failed"] == 0
    assert {"search_p50_ms", "recall", "setup_s"} <= set(out["metrics"])
