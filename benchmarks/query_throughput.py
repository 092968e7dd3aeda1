"""Batched GraphQueryEngine vs looped single-query baseline.

The serving claim of the engine subsystem: a 64-query batch over a >= 5k
graph DB answers at >= 2x the queries/sec of looping ``FlatMSQIndex.query``
— with *identical* candidate sets (asserted here, not assumed).

    PYTHONPATH=src python -m benchmarks.query_throughput [--n 5000] [--q 64]

``--layout {dense,hot,packed,all}`` picks the serving FilterSlab layout
(DESIGN.md §11); ``all`` measures every layout with identical-candidate
assertions and records the space/speed comparison (bits-per-graph of the
resident F_D carrier vs q/s) to
``artifacts/bench/query_throughput_layouts.{csv,json}``.

``--sharded`` additionally runs the ``ShardedGraphQueryEngine`` on a
simulated multi-device CPU mesh (``--devices``, default 8) in both the
graph- and vocab-sharded layouts (``--sharded-layout``), asserts candidate
parity against the single-host engine, and records single-host vs sharded
numbers to ``artifacts/bench/query_throughput_sharded.{csv,json}`` (same
schema).  On fake CPU devices this measures the orchestration overhead
floor, not a speedup — the per-device win needs real accelerators
(DESIGN.md §10).

``--pipeline`` measures the async pipelined engine (DESIGN.md §12) with
verification ON: synchronous ``submit`` vs ``AsyncGraphQueryEngine``
(``--pipeline-workers`` verifiers, batches of ``--pipeline-batch``),
asserts bit-identical results, and records overlap-efficiency — how much
of the device filter time ran *while* verification was in flight, from
the async engine's ``filter`` and ``verify`` spans — to
``artifacts/bench/query_throughput_pipeline.{csv,json}``.

``--obs-overhead`` measures span-recording overhead (DESIGN.md §17):
engine q/s with spans off vs on, identical candidates asserted, recorded
to ``artifacts/bench/query_throughput_obs.json`` (budget: <= 2% loss).
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List

import numpy as np

from benchmarks.common import Csv, art_path, dataset, save_json

# the per-PR perf trajectory lives at the repo root so regressions are a
# one-file diff review away (``--record``, DESIGN.md §13)
BENCH_LOG = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "BENCH_query_throughput.json"))


def record_trajectory(recs: List[Dict], commit: str, date: str,
                      path: str = BENCH_LOG,
                      verified: Dict = None) -> Dict:
    """Append one per-PR row (q/s per backend x layout, plus the
    verified-q/s LB on/off section when measured) to the repo-root
    trajectory log and return it."""
    row = {
        "commit": commit, "date": date,
        "n_db": recs[0]["n_db"], "n_queries": recs[0]["n_queries"],
        "qps_loop": recs[0]["qps_loop"],
        "qps": {f"{r['backend']}/{r['slab']}": round(r["qps_batched"], 1)
                for r in recs},
    }
    if verified is not None:
        row["verified"] = {
            "dataset": verified["dataset"], "tau": verified["tau"],
            "n_queries": verified["n_queries"],
            "qps_off": round(verified["qps_verified_off"], 3),
            "qps_on": round(verified["qps_verified_on"], 3),
            "speedup": round(verified["verified_speedup"], 2),
            "lb_pruned": verified["lb_pruned"],
            "identical_matches": verified["identical_matches"],
        }
    log = []
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            log = json.load(f)
    log.append(row)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(log, f, indent=1)
    print(f"recorded {row['qps']} @ {commit} -> {path}")
    return row


def make_queries(db, num: int, seed: int = 1):
    from repro.graphs.generators import perturb_graph
    rng = np.random.default_rng(seed)
    idx = rng.choice(len(db), size=num, replace=True)
    taus = rng.integers(1, 4, size=num)
    graphs = [perturb_graph(db[int(i)], int(t), rng, db.n_vlabels,
                            db.n_elabels) for i, t in zip(idx, taus)]
    return graphs, [int(t) for t in taus]


def run(csv: Csv, n_db: int = 5000, n_queries: int = 64,
        backend: str = "auto", repeats: int = 3,
        slab: str = "dense", hot_d: int = 128) -> Dict:
    from repro.core.search import FlatMSQIndex
    from repro.serve.graph_engine import GraphQuery, GraphQueryEngine

    db = dataset("aids", n_db)
    flat = FlatMSQIndex(db)
    graphs, taus = make_queries(db, n_queries)
    reqs = [GraphQuery(g, t, verify=False) for g, t in zip(graphs, taus)]

    # looped per-query baseline (candidate generation only; verification
    # cost is identical on both paths).  Warm once, then best-of-repeats —
    # the same protocol as the engine path below, so qps_loop is
    # comparable across --record rows instead of drifting with whatever
    # first-pass cache/alloc effects the host happens to have.
    base = [flat.query(g, t, verify=False).candidates
            for g, t in zip(graphs, taus)]              # warm
    t_loops = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        base = [flat.query(g, t, verify=False).candidates
                for g, t in zip(graphs, taus)]
        t_loops.append(time.perf_counter() - t0)
    t_loop = min(t_loops)

    # result_cache_size=0: every timed submit does the real filter work
    engine = GraphQueryEngine(flat, backend=backend, result_cache_size=0,
                              slab_layout=slab, hot_d=hot_d)
    engine.submit(reqs)                      # warm: builds the slab, jits
    t_batch = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = engine.submit(reqs)
        t_batch.append(time.perf_counter() - t0)
    t_eng = min(t_batch)

    for got, want in zip(out, base):
        assert got.candidates == want, "candidate sets diverged"

    slab_bits = flat.filter_eval(engine.backend, slab=slab,
                                 hot_d=hot_d).slab.bits_per_graph()
    qps_loop = n_queries / t_loop
    qps_eng = n_queries / t_eng
    speedup = qps_eng / qps_loop
    csv.add(f"throughput_loop_n{n_db}_q{n_queries}", t_loop / n_queries,
            f"{qps_loop:.1f} q/s")
    csv.add(f"throughput_batched_{engine.backend}_{slab}_n{n_db}"
            f"_q{n_queries}",
            t_eng / n_queries, f"{qps_eng:.1f} q/s ({speedup:.1f}x)")
    rec = {"n_db": n_db, "n_queries": n_queries,
           "backend": engine.backend, "slab": slab,
           "slab_bits_per_graph": slab_bits,
           "qps_loop": qps_loop, "qps_batched": qps_eng,
           "speedup": speedup, "identical_candidates": True}
    print(f"batched engine [{engine.backend}/{slab}]: {qps_eng:.1f} q/s vs "
          f"looped {qps_loop:.1f} q/s -> {speedup:.2f}x "
          f"({slab_bits:.0f} slab bits/graph, identical candidate sets)")
    return rec


def run_obs_overhead(csv: Csv, n_db: int = 5000, n_queries: int = 64,
                     backend: str = "auto", repeats: int = 5,
                     slab: str = "dense") -> Dict:
    """Tracing overhead: engine q/s with span recording OFF (the default
    ``Observability``) vs ON (DESIGN.md §17), same warm + best-of-repeats
    protocol as ``run`` and identical candidate sets asserted.  The PR
    acceptance budget is <= 2% q/s loss with spans on."""
    from repro.core.search import FlatMSQIndex
    from repro.obs import Observability
    from repro.serve.graph_engine import GraphQuery, GraphQueryEngine

    db = dataset("aids", n_db)
    flat = FlatMSQIndex(db)
    graphs, taus = make_queries(db, n_queries)
    reqs = [GraphQuery(g, t, verify=False) for g, t in zip(graphs, taus)]

    def rate(obs):
        eng = GraphQueryEngine(flat, backend=backend, result_cache_size=0,
                               slab_layout=slab, obs=obs)
        eng.submit(reqs)                     # warm: builds the slab, jits
        best, out = np.inf, None
        for _ in range(repeats):
            t0 = time.perf_counter()
            o = eng.submit(reqs)
            dt = time.perf_counter() - t0
            if dt < best:
                best, out = dt, o
        return n_queries / best, out

    qps_off, ref = rate(None)                # default: spans disabled
    obs_on = Observability(spans=True)
    qps_on, got = rate(obs_on)
    for a, b in zip(got, ref):
        assert a.candidates == b.candidates, "candidate sets diverged"

    overhead_pct = (qps_off - qps_on) / qps_off * 100.0
    rec = {"n_db": n_db, "n_queries": n_queries, "backend": backend,
           "slab": slab, "qps_obs_off": qps_off, "qps_obs_on": qps_on,
           "overhead_pct": overhead_pct,
           "spans_recorded": len(obs_on.spans),
           "identical_candidates": True}
    csv.add(f"obs_off_n{n_db}_q{n_queries}", 1.0 / qps_off,
            f"{qps_off:.1f} q/s")
    csv.add(f"obs_on_n{n_db}_q{n_queries}", 1.0 / qps_on,
            f"{qps_on:.1f} q/s ({overhead_pct:+.2f}%)")
    print(f"obs overhead [{slab}]: spans on {qps_on:.1f} q/s vs off "
          f"{qps_off:.1f} q/s -> {overhead_pct:+.2f}% "
          f"({rec['spans_recorded']} spans recorded, identical "
          f"candidate sets)")
    return rec


def run_verified(csv: Csv, n_db: int = 5000, n_queries: int = 16,
                 backend: str = "auto", tau: int = 6,
                 repeats: int = 2) -> Dict:
    """Verified q/s (filter + A* verification end-to-end) with the
    stage-1.5 assignment lower bound off vs on (DESIGN.md §16).

    Runs on the label-poor graphgen DB ('s100k', 5 vertex labels) at a
    verification-heavy tau: the q-gram filter admits hundreds of
    candidates per query whose true GED is far above tau, and the A*
    exhaustion bill on those non-matches dominates wall time.  The
    branch bound prices exactly that gap, so the LB pass prunes the
    worklist before a single A* node expands.  Match sets are asserted
    bit-identical — the bound is provable, it moves work, not recall.
    """
    from repro.core.search import FlatMSQIndex
    from repro.graphs.generators import perturb_graph
    from repro.serve.graph_engine import GraphQuery, GraphQueryEngine

    db = dataset("s100k", n_db)
    flat = FlatMSQIndex(db)
    rng = np.random.default_rng(2)
    idx = rng.choice(len(db), size=n_queries, replace=False)
    graphs = [perturb_graph(db[int(i)], max(tau // 2, 1), rng,
                            db.n_vlabels, db.n_elabels) for i in idx]
    reqs = [GraphQuery(g, tau, verify=True) for g in graphs]

    def rate(assign_lb: bool):
        eng = GraphQueryEngine(flat, backend=backend, result_cache_size=0,
                               assign_lb=assign_lb)
        eng.submit([GraphQuery(g, tau, verify=False)    # warm: slab + jit
                    for g in graphs[:4]])
        best, out = np.inf, None
        for _ in range(repeats):
            t0 = time.perf_counter()
            o = eng.submit(reqs)
            dt = time.perf_counter() - t0
            if dt < best:
                best, out = dt, o
        return n_queries / best, out, dict(eng.stats)

    qps_off, ref, _ = rate(False)
    qps_on, got, st = rate(True)
    for a, b in zip(got, ref):
        assert a.candidates == b.candidates, "candidate sets diverged"
        assert a.matches == b.matches, "match sets diverged (LB unsound?)"

    speedup = qps_on / qps_off
    rec = {"dataset": "s100k", "n_db": n_db, "n_queries": n_queries,
           "tau": tau,
           "qps_verified_off": qps_off, "qps_verified_on": qps_on,
           "verified_speedup": speedup,
           "lb_pruned": st.get("lb_pruned", 0),
           "lb_tightened": st.get("lb_tightened", 0),
           "verified_pairs_on": st.get("verified_pairs", 0),
           "identical_matches": True}
    csv.add(f"verified_lb_off_s100k_n{n_db}_q{n_queries}_t{tau}",
            1.0 / qps_off, f"{qps_off:.2f} q/s")
    csv.add(f"verified_lb_on_s100k_n{n_db}_q{n_queries}_t{tau}",
            1.0 / qps_on, f"{qps_on:.2f} q/s ({speedup:.1f}x)")
    print(f"verified q/s [s100k n={n_db} tau={tau}]: LB on "
          f"{qps_on:.2f} q/s vs off {qps_off:.2f} q/s -> {speedup:.2f}x "
          f"({rec['lb_pruned']} pairs pruned before A*, identical "
          f"match sets)")
    return rec


def run_sharded(csv: Csv, n_db: int = 5000, n_queries: int = 64,
                layout: str = "graph", model_parallel: int = 1,
                repeats: int = 3, slab: str = "dense",
                hot_d: int = 128) -> Dict:
    """Single-host (numpy) vs sharded engine on the host's device mesh;
    identical candidates asserted, both rates recorded."""
    from repro.core.search import FlatMSQIndex
    from repro.launch.mesh import make_serving_mesh
    from repro.serve.graph_engine import (GraphQuery, GraphQueryEngine,
                                          ShardedGraphQueryEngine)

    db = dataset("aids", n_db)
    graphs, taus = make_queries(db, n_queries)
    reqs = [GraphQuery(g, t, verify=False) for g, t in zip(graphs, taus)]

    def rate(engine) -> float:
        engine.submit(reqs)                  # warm: builds arrays, jits
        best = min(_timed(engine, reqs) for _ in range(repeats))
        return n_queries / best

    single = GraphQueryEngine(FlatMSQIndex(db), backend="numpy",
                              result_cache_size=0)
    sharded = ShardedGraphQueryEngine(
        FlatMSQIndex(db), make_serving_mesh(model_parallel), layout=layout,
        slab_layout=slab, hot_d=hot_d, result_cache_size=0)
    qps_single = rate(single)
    qps_sharded = rate(sharded)
    ref = single.submit(reqs)
    got = sharded.submit(reqs)
    for a, b in zip(got, ref):
        assert a.candidates == b.candidates, "candidate sets diverged"

    import jax
    devices = len(jax.devices())
    speedup = qps_sharded / qps_single
    csv.add(f"throughput_single_host_n{n_db}_q{n_queries}",
            1.0 / qps_single, f"{qps_single:.1f} q/s")
    csv.add(f"throughput_sharded_{layout}_{slab}_d{devices}_n{n_db}"
            f"_q{n_queries}",
            1.0 / qps_sharded, f"{qps_sharded:.1f} q/s ({speedup:.2f}x)")
    rec = {"n_db": n_db, "n_queries": n_queries, "devices": devices,
           "layout": layout, "slab": slab,
           "model_parallel": model_parallel,
           "qps_single_host": qps_single, "qps_sharded": qps_sharded,
           "speedup": speedup, "identical_candidates": True,
           "shard_stats": sharded.shard_stats}
    print(f"sharded engine [{layout}/{slab}, {devices} devices]: "
          f"{qps_sharded:.1f} q/s vs single-host {qps_single:.1f} q/s "
          f"-> {speedup:.2f}x (identical candidate sets)")
    return rec


def _timed(engine, reqs) -> float:
    t0 = time.perf_counter()
    engine.submit(reqs)
    return time.perf_counter() - t0


def _union_length(spans) -> float:
    """Total length of the union of (start, end) spans."""
    total = 0.0
    end = -np.inf
    for s, e in sorted(spans):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _overlap_length(a, b) -> float:
    """Length of intersection(union(a), union(b)) by two-pointer merge."""
    def merged(spans):
        out = []
        for s, e in sorted(spans):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out

    am, bm = merged(a), merged(b)
    i = j = 0
    total = 0.0
    while i < len(am) and j < len(bm):
        lo = max(am[i][0], bm[j][0])
        hi = min(am[i][1], bm[j][1])
        if hi > lo:
            total += hi - lo
        if am[i][1] <= bm[j][1]:
            i += 1
        else:
            j += 1
    return total


def run_pipeline(csv: Csv, n_db: int = 5000, n_queries: int = 64,
                 backend: str = "auto", workers: int = 2,
                 max_batch: int = 0, repeats: int = 1) -> Dict:
    """Sync submit vs the async pipelined engine, verification ON, with
    filter/verify overlap accounting (device busy during verification)
    from the async engine's ``filter`` and ``verify`` spans."""
    from repro.core.search import FlatMSQIndex
    from repro.obs import Observability
    from repro.serve.graph_engine import GraphQuery, GraphQueryEngine
    from repro.serve.pipeline import AsyncGraphQueryEngine

    db = dataset("aids", n_db)
    flat = FlatMSQIndex(db)
    graphs, taus = make_queries(db, n_queries)
    reqs = [GraphQuery(g, t, verify=True) for g, t in zip(graphs, taus)]
    max_batch = max_batch or max(4, n_queries // 8)

    sync = GraphQueryEngine(flat, backend=backend, result_cache_size=0)
    sync.submit([GraphQuery(g, t, verify=False)       # warm: slab + jit
                 for g, t in zip(graphs[:4], taus[:4])])
    t0 = time.perf_counter()
    ref = sync.submit(reqs)
    wall_sync = time.perf_counter() - t0

    wall_async = np.inf
    for _ in range(repeats):
        eng = GraphQueryEngine(flat, backend=backend, result_cache_size=0,
                               obs=Observability(spans=True))
        run_pipe = AsyncGraphQueryEngine(eng, max_batch=max_batch,
                                         max_delay_s=0.002,
                                         num_workers=workers)
        t0 = time.perf_counter()
        tickets = run_pipe.submit_many(reqs)
        run_out = [t.result(timeout=600) for t in tickets]
        wall = time.perf_counter() - t0
        run_pipe.close()
        if wall < wall_async:   # keep wall + spans from the same run
            wall_async, spans, out = wall, eng.obs.spans.spans(), run_out

    for got, want in zip(out, ref):
        assert got.candidates == want.candidates, "candidate sets diverged"
        assert got.matches == want.matches, "match sets diverged"

    filter_iv = [(s.t0, s.t1) for s in spans if s.name == "filter"]
    verify_iv = [(s.t0, s.t1) for s in spans if s.name == "verify"]
    filter_busy = _union_length(filter_iv)
    verify_busy = _union_length(verify_iv)
    overlap = _overlap_length(filter_iv, verify_iv)
    qps_sync = n_queries / wall_sync
    qps_async = n_queries / wall_async
    rec = {"n_db": n_db, "n_queries": n_queries, "backend": eng.backend,
           "workers": workers, "max_batch": max_batch,
           "wall_sync_s": wall_sync, "wall_async_s": wall_async,
           "qps_sync": qps_sync, "qps_async": qps_async,
           "speedup": qps_async / qps_sync,
           "filter_busy_s": filter_busy, "verify_busy_s": verify_busy,
           "overlap_s": overlap,
           # fraction of device-filter time that ran while A* verification
           # was simultaneously in flight (the pipelining claim)
           "overlap_frac_of_filter": overlap / max(filter_busy, 1e-12),
           "pipeline_efficiency": (filter_busy + verify_busy)
                                  / max(wall_async, 1e-12),
           "identical_results": True}
    csv.add(f"pipeline_sync_{eng.backend}_n{n_db}_q{n_queries}",
            wall_sync / n_queries, f"{qps_sync:.1f} q/s")
    csv.add(f"pipeline_async_{eng.backend}_w{workers}_b{max_batch}"
            f"_n{n_db}_q{n_queries}",
            wall_async / n_queries,
            f"{qps_async:.1f} q/s ({rec['speedup']:.2f}x) "
            f"overlap {overlap * 1e3:.1f}ms "
            f"({rec['overlap_frac_of_filter'] * 100:.0f}% of filter)")
    print(f"pipelined engine [{eng.backend}, {workers} workers]: "
          f"{qps_async:.1f} q/s vs sync {qps_sync:.1f} q/s "
          f"({rec['speedup']:.2f}x); filter busy {filter_busy * 1e3:.1f}ms, "
          f"verify busy {verify_busy * 1e3:.1f}ms, overlap "
          f"{overlap * 1e3:.1f}ms "
          f"({rec['overlap_frac_of_filter'] * 100:.0f}% of filter time had "
          f"verification in flight); identical results")
    return rec


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=5000)
    ap.add_argument("--q", type=int, default=64)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "numpy", "jax", "pallas"])
    ap.add_argument("--layout", default="dense",
                    choices=["dense", "hot", "packed", "all"],
                    help="serving FilterSlab layout (DESIGN.md §11); "
                         "'all' measures every layout and records the "
                         "space/speed comparison")
    ap.add_argument("--hot-d", type=int, default=128,
                    help="hot-prefix width of the 'hot' slab layout")
    ap.add_argument("--sharded", action="store_true",
                    help="also measure ShardedGraphQueryEngine on a "
                         "multi-device CPU mesh (both sharding layouts)")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--sharded-layout", default="both",
                    choices=["both", "graph", "vocab"])
    ap.add_argument("--pipeline", action="store_true",
                    help="measure AsyncGraphQueryEngine (verification ON) "
                         "with filter/verify overlap accounting "
                         "(DESIGN.md §12)")
    ap.add_argument("--pipeline-workers", type=int, default=2)
    ap.add_argument("--pipeline-batch", type=int, default=0,
                    help="async batch-former size (0 = n_queries // 8)")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="measure span-recording overhead: engine q/s "
                         "with spans off vs on (DESIGN.md §17; budget "
                         "is <= 2%% q/s loss)")
    ap.add_argument("--verified", action="store_true",
                    help="also measure verified q/s (A* verification ON) "
                         "with the stage-1.5 assignment LB off vs on "
                         "(DESIGN.md §16) on the verification-heavy "
                         "s100k workload")
    ap.add_argument("--verified-q", type=int, default=16)
    ap.add_argument("--verified-tau", type=int, default=6,
                    help="tau for the verified section (6 is "
                         "verification-heavy on s100k: the filter admits "
                         "~100+ candidates/query, almost all non-matches)")
    ap.add_argument("--record", action="store_true",
                    help="append this run (q/s per backend x layout) to "
                         "the repo-root BENCH_query_throughput.json "
                         "perf trajectory")
    ap.add_argument("--commit", default="unknown",
                    help="commit label for --record")
    ap.add_argument("--date", default=time.strftime("%Y-%m-%d"),
                    help="date label for --record")
    args = ap.parse_args()
    if args.sharded:
        # must land before the first jax import: jax locks the device
        # count on backend init.  Append to any pre-set XLA_FLAGS — a
        # setdefault would silently drop the device-count override.
        import os
        flag = f"--xla_force_host_platform_device_count={args.devices}"
        have = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in have:
            os.environ["XLA_FLAGS"] = f"{have} {flag}".strip()
    csv = Csv()
    slabs = (["dense", "hot", "packed"] if args.layout == "all"
             else [args.layout])
    recs = [run(csv, n_db=args.n, n_queries=args.q, backend=args.backend,
                slab=s, hot_d=args.hot_d) for s in slabs]
    save_json("query_throughput.json", recs[0])
    if args.obs_overhead:
        orec = run_obs_overhead(csv, n_db=args.n, n_queries=args.q,
                                backend=args.backend, slab=slabs[0])
        save_json("query_throughput_obs.json", orec)
    vrec = None
    if args.verified:
        vrec = run_verified(csv, n_db=args.n, n_queries=args.verified_q,
                            backend=args.backend, tau=args.verified_tau)
        save_json("query_throughput_verified.json", vrec)
    csv.dump(art_path("query_throughput.csv"))
    if args.record:
        record_trajectory(recs, args.commit, args.date, verified=vrec)
    if len(recs) > 1:
        # the space/speed trade-off on the serving format, one row per
        # layout (bits-per-graph of the resident F_D carrier vs q/s)
        save_json("query_throughput_layouts.json", recs)
        lcsv = Csv()
        for r in recs:
            lcsv.add(f"layout_{r['slab']}_n{args.n}_q{args.q}",
                     1.0 / r["qps_batched"],
                     f"{r['qps_batched']:.1f} q/s @ "
                     f"{r['slab_bits_per_graph']:.0f} bits/graph")
        lcsv.dump(art_path("query_throughput_layouts.csv"))
    if args.pipeline:
        pcsv = Csv()
        prec = run_pipeline(pcsv, n_db=args.n, n_queries=args.q,
                            backend=args.backend,
                            workers=args.pipeline_workers,
                            max_batch=args.pipeline_batch)
        save_json("query_throughput_pipeline.json", prec)
        pcsv.dump(art_path("query_throughput_pipeline.csv"))
    if args.sharded:
        layouts = {"both": ["graph", "vocab"], "graph": ["graph"],
                   "vocab": ["vocab"]}[args.sharded_layout]
        sharded_csv = Csv()
        srecs = []
        for lay in layouts:
            # vocab sharding needs a 'model' axis of >= 2 devices
            mp = max(args.devices // 2, 2) if lay == "vocab" else 1
            if lay == "vocab" and (args.devices < 2 or args.devices % mp):
                print(f"skipping vocab layout: {args.devices} devices "
                      f"don't split into a (data, model={mp}) mesh")
                continue
            if len(slabs) > 1:
                print(f"sharded section measures slab {slabs[0]!r} only "
                      f"(one slab per --sharded run)")
            slab = slabs[0]
            if lay == "vocab" and slab == "packed":
                # packed has no vocab dim to shard over 'model'
                print("vocab sharding cannot split the packed slab; "
                      "measuring dense instead for this layout")
                slab = "dense"
            srecs.append(run_sharded(sharded_csv, n_db=args.n,
                                     n_queries=args.q, layout=lay,
                                     model_parallel=mp, slab=slab,
                                     hot_d=args.hot_d))
        save_json("query_throughput_sharded.json", srecs)
        sharded_csv.dump(art_path("query_throughput_sharded.csv"))


if __name__ == "__main__":
    main()
