"""Mixed-tenant SLO benchmark for the async serving pipeline.

Replays deterministic seeded traffic traces (``repro.serve.traffic``,
DESIGN.md §15) — per-tenant mixes of range-τ, top-k, and deadline
queries, in open- and closed-loop arrival models — against an
``AsyncGraphQueryEngine`` and records p50/p99 latency, goodput under
each tenant's deadline SLO, and partial-result rates.

    PYTHONPATH=src python -m benchmarks.serving_slo [--n 2000] [--smoke]

``--record --commit <sha> --date <YYYY-MM-DD>`` appends one row per run
to the repo-root ``BENCH_serving_slo.json`` trajectory (same convention
as ``BENCH_query_throughput.json``): this is the serving harness every
later PR gets judged by.  ``--smoke`` runs a tiny trace and asserts the
report schema (non-empty percentiles, goodput, partial-rate, per-stage
breakdown columns) — wired into ``make bench-smoke``.  ``--trace`` turns
span recording on and writes one Chrome trace-event artifact per
mix/mode to ``artifacts/bench/`` (DESIGN.md §17); with ``--smoke`` the
artifact is schema-validated too.  ``--faults`` additionally replays
each mix under the deterministic ``fault_plan()`` chaos schedule
(poisoned filter batches, latency spikes, a verifier worker kill,
admission shedding) and asserts bounded errors and zero stuck queries —
``make chaos-smoke`` runs ``--faults --smoke`` in CI (DESIGN.md §18).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time
from typing import Dict, List

from benchmarks.common import Csv, art_path, dataset, save_json
from repro.serve.traffic import (TenantSpec, generate_trace, replay,
                                 tenant_weights)

BENCH_LOG = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "BENCH_serving_slo.json"))

# the two standing tenant mixes every serving PR is judged on: an
# interactive/bulk split and a deadline-heavy top-k explorer mix
MIXES: Dict[str, List[TenantSpec]] = {
    "interactive_bulk": [
        TenantSpec("interactive", weight=1.0, rate_qps=60.0, clients=3,
                   queries_per_client=6, topk_frac=0.7, k_range=(1, 4),
                   cap=4, tau_range=(1, 2), deadline_s=0.25,
                   edits_range=(1, 2)),
        TenantSpec("bulk", weight=1.0, rate_qps=25.0, clients=2,
                   queries_per_client=5, topk_frac=0.0, tau_range=(1, 3),
                   deadline_s=None, edits_range=(1, 2)),
    ],
    "topk_explorer": [
        TenantSpec("explorer", weight=1.0, rate_qps=45.0, clients=3,
                   queries_per_client=6, topk_frac=1.0, k_range=(2, 6),
                   cap=5, deadline_s=0.35, edits_range=(1, 3)),
        TenantSpec("analytics", weight=1.0, rate_qps=20.0, clients=2,
                   queries_per_client=4, topk_frac=0.3, k_range=(1, 3),
                   cap=4, tau_range=(2, 3), deadline_s=0.8,
                   edits_range=(1, 2)),
    ],
}


MAX_BATCH = 8


def make_pipe(db, *, backend: str = "numpy", workers: int = 2,
              max_batch: int = MAX_BATCH, obs=None, faults=None,
              verify_executor: str = "thread", inbox_limit=None,
              shed_policy: str = "reject", tenant_weights=None):
    from repro.core.search import FlatMSQIndex
    from repro.serve.graph_engine import GraphQueryEngine
    from repro.serve.pipeline import AsyncGraphQueryEngine
    eng = GraphQueryEngine(FlatMSQIndex(db), backend=backend,
                           result_cache_size=0, obs=obs, faults=faults)
    return AsyncGraphQueryEngine(eng, max_batch=max_batch,
                                 max_delay_s=0.002, num_workers=workers,
                                 verify_executor=verify_executor,
                                 faults=faults, inbox_limit=inbox_limit,
                                 shed_policy=shed_policy,
                                 tenant_weights=tenant_weights)


def fault_plan():
    """The standing chaos schedule for ``--faults`` runs (DESIGN.md §18)
    and the error budget it can legitimately cost: only the two
    filter-batch raises fail queries (one poisoned batch each); slice
    faults degrade to partials, kills/delays cost latency only."""
    from repro.serve.faults import FaultSpec
    specs = [
        FaultSpec("filter.batch", on_calls=(3, 9)),
        FaultSpec("filter.batch", kind="delay", every=5, delay_s=0.01,
                  times=4),
        FaultSpec("device.filter", every=7),
        FaultSpec("verify.pool", kind="kill_worker", on_calls=(5,)),
        FaultSpec("verify.slice", on_calls=(11,)),
    ]
    return specs, 2 * MAX_BATCH


def check_report(rep: dict, *, faulted: bool = False,
                 n_expected=None) -> None:
    """Schema gate (the bench-smoke assertion): percentiles present and
    finite, goodput/partial-rate/SLO fields populated, per-stage
    breakdown columns present (DESIGN.md §17).  Fault-free runs must be
    error-free; ``--faults`` runs get the ``fault_plan`` error budget
    plus the zero-stuck check — every issued query resolved to a typed
    outcome (DESIGN.md §18)."""
    _, err_budget = fault_plan()
    for scope, b in [("overall", rep["overall"]),
                     *rep["per_tenant"].items()]:
        assert b["n"] > 0, f"{scope}: empty bucket"
        for fld in ("p50_ms", "p99_ms"):
            assert math.isfinite(b[fld]) and b[fld] > 0, \
                f"{scope}.{fld} not a positive finite latency: {b[fld]}"
        for fld in ("goodput_qps", "partial_rate", "slo_miss_rate"):
            assert fld in b and b[fld] >= 0, f"{scope}.{fld} missing"
        for fld in ("filter_ms", "lb_ms", "verify_ms", "queue_ms"):
            assert fld in b and math.isfinite(b[fld]) and b[fld] >= 0, \
                f"{scope}.{fld} breakdown missing/invalid: {b.get(fld)}"
        if faulted:
            assert b["errors"] <= err_budget, \
                f"{scope}: {b['errors']} errors > fault budget {err_budget}"
        else:
            assert b["errors"] == 0, f"{scope}: {b['errors']} query errors"
    if n_expected is not None:
        got = rep["overall"]["n"]
        assert got == n_expected, \
            f"stuck queries: only {got}/{n_expected} resolved"


def run_mix(csv: Csv, db, mix: str, mode: str, *, backend: str,
            workers: int, duration_s: float, seed: int,
            speed: float, span_trace: bool = False,
            validate: bool = False, faulted: bool = False) -> Dict:
    trace = generate_trace(MIXES[mix], len(db), mode=mode,
                           duration_s=duration_s, seed=seed)
    obs = None
    if span_trace:
        from repro.obs import Observability
        obs = Observability(spans=True)
    faults = None
    pipe_kw: Dict = {}
    if faulted:
        # the deterministic chaos schedule + admission control: process
        # verifiers (so worker kills are real), a bounded inbox with
        # tenant-weighted shed-oldest (DESIGN.md §18)
        from repro.serve.faults import FaultInjector
        specs, _ = fault_plan()
        faults = FaultInjector(specs, seed=seed)
        pipe_kw = dict(faults=faults, verify_executor="process",
                       inbox_limit=16, shed_policy="shed_oldest",
                       tenant_weights=tenant_weights(MIXES[mix]))
    pipe = make_pipe(db, backend=backend, workers=workers, obs=obs,
                     **pipe_kw)
    try:
        # warm the slab + caches so the first arrivals don't pay build
        # cost — the bench measures steady-state serving
        from repro.serve.graph_engine import GraphQuery
        pipe.submit(GraphQuery(db[0], 1, verify=False)).result(60)
        report = replay(trace, pipe, db, speed=speed)
    finally:
        pipe.close()
    rep = report.to_json()
    check_report(rep, faulted=faulted,
                 n_expected=len(trace.queries) if faulted else None)
    trace_path = None
    if span_trace:
        trace_path = art_path(f"serving_slo_{mix}_{mode}.trace.json")
        obs.export_trace(trace_path)
        print(f"[{mix}/{mode}] trace -> {trace_path} "
              f"({len(obs.spans)} spans, {obs.spans.dropped} dropped)")
        if validate:
            from repro.obs.export import load_trace, validate_trace
            validate_trace(load_trace(trace_path))
    o = rep["overall"]
    key = f"{mix}/{mode}" + ("/faulted" if faulted else "")
    csv.add(f"slo_{key.replace('/', '_')}_p99", o["p99_ms"] / 1e3,
            f"{o['goodput_qps']:.1f} good q/s, "
            f"{o['partial_rate'] * 100:.1f}% partial")
    print(f"[{key}] n={o['n']} (topk {o['n_topk']}) "
          f"p50={o['p50_ms']:.1f}ms p99={o['p99_ms']:.1f}ms "
          f"goodput={o['goodput_qps']:.1f} q/s "
          f"partial={o['partial_rate']:.3f} "
          f"slo_miss={o['slo_miss_rate']:.3f}"
          + (f" rejected={o['rejected']} errors={o['errors']} "
             f"faults_fired={faults.summary()['n_fired']}"
             if faulted else ""))
    rec = {"mix": mix, "mode": mode, "seed": seed,
           "n_db": len(db), "backend": backend, "workers": workers,
           "trace_digest": trace.digest(), "span_trace": trace_path,
           **rep}
    if faulted:
        rec["faulted"] = True
        rec["faults"] = faults.summary()
    return rec


def record_trajectory(recs: List[Dict], commit: str, date: str,
                      path: str = BENCH_LOG) -> Dict:
    """Append one per-PR row (per mix x loop SLO metrics) to the
    repo-root trajectory log and return it."""
    row = {
        "commit": commit, "date": date, "n_db": recs[0]["n_db"],
        "mixes": {
            f"{r['mix']}/{r['mode']}"
            + ("/faulted" if r.get("faulted") else ""): {
                "n": r["overall"]["n"],
                "p50_ms": r["overall"]["p50_ms"],
                "p99_ms": r["overall"]["p99_ms"],
                "goodput_qps": r["overall"]["goodput_qps"],
                "partial_rate": r["overall"]["partial_rate"],
                "slo_miss_rate": r["overall"]["slo_miss_rate"],
                # per-tenant stage breakdowns (DESIGN.md §17)
                "per_tenant": {name: {
                    "filter_ms": b["filter_ms"], "lb_ms": b["lb_ms"],
                    "verify_ms": b["verify_ms"], "queue_ms": b["queue_ms"],
                } for name, b in r["per_tenant"].items()},
                # fault-mode extras: the chaos row every later PR's
                # availability story is judged by (DESIGN.md §18)
                **({"faulted": True,
                    "rejected": r["overall"]["rejected"],
                    "errors": r["overall"]["errors"],
                    "faults_fired": r["faults"]["n_fired"]}
                   if r.get("faulted") else {}),
            } for r in recs},
    }
    log = []
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            log = json.load(f)
    log.append(row)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(log, f, indent=1)
    print(f"recorded {sorted(row['mixes'])} @ {commit} -> {path}")
    return row


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2000, help="db size")
    ap.add_argument("--backend", default="numpy")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--duration", type=float, default=0.6,
                    help="open-loop trace duration (trace seconds)")
    ap.add_argument("--speed", type=float, default=1.0,
                    help="open-loop replay speedup")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--mix", default="all",
                    choices=["all", *MIXES])
    ap.add_argument("--mode", default="both",
                    choices=["both", "open", "closed"])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace; assert report schema only")
    ap.add_argument("--faults", action="store_true",
                    help="also replay each mix (open loop) under the "
                         "deterministic fault_plan() chaos schedule with "
                         "admission control on — asserts bounded errors "
                         "and zero stuck queries (DESIGN.md §18); "
                         "``make chaos-smoke`` wires this into CI")
    ap.add_argument("--trace", action="store_true",
                    help="record per-query spans; write one Chrome "
                         "trace-event artifact per mix/mode to "
                         "artifacts/bench/ (DESIGN.md §17)")
    ap.add_argument("--record", action="store_true",
                    help=f"append SLO metrics to {BENCH_LOG}")
    ap.add_argument("--commit", default="unknown",
                    help="commit label for --record")
    ap.add_argument("--date", default=time.strftime("%Y-%m-%d"),
                    help="date label for --record")
    args = ap.parse_args()

    if args.smoke:
        args.n = min(args.n, 300)
        args.duration = min(args.duration, 0.2)

    db = dataset("aids", args.n)
    csv = Csv()
    mixes = list(MIXES) if args.mix == "all" else [args.mix]
    modes = ["open", "closed"] if args.mode == "both" else [args.mode]
    recs = [run_mix(csv, db, mix, mode, backend=args.backend,
                    workers=args.workers, duration_s=args.duration,
                    seed=args.seed, speed=args.speed,
                    span_trace=args.trace, validate=args.smoke)
            for mix in mixes for mode in modes]
    if args.faults:
        recs += [run_mix(csv, db, mix, "open", backend=args.backend,
                         workers=args.workers, duration_s=args.duration,
                         seed=args.seed, speed=args.speed, faulted=True)
                 for mix in mixes]

    save_json("serving_slo.json", recs)
    csv.dump(art_path("serving_slo.csv"))
    if args.smoke:
        print(f"smoke OK: {len(recs)} mix/mode reports, schema checked"
              + (" (incl. faulted)" if args.faults else ""))
    if args.record:
        record_trajectory(recs, args.commit, args.date)


if __name__ == "__main__":
    main()
