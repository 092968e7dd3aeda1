#!/usr/bin/env python3
"""Sweep the load on a cell's deployment: one build, then one window per
load point, in one process.

    python3 chipbench/sweep.py --workload <cell> --seconds <s> \
        --seed <n> (--rates <q/s> ... | --clients <n> ...)

``--rates`` offers each rate open loop (exponential-quantile gaps);
``--clients`` runs the cell's closed loop with each number of clients.
Every other parameter is the cell's own mix.  Prints one JSON line per
point: the queries sent, the share answered inside the window, the
answers a second, the median and 95th-percentile latency, and the median
latency of the first and the last third of the queries (a backlog that
grows makes the last third slower).  The benchmark's own runs never run
this.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    pts = ap.add_mutually_exclusive_group(required=True)
    pts.add_argument("--rates", type=float, nargs="+")
    pts.add_argument("--clients", type=int, nargs="+")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import run
    import bench
    import traffic
    cell, cfg, mix = bench.find_cell(bench.load_spec(), args.workload)
    run.configure_compile_cache()
    bench.require_devices(int(cell["chips"]))
    counter = bench.CompileCounter().install()
    dep = bench.build_deployment(cfg)
    if args.rates:
        points = [dict(mix, loop="open", arrivals="exponential_gaps",
                       rate_qps=r) for r in args.rates]
    else:
        points = [dict(mix, loop="closed", clients=c) for c in args.clients]
    for i, pmix in enumerate(points):
        seed = args.seed + i
        qs = bench.window_queries(dep, cfg, pmix, args.seconds, seed)
        warm = bench.warm_up(dep, cfg, pmix, qs, counter, seed)
        w = bench.measure(dep, cfg, pmix, qs, args.seconds, counter)
        lat = w.log.latencies()
        done = [x for x in lat if x is not None]
        third = max(len(lat) // 3, 1)
        head = [x for x in lat[:third] if x is not None]
        tail = [x for x in lat[-third:] if x is not None]
        pct = traffic.percentile
        answered = w.log.completed_by(w.t_close)
        print(json.dumps({
            "workload": cell["name"], "seed": seed,
            "rate_qps": pmix.get("rate_qps"), "clients": pmix.get("clients"),
            "sent": len(lat), "answered_in_window": answered / len(lat),
            "answers_per_s": answered / args.seconds,
            "failed": sum(e is not None for e in w.outcome.errors),
            "p50_ms": 1e3 * pct(done, 50) if done else None,
            "p95_ms": 1e3 * pct(done, 95) if done else None,
            "first_third_p50_ms": 1e3 * pct(head, 50) if head else None,
            "last_third_p50_ms": 1e3 * pct(tail, 50) if tail else None,
            "warm_up": warm, "built_in_window": w.built}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
