#!/usr/bin/env python3
"""The control: the reference put in the program's place with one
guarantee broken, checked by the same comparison as a run.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --sent <n> --seeds <n> [<n> ...]

For each seed it draws the window's queries and the check sample exactly
as a run that sent ``--sent`` queries does, answers the sample with the
reference filter and the greedy (upper-bound) edit distance in place of
the exact one, and prints the comparison's numbers.  The comparison has
to fail it on every seed.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))


def control_outcome(qs, index, plain_db, cfg, sample):
    """Answers to the sampled queries sent from the control; the rest
    stand in as empty complete answers, which the comparison never
    reads."""
    import bench
    import reference
    ref = reference.ReferenceIndex(plain_db, cfg["n_vlabels"],
                                   cfg["n_elabels"])
    results = [SimpleNamespace(candidates=[], matches=[], stats={})
               for _ in index]
    for k in sample:
        i = index[k]
        cand, matches = qs.kind.expected(ref, qs.plain[i], qs.arrivals[i],
                                         ged=reference.greedy_ged)
        results[k] = SimpleNamespace(candidates=cand, matches=matches,
                                     stats={})
    return bench.Outcome(results, [None] * len(index), 0)


def plain_queries(plain_db, cfg, mix, seconds, seed):
    """The window's queries without the program (no requests)."""
    import bench
    import traffic
    arrivals = traffic.schedule(mix, traffic.size_order(plain_db), seconds,
                                seed)
    plain = traffic.materialise(arrivals, plain_db, cfg["n_vlabels"],
                                cfg["n_elabels"])
    return bench.Queries(arrivals, plain, [], traffic.pieces(mix)[2])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sent", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import bench
    import data
    import traffic
    cell, cfg, mix = bench.find_cell(bench.load_spec(), args.workload)
    t0 = time.perf_counter()
    plain_db = data.build_database(cfg)
    bench.log(f"control {cell['name']}: {len(plain_db)} graphs "
              f"({time.perf_counter() - t0:.1f} s)")
    for seed in args.seeds:
        qs = plain_queries(plain_db, cfg, mix, args.seconds, seed)
        index = [k % len(qs.arrivals) for k in range(args.sent)]
        sample = traffic.check_sample(len(index), mix["check_sample"], seed)
        out = control_outcome(qs, index, plain_db, cfg, sample)
        checks = bench.check_answers(out, index, qs, plain_db, cfg, sample)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
