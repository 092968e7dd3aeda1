#!/usr/bin/env python3
"""Bring-up check: serve a full deployment on the TPU and check every answer.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py               # one chip: the 42,687-graph AIDS
                                       # deployment (configs/msq_aids.py)
    python chip_smoke.py --four-chips  # four chips: only the sharded engine,
                                       # on a cut-down PubChem deployment

One chip: builds ``msq_aids`` through ``FlatMSQIndex`` and serves a seeded
batch of range-τ and top-k queries through ``AsyncGraphQueryEngine`` on the
``pallas`` backend in every FilterSlab layout (dense, hot, packed), then on
``backend="auto"``.  Four chips: serves ``ShardedGraphQueryEngine`` on a
4-device mesh, graph-sharded with the packed and hot slabs and
vocab-sharded ('data', 'model') = (2, 2) with the hot slab, and checks that
every cached slab shard sits on its own device.

Every query's candidates and matches must equal the host oracle's
(``FlatMSQIndex.query`` plus exact GED; the numpy backend for top-k
candidates).  The run fails on a typed error or partial result, on any
degradation-ladder step, on an unhealthy stage, on a Pallas kernel called
in interpret mode, and when the lowered pallas path lacks a Mosaic kernel.
It refuses to run without a TPU.  The times it prints are smoke timings,
not benchmark numbers.  The last line of its output is a JSON object with
``"ok": true`` and the device JAX reports.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# what the run serves: (range-τ queries, top-k queries), top-k's k
N_RANGE, N_TOPK, TOP_K = 32, 4, 5
# the four-chip PubChem cut: the host build is single-threaded Python
PUBCHEM_GRAPHS = 100_000
RESULT_TIMEOUT_S = 900.0


class SmokeFailure(RuntimeError):
    """One check of the run did not hold."""


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------

def forbid_interpret_mode() -> list:
    """Make any Pallas kernel traced in interpret mode raise.  Returns the
    list the guard appends each offending call to (it should stay empty:
    a raise inside the filter stage is also caught by the ladder check)."""
    from jax.experimental import pallas as pl
    seen: list = []
    real = pl.pallas_call

    def guarded(*args, **kwargs):
        if kwargs.get("interpret"):
            seen.append(getattr(args[0], "__name__", repr(args[0])))
            raise SmokeFailure("a Pallas kernel was called with "
                               "interpret=True on the TPU")
        return real(*args, **kwargs)

    pl.pallas_call = guarded
    return seen


def check_ladder_and_health(ev, metrics, label: str) -> None:
    """No degradation-ladder step and every health gauge healthy."""
    bad = {k: v for k, v in ev.ladder_stats.items() if v}
    _check(not bad, f"{label}: the fallback ladder moved: {bad}")
    gauges = metrics.snapshot()["gauges"]
    sick = {k: v for k, v in gauges.items()
            if k.startswith("health.") and v != 0}
    _check(not sick, f"{label}: unhealthy stages: {sick}")


# ---------------------------------------------------------------------------
# deployment, traffic, oracle
# ---------------------------------------------------------------------------

def build_deployment(name: str, num_graphs=None):
    """(config, db, index) for one configured deployment, seeded."""
    from repro.configs import get_msq_config
    from repro.core.search import FlatMSQIndex
    from repro.graphs.generators import aids_like_db
    cfg = get_msq_config(name)
    n = cfg.num_graphs if num_graphs is None else int(num_graphs)
    db = aids_like_db(n, seed=cfg.seed, n_vlabels=cfg.n_vlabels,
                      n_elabels=cfg.n_elabels)
    return cfg, db, FlatMSQIndex(db, l=cfg.subregion_l)


def make_requests(db, seed: int, n_range: int = N_RANGE,
                  n_topk: int = N_TOPK):
    """Seeded range-τ queries (τ in 1-3) and top-k queries (cap 3):
    database graphs perturbed by a few edits, no deadlines."""
    import numpy as np

    from repro.graphs.generators import perturb_graph
    from repro.serve import GraphQuery
    rng = np.random.default_rng(seed)
    picks = rng.choice(len(db), size=n_range + n_topk, replace=False)
    reqs = []
    for j, gi in enumerate(picks):
        tau = int(rng.integers(1, 4)) if j < n_range else 3
        h = perturb_graph(db[int(gi)], max(tau // 2, 1), rng, db.n_vlabels,
                          db.n_elabels)
        reqs.append(GraphQuery(h, tau) if j < n_range
                    else GraphQuery(h, tau, top_k=TOP_K))
    return reqs


def host_oracle(index, reqs):
    """Per query (candidates, matches) from the host reference.

    Range-τ: ``FlatMSQIndex.query`` (per-query numpy filter plus exact
    GED).  Top-k: matches are the k smallest (ged, gid) of the exact range
    answer at the cap; candidates come from the numpy backend, since a
    top-k query's candidates are every id its τ escalation admitted."""
    from repro.serve import GraphQueryEngine
    out = []
    for r in reqs:
        res = index.query(r.graph, r.tau)
        if r.top_k is None:
            out.append((list(res.candidates), sorted(res.matches)))
        else:
            ranked = sorted(res.matches, key=lambda m: (m[1], m[0]))
            out.append((None, ranked[:r.top_k]))
    topk = [i for i, r in enumerate(reqs) if r.top_k is not None]
    if topk:
        ref = GraphQueryEngine(index, backend="numpy", result_cache_size=0)
        for i, res in zip(topk, ref.submit([reqs[i] for i in topk])):
            out[i] = (list(res.candidates), out[i][1])
    return out


def serve(engine, reqs):
    """Serve every request through the async pipeline; raise on a typed
    error, a partial result or a timeout."""
    from repro.serve import AsyncGraphQueryEngine
    with AsyncGraphQueryEngine(engine, num_workers=4) as pipe:
        tickets = pipe.submit_many(reqs)
        results = [t.result(timeout=RESULT_TIMEOUT_S) for t in tickets]
    for i, res in enumerate(results):
        _check(not res.stats.get("partial"), f"query {i} ended partial")
    return results


def compare(results, oracle, label: str) -> None:
    for i, (res, (cand, matches)) in enumerate(zip(results, oracle)):
        got = [tuple(m) for m in res.matches]
        want = [tuple(m) for m in matches]
        _check(list(res.candidates) == cand,
               f"{label}: query {i} candidates differ from the oracle "
               f"({len(res.candidates)} vs {len(cand)})")
        _check(got == want, f"{label}: query {i} matches differ from the "
               f"oracle ({got[:5]} vs {want[:5]})")


# ---------------------------------------------------------------------------
# kernels: the lowered pallas path must be Mosaic, not plain HLO
# ---------------------------------------------------------------------------

def check_kernels_lower_to_mosaic(slab, n_vlabels: int, n_elabels: int
                                  ) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.assign_lb.ops import assign_lb_bounds_batched
    from repro.kernels.bitunpack.kernel import bitunpack_call
    from repro.kernels.qgram_filter.ops import fused_filter_bounds_batched
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    Q, B, VM = 8, 512, slab.degseq.shape[1]
    U, VMb, KB = slab.U, slab.bvlab.shape[1], -(-slab.U // 128)
    lowered = {
        "qgram_filter": jax.jit(fused_filter_bounds_batched).lower(
            i32(Q, 6), i32(B, U), i32(Q, U), i32(B, n_vlabels),
            i32(Q, n_vlabels), i32(B, n_elabels), i32(Q, n_elabels),
            i32(B, VM), i32(Q, VM), i32(B, 5), i32(Q, B)),
        "assign_lb": jax.jit(assign_lb_bounds_batched).lower(
            i32(Q, 64), i32(Q, 64), i32(Q, 64, n_elabels), i32(Q),
            i32(B, VMb), i32(B, VMb), i32(B, VMb, n_elabels), i32(B)),
        "bitunpack": jax.jit(lambda sb, w, words: bitunpack_call(
            sb, w, words, n_blocks=B * KB)).lower(
            i32(B * KB), i32(B * KB), i32(B * KB * 128 + 128)),
    }
    for name, low in lowered.items():
        _check("tpu_custom_call" in low.as_text(),
               f"the {name} kernel did not lower to a Mosaic custom call")
        _log(f"kernel {name}: lowers to tpu_custom_call")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def one_chip(seed: int, num_graphs=None) -> None:
    from repro.serve import GraphQueryEngine
    t0 = time.perf_counter()
    cfg, db, index = build_deployment("msq_aids", num_graphs)
    _log(f"build: {len(db)} graphs, {cfg.n_vlabels} vertex labels, "
         f"{cfg.n_elabels} edge labels, vocabulary "
         f"{index.vocab.n_degree_ids} ({time.perf_counter() - t0:.1f} s, "
         f"smoke timing)")
    reqs = make_requests(db, seed)
    t0 = time.perf_counter()
    oracle = host_oracle(index, reqs)
    _log(f"oracle: {len(reqs)} queries, "
         f"{sum(len(c or []) for c, _ in oracle)} candidates, "
         f"{sum(len(m) for _, m in oracle)} matches "
         f"({time.perf_counter() - t0:.1f} s)")

    runs = [("pallas", "dense"), ("pallas", "hot"), ("pallas", "packed"),
            ("auto", "dense")]
    for backend, layout in runs:
        label = f"{backend}/{layout}"
        hot_mass = cfg.hot_mass if layout == "hot" else None
        eng = GraphQueryEngine(index, backend=backend, slab_layout=layout,
                               hot_mass=hot_mass, result_cache_size=0)
        # on the TPU "auto" must pick the device path, never numpy
        want = "jax" if backend == "auto" else backend
        _check(eng.backend == want,
               f"{label}: resolved to backend {eng.backend!r}, not {want!r}")
        t0 = time.perf_counter()
        results = serve(eng, reqs)
        dt = time.perf_counter() - t0
        compare(results, oracle, label)
        ev = index.filter_eval(eng.backend, slab=layout, hot_mass=hot_mass)
        _check(ev.assign_lb, f"{label}: the assignment LB stage is off")
        check_ladder_and_health(ev, eng.obs.metrics, label)
        _log(f"serve {label} (backend {eng.backend}): {len(results)} "
             f"queries equal the oracle, ladder quiet "
             f"({dt:.1f} s incl. compile, smoke timing)")
        if (backend, layout) == ("pallas", "dense"):
            check_kernels_lower_to_mosaic(ev.slab, db.n_vlabels,
                                          db.n_elabels)


def check_shard_placement(ev, n_devices: int, label: str) -> None:
    """Every cached device array of the sharded evaluator spans all the
    mesh's devices, each holding only its block — none whole on one."""
    import jax
    n_arrays = 0
    for _key, field, value in ev.device_cache.items():
        for a in jax.tree.leaves(value):
            if not isinstance(a, jax.Array):
                continue              # host-side gathers
            n_arrays += 1
            devs = {s.device for s in a.addressable_shards}
            _check(len(devs) == n_devices,
                   f"{label}: {field} sits on {len(devs)} devices")
            _check(all(s.data.shape != a.shape
                       for s in a.addressable_shards),
                   f"{label}: {field} {a.shape} is whole on a device")
    _check(n_arrays > 0, f"{label}: no device-resident slab arrays")
    _log(f"{label}: {n_arrays} cached slab arrays, each split over "
         f"{n_devices} devices")


def four_chips(seed: int, num_graphs: int = PUBCHEM_GRAPHS) -> None:
    import jax

    from repro.launch.mesh import make_serving_mesh
    from repro.serve import ShardedGraphQueryEngine
    n_dev = len(jax.devices())
    _check(n_dev == 4, f"--four-chips needs 4 devices, JAX sees {n_dev}")
    t0 = time.perf_counter()
    cfg, db, index = build_deployment("msq_pubchem", num_graphs)
    _log(f"build: {len(db)} of {cfg.num_graphs} PubChem-like graphs "
         f"({time.perf_counter() - t0:.1f} s, smoke timing)")
    # half the one-chip traffic: the host oracle runs while 4 chips wait
    reqs = make_requests(db, seed, N_RANGE // 2, N_TOPK // 2)
    oracle = host_oracle(index, reqs)
    for layout, slab, model_parallel in (("graph", "packed", 1),
                                         ("graph", "hot", 1),
                                         ("vocab", "hot", 2)):
        label = f"{layout}-sharded/{slab}"
        mesh = make_serving_mesh(model_parallel=model_parallel)
        eng = ShardedGraphQueryEngine(
            index, mesh, layout=layout, k=cfg.shard_topk, slab_layout=slab,
            hot_mass=cfg.hot_mass, result_cache_size=0)
        t0 = time.perf_counter()
        results = serve(eng, reqs)
        dt = time.perf_counter() - t0
        compare(results, oracle, label)
        check_ladder_and_health(eng.evaluator, eng.obs.metrics, label)
        check_shard_placement(eng.evaluator, n_dev, label)
        _log(f"serve {label} on mesh {dict(mesh.shape)}: {len(results)} "
             f"queries equal the oracle ({dt:.1f} s incl. compile, smoke "
             f"timing; {eng.shard_stats})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engine on a 4-chip host")
    ap.add_argument("--seed", type=int, default=11,
                    help="seed of the query traffic")
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: refusing to run: JAX's default backend is "
              f"{backend!r}, not 'tpu' (this check needs the chip)",
              file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: no repro package under {ROOT}/src",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache
    _log(f"compile cache: {enable_compile_cache()}")
    dev = jax.devices()[0]
    _log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    interpreted = forbid_interpret_mode()
    try:
        if args.four_chips:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
        _check(not interpreted,
               f"kernels traced in interpret mode: {interpreted}")
    except Exception as e:  # noqa: BLE001 — report, then fail the run
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        raise
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
