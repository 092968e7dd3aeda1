"""Observability substrate tests (DESIGN.md §17).

Covers the registry algebra (snapshot / merge associativity / delta /
absorb), the ``StatsView`` mapping facade the legacy stats dicts became,
the bounded span ring, the Chrome trace-event export round-trip, and —
on a real tiny engine — the span-nesting invariants, cache-hit replay
semantics, and the load-bearing parity claim: observability never
changes candidate or match sets.
"""
import numpy as np
import pytest

from repro.core.search import FlatMSQIndex
from repro.graphs.generators import aids_like_db, perturb_graph
from repro.obs import MetricsRegistry, Observability, SpanRecorder
from repro.obs.export import (load_trace, spans_from_trace, to_trace_events,
                              validate_trace, write_trace)
from repro.serve.graph_engine import GraphQuery, GraphQueryEngine


@pytest.fixture(scope="module")
def small_db():
    return aids_like_db(150, seed=3)


@pytest.fixture(scope="module")
def flat(small_db):
    return FlatMSQIndex(small_db)


def _requests(db, n, seed, verify=True, tau_hi=4):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        tau = int(rng.integers(1, tau_hi))
        h = perturb_graph(db[int(rng.integers(0, len(db)))], tau, rng,
                          db.n_vlabels, db.n_elabels)
        out.append(GraphQuery(h, tau, verify=verify))
    return out


# ---- registry algebra ------------------------------------------------------

def _reg(counters, gauges=(), hist=()):
    r = MetricsRegistry()
    for k, v in counters:
        r.counter_add(k, v)
    for k, v in gauges:
        r.gauge_set(k, v)
    for k, v in hist:
        r.observe(k, v)
    return r


def test_registry_counters_gauges_hists():
    r = _reg([("a.x", 2), ("a.x", 3), ("b.y", 1.5)],
             gauges=[("g", 7)], hist=[("h", 0.01), ("h", 2.0)])
    snap = r.snapshot()
    assert snap["counters"]["a.x"] == 5
    assert snap["counters"]["b.y"] == 1.5
    assert snap["gauges"]["g"] == 7
    assert snap["hists"]["h"]["count"] == 2
    assert snap["hists"]["h"]["sum"] == pytest.approx(2.01)


def test_registry_merge_associative_commutative():
    a = _reg([("x", 1), ("y", 2)], gauges=[("g", 3)], hist=[("h", 0.1)])
    b = _reg([("x", 10)], gauges=[("g", 1)], hist=[("h", 5.0)])
    c = _reg([("y", 7), ("z", 1)], gauges=[("g2", 4)])
    sa, sb, sc = a.snapshot(), b.snapshot(), c.snapshot()
    m = MetricsRegistry.merge
    assert m(m(sa, sb), sc) == m(sa, m(sb, sc))
    assert m(sa, sb) == m(sb, sa)
    out = m(sa, sb)
    assert out["counters"]["x"] == 11
    assert out["gauges"]["g"] == 3          # gauges take the max
    assert out["hists"]["h"]["count"] == 2


def test_registry_delta_and_absorb():
    r = _reg([("x", 5)], gauges=[("g", 2)])
    old = r.snapshot()
    r.counter_add("x", 3)
    r.gauge_set("g", 9)
    d = MetricsRegistry.delta(r.snapshot(), old)
    assert d["counters"]["x"] == 3
    assert d["gauges"]["g"] == 9            # gauges keep the new value

    sink = _reg([("x", 1)])
    sink.absorb(r.snapshot())
    assert sink.snapshot()["counters"]["x"] == 9


def test_stats_view_mapping_semantics():
    r = MetricsRegistry()
    s = r.view("engine", initial={"queries": 0, "filter_s": 0.0})
    s["queries"] += 2
    s["filter_s"] += 0.5
    assert s["queries"] == 2
    assert dict(s) == {"queries": 2, "filter_s": 0.5}
    assert s.get("missing", -1) == -1
    assert "queries" in s and "missing" not in s
    assert set(s) == {"queries", "filter_s"}
    # namespaces are isolated: another view never sees these keys
    other = r.view("sched", initial={"queries": 0})
    assert other["queries"] == 0
    # the numbers live in the registry, fully-qualified
    assert r.snapshot()["counters"]["engine.queries"] == 2


# ---- span ring -------------------------------------------------------------

def test_span_ring_bounded_and_counts_drops():
    rec = SpanRecorder(capacity=8, enabled=True)
    for i in range(20):
        rec.record("s", float(i), float(i) + 0.5)
    assert len(rec) == 8
    assert rec.dropped == 12
    assert [s.t0 for s in rec.spans()] == [float(i) for i in range(12, 20)]
    count, total = rec.aggregate()["s"]
    assert count == 8 and total == pytest.approx(4.0)


def test_span_recorder_disabled_is_noop():
    rec = SpanRecorder(capacity=8, enabled=False)
    rec.record("s", 0.0, 1.0)
    with rec.span("t"):
        pass
    rec.extend([])
    assert len(rec) == 0 and rec.dropped == 0


# ---- export ----------------------------------------------------------------

def test_trace_round_trip(tmp_path):
    obs = Observability(spans=True)
    obs.metrics.counter_add("engine.queries", 3)
    obs.spans.record("filter", 1.0, 1.25, tid="filter-thread", rows=4)
    obs.spans.record("verify", 1.1, 1.2, tid="verify-0", qid=7, gid=12)
    path = str(tmp_path / "t.trace.json")
    obs.export_trace(path)
    obj = load_trace(path)
    validate_trace(obj)
    assert obj["otherData"]["metrics"]["counters"]["engine.queries"] == 3

    back = spans_from_trace(obj)
    assert [s.name for s in back] == ["filter", "verify"]
    f, v = back
    assert f.tid == "filter-thread" and f.args == {"rows": 4}
    assert f.t0 == pytest.approx(1.0) and f.t1 == pytest.approx(1.25)
    assert v.qid == 7 and v.args == {"gid": 12}


def test_validate_trace_rejects_bad_schema():
    with pytest.raises(AssertionError):
        validate_trace({"traceEvents": "nope"})
    with pytest.raises(AssertionError):
        validate_trace({"traceEvents": []})        # no complete events
    ev = to_trace_events([])
    assert ev == []


# ---- on a real engine ------------------------------------------------------

def test_engine_span_nesting_invariants(small_db, flat):
    reqs = _requests(small_db, 10, seed=5, verify=True)
    eng = GraphQueryEngine(flat, backend="numpy",
                           obs=Observability(spans=True))
    out = eng.submit(reqs)
    spans = eng.obs.spans.spans()
    names = {s.name for s in spans}
    assert {"admission", "filter", "query"} <= names
    roots = {s.qid: s for s in spans if s.name == "query"}
    assert len(roots) == len(reqs)
    # every per-query child lies within its root's interval
    for s in spans:
        if s.qid is None or s.name == "query":
            continue
        root = roots[s.qid]
        assert root.t0 <= s.t0 and s.t1 <= root.t1, \
            f"{s.name} span escapes its query root"
    # verify spans carry the pair provenance args
    verifies = [s for s in spans if s.name == "verify"]
    if any(len(r.candidates) for r in out):
        assert verifies
    for s in verifies:
        assert {"gid", "bound", "expansions", "decided"} <= set(s.args)
    # flat sources also record the batched stage spans
    assert {"bucket", "filter_bucket"} <= names


def test_cache_hit_replay_zeroed_timings(small_db, flat):
    eng = GraphQueryEngine(flat, backend="numpy",
                           obs=Observability(spans=True))
    req = _requests(small_db, 1, seed=6, verify=True)[0]
    first = eng.submit([req])[0]
    assert "cache_hit" not in first.stats
    again = eng.submit([GraphQuery(req.graph, req.tau, verify=True)])[0]
    assert again.stats.get("cache_hit") == 1
    assert again.filter_time_s == 0.0
    assert again.verify_time_s == 0.0
    assert again.stats.get("lb_s") == 0.0
    assert again.stats.get("queue_s") == 0.0
    assert again.candidates == first.candidates
    assert again.matches == first.matches
    hits = [s for s in eng.obs.spans.spans()
            if s.name == "query" and s.args.get("cache_hit")]
    assert len(hits) == 1


def test_obs_on_off_parity(small_db, flat):
    reqs = _requests(small_db, 12, seed=9, verify=True)
    off = GraphQueryEngine(flat, backend="numpy",
                           result_cache_size=0).submit(reqs)
    on = GraphQueryEngine(flat, backend="numpy", result_cache_size=0,
                          obs=Observability(spans=True)).submit(reqs)
    for a, b in zip(on, off):
        assert a.candidates == b.candidates
        assert a.matches == b.matches


def test_async_pipeline_queue_and_root_spans(small_db, flat):
    from repro.serve.pipeline import AsyncGraphQueryEngine
    reqs = _requests(small_db, 8, seed=4, verify=True)
    eng = GraphQueryEngine(flat, backend="numpy", result_cache_size=0,
                           obs=Observability(spans=True))
    with AsyncGraphQueryEngine(eng, max_batch=4, num_workers=2) as apipe:
        out = [t.result(timeout=120) for t in apipe.submit_many(reqs)]
    spans = eng.obs.spans.spans()
    queues = [s for s in spans if s.name == "queue"]
    roots = [s for s in spans if s.name == "query"]
    assert len(queues) >= len(reqs)
    assert len(roots) == len(reqs)
    for res in out:
        assert res.stats.get("queue_s", 0.0) >= 0.0
    # the async stats facade still reads like the old dict
    assert apipe.stats["queries"] >= len(reqs)


def test_topk_round_spans_carry_tau(small_db, flat):
    eng = GraphQueryEngine(flat, backend="numpy", result_cache_size=0,
                           obs=Observability(spans=True))
    g = perturb_graph(small_db[0], 1, np.random.default_rng(0),
                      small_db.n_vlabels, small_db.n_elabels)
    res = eng.submit([GraphQuery(g, 3, top_k=2)])[0]
    assert len(res.matches) <= 2
    rounds = [s for s in eng.obs.spans.spans() if s.name == "topk_round"]
    assert rounds, "top-k escalation recorded no round spans"
    for s in rounds:
        assert s.args["tau"] >= 0 and s.args["round"] >= 1


def test_process_pool_astar_slice_spans(small_db):
    from repro.serve.graph_engine import VerifyScheduler
    flat = FlatMSQIndex(small_db)
    reqs = _requests(small_db, 4, seed=11, verify=True)
    ref = GraphQueryEngine(flat, backend="numpy").submit(reqs)
    obs = Observability(spans=True)
    sched = VerifyScheduler(small_db, executor="process", workers=2,
                            slice_expansions=40, obs=obs)
    try:
        jobs = [sched.add_job(r.graph, r.tau, res.candidates,
                              [0] * len(res.candidates))
                for r, res in zip(reqs, ref)]
        sched.run_until_idle()
    finally:
        sched.close()
        sched.shutdown()
    for job, res in zip(jobs, ref):
        assert sorted(job.matches) == res.matches
    if any(len(r.candidates) for r in ref):
        frags = [s for s in obs.spans.spans() if s.name == "astar_slice"]
        assert frags, "no worker span fragments crossed the pool"
        assert all(s.tid.startswith("ged-pool-") for s in frags)


# ---- spans inside the filter, LB and verify layers -------------------------

def _jax_engine(flat, spans=True):
    return GraphQueryEngine(flat, backend="jax", result_cache_size=0,
                            obs=Observability(spans=spans))


def _run_async(eng, reqs):
    from repro.serve.pipeline import AsyncGraphQueryEngine
    with AsyncGraphQueryEngine(eng, max_batch=4, num_workers=2) as apipe:
        out = [t.result(timeout=300) for t in apipe.submit_many(reqs)]
        stats = apipe.stats
    return out, stats


def test_child_spans_nest_in_their_layer_spans(small_db, flat):
    reqs = _requests(small_db, 8, seed=21, verify=True)
    eng = _jax_engine(flat)
    _run_async(eng, reqs)
    spans = eng.obs.spans.spans()
    names = {s.name for s in spans}
    assert {"slab_gather", "slab_upload", "filter_device", "filter_select",
            "lb_device", "enqueue", "verify"} <= names
    parents = {"filter_device": {"filter_bucket"},
               "filter_select": {"filter_bucket"},
               "lb_device": {"assign_lb"},
               "slab_gather": {"filter_bucket", "assign_lb"},
               "slab_upload": {"filter_bucket", "assign_lb"}}
    for s in spans:
        if s.name not in parents:
            continue
        assert any(p.name in parents[s.name] and p.tid == s.tid
                   and p.t0 <= s.t0 and s.t1 <= p.t1 for p in spans), \
            f"{s.name} span lies in no {parents[s.name]} span of its thread"
    # enqueue picks up where its batch's filter span ends
    filters = {(s.tid, s.t1) for s in spans if s.name == "filter"}
    for s in spans:
        if s.name == "enqueue":
            assert (s.tid, s.t0) in filters
    for s in spans:
        if s.name in ("slab_gather", "slab_upload"):
            assert s.args["rows"] > 0 and s.args["bytes"] > 0
            assert (s.args["field"] == "sub") == (s.name == "slab_gather")
    timed = [s for s in spans if "cpu_ms" in s.args]
    assert {s.name for s in timed} >= {
        "filter_bucket", "assign_lb", "verify", "slab_gather", "slab_upload",
        "filter_device", "filter_select", "lb_device", "enqueue"}
    for s in timed:
        assert 0.0 <= s.args["cpu_ms"] <= 1e3 * s.dur + 1.0, s


def test_slab_cache_counters_reach_async_stats(small_db):
    fresh = FlatMSQIndex(small_db)
    ev = fresh.filter_eval("jax")
    ev.device_cache.max_entries = 2        # small enough to evict
    reqs = _requests(small_db, 8, seed=22, verify=False)
    eng = _jax_engine(fresh, spans=False)
    _run_async(eng, reqs)
    _, stats = _run_async(eng, reqs)       # the same buckets again
    cache = ev.device_cache.snapshot()
    fields = {k.split(".")[1] for k in stats if k.startswith("slab_cache.")
              and k.count(".") == 2}
    assert {"sub", "jax_db", "lb_db"} <= fields
    for kind in ("hits", "misses"):
        assert sum(stats.get(f"slab_cache.{f}.{kind}", 0)
                   for f in fields) == cache[kind]
    assert cache["misses"] > 0 and cache["hits"] > 0
    assert stats["slab_cache.evictions"] == cache["evictions"] > 0
    assert stats["slab_cache.upload_bytes"] > 0


def test_compile_span_and_counter_under_use_obs():
    import jax
    import jax.numpy as jnp

    from repro.obs import use_obs
    obs = Observability(spans=True)
    with use_obs(obs):
        jax.jit(lambda x: x * 7 + 3)(jnp.ones(37, jnp.int32))
    assert obs.metrics.counter_get("engine.compiles", 0) >= 1
    comp = [s for s in obs.spans.spans() if s.name == "compile"]
    assert comp and all(s.dur >= 0 for s in comp)
    outside = Observability(spans=True)
    jax.jit(lambda x: x * 11 + 5)(jnp.ones(41, jnp.int32))
    assert len(outside.spans) == 0   # only the ambient obs is charged


def test_jitted_passes_carry_stable_names(small_db, flat):
    import jax.numpy as jnp

    from repro.core.arrays import DBArrays, QueryArrays
    from repro.core.engine import (_assign_lb_jit, _bounds_multi_jit,
                                   sparse_query_fd)
    from repro.core.slab import branch_features
    from repro.kernels.assign_lb import ops as aops
    ev = flat.filter_eval("jax")
    reqs = _requests(small_db, 8, seed=23)
    sub = ev.slab.gather(np.arange(len(small_db)), 512)
    rows = np.full(512, len(small_db), np.int32)
    rows[:len(small_db)] = np.arange(len(small_db))
    qb = ev.stack_queries([ev.query_arrays(r.graph, r.tau) for r in reqs])
    qids, qcnt = sparse_query_fd(qb.fd)
    text = _bounds_multi_jit("dense").lower(
        DBArrays(*[jnp.asarray(x)
                   for x in ev.slab.base_arrays(sentinel=True)]),
        jnp.asarray(rows), QueryArrays(*[jnp.asarray(x) for x in qb]),
        jnp.asarray(qids), jnp.asarray(qcnt)).as_text()
    assert "msq_qgram_filter_dense" in text
    hs = [r.graph for r in reqs]
    qv, qd, qeh = branch_features(hs, small_db.n_elabels,
                                  max(h.n for h in hs))
    qn = np.asarray([h.n for h in hs], np.int32)
    text = _assign_lb_jit().lower(
        *aops.pad_query_block(qv, qd, qeh, qn),
        sub.bvlab, sub.bdeg, sub.behist, sub.nv).as_text()
    assert "msq_assign_lb" in text


def test_spans_off_records_nothing_and_answers_match(small_db, flat):
    reqs = _requests(small_db, 10, seed=24, verify=True)
    off = _jax_engine(flat, spans=False)
    on = _jax_engine(flat, spans=True)
    out_off, stats_off = _run_async(off, reqs)
    out_on, _ = _run_async(on, reqs)
    assert len(off.obs.spans) == 0 and off.obs.spans.dropped == 0
    assert len(on.obs.spans) > 0
    for a, b in zip(out_on, out_off):
        assert a.candidates == b.candidates
        assert a.matches == b.matches
    # the cache counters are on either way
    assert any(k.startswith("slab_cache.") for k in stats_off)
