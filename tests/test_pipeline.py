"""AsyncGraphQueryEngine: pipelined parity, streaming, deadlines, shutdown.

The load-bearing invariant (DESIGN.md §12): with no deadlines, every
completed ticket is bit-identical to the synchronous ``submit()`` — same
candidates, same matches, same n_filtered — for every backend x FilterSlab
layout, independent of verifier worker count, batch forming, or A*
timeslicing.  Deadlines only ever produce recall-safe partials (candidates
untouched, ``partial`` flagged), and ``close()`` leaks no threads.
"""
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro.core.search import FlatMSQIndex, MSQIndex
from repro.core.verify import GEDSearch, ged_upto
from repro.serve.errors import FilterStageError
from repro.serve.graph_engine import GraphQuery, GraphQueryEngine
from repro.serve.pipeline import AsyncGraphQueryEngine, as_completed

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def small_db():
    from repro.graphs.generators import aids_like_db
    return aids_like_db(150, seed=7)


@pytest.fixture(scope="module")
def flat(small_db):
    return FlatMSQIndex(small_db)


def _requests(db, n, seed, verify=True, tau_hi=3):
    from repro.graphs.generators import perturb_graph
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        tau = int(rng.integers(1, tau_hi))
        h = perturb_graph(db[int(rng.integers(0, len(db)))], tau, rng,
                          db.n_vlabels, db.n_elabels)
        out.append(GraphQuery(h, tau, verify=verify))
    return out


def _assert_same(got, ref):
    for a, b in zip(got, ref):
        assert a.candidates == b.candidates
        assert a.matches == b.matches
        assert a.n_filtered == b.n_filtered


# --------------------------------------------------------------------------
# bit-identical parity across backends x slab layouts
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend,slab", [
    ("numpy", "dense"), ("numpy", "hot"), ("numpy", "packed"),
    ("jax", "dense"), ("jax", "packed"), ("pallas", "dense")])
def test_async_bit_identical_to_submit(small_db, flat, backend, slab):
    reqs = _requests(small_db, 8, seed=1)
    ref = GraphQueryEngine(flat, backend=backend,
                           slab_layout=slab).submit(reqs)
    eng = GraphQueryEngine(flat, backend=backend, slab_layout=slab)
    with AsyncGraphQueryEngine(eng, max_batch=3, num_workers=2) as apipe:
        out = [t.result(timeout=90)
               for t in apipe.submit_many(reqs)]
    _assert_same(out, ref)


def test_async_over_tree_source(small_db):
    """Tree sources carry no filter bounds (worklist order degrades to
    admission order) — results must still match the sync path."""
    tree = MSQIndex(small_db)
    reqs = _requests(small_db, 6, seed=2)
    ref = GraphQueryEngine(tree).submit(reqs)
    with AsyncGraphQueryEngine(GraphQueryEngine(tree),
                               max_batch=2, num_workers=2) as apipe:
        out = [t.result(timeout=90) for t in apipe.submit_many(reqs)]
    _assert_same(out, ref)


def test_async_deterministic_1_vs_4_workers(small_db, flat):
    """Match sets must not depend on worker count, completion order, or
    A* timeslicing (tiny slices force many resumed runs)."""
    reqs = _requests(small_db, 10, seed=3)
    outs = []
    for workers, slice_exp in ((1, None), (4, None), (4, 3)):
        eng = GraphQueryEngine(flat, backend="numpy")
        with AsyncGraphQueryEngine(eng, max_batch=4, num_workers=workers,
                                   slice_expansions=slice_exp) as apipe:
            outs.append([t.result(timeout=90)
                         for t in apipe.submit_many(reqs)])
        if slice_exp is not None and any(len(r.candidates) for r in outs[-1]):
            assert apipe.stats["resumed_runs"] > 0
    _assert_same(outs[1], outs[0])
    _assert_same(outs[2], outs[0])


def test_process_pool_verifier_parity(small_db, flat):
    """The ProcessPoolExecutor verifier (ROADMAP item: GED off the GIL)
    must be bit-identical to the thread-pool path — pickled GEDSearch
    slices round-trip the frontier, so even resumed searches agree.  A
    pool dispatch failure degrades to in-process slices, never to missing
    matches."""
    reqs = _requests(small_db, 8, seed=11)
    ref = GraphQueryEngine(flat, backend="numpy").submit(reqs)

    eng = GraphQueryEngine(flat, backend="numpy")
    with AsyncGraphQueryEngine(eng, max_batch=4, num_workers=2,
                               verify_executor="process",
                               slice_expansions=50) as apipe:
        out = [t.result(timeout=180) for t in apipe.submit_many(reqs)]
    _assert_same(out, ref)
    # the sliced searches really crossed the process boundary and resumed
    if any(len(r.candidates) > 0 for r in ref):
        assert apipe.scheduler.stats["verified_pairs"] > 0


def test_process_pool_scheduler_direct(small_db, flat):
    """VerifyScheduler(executor='process', workers=N) drains a sync
    worklist through the pool with identical match sets."""
    from repro.serve.graph_engine import VerifyScheduler
    reqs = _requests(small_db, 6, seed=12)
    ref = GraphQueryEngine(flat, backend="numpy").submit(reqs)
    sched = VerifyScheduler(small_db, executor="process", workers=2,
                            slice_expansions=40)
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


def test_verify_pool_child_never_imports_jax(small_db):
    """A verify-pool worker only unpickles a ``GEDSearch`` and runs
    ``run_search_slice``; that import chain must stay free of jax, so a
    worker can never initialize a backend and take the chip from the
    serving process.  Replays the worker's job in a fresh interpreter."""
    import pickle
    search = GEDSearch(small_db[0], small_db[1], 3)
    child = textwrap.dedent("""
        import pickle, sys
        search = pickle.loads(sys.stdin.buffer.read())
        from repro.core.verify import run_search_slice
        d, search = run_search_slice(search, None, None)
        print(d, "jax" in sys.modules)
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", child],
                         input=pickle.dumps(search), capture_output=True,
                         env=env, timeout=120, check=True)
    decision, jax_loaded = out.stdout.decode().split()
    assert int(decision) == ged_upto(small_db[0], small_db[1], 3)
    assert jax_loaded == "False"


def test_pool_worker_kill_resumes_at_frontier(small_db, flat, monkeypatch):
    """A worker killed mid-slice re-enqueues the resumable GEDSearch at
    its last frontier — one construction per pair, never a restart —
    and the poisoned pool is rebuilt (DESIGN.md §18)."""
    import repro.serve.graph_engine as ge
    from repro.serve.faults import FaultInjector, FaultSpec
    from repro.serve.graph_engine import VerifyScheduler

    reqs = _requests(small_db, 5, seed=12)
    ref = GraphQueryEngine(flat, backend="numpy").submit(reqs)
    n_pairs = sum(len(r.candidates) for r in ref)
    assert n_pairs > 3

    made = []
    real = ge.GEDSearch

    def counting_ctor(*a, **kw):
        # a factory, not a subclass: the instance must stay the real
        # (picklable) GEDSearch so the spawn pool can round-trip it
        made.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(ge, "GEDSearch", counting_ctor)
    faults = FaultInjector(
        [FaultSpec("verify.pool", kind="kill_worker", on_calls=(3,))],
        seed=5)
    sched = VerifyScheduler(small_db, executor="process", workers=2,
                            slice_expansions=40, faults=faults)
    try:
        jobs = [sched.add_job(r.graph, r.tau, res.candidates,
                              [0] * len(res.candidates))
                for r, res in zip(reqs, ref)]
        sched.run_until_idle()
    finally:
        sched.close()
        sched.shutdown()
    # completed matches bit-identical to the fault-free run
    for job, res in zip(jobs, ref):
        assert sorted(job.matches) == res.matches
        assert job.unverified == 0       # the struck pair resumed, not died
    ss = sched.stats_snapshot()
    assert ss["error_pairs"] == 0
    assert ss["pool_rebuilds"] >= 1      # poisoned pool was replaced
    assert faults.fired_at("verify.pool"), "kill spec never fired"
    # the frontier-resume invariant: every pair built its search exactly
    # once; interrupted slices re-entered the heap as resumes
    assert len(made) == n_pairs
    assert ss["resumed_runs"] >= 1


def test_scheduler_rejects_unknown_executor(small_db):
    from repro.serve.graph_engine import VerifyScheduler
    with pytest.raises(ValueError):
        VerifyScheduler(small_db, executor="fiber")


# --------------------------------------------------------------------------
# streaming delivery
# --------------------------------------------------------------------------

def test_stream_yields_every_match_then_ends(small_db, flat):
    reqs = _requests(small_db, 6, seed=4)
    eng = GraphQueryEngine(flat, backend="numpy")
    with AsyncGraphQueryEngine(eng, max_batch=3, num_workers=2) as apipe:
        tickets = apipe.submit_many(reqs)
        streamed = [list(t.stream(timeout=90)) for t in tickets]
        results = [t.result(timeout=90) for t in tickets]
    for s, r in zip(streamed, results):
        assert sorted(s) == r.matches   # every match streamed exactly once
    # as_completed covers every ticket exactly once
    idxs = sorted(i for i, _ in as_completed(tickets, timeout=5))
    assert idxs == list(range(len(tickets)))


def test_stream_single_worker_cheapest_first(small_db, flat):
    """With one worker the worklist is drained strictly cheapest-bound
    first, so each query's matches stream in nondecreasing bound order;
    here we check the observable contract: streaming beats completion and
    replays exactly the final match set (cache hits included)."""
    reqs = _requests(small_db, 4, seed=5)
    eng = GraphQueryEngine(flat, backend="numpy")
    with AsyncGraphQueryEngine(eng, max_batch=4, num_workers=1) as apipe:
        t0 = apipe.submit_many(reqs)[0]
        got = list(t0.stream(timeout=90))
        assert sorted(got) == t0.result(timeout=1).matches
        # a repeat of the same request resolves from the result cache and
        # still streams the full match set before ending
        t1 = apipe.submit(reqs[0])
        assert sorted(t1.stream(timeout=90)) == t1.result(timeout=1).matches
        assert t1.result().stats.get("cache_hit") == 1


# --------------------------------------------------------------------------
# deadlines: recall-safe partials; budgeted/resumable A*
# --------------------------------------------------------------------------

def test_deadline_partial_flagged_and_recall_safe(small_db, flat):
    reqs = _requests(small_db, 5, seed=6)
    ref = GraphQueryEngine(flat, backend="numpy").submit(reqs)
    expired = [GraphQuery(r.graph, r.tau, verify=True, deadline_s=0.0)
               for r in reqs]
    eng = GraphQueryEngine(flat, backend="numpy")
    with AsyncGraphQueryEngine(eng, max_batch=5, num_workers=2) as apipe:
        out = [t.result(timeout=90) for t in apipe.submit_many(expired)]
    assert apipe.stats["expired_pairs"] > 0
    for a, b in zip(out, ref):
        assert a.candidates == b.candidates      # never truncated
        assert set(a.matches) <= set(b.matches)  # only confirmed matches
        if a.candidates:
            assert a.stats["partial"] == 1
            assert a.stats["unverified"] + len(a.matches) \
                <= len(a.candidates)
    # partials are not cached: a deadline-free repeat recomputes fully
    with AsyncGraphQueryEngine(eng, max_batch=5, num_workers=2) as apipe2:
        full = [t.result(timeout=90) for t in apipe2.submit_many(reqs)]
    _assert_same(full, ref)


def test_sync_submit_honors_deadline_too(small_db, flat):
    """The sync engine is the one-worker special case of the same
    scheduler, deadlines included."""
    reqs = [GraphQuery(r.graph, r.tau, verify=True, deadline_s=0.0)
            for r in _requests(small_db, 3, seed=7)]
    eng = GraphQueryEngine(flat, backend="numpy")
    out = eng.submit(reqs)
    for r in out:
        if r.candidates:
            assert r.stats["partial"] == 1
            assert r.matches == []
    assert eng.stats["expired_pairs"] > 0


def test_ged_search_budgeted_resume_equals_oneshot(small_db):
    rng = np.random.default_rng(8)
    for _ in range(5):
        g = small_db[int(rng.integers(0, len(small_db)))]
        h = small_db[int(rng.integers(0, len(small_db)))]
        tau = int(rng.integers(1, 4))
        want = ged_upto(g, h, tau)
        s = GEDSearch(g, h, tau)
        hops = 0
        r = None
        while r is None:
            r = s.run(max_expansions=2)
            hops += 1
        assert r == want
        assert s.done and s.min_f() == want
        assert s.run() == want          # running a decided search is a no-op
        if s.expansions > 2:
            assert hops > 1             # the budget actually sliced the run


def test_ged_upto_deadline_returns_none_mid_search(small_db):
    import time
    g, h = small_db[0], small_db[1]
    want = ged_upto(g, h, 3)
    s = GEDSearch(g, h, 3)
    if not s.done:   # an immediate heuristic cutoff can't be interrupted
        assert s.run(deadline=time.perf_counter()) is None
    assert s.run() == want


# --------------------------------------------------------------------------
# shutdown hygiene
# --------------------------------------------------------------------------

def test_close_leaks_no_threads_and_rejects_new_work(small_db, flat):
    before = set(threading.enumerate())
    eng = GraphQueryEngine(flat, backend="numpy")
    apipe = AsyncGraphQueryEngine(eng, max_batch=4, num_workers=3,
                                  name="leakcheck")
    tickets = apipe.submit_many(_requests(small_db, 6, seed=9))
    apipe.close(timeout=90)
    assert all(t.done() for t in tickets)   # close() drains, never drops
    leaked = [t for t in set(threading.enumerate()) - before
              if t.name.startswith("leakcheck") and t.is_alive()]
    assert not leaked
    with pytest.raises(RuntimeError):
        apipe.submit(GraphQuery(small_db[0], 1))
    apipe.close()                           # idempotent


def test_async_sharded_parity_subprocess():
    """The pipelined engine over ShardedGraphQueryEngine's shard_map
    filter path (2-device CPU mesh, subprocess so the main process keeps
    1 device) stays bit-identical to the sync sharded engine."""
    code = """
    import numpy as np
    from repro.core import jax_compat as jc
    from repro.core.search import FlatMSQIndex
    from repro.graphs.generators import aids_like_db, perturb_graph
    from repro.serve.graph_engine import (GraphQuery, GraphQueryEngine,
                                          ShardedGraphQueryEngine)
    from repro.serve.pipeline import AsyncGraphQueryEngine

    db = aids_like_db(120, seed=11)
    rng = np.random.default_rng(3)
    reqs = []
    for _ in range(8):
        tau = int(rng.integers(1, 3))
        h = perturb_graph(db[int(rng.integers(0, len(db)))], tau, rng,
                          db.n_vlabels, db.n_elabels)
        reqs.append(GraphQuery(h, tau, verify=True))
    mesh = jc.make_mesh((2,), ("data",))
    ref = ShardedGraphQueryEngine(FlatMSQIndex(db), mesh, k=64,
                                  shard_pad=64).submit(reqs)
    eng = ShardedGraphQueryEngine(FlatMSQIndex(db), mesh, k=64,
                                  shard_pad=64)
    with AsyncGraphQueryEngine(eng, max_batch=3, num_workers=2) as apipe:
        out = [t.result(timeout=120) for t in apipe.submit_many(reqs)]
    for a, b in zip(out, ref):
        assert a.candidates == b.candidates
        assert a.matches == b.matches
    print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout


# --------------------------------------------------------------------------
# result-cache stat replay (satellite fix)
# --------------------------------------------------------------------------

def test_cache_hits_tagged_and_counted(small_db, flat):
    reqs = _requests(small_db, 4, seed=10)
    eng = GraphQueryEngine(flat, backend="numpy")
    first = eng.submit(reqs)
    assert eng.stats["cache_hits"] == 0
    again = eng.submit(reqs)
    assert eng.stats["cache_hits"] == len(reqs)
    for a, b in zip(again, first):
        assert a.candidates == b.candidates
        assert a.matches == b.matches
        assert a.stats.get("cache_hit") == 1
        assert a.filter_time_s == 0.0 and a.verify_time_s == 0.0
        assert b.stats.get("cache_hit") is None   # originals untouched


# --------------------------------------------------------------------------
# review regressions: coalescing vs deadlines, stage-failure containment
# --------------------------------------------------------------------------

def test_deadline_duplicate_not_coalesced_with_deadline_free(small_db, flat):
    """A deadline-free request must never inherit a same-batch duplicate's
    partial result (the coalescing key includes the deadline)."""
    rng = np.random.default_rng(12)
    from repro.graphs.generators import perturb_graph
    g = small_db[int(rng.integers(0, len(small_db)))]
    h = perturb_graph(g, 1, rng, small_db.n_vlabels, small_db.n_elabels)
    want = GraphQueryEngine(flat, backend="numpy").query(h, 2)
    eng = GraphQueryEngine(flat, backend="numpy")
    out = eng.submit([GraphQuery(h, 2, verify=True, deadline_s=0.0),
                      GraphQuery(h, 2, verify=True)])
    assert out[1].matches == want.matches        # full answer, not partial
    assert out[1].stats.get("partial") is None
    if out[0].candidates:
        assert out[0].stats.get("partial") == 1
    # async path shares _admit, so the same holds pipelined
    eng2 = GraphQueryEngine(flat, backend="numpy", result_cache_size=0)
    with AsyncGraphQueryEngine(eng2, max_batch=2, num_workers=2) as apipe:
        t_dead, t_free = apipe.submit_many(
            [GraphQuery(h, 2, verify=True, deadline_s=0.0),
             GraphQuery(h, 2, verify=True)])
        assert t_free.result(timeout=90).matches == want.matches


def test_filter_stage_failure_fails_batch_not_pipeline(small_db, flat):
    """A poisoned request errors its own batch's tickets and leaves the
    pipeline serving later batches."""
    reqs = _requests(small_db, 3, seed=13)
    eng = GraphQueryEngine(flat, backend="numpy")
    ref = GraphQueryEngine(flat, backend="numpy").submit(reqs)
    with AsyncGraphQueryEngine(eng, max_batch=1, num_workers=2) as apipe:
        bad = apipe.submit(GraphQuery(None, 1))       # type: ignore[arg-type]
        # batch failures surface as the typed FilterStageError with the
        # original exception chained (DESIGN.md §18)
        with pytest.raises(FilterStageError) as ei:
            bad.result(timeout=30)
        assert isinstance(ei.value.cause, AttributeError)
        assert ei.value.stage == "filter"
        with pytest.raises(FilterStageError):
            list(bad.stream(timeout=30))
        good = [t.result(timeout=90) for t in apipe.submit_many(reqs)]
    _assert_same(good, ref)


def test_as_completed_timeout_and_error_contract(small_db, flat):
    from repro.serve.pipeline import QueryTicket

    eng = GraphQueryEngine(flat, backend="numpy")
    with AsyncGraphQueryEngine(eng, max_batch=1, num_workers=1) as apipe:
        apipe.submit(GraphQuery(small_db[0], 1)).result(timeout=60)
        # an unresolved ticket: as_completed times out with the same
        # exception type as result()/stream()
        stuck = QueryTicket(GraphQuery(small_db[0], 1))
        with pytest.raises(TimeoutError):
            list(as_completed([stuck], timeout=0.05))
        bad = apipe.submit(GraphQuery(None, 1))       # type: ignore[arg-type]
        with pytest.raises(FilterStageError):
            list(as_completed([bad], timeout=30))


# --------------------------------------------------------------------------
# top-k modality: escalation re-entry, cache modality safety, deadline
# partials under both verify executors (DESIGN.md §15)
# --------------------------------------------------------------------------

def _topk_requests(db, n=4, seed=21, cap=4, deadline_s=None):
    from repro.graphs.generators import perturb_graph
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        g = perturb_graph(db[int(rng.integers(0, len(db)))],
                          int(rng.integers(1, 3)), rng, db.n_vlabels,
                          db.n_elabels)
        out.append(GraphQuery(g, cap, top_k=int(rng.integers(1, 5)),
                              deadline_s=deadline_s))
    return out


def test_async_topk_equals_sync_and_never_redecides(small_db, flat):
    """Pipelined top-k (tickets re-entering the batch former per widened-τ
    round) returns exactly the sync engine's k-best, range queries mixed
    in; scheduler stats account for every seen pair once — escalation
    never re-verifies a decided (query, gid) pair."""
    topk = _topk_requests(small_db, 4, seed=21)
    mixed = topk + _requests(small_db, 3, seed=22)
    ref = GraphQueryEngine(flat, backend="numpy",
                           result_cache_size=0).submit(mixed)
    eng = GraphQueryEngine(flat, backend="numpy", result_cache_size=0)
    with AsyncGraphQueryEngine(eng, max_batch=3, num_workers=2,
                               slice_expansions=40) as apipe:
        out = [t.result(timeout=120) for t in apipe.submit_many(mixed)]
    _assert_same(out, ref)
    s = apipe.stats
    assert s["topk_rounds"] > len(topk)       # someone actually escalated
    # every seen pair is decided exactly once: run to completion, pruned
    # by the kth-best cutoff, expired, or pruned by the stage-1.5
    # assignment LB before ever entering the heap (DESIGN.md §16)
    decided = (s["verified_pairs"] + s["pruned_pairs"]
               + s["expired_pairs"] + s["lb_pruned"])
    assert decided == sum(len(r.candidates) for r in out)
    if s["pruned_pairs"]:                     # kth-best cutoff engaged
        assert all([tuple(m) for m in a.matches]
                   == [tuple(m) for m in b.matches]
                   for a, b in zip(out, ref))


def test_async_topk_cache_modality_safe(small_db, flat):
    """A cached range-τ entry must not answer a top-k query at the same
    (graph, τ) and vice versa; repeats within each modality do hit, and
    the cache_hits counter stays exact."""
    g = _topk_requests(small_db, 1, seed=23)[0].graph
    eng = GraphQueryEngine(flat, backend="numpy")
    with AsyncGraphQueryEngine(eng, max_batch=1, num_workers=2) as apipe:
        r_range = apipe.submit(GraphQuery(g, 4)).result(timeout=120)
        r_topk = apipe.submit(
            GraphQuery(g, 4, top_k=2)).result(timeout=120)
        assert "top_k" not in r_range.stats
        assert r_topk.stats["top_k"] == 2
        assert "cache_hit" not in r_topk.stats    # range entry didn't leak
        hits_before = apipe.stats["cache_hits"]
        again_r = apipe.submit(GraphQuery(g, 4)).result(timeout=120)
        again_k = apipe.submit(
            GraphQuery(g, 4, top_k=2)).result(timeout=120)
        other_k = apipe.submit(
            GraphQuery(g, 4, top_k=3)).result(timeout=120)
    assert again_r.stats.get("cache_hit") == 1
    assert again_k.stats.get("cache_hit") == 1
    assert again_k.matches == r_topk.matches
    assert "cache_hit" not in other_k.stats       # k is part of the key
    assert apipe.stats["cache_hits"] == hits_before + 2


@pytest.mark.parametrize("executor", ["thread", "process"])
def test_topk_deadline_partial_both_executors(small_db, flat, executor):
    """A deadline hit mid-escalation resolves the verified prefix flagged
    ``partial`` — under the thread AND the process verify executor — and
    the partial is never cached: a deadline-free repeat recomputes the
    exact k-best."""
    reqs = _topk_requests(small_db, 3, seed=24, deadline_s=0.0)
    free = [GraphQuery(r.graph, r.tau, top_k=r.top_k) for r in reqs]
    ref = GraphQueryEngine(flat, backend="numpy",
                           result_cache_size=0).submit(free)
    eng = GraphQueryEngine(flat, backend="numpy")
    with AsyncGraphQueryEngine(eng, max_batch=3, num_workers=2,
                               verify_executor=executor,
                               slice_expansions=30) as apipe:
        out = [t.result(timeout=180) for t in apipe.submit_many(reqs)]
        for a, b in zip(out, ref):
            assert a.stats["partial"] == 1
            assert a.stats["top_k"] == b.stats["top_k"]
            # the verified prefix is a prefix of the true k-best list
            assert [tuple(m) for m in a.matches] \
                == [tuple(m) for m in b.matches][:len(a.matches)]
        # never cached: the deadline-free repeat is exact, not a hit
        full = [t.result(timeout=180) for t in apipe.submit_many(free)]
    for a, b in zip(full, ref):
        assert a.matches == b.matches
        assert "partial" not in a.stats
        assert "cache_hit" not in a.stats


def test_topk_escalation_survives_close(small_db, flat):
    """close() immediately after submission: in-flight escalation rounds
    keep the filter stage alive until every top-k ticket resolves."""
    reqs = _topk_requests(small_db, 3, seed=25)
    ref = GraphQueryEngine(flat, backend="numpy",
                           result_cache_size=0).submit(reqs)
    eng = GraphQueryEngine(flat, backend="numpy", result_cache_size=0)
    apipe = AsyncGraphQueryEngine(eng, max_batch=2, num_workers=2,
                                  slice_expansions=30)
    tickets = apipe.submit_many(reqs)
    apipe.close(timeout=120)
    out = [t.result(timeout=1) for t in tickets]   # already resolved
    _assert_same(out, ref)
