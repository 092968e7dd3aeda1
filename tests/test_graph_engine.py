"""GraphQueryEngine: batched == per-query equivalence and edge cases.

The load-bearing invariant of the batched serving path: for every backend
and both index kinds, the engine's candidate sets and verified matches are
IDENTICAL to the single-query ``MSQIndex.query`` / ``FlatMSQIndex.query``
— bucketing, padding, and worklist ordering must never change answers.
"""
import numpy as np
import pytest

from repro.core.engine import BatchedFilterEval, bucket_queries
from repro.core.search import FlatMSQIndex, MSQIndex
from repro.graphs.generators import aids_like_db, graphgen_db, perturb_graph
from repro.graphs.graph import Graph
from repro.serve.graph_engine import GraphQuery, GraphQueryEngine


@pytest.fixture(scope="module")
def small_db():
    return aids_like_db(180, seed=7)


@pytest.fixture(scope="module")
def flat(small_db):
    return FlatMSQIndex(small_db)


@pytest.fixture(scope="module")
def tree(small_db):
    return MSQIndex(small_db)


def _requests(db, n, seed, verify=False, tau_hi=5):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        tau = int(rng.integers(1, tau_hi))
        h = perturb_graph(db[int(rng.integers(0, len(db)))], tau, rng,
                          db.n_vlabels, db.n_elabels)
        out.append(GraphQuery(h, tau, verify=verify))
    return out


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_batched_equals_per_query_flat(small_db, flat, backend):
    reqs = _requests(small_db, 16, seed=1)
    eng = GraphQueryEngine(flat, backend=backend)
    out = eng.submit(reqs)
    for r, got in zip(reqs, out):
        assert got.candidates == flat.candidates(r.graph, r.tau)


def test_batched_equals_per_query_tree(small_db, tree):
    reqs = _requests(small_db, 12, seed=2)
    out = GraphQueryEngine(tree).submit(reqs)
    for r, got in zip(reqs, out):
        assert got.candidates == tree.candidates(r.graph, r.tau)[0]


def test_batched_matches_equal_per_query(small_db, flat):
    reqs = _requests(small_db, 6, seed=3, verify=True, tau_hi=3)
    out = GraphQueryEngine(flat).submit(reqs)
    for r, got in zip(reqs, out):
        ref = flat.query(r.graph, r.tau)
        assert got.candidates == ref.candidates
        assert got.matches == ref.matches


def test_other_dbs_and_taus(tmp_path):
    """Equivalence across a second generator family and the full tau sweep."""
    db = graphgen_db(90, num_edges=12, density=0.5, n_vlabels=4,
                     n_elabels=2, seed=13)
    flat = FlatMSQIndex(db)
    eng = GraphQueryEngine(flat)
    rng = np.random.default_rng(4)
    for tau in (0, 1, 2, 4, 6):
        h = perturb_graph(db[int(rng.integers(0, len(db)))], max(tau, 1),
                          rng, db.n_vlabels, db.n_elabels)
        got = eng.query(h, tau, verify=False)
        assert got.candidates == flat.candidates(h, tau)


def test_empty_batch(flat):
    assert GraphQueryEngine(flat).submit([]) == []


def test_empty_region_query(small_db, flat, tree):
    """A query far outside every populated region must return cleanly."""
    giant = Graph(n=500, vlabels=np.zeros(500, np.int32),
                  edges=np.array([(i, i + 1) for i in range(499)], np.int64),
                  elabels=np.zeros(499, np.int32))
    for eng in (GraphQueryEngine(flat), GraphQueryEngine(tree)):
        res = eng.query(giant, 1)
        assert res.candidates == []
        assert res.matches == []
        assert res.n_filtered == len(small_db)


def test_result_cache_and_duplicates(small_db, flat):
    reqs = _requests(small_db, 4, seed=5)
    dup = [reqs[0], reqs[1], reqs[0], reqs[2], reqs[0], reqs[3]]
    eng = GraphQueryEngine(flat)
    out1 = eng.submit(dup)
    assert out1[0].candidates == out1[2].candidates == out1[4].candidates
    # a second submit of the same batch is served from the result cache
    before = eng.cache_info["result_hits"]
    out2 = eng.submit(dup)
    assert eng.cache_info["result_hits"] > before
    for a, b in zip(out1, out2):
        assert a.candidates == b.candidates


def test_bucketing_groups_equal_rectangles(small_db, flat):
    reqs = _requests(small_db, 20, seed=6)
    graphs = [r.graph for r in reqs]
    taus = [r.tau for r in reqs]
    buckets = bucket_queries(flat.partition, graphs, taus)
    assert sorted(qi for qis in buckets.values() for qi in qis) \
        == list(range(len(reqs)))
    for (i1, i2, j1, j2), qis in buckets.items():
        for qi in qis:
            assert flat.partition.query_region(
                graphs[qi].n, graphs[qi].m, taus[qi]) == (i1, i2, j1, j2)


def test_filter_eval_reused_across_batches(flat):
    ev1 = flat.filter_eval("numpy")
    ev2 = flat.filter_eval("numpy")
    assert ev1 is ev2
    assert isinstance(ev1, BatchedFilterEval)


# ---- stage-1.5 assignment lower bound (DESIGN.md §16) ----------------------

@pytest.fixture(scope="module")
def lb_db():
    # label-poor on purpose: the q-gram filter admits candidates whose GED
    # is far above tau, so the LB stage actually prunes here instead of
    # riding along inert
    return graphgen_db(120, num_edges=12, density=0.5, n_vlabels=3,
                       n_elabels=2, seed=3)


@pytest.mark.parametrize("backend,slab", [
    ("numpy", "dense"), ("numpy", "hot"), ("numpy", "packed"),
    ("jax", "dense"), ("pallas", "dense"),
])
def test_assign_lb_match_parity(lb_db, backend, slab):
    """The recall-safety invariant: candidates AND verified matches are
    bit-identical with the LB stage off / on / on+Hungarian — the bound
    only moves verification work, never answers."""
    flat = FlatMSQIndex(lb_db)
    rng = np.random.default_rng(11)
    reqs = [GraphQuery(perturb_graph(lb_db[int(rng.integers(0, len(lb_db)))],
                                     2, rng, lb_db.n_vlabels,
                                     lb_db.n_elabels), 4, verify=True)
            for _ in range(6)]
    base = GraphQueryEngine(flat, backend=backend, slab_layout=slab,
                            assign_lb=False).submit(reqs)
    for lb_hungarian in (0, 4):
        eng = GraphQueryEngine(flat, backend=backend, slab_layout=slab,
                               assign_lb=True, lb_hungarian=lb_hungarian)
        out = eng.submit(reqs)
        for a, b in zip(out, base):
            assert a.candidates == b.candidates
            assert a.matches == b.matches
        if lb_hungarian == 0:
            # the stage must actually fire on this workload, not pass
            # vacuously
            assert eng.stats["lb_pruned"] > 0
            assert eng.stats["lb_pruned"] + eng.stats["verified_pairs"] > 0


# ---- top-k modality (adaptive-τ escalation, DESIGN.md §15) -----------------

from hypothesis import given, settings, strategies as st


@pytest.fixture(scope="module")
def topk_db():
    # small enough that the k-smallest-GED brute-force oracle is cheap
    return aids_like_db(100, seed=9)


@pytest.fixture(scope="module")
def topk_flat(topk_db):
    return FlatMSQIndex(topk_db)


def _topk_queries(db, n=4, seed=21):
    rng = np.random.default_rng(seed)
    return [perturb_graph(db[int(rng.integers(0, len(db)))],
                          int(rng.integers(1, 3)), rng, db.n_vlabels,
                          db.n_elabels) for _ in range(n)]


def _oracle_topk(db, g, k, cap):
    """Brute-force k smallest GEDs over the whole db, tie rule (ged, gid)
    — independent of every filter/index/scheduler code path."""
    from repro.core.verify import ged_upto
    ds = sorted((ged_upto(g, h, cap), gid) for gid, h in enumerate(db))
    return [(gid, d) for d, gid in ds if d <= cap][:k]


@pytest.fixture(scope="module")
def topk_oracle(topk_db):
    """One oracle evaluation shared across the backend x layout matrix."""
    qs = _topk_queries(topk_db)
    return qs, {(i, k, cap): _oracle_topk(topk_db, g, k, cap)
                for i, g in enumerate(qs)
                for k, cap in ((1, 3), (3, 4), (5, 4))}


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
@pytest.mark.parametrize("slab", ["dense", "hot", "packed"])
def test_topk_equals_oracle_backend_layout_matrix(topk_db, topk_flat,
                                                  topk_oracle, backend,
                                                  slab):
    """Engine top-k is bit-identical to the brute-force k-smallest-GED
    oracle for every backend x FilterSlab layout, and the escalation
    never decides a (query, gid) pair twice (scheduler stats account for
    every seen candidate exactly once: verified, pruned, or expired)."""
    qs, oracle = topk_oracle
    eng = GraphQueryEngine(topk_flat, backend=backend, slab_layout=slab,
                           hot_d=8, result_cache_size=0)
    reqs, want = [], []
    for i, g in enumerate(qs):
        for k, cap in ((1, 3), (3, 4), (5, 4)):
            reqs.append(GraphQuery(g, cap, top_k=k))
            want.append(oracle[(i, k, cap)])
    out = eng.submit(reqs)
    for r, got, ref in zip(reqs, out, want):
        assert [tuple(m) for m in got.matches] == ref, \
            (backend, slab, r.top_k, r.tau)
        assert got.stats["top_k"] == r.top_k
    decided = (eng.stats["verified_pairs"] + eng.stats["pruned_pairs"]
               + eng.stats["expired_pairs"])
    assert decided == sum(len(r.candidates) for r in out), \
        "a decided (query, gid) pair was re-verified across escalation"
    assert eng.stats["expired_pairs"] == 0


def test_topk_escalates_and_stops_early(topk_db, topk_flat):
    """k hits inside a small τ: escalation stops once the kth-best bound
    proves no wider τ helps (final τ < cap), and stats record rounds."""
    g = topk_db[5]                       # exact member: d(g, 5) = 0
    eng = GraphQueryEngine(topk_flat, backend="numpy",
                           result_cache_size=0)
    res = eng.query_topk(g, k=1, cap=6)
    assert [tuple(m) for m in res.matches] == [(5, 0)]
    assert res.stats["topk_rounds"] >= 1
    assert res.stats["topk_tau_final"] < 6   # kth-best (0) ended it early
    assert "partial" not in res.stats


def test_topk_exhausted_when_k_exceeds_cap_ball(topk_db, topk_flat):
    """Fewer than k graphs within the cap: every one is returned, the
    result is flagged exhausted, never partial."""
    qs = _topk_queries(topk_db, n=2, seed=33)
    eng = GraphQueryEngine(topk_flat, backend="numpy",
                           result_cache_size=0)
    for g in qs:
        want = _oracle_topk(topk_db, g, len(topk_db), 1)
        res = eng.query_topk(g, k=len(topk_db), cap=1)
        assert [tuple(m) for m in res.matches] == want
        assert res.stats["topk_exhausted"] == 1
        assert "partial" not in res.stats


def test_topk_mixed_batch_matches_solo(topk_db, topk_flat):
    """Top-k and range queries share one submit(): same answers as when
    issued alone (the split paths must not interfere)."""
    qs = _topk_queries(topk_db, n=3, seed=44)
    mixed = [GraphQuery(qs[0], 4, top_k=2), GraphQuery(qs[1], 2),
             GraphQuery(qs[2], 4, top_k=4), GraphQuery(qs[0], 1),
             GraphQuery(qs[1], 3, top_k=1)]
    eng = GraphQueryEngine(topk_flat, backend="numpy",
                           result_cache_size=0)
    out = eng.submit(mixed)
    solo = GraphQueryEngine(topk_flat, backend="numpy",
                            result_cache_size=0)
    for r, got in zip(mixed, out):
        ref = solo.submit([r])[0]
        assert got.matches == ref.matches
        assert got.candidates == ref.candidates


def test_topk_validation(topk_db):
    with pytest.raises(ValueError, match="top_k"):
        GraphQuery(topk_db[0], 3, top_k=0)
    with pytest.raises(ValueError, match="verify"):
        GraphQuery(topk_db[0], 3, top_k=2, verify=False)


def test_topk_result_cache_is_modality_safe(topk_db, topk_flat):
    """A cached range-τ result must never answer a top-k query at the
    same (graph, τ) — and vice versa; repeats within a modality hit."""
    g = _topk_queries(topk_db, n=1, seed=55)[0]
    eng = GraphQueryEngine(topk_flat, backend="numpy")
    r_range = eng.query(g, 4)
    r_topk = eng.query_topk(g, k=2, cap=4)
    assert "top_k" not in r_range.stats
    assert r_topk.stats["top_k"] == 2
    # same modality repeats are cache hits with identical payloads
    again_r = eng.query(g, 4)
    again_k = eng.query_topk(g, k=2, cap=4)
    assert again_r.stats.get("cache_hit") == 1
    assert again_k.stats.get("cache_hit") == 1
    assert again_r.matches == r_range.matches
    assert again_k.matches == r_topk.matches
    # distinct k at the same (graph, τ) is a distinct entry
    r_k3 = eng.query_topk(g, k=3, cap=4)
    assert "cache_hit" not in r_k3.stats
    assert len(r_k3.matches) >= len(r_topk.matches)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 8), st.integers(1, 4), st.integers(0, 2 ** 31 - 1))
def test_topk_property_random_k_cap(topk_db, topk_flat, k, cap, seed):
    """Property: for random (k, cap, query) draws the engine's top-k list
    equals the oracle's k-smallest (ged, gid) — including sort order."""
    rng = np.random.default_rng(seed)
    g = perturb_graph(topk_db[int(rng.integers(0, len(topk_db)))],
                      int(rng.integers(1, 3)), rng, topk_db.n_vlabels,
                      topk_db.n_elabels)
    eng = GraphQueryEngine(topk_flat, backend="numpy",
                           result_cache_size=0)
    res = eng.query_topk(g, k=k, cap=cap)
    assert [tuple(m) for m in res.matches] == _oracle_topk(
        topk_db, g, k, cap)
