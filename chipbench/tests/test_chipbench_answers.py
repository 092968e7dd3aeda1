"""The answer comparison, the reference against the program's own
oracle, and the control that the comparison has to fail (CPU only)."""
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import bench  # noqa: E402
import control  # noqa: E402
import data  # noqa: E402
import reference  # noqa: E402
import registry  # noqa: E402
import traffic  # noqa: E402

CFG = {"n_vlabels": 62, "n_elabels": 3}
MIX = {"loop": "closed", "clients": 4, "pool_qps": 10.0, "kind": "range",
       "tau": [1, 2, 3], "edits": [1, 2], "bases": "size_strata",
       "repeats": False, "deadline_s": None}
RANGE = registry.load("queries", "range")


def make_db(name, n, seed):
    return getattr(data.generator(name), f"{name}_db")(n, seed=seed)


@pytest.fixture(scope="module")
def aids():
    db = make_db("aids_like", 600, 11)
    qs = control.plain_queries(db, CFG, MIX, 3.0, 5)
    ref = reference.ReferenceIndex(db, 62, 3)
    answers = [RANGE.expected(ref, q, a)
               for q, a in zip(qs.plain, qs.arrivals)]
    return db, qs, answers


def res(cand, matches, **stats):
    return SimpleNamespace(candidates=list(cand), matches=list(matches),
                           stats=stats)


def test_compare_flags_each_planted_fault(aids):
    _, _, answers = aids
    i = next(k for k, (c, m) in enumerate(answers) if m)
    cand, matches = exp = answers[i]
    assert RANGE.compare(res(cand, matches), exp) == (0, 0)
    extra = max(cand) + 1
    assert RANGE.compare(res(sorted(cand + [extra]), matches), exp) == (1, 0)
    wrong_ged = [(g, d + 1) for g, d in matches]
    assert RANGE.compare(res(cand, wrong_ged), exp) == (0, 1)
    assert RANGE.compare(res(cand, matches[1:]), exp) == (0, 1)
    assert RANGE.compare(None, exp) == (1, 1)


def test_check_counts_partials_errors_and_fallbacks_as_failed(aids):
    db, qs, answers = aids
    results = [res(c, m) for c, m in answers]
    index = list(range(len(results)))
    ok = bench.check_answers(bench.Outcome(results, [None] * len(index), 0),
                             index, qs, db, CFG, index)
    assert all(c["value"] == 0 for n, c in ok.items() if "limit" in c)
    results[3] = res(*answers[3], partial=1)
    errors = [None] * len(index)
    errors[4] = RuntimeError("filter stage failed")
    bad = bench.check_answers(bench.Outcome(results, errors, 2), index, qs,
                              db, CFG, index)
    assert bad["failed_queries"]["value"] == 4


def test_check_follows_each_query_sent_to_its_arrival(aids):
    """A closed loop that wrapped sends arrival i again as query k: the
    k-th answer is judged against arrival index[k]."""
    db, qs, answers = aids
    n = len(answers)
    index = [k % n for k in range(n + 5)]
    results = [res(*answers[i]) for i in index]
    out = bench.Outcome(results, [None] * len(index), 0)
    ok = bench.check_answers(out, index, qs, db, CFG, list(range(n, n + 5)))
    assert ok["wrong_matches"]["value"] == 0
    shifted = [k % n for k in range(1, n + 6)]
    j = next(k for k in range(n, n + 5)
             if answers[shifted[k]] != answers[index[k]])
    bad = bench.check_answers(out, shifted, qs, db, CFG, [j])
    assert bad["wrong_candidates"]["value"] + bad["wrong_matches"]["value"]


@pytest.mark.parametrize("gen,seed,nv,ne,taus", [
    ("aids_like", 1, 62, 3, (1, 2, 3)),
    ("graphgen", 2, 5, 2, (1, 2, 3, 4)),
])
def test_reference_agrees_with_the_program_oracle(gen, seed, nv, ne, taus):
    """At a small size the reference gives the program's host oracle's
    candidates and matches (FlatMSQIndex.query plus its exact GED)."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.search import FlatMSQIndex
    from repro.graphs.graph import GraphDB
    db = make_db(gen, 800, seed)
    to_program = registry.load("engines", "single_chip").to_program_graph
    index = FlatMSQIndex(GraphDB([to_program(g) for g in db], nv, ne), l=4)
    ref = reference.ReferenceIndex(db, nv, ne)
    rng = np.random.default_rng(seed)
    for _ in range(12):
        tau = int(rng.choice(taus))
        q = data.perturb_graph(db[int(rng.integers(len(db)))],
                               int(rng.integers(1, 3)), rng, nv, ne)
        got = index.query(to_program(q), tau)
        cand, matches = ref.answer(q, tau)
        assert list(got.candidates) == cand
        assert sorted((int(g), int(d)) for g, d in got.matches) == matches


def test_exact_ged_on_known_edits():
    g = data.make_graph(4, [0, 1, 2, 0], [(0, 1), (1, 2), (2, 3)],
                        [0, 0, 1])
    assert reference.exact_ged(g, g, 3) == 0
    h = data.make_graph(4, [0, 1, 2, 1], [(0, 1), (1, 2), (2, 3)],
                        [0, 0, 1])                       # one relabel
    assert reference.exact_ged(g, h, 3) == 1
    k = data.make_graph(5, [0, 1, 2, 1, 0], [(0, 1), (1, 2), (2, 3),
                                             (3, 4)], [0, 0, 1, 0])
    assert reference.exact_ged(g, k, 3) == 3             # +v, +e, relabel
    assert reference.exact_ged(g, k, 2) is None


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_the_control_fails_the_comparison(aids, seed):
    """The greedy edit distance, put in the program's place, is caught."""
    db, _, _ = aids
    qs = control.plain_queries(db, CFG, MIX, 3.0, seed)
    index = list(range(len(qs.arrivals)))
    sample = traffic.check_sample(len(index), 20, seed)
    out = control.control_outcome(qs, index, db, CFG, sample)
    checks = bench.check_answers(out, index, qs, db, CFG, sample)
    assert checks["wrong_candidates"]["value"] == 0
    assert checks["wrong_matches"]["value"] > checks["wrong_matches"]["limit"]
