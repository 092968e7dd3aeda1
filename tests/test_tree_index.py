"""End-to-end index tests: Algorithm 1/2 correctness, no false dismissal,
tree == flat equivalence, region reduction soundness, space accounting."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.region import default_partition, group_by_region
from repro.core.search import FlatMSQIndex, MSQIndex
from repro.core.verify import ged_upto
from repro.graphs.generators import aids_like_db, graphgen_db, perturb_graph


@pytest.fixture(scope="module")
def small_db():
    return aids_like_db(150, seed=11)


@pytest.fixture(scope="module")
def index(small_db):
    return MSQIndex(small_db)


@pytest.fixture(scope="module")
def flat(small_db):
    return FlatMSQIndex(small_db)


@pytest.mark.parametrize("tau", [0, 1, 2, 3, 5])
def test_no_false_dismissal(small_db, index, tau):
    rng = np.random.default_rng(tau)
    h = perturb_graph(small_db[17], max(tau, 1), rng, small_db.n_vlabels,
                      small_db.n_elabels)
    res = index.query(h, tau)
    truth = sorted(i for i in range(len(small_db))
                   if ged_upto(small_db[i], h, tau) <= tau)
    assert sorted(m[0] for m in res.matches) == truth
    assert set(truth) <= set(res.candidates)


@pytest.mark.parametrize("tau", [1, 3, 5])
def test_tree_equals_flat(small_db, index, flat, tau):
    rng = np.random.default_rng(100 + tau)
    for qi in (3, 40, 77):
        h = perturb_graph(small_db[qi], tau, rng, small_db.n_vlabels,
                          small_db.n_elabels)
        assert index.candidates(h, tau)[0] == flat.candidates(h, tau)


def test_self_query_finds_self(small_db, index):
    res = index.query(small_db[42], 0)
    assert any(gid == 42 and d == 0 for gid, d in res.matches)


def test_region_reduction_sound(small_db):
    """Every graph within number-count tau of the query must fall inside
    the reduced query region Q_h (Section 4)."""
    nv, ne = small_db.sizes()
    part = default_partition(nv, ne, l=4)
    ri, rj = part.region_of(nv, ne)
    rng = np.random.default_rng(5)
    for tau in (1, 2, 4):
        h = perturb_graph(small_db[int(rng.integers(0, len(small_db)))],
                          tau, rng, small_db.n_vlabels, small_db.n_elabels)
        i1, i2, j1, j2 = part.query_region(h.n, h.m, tau)
        close = np.abs(nv - h.n) + np.abs(ne - h.m) <= tau
        inside = (ri >= i1) & (ri <= i2) & (rj >= j1) & (rj <= j2)
        assert np.all(inside[close])


def test_regions_partition_db(small_db):
    nv, ne = small_db.sizes()
    part = default_partition(nv, ne)
    groups = group_by_region(part, nv, ne)
    all_ids = np.sort(np.concatenate(list(groups.values())))
    assert np.array_equal(all_ids, np.arange(len(small_db)))


def test_succinct_smaller_than_plain(index):
    sq = index.size_bits()
    q = index.plain_size_bits()
    # Table 3: >80% total reduction, >90% on the frequency arrays
    assert sq["total"] < 0.2 * q["total"]
    assert sq["S_b"] + sq["S_c"] < 0.12 * (q["S_b"] + q["S_c"])


def test_dense_graphs_db():
    db = graphgen_db(60, num_edges=30, density=0.5, n_vlabels=5,
                     n_elabels=2, seed=2)
    idx = MSQIndex(db)
    rng = np.random.default_rng(0)
    h = perturb_graph(db[10], 2, rng, db.n_vlabels, db.n_elabels)
    res = idx.query(h, 2, verify=False)
    flat = FlatMSQIndex(db)
    assert res.candidates == flat.candidates(h, 2)


def test_query_stats(index, small_db):
    rng = np.random.default_rng(3)
    h = perturb_graph(small_db[5], 1, rng, small_db.n_vlabels,
                      small_db.n_elabels)
    res = index.query(h, 1, collect_stats=True)
    s = res.stats
    assert s["regions_visited"] <= s["regions_total"]
    assert s["leaves_checked"] <= s["nodes_visited"]
