"""Baseline filters (C-Star, Branch, path q-grams, kappa-AT) must also be
admissible, and the paper's comparative claims should hold in trend."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import baselines
from repro.core.verify import ged_bruteforce
from repro.graphs.generators import perturb_graph, random_graph

NV, NE = 4, 3


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_baseline_bounds_admissible(seed):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, int(rng.integers(1, 5)), int(rng.integers(0, 5)),
                     NV, NE, connected=False)
    h = random_graph(rng, int(rng.integers(1, 5)), int(rng.integers(0, 5)),
                     NV, NE, connected=False)
    true = ged_bruteforce(g, h)
    assert baselines.cstar_lb(g, h) <= true + 1e-9
    assert baselines.branch_lb(g, h) <= true + 1e-9
    assert baselines.path_qgram_lb(g, h, p=2) <= true + 1e-9
    assert baselines.kat_lb(g, h) <= true + 1e-9


def test_baseline_zero_on_identity():
    rng = np.random.default_rng(1)
    g = random_graph(rng, 6, 7, NV, NE)
    assert baselines.cstar_lb(g, g) == 0
    assert baselines.branch_lb(g, g) == 0
    assert baselines.path_qgram_lb(g, g) == 0
    assert baselines.kat_lb(g, g) == 0


def test_index_size_ordering():
    """Fig 7 claim (trend at test scale): MSQ-Index is a fraction of the
    baselines.  The paper's 5–15% ratio needs large |G| to amortise the
    per-node tree overhead — benchmarks/index_size.py measures that; here
    we assert the ordering at small |G|."""
    from repro.core.search import MSQIndex
    from repro.graphs.generators import aids_like_db
    db = aids_like_db(500, seed=4)
    idx = MSQIndex(db)
    msq_bits = idx.size_bits()["total"]
    assert msq_bits < 0.30 * baselines.branch_index_bits(db)
    assert msq_bits < 0.35 * baselines.cstar_index_bits(db)
    assert msq_bits < 0.45 * baselines.path_index_bits(db, p=2)


def test_star_structures_shapes():
    rng = np.random.default_rng(2)
    g = random_graph(rng, 5, 6, NV, NE)
    stars = baselines.star_structures(g)
    assert len(stars) == g.n
    degs = g.degrees()
    for v, (l, nb, el) in enumerate(stars):
        assert len(nb) == degs[v] == len(el)
