"""Exact-GED verification tests: A* vs brute force + metric properties."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.verify import ged_bruteforce, ged_exact, ged_upto
from repro.graphs.generators import perturb_graph, random_graph
from repro.graphs.graph import Graph

NV, NE = 3, 2


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_astar_equals_bruteforce(seed):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, int(rng.integers(1, 5)), int(rng.integers(0, 5)),
                     NV, NE, connected=False)
    h = random_graph(rng, int(rng.integers(1, 5)), int(rng.integers(0, 5)),
                     NV, NE, connected=False)
    assert ged_exact(g, h) == ged_bruteforce(g, h)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_ged_symmetry_and_identity(seed):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, int(rng.integers(1, 5)), int(rng.integers(0, 4)),
                     NV, NE, connected=False)
    h = random_graph(rng, int(rng.integers(1, 5)), int(rng.integers(0, 4)),
                     NV, NE, connected=False)
    assert ged_exact(g, g) == 0
    assert ged_exact(g, h) == ged_exact(h, g)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_ged_triangle_inequality(seed):
    rng = np.random.default_rng(seed)
    gs = [random_graph(rng, int(rng.integers(1, 4)), int(rng.integers(0, 3)),
                       NV, NE, connected=False) for _ in range(3)]
    d01 = ged_exact(gs[0], gs[1])
    d12 = ged_exact(gs[1], gs[2])
    d02 = ged_exact(gs[0], gs[2])
    assert d02 <= d01 + d12


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(0, 3))
def test_perturbation_upper_bound(seed, k):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, int(rng.integers(2, 6)), int(rng.integers(1, 6)),
                     NV, NE)
    h = perturb_graph(g, k, rng, NV, NE)
    assert ged_upto(g, h, k) <= k


def test_ged_upto_cutoff_semantics():
    rng = np.random.default_rng(7)
    g = random_graph(rng, 4, 4, NV, NE)
    h = perturb_graph(g, 6, rng, NV, NE)
    true = ged_exact(g, h)
    for tau in range(0, true + 2):
        r = ged_upto(g, h, tau)
        if tau >= true:
            assert r == true
        else:
            assert r == tau + 1


def test_isomorphic_relabeling_is_zero():
    rng = np.random.default_rng(9)
    g = random_graph(rng, 6, 8, NV, NE)
    perm = rng.permutation(6)
    assert ged_exact(g, g.relabel_vertices(perm)) == 0


# --------------------------------------------------------------------------
# escalation invariants (DESIGN.md §15): decisions made at a narrow filter
# τ stay valid at every wider τ, and a cap-cutoff GEDSearch sliced across
# escalation rounds decides exactly like a one-shot run
# --------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, 5))
def test_decided_pair_valid_across_tau_widening(seed, cap):
    """The no-recompute premise of adaptive-τ top-k: once ``ged_upto(g, h,
    cap)`` decides a pair, re-asking at any admission τ' changes nothing
    — a decided exact d <= cap is the same d for every cutoff >= d."""
    rng = np.random.default_rng(seed)
    g = random_graph(rng, int(rng.integers(2, 5)), int(rng.integers(1, 5)),
                     NV, NE, connected=False)
    h = perturb_graph(g, int(rng.integers(0, cap + 2)), rng, NV, NE)
    d = ged_upto(g, h, cap)
    if d <= cap:                         # decided: exact at cutoff cap
        for wider in range(d, cap + 3):
            assert ged_upto(g, h, wider) == d
    else:                                # undecided at cap: only > cap known
        assert d == cap + 1
        assert ged_upto(g, h, cap + 2) > cap


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, 4), st.integers(1, 6))
def test_ged_search_resume_across_rounds_equals_oneshot(seed, cap, budget):
    """Top-k escalation parks an undecided ``GEDSearch`` (cutoff = the
    query cap) and resumes it in a later round: arbitrary slicing of the
    same search object must reproduce the one-shot decision and frontier
    bound exactly."""
    from repro.core.verify import GEDSearch
    rng = np.random.default_rng(seed)
    g = random_graph(rng, int(rng.integers(2, 5)), int(rng.integers(1, 5)),
                     NV, NE, connected=False)
    h = perturb_graph(g, int(rng.integers(0, cap + 2)), rng, NV, NE)
    want = ged_upto(g, h, cap)
    s = GEDSearch(g, h, cap)
    rounds = 0
    r = None
    while r is None:
        r = s.run(max_expansions=budget)   # one escalation round's slice
        rounds += 1
        assert rounds < 10_000
    assert r == want
    assert s.done and s.min_f() == want
    # a decided search re-entered by a later round is a no-op, not a redo
    exp_before = s.expansions
    assert s.run(max_expansions=budget) == want
    assert s.expansions == exp_before
