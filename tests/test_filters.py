"""Filter admissibility + cross-implementation equality (property tests).

The central invariant of the paper: every filter is a LOWER bound on GED,
i.e. no false dismissals ever.  We verify against brute-force GED on random
small graphs, and verify the scalar / batched-numpy / batched-jax / Pallas
paths agree exactly.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import filters
from repro.core.verify import ged_bruteforce
from repro.graphs.generators import perturb_graph, random_graph

NV, NE = 4, 3


def rand_pair(seed):
    rng = np.random.default_rng(seed)
    g = random_graph(rng, int(rng.integers(1, 5)), int(rng.integers(0, 5)),
                     NV, NE, connected=False)
    h = random_graph(rng, int(rng.integers(1, 5)), int(rng.integers(0, 5)),
                     NV, NE, connected=False)
    return g, h


@settings(max_examples=60, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_all_filters_admissible(seed):
    g, h = rand_pair(seed)
    true = ged_bruteforce(g, h)
    bounds = filters.pairwise_bounds(g, h, NV, NE)
    for name, b in bounds.items():
        assert b <= true, (name, b, true)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(0, 4))
def test_perturbation_upper_bounds_filters(seed, k):
    """ged(g, perturb(g, k)) <= k, so every filter bound must be <= k."""
    rng = np.random.default_rng(seed)
    g = random_graph(rng, int(rng.integers(2, 7)), int(rng.integers(1, 8)),
                     NV, NE)
    h = perturb_graph(g, k, rng, NV, NE)
    bounds = filters.pairwise_bounds(g, h, NV, NE)
    assert bounds["combined"] <= k, bounds


def test_filters_identity():
    rng = np.random.default_rng(0)
    g = random_graph(rng, 6, 7, NV, NE)
    b = filters.pairwise_bounds(g, g, NV, NE)
    assert b["combined"] == 0


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_degseq_delta_symmetry_and_zero(seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 8, rng.integers(1, 9))
    y = rng.integers(0, 8, len(x))
    assert filters.degseq_delta(x, x) == 0
    assert filters.degseq_delta(x, y) == filters.degseq_delta(y, x)


def test_batched_matches_scalar():
    rng = np.random.default_rng(1)
    from repro.graphs.batching import PaddedGraphBatch
    from repro.graphs.graph import GraphDB
    from repro.core.qgrams import EncodedDB, sparse_intersection_size
    from repro.core.tree import QueryTuple

    graphs = [random_graph(rng, int(rng.integers(1, 7)),
                           int(rng.integers(0, 8)), NV, NE, connected=False)
              for _ in range(40)]
    db = GraphDB(graphs, NV, NE)
    h = random_graph(rng, 5, 6, NV, NE)
    enc = EncodedDB.build(db)
    q = QueryTuple.from_graph(h, enc.vocab)
    batch = PaddedGraphBatch.from_db(db)
    c_d = np.array([sparse_intersection_size(*enc.row_degree(i), q.d_ids,
                                             q.d_cnt)
                    for i in range(len(db))])
    sig = np.zeros(batch.vmax, np.int64)
    sig[:min(h.n, batch.vmax)] = q.sigma[:batch.vmax]
    out = filters.batched_bounds_np(
        batch.nv, batch.ne, batch.degseq, batch.vlabel_hist,
        batch.elabel_hist, c_d, h.n, h.m, sig,
        h.vertex_label_hist(NV), h.edge_label_hist(NE))
    for i, g in enumerate(graphs):
        b = filters.pairwise_bounds(g, h, NV, NE)
        for name in ("number_count", "label_qgram", "degree_qgram",
                     "degree_sequence"):
            assert out[name][i] == b[name], (i, name, out[name][i], b[name])


def test_jax_matches_numpy():
    import jax.numpy as jnp
    from repro.core import filters_jax as fj
    from repro.core.search import FlatMSQIndex
    from repro.graphs.generators import aids_like_db

    db = aids_like_db(60, seed=3)
    flat = FlatMSQIndex(db)
    dbar = fj.db_arrays_from_encoded(flat.enc, flat.partition)
    rng = np.random.default_rng(0)
    h = perturb_graph(db[7], 2, rng, db.n_vlabels, db.n_elabels)
    for tau in (1, 3, 5):
        q = fj.query_arrays_from_graph(h, flat.vocab, flat.partition, tau,
                                       vmax=dbar.degseq.shape[1])
        mask, _ = fj.filter_pass(dbar, q, flat.partition.x0,
                                 flat.partition.y0, flat.partition.l)
        cand_jax = sorted(np.flatnonzero(np.asarray(mask)).tolist())
        assert cand_jax == flat.candidates(h, tau)
