"""DeviceSlabCache + query-batched pallas engine path (DESIGN.md §13).

Three invariants:

* the batched pallas `_bounds_pallas` (one kernel launch per bucket)
  matches the numpy backend bit-for-bit across layouts and ragged
  (Q, B) shapes — and is invariant to the (qb, bb, bu) tile choice;
* repeated batches hit the device cache (no re-gather / re-upload), and
  eviction/invalidation never changes results;
* invalidation hooks fire: ``rebuild_slab`` and ``set_filter_eval``
  empty the cache of a replaced slab.
"""
import json
import os

import numpy as np
import pytest

from repro.core.engine import BatchedFilterEval, sparse_query_fd
from repro.core.device_cache import DeviceSlabCache, bucket_key
from repro.core.search import FlatMSQIndex
from repro.graphs.generators import aids_like_db, perturb_graph


@pytest.fixture(scope="module")
def small_db():
    return aids_like_db(140, seed=5)


@pytest.fixture(scope="module")
def flat(small_db):
    return FlatMSQIndex(small_db)


def _queries(db, ev, n, seed=1):
    rng = np.random.default_rng(seed)
    qs, taus = [], []
    for _ in range(n):
        tau = int(rng.integers(1, 4))
        h = perturb_graph(db[int(rng.integers(0, len(db)))], tau, rng,
                          db.n_vlabels, db.n_elabels)
        qs.append(ev.query_arrays(h, tau))
        taus.append(tau)
    return qs, taus


# --------------------------------------------------------------------------
# batched pallas parity vs numpy, layouts x ragged shapes x tiles
# --------------------------------------------------------------------------

@pytest.mark.parametrize("slab", ["dense", "hot", "packed"])
@pytest.mark.parametrize("Q,N", [(1, 17), (5, 140), (11, 97)])
def test_batched_pallas_matches_numpy(flat, small_db, slab, Q, N):
    ev_np = flat.filter_eval("numpy", slab=slab, hot_d=24)
    ev_pl = flat.filter_eval("pallas", slab=slab, hot_d=24)
    qs, _ = _queries(small_db, ev_np, Q, seed=Q * 100 + N)
    idx = np.sort(np.random.default_rng(N).choice(
        len(small_db), size=N, replace=False))
    want = ev_np.bounds(idx, qs)
    got = ev_pl.bounds(idx, qs)
    assert np.array_equal(np.asarray(got, np.int64),
                          np.asarray(want, np.int64))


def test_batched_pallas_tile_invariance(flat, small_db):
    from repro.kernels.qgram_filter.autotune import TileTable
    ev_np = flat.filter_eval("numpy", slab="dense")
    qs, _ = _queries(small_db, ev_np, 6, seed=9)
    idx = np.arange(len(small_db))
    want = np.asarray(ev_np.bounds(idx, qs), np.int64)
    for tiles in [(4, 32, 128), (8, 128, 512), (16, 64, 256)]:
        ev = BatchedFilterEval(flat.db, flat.enc, flat.partition, "pallas",
                               tile_table=TileTable(default=tiles))
        got = ev.bounds(idx, qs)
        assert np.array_equal(np.asarray(got, np.int64), want), tiles


# --------------------------------------------------------------------------
# cache behaviour
# --------------------------------------------------------------------------

def test_cache_hits_on_repeat_and_results_stable(flat, small_db):
    ev = BatchedFilterEval(flat.db, flat.enc, flat.partition, "pallas")
    qs, _ = _queries(small_db, ev, 4, seed=2)
    idx = np.arange(len(small_db))
    first = ev.bounds(idx, qs)
    misses = ev.device_cache.stats["misses"]
    assert misses > 0 and ev.device_cache.stats["hits"] == 0
    again = ev.bounds(idx, qs)
    assert ev.device_cache.stats["misses"] == misses   # all fields reused
    assert ev.device_cache.stats["hits"] > 0
    assert np.array_equal(first, again)


def test_cache_invalidated_on_rebuild_slab(flat, small_db):
    ev = BatchedFilterEval(flat.db, flat.enc, flat.partition, "numpy",
                           slab="dense")
    qs, _ = _queries(small_db, ev, 3, seed=3)
    idx = np.arange(len(small_db))
    want = np.asarray(ev.bounds(idx, qs))
    assert len(ev.device_cache) > 0
    ev.rebuild_slab(layout="hot", hot_d=16)
    assert len(ev.device_cache) == 0           # stale uploads dropped
    assert ev.slab_layout == "hot"
    got = np.asarray(ev.bounds(idx, qs))       # rebuilt slab, same bounds
    assert np.array_equal(got, want)
    assert ev.device_cache.stats["invalidations"] == 1


def test_set_filter_eval_invalidates_replaced_evaluator(small_db):
    flat = FlatMSQIndex(small_db)
    ev1 = flat.filter_eval("numpy")
    qs, _ = _queries(small_db, ev1, 2, seed=4)
    ev1.bounds(np.arange(len(small_db)), qs)
    assert len(ev1.device_cache) > 0
    ev2 = BatchedFilterEval(flat.db, flat.enc, flat.partition, "numpy")
    flat.set_filter_eval("numpy", ev2)
    assert len(ev1.device_cache) == 0
    # re-registering the same evaluator must NOT clear its cache
    ev2.bounds(np.arange(len(small_db)), qs)
    n = len(ev2.device_cache)
    flat.set_filter_eval("numpy", ev2)
    assert len(ev2.device_cache) == n


def test_cache_lru_eviction_bounded():
    cache = DeviceSlabCache(max_entries=2)
    for i in range(5):
        cache.get_or_build(("k", i), "f", lambda i=i: i)
    assert len(cache) == 2
    assert cache.stats["evictions"] == 3
    # survivors are the most recent keys
    assert cache.get_or_build(("k", 4), "f", lambda: -1) == 4


def test_bucket_key_exact_identity():
    a = np.array([1, 2, 3], np.int64)
    b = np.array([1, 2, 4], np.int64)
    assert bucket_key(a, 8) == bucket_key(a.copy(), 8)
    assert bucket_key(a, 8) != bucket_key(b, 8)
    assert bucket_key(a, 8) != bucket_key(a, 16)


# --------------------------------------------------------------------------
# sparse query C_D helper + autotune table
# --------------------------------------------------------------------------

def test_sparse_query_fd_roundtrip():
    rng = np.random.default_rng(0)
    qfd = rng.integers(0, 3, (5, 40)).astype(np.int32)
    ids, cnt = sparse_query_fd(qfd, pad=8)
    assert ids.shape == cnt.shape and ids.shape[1] % 8 == 0
    dense = np.zeros_like(qfd)
    for r in range(5):
        np.add.at(dense[r], ids[r], cnt[r])   # id-0 pads carry count 0
    assert np.array_equal(dense, qfd)


def test_autotune_roundtrip(flat, tmp_path):
    """The sweep on a tiny slab persists a table that loads back and
    resolves real shapes; unknown shapes fall back to the defaults."""
    from repro.kernels.qgram_filter import autotune
    ev = BatchedFilterEval(flat.db, flat.enc, flat.partition, "pallas")
    path = os.path.join(tmp_path, "tiles.json")
    table = ev.autotune_tiles(qs=(4,), save_path=path, repeats=1,
                              candidates=[(4, 64, 128), (8, 128, 256)])
    assert len(table) > 0
    doc = json.load(open(path))
    assert doc["entries"] and doc["timed_on"]
    loaded = autotune.load_tile_table(path)
    key = next(iter(loaded.entries))
    q, b, u = (int(x) for x in key.split("x"))
    assert loaded.lookup(q, b, u) == tuple(loaded.entries[key])
    assert loaded.lookup(10 ** 6, 10 ** 6, 10 ** 6) == loaded.default
    autotune.load_tile_table.cache_clear()


def test_load_tile_table_missing_file_is_default():
    from repro.kernels.qgram_filter.autotune import (DEFAULT_TILES,
                                                     load_tile_table)
    t = load_tile_table("/nonexistent/qgram_filter.json")
    assert t.lookup(8, 512, 1024) == DEFAULT_TILES
    load_tile_table.cache_clear()


def test_save_table_never_downgrades_tpu_entries(tmp_path):
    """A CPU-interpret sweep must not clobber TPU-timed tiles — the one
    provenance that actually tunes anything."""
    from repro.kernels.qgram_filter import autotune
    path = os.path.join(tmp_path, "t.json")
    with open(path, "w") as f:
        json.dump({"version": 1, "timed_on": "tpu",
                   "entries": {"8x512x1024": {"tiles": [16, 256, 512],
                                              "us": 5.0,
                                              "timed_on": "tpu"}}}, f)
    table = autotune.save_table(
        {"8x512x1024": {"tiles": [4, 64, 128], "us": 1.0},
         "8x64x128": {"tiles": [4, 64, 128], "us": 1.0}}, path)
    doc = json.load(open(path))
    assert doc["entries"]["8x512x1024"]["tiles"] == [16, 256, 512]  # kept
    assert "8x64x128" in doc["entries"]                # new keys merge in
    assert doc["timed_on"] == "tpu"
    assert table.entries["8x512x1024"] == (16, 256, 512)
    autotune.load_tile_table.cache_clear()


def test_engine_tile_table_plumbs_to_evaluator(flat, small_db):
    """GraphQueryEngine(tile_table=...) must reach the pallas evaluator —
    the config knob is real, not decorative."""
    from repro.kernels.qgram_filter.autotune import TileTable
    from repro.serve.graph_engine import GraphQuery, GraphQueryEngine
    table = TileTable(default=(4, 64, 128))
    eng = GraphQueryEngine(flat, backend="pallas", result_cache_size=0,
                           tile_table=table)
    qs, taus = _queries(small_db, flat.filter_eval("numpy"), 2, seed=8)
    rng = np.random.default_rng(0)
    g = perturb_graph(small_db[0], 1, rng, small_db.n_vlabels,
                      small_db.n_elabels)
    eng.submit([GraphQuery(g, 2, verify=False)])
    assert flat.filter_eval("pallas").tile_table is table


# --------------------------------------------------------------------------
# jax backend: the whole slab resident, buckets gathered on the device
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mid_flat():
    """More than 512 graphs, so a bucket can sit just above a pad step."""
    return FlatMSQIndex(aids_like_db(530, seed=7))


def _bucket(ev, kind, N, seed):
    """Row indices of one bucket: a region bucket (non-contiguous, as
    ``graphs_in_rect`` gives it), a random sorted subset, or all rows."""
    B = ev.slab.B
    if kind == "rect":
        ri, rj = ev.slab.region_i, ev.slab.region_j
        idx = ev.graphs_in_rect((int(ri.min()), int(np.median(ri)),
                                 int(rj.min()), int(rj.max())))
        assert 0 < len(idx) < B and np.any(np.diff(idx) > 1)
        return idx
    if N >= B:
        return np.arange(B)
    return np.sort(np.random.default_rng(seed).choice(B, N, replace=False))


@pytest.mark.parametrize("slab", ["dense", "hot", "packed"])
@pytest.mark.parametrize("Q,N,kind", [(1, 17, "subset"), (5, 140, "subset"),
                                      (3, 0, "rect"), (9, 513, "subset"),
                                      (11, 530, "all")])
def test_jax_resident_matches_numpy(mid_flat, slab, Q, N, kind):
    db = mid_flat.db
    ev_np = mid_flat.filter_eval("numpy", slab=slab, hot_d=24)
    ev_jx = mid_flat.filter_eval("jax", slab=slab, hot_d=24)
    qs, taus = _queries(db, ev_np, Q, seed=Q * 1000 + N)
    idx = _bucket(ev_np, kind, N, seed=N)
    want = np.asarray(ev_np.bounds(idx, qs), np.int64)
    got = np.asarray(ev_jx.bounds(idx, qs), np.int64)
    assert got.shape == (Q, len(idx))
    assert np.array_equal(got, want)
    for (gi, gb), (wi, wb) in zip(ev_jx.bucket_candidates(idx, qs, taus),
                                  ev_np.bucket_candidates(idx, qs, taus)):
        assert gi == wi
        assert np.array_equal(np.asarray(gb, np.int64),
                              np.asarray(wb, np.int64))


@pytest.mark.parametrize("slab", ["dense", "packed"])
def test_jax_filter_uploads_the_slab_once(flat, small_db, slab):
    ev = BatchedFilterEval(flat.db, flat.enc, flat.partition, "jax",
                           slab=slab)
    qs, _ = _queries(small_db, ev, 4, seed=11)
    rng = np.random.default_rng(12)
    for n in (5, 40, 77, 120, 139, 140):
        ev.bounds(np.sort(rng.choice(len(small_db), n, replace=False)), qs)
    fields = {f for _, f, _ in ev.device_cache.items()}
    assert fields == ({"jax_db", "jax_packed"} if slab == "packed"
                      else {"jax_db"})            # no host gather (sub)
    assert ev.device_cache.stats["misses"] == len(fields)
    assert ev.device_cache.stats["hits"] == 5 * len(fields)
    (db,) = [v for _, f, v in ev.device_cache.items() if f == "jax_db"]
    assert db.fd.shape[0] == len(small_db) + 1   # the sentinel row
    assert int(db.region_i[-1]) == -(2 ** 20) and int(db.nv[-1]) == 0


def test_resident_slab_pinned_until_invalidated(flat, small_db):
    ev = BatchedFilterEval(flat.db, flat.enc, flat.partition, "jax",
                           device_cache_entries=1)
    qs, _ = _queries(small_db, ev, 3, seed=13)
    graphs = [small_db[i] for i in range(3)]
    rng = np.random.default_rng(14)
    for n in (30, 60, 90):
        idx = np.sort(rng.choice(len(small_db), n, replace=False))
        ev.bounds(idx, qs)
        # each LB call keys LRU entries of its own (sub, lb_db)
        ev.bucket_assign_lbs(graphs, [[int(g) for g in idx[:k]]
                                      for k in (2, 3, 4)])
    snap = ev.device_cache.snapshot()
    assert snap["evictions"] >= 2
    assert snap["entries"] == 2                 # one LRU entry + the pin
    keys = {k for k, _, _ in ev.device_cache.items()}
    assert ("resident", "dense") in keys
    assert sum(1 for _, f, _ in ev.device_cache.items()
               if f == "jax_db") == 1
    ev.device_cache.invalidate()
    assert len(ev.device_cache) == 0
    want = ev.bounds(np.arange(len(small_db)), qs)   # uploaded again
    assert ("resident", "dense") in {k for k, _, _ in
                                     ev.device_cache.items()}
    ev.rebuild_slab(layout="hot", hot_d=16)
    assert len(ev.device_cache) == 0
    got = ev.bounds(np.arange(len(small_db)), qs)
    assert ("resident", "hot") in {k for k, _, _ in ev.device_cache.items()}
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_same_padded_bucket_shape_builds_no_executable(flat, small_db):
    from repro.obs import Observability, use_obs
    ev = BatchedFilterEval(flat.db, flat.enc, flat.partition, "jax")
    qs, _ = _queries(small_db, ev, 17, seed=15)  # a query pad (24) of its own
    obs = Observability()
    with use_obs(obs):
        ev.bounds(np.arange(0, 100), qs)
        built = obs.metrics.counter_get("engine.compiles", 0)
        assert built >= 1
        ev.bounds(np.arange(1, 140, 2), qs)     # another bucket, same pad
        ev.bounds(np.arange(40, 140), qs)
        assert obs.metrics.counter_get("engine.compiles", 0) == built


def test_build_racing_invalidation_is_not_kept():
    cache = DeviceSlabCache(max_entries=4)

    def build():
        cache.invalidate()          # the slab is replaced mid-build
        return "stale"
    assert cache.get_or_build(("resident", "dense"), "jax_db", build,
                              pin=True) == "stale"
    assert len(cache) == 0
    assert cache.get_or_build(("resident", "dense"), "jax_db",
                              lambda: "fresh", pin=True) == "fresh"
    assert len(cache) == 1
