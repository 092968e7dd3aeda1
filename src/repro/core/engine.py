"""Batched multi-query candidate generation — the GraphQueryEngine core.

The per-query path (``MSQIndex.query`` / ``FlatMSQIndex.query``) walks the
index once per request: region reduction, then a Python sweep over the
region's graphs.  Serving batches of queries, that repeats all of the
region bookkeeping and — worse — re-touches every region graph once per
query.  This module amortises both (Nass / EmbAssi style):

Stage 1 — **bucket** (``bucket_queries``): group requests by their reduced
  query region rectangle (formula (1)).  Every query in a bucket prunes
  against the *identical* set of region graphs, so that set is gathered
  once per batch.

Stage 2 — **shard**: lay the bucket's ``FilterSlab`` out for the filter
  pass.  The slab's F_D carrier is one of three layouts (DESIGN.md §11):
  ``dense`` (full-vocab matrix), ``hot`` (hot prefix + batched CSR tail
  correction added to C_D before thresholding), or ``packed`` (hybrid
  bit-packed rows decoded on device inside the pass).  Single-host
  backends gather the slab into one padded (Q, N) block; the
  ``distributed`` backend block-partitions the slab (hot prefixes /
  packed words instead of dense F_D) over the mesh's batch axes and
  replicates the padded query block to every device (graph-sharded),
  optionally also splitting the dense/hot F_D over the ``'model'`` axis
  (vocab-sharded) — see DESIGN.md §10.

Stage 3 — **filter** (``BatchedFilterEval``): evaluate the full leaf-level
  filter cascade for the whole bucket.  Backends: ``jax`` (jit + vmap over
  ``filters_jax.batched_bounds``), ``numpy`` (vectorised per-query rows,
  no device round-trip), ``pallas`` (the fused q-gram filter kernel per
  query; interpret mode off-TPU), and ``distributed`` (the cascade inside
  shard_map per device, all-gathering fixed-size top-k candidate blocks;
  overflowing blocks fall back to exact per-device ids so truncation is
  recall-safe).

Stage 4 — **worklist** (shared verification) lives in
``repro.serve.graph_engine``; the ``CandidateSource`` protocol below is
what lets that engine run tree-backed (``MSQIndex``) or flat
(``FlatMSQIndex``) without caring which.
"""
from __future__ import annotations

import functools
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Protocol, Sequence, Tuple, runtime_checkable

import numpy as np

from repro.obs import ambient_span, current_obs
from repro.obs.health import FAILING, StageHealth

from repro.core import arrays, filters
from repro.core.arrays import DBArrays, QueryArrays
from repro.core.device_cache import DeviceSlabCache, bucket_key
from repro.core.qgrams import EncodedDB, QGramVocab
from repro.core.region import RegionPartition
from repro.core.slab import FilterSlab
from repro.core.tree import QueryTuple
from repro.graphs.graph import Graph, GraphDB

Rect = Tuple[int, int, int, int]          # inclusive (i1, i2, j1, j2)

# shape buckets for the jit'd (Q, N) pass: pad to these multiples so the
# number of distinct compiled programs stays small across buckets
_Q_PAD = 8
_N_PAD = 512
# per-device candidate-block size of the distributed backend
_K_DEFAULT = 256

# the recall-safe degradation ladders (DESIGN.md §18).  Every backend
# computes bit-identical bounds and every slab layout decodes to the
# same F_D, so stepping down a rung changes cost, never candidates.
_BACKEND_LADDER = {
    "pallas": ("pallas", "jax", "numpy"),
    "jax": ("jax", "numpy"),
    "numpy": ("numpy",),
    "distributed": ("distributed", "numpy"),
}
_SLAB_LADDER = {"packed": "hot", "hot": "dense"}


@runtime_checkable
class CandidateSource(Protocol):
    """What the serving engine needs from an index (tree or flat)."""

    db: GraphDB
    vocab: QGramVocab
    partition: RegionPartition

    def candidate_ids(self, h: Graph, tau: int) -> List[int]:
        """Sorted candidate graph ids for one query."""
        ...

    def batched_candidates(self, graphs: Sequence[Graph],
                           taus: Sequence[int],
                           qtuples: Optional[Sequence[QueryTuple]] = None
                           ) -> "CandidateBatch":
        """Candidates for a whole batch; per-query order preserved."""
        ...


@dataclass
class CandidateBatch:
    """Per-query candidate ids plus (when the source computes them) the
    filter lower bounds, used to order the shared verification worklist.

    ``lbs`` carries the stage-1.5 assignment lower bounds (DESIGN.md
    §16), aligned with ``ids`` like ``bounds``.  The LB never drops a
    candidate — ``ids`` stays bit-identical with the stage off — it only
    tightens what verification sees: the serving engine prunes pairs
    whose LB exceeds the working radius from the worklist and seeds the
    survivors' A* with ``max(bound, lb)``.
    """

    ids: List[List[int]]
    bounds: List[Optional[np.ndarray]]     # aligned with ids; None for trees
    lbs: Optional[List[Optional[np.ndarray]]] = None
    # per-query share of the assignment-LB wall time (seconds), for the
    # serving engine's stage breakdown (DESIGN.md §17); None when the
    # stage is off
    lb_s: Optional[List[float]] = None


def bucket_queries(partition: RegionPartition, graphs: Sequence[Graph],
                   taus: Sequence[int]) -> Dict[Rect, List[int]]:
    """Stage 1: query indices grouped by reduced-query-region rectangle."""
    buckets: Dict[Rect, List[int]] = {}
    for qi, (h, tau) in enumerate(zip(graphs, taus)):
        rect = partition.query_region(h.n, h.m, int(tau))
        buckets.setdefault(rect, []).append(qi)
    return buckets


def _pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def resolve_backend() -> str:
    """Best default for the host: the jit/vmap pass on an accelerator,
    plain vectorised numpy on CPU (no compile latency, same candidates)."""
    from repro.kernels.qgram_filter.ops import on_tpu
    return "jax" if on_tpu() else "numpy"


@functools.lru_cache(maxsize=None)
def _bounds_multi_jit(layout: str = "dense"):
    """jit'd (Q, N) filter pass per slab layout: vmap of the single-query
    cascade, with the layout's C_D construction fused in (DESIGN.md §11).

    The database operands are the evaluator's whole device-resident slab
    (with its sentinel row, ``FilterSlab.base_arrays(sentinel=True)``),
    passed as arguments — never closed over, which would bake the slab
    into the executable as a constant — and ``rows`` picks the bucket:
    (N,) int32 slab rows padded with the sentinel's index.  The pass
    gathers the bucket on the device, so the padded shapes, and with
    them the executables, are those of a per-bucket operand.

    C_D is evaluated *query-sparse* (DESIGN.md §13): a query graph touches
    a few dozen degree-q-gram ids, and ``min(F_D[:, j], 0) = 0`` for every
    column the query misses, so the min-sum gathers only the query's
    nonzero columns (``qids``/``qcnt``, zero-padded — pad slots contribute
    ``min(fd, 0) = 0``).  Bit-identical to the dense sweep, ~U/K times
    less work on the serving-dominant wide-vocabulary slabs.  Dense and
    hot F_D are gathered by row first (contiguous rows, one (N, U) block
    on the device) and then by the query's columns: indexing rows and
    columns in one gather reads fewer entries but lowers to a scalar
    gather per entry.

    The pass is named ``msq_qgram_filter_<layout>``, so the device trace
    and the HLO module read ``jit_msq_qgram_filter_<layout>``.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import filters_jax as fj

    def bucket(db: DBArrays, rows) -> DBArrays:
        # the bucket's rows of every per-graph operand, F_D included
        return DBArrays(*(jnp.take(x, rows, axis=0) for x in db))

    def sparse_cd(fdq, cnt):
        return jnp.minimum(fdq, cnt[None, :]).astype(jnp.int32).sum(axis=1)

    if layout == "dense":
        def multi(db: DBArrays, rows, qb: QueryArrays, qids,
                  qcnt) -> "jax.Array":
            sub = bucket(db, rows)

            def one(q, ids, cnt):
                return fj.batched_bounds(sub, q,
                                         c_d=sparse_cd(sub.fd[:, ids], cnt))
            return jax.vmap(one)(qb, qids, qcnt)
    elif layout == "hot":
        # db.fd is the (B + 1, H) hot prefix, qids/qcnt the query's nonzero
        # entries within it, and cdt the host-computed (Q, N) CSR tail
        # correction — added to C_D before thresholding so the bound
        # stays admissible (DESIGN.md §3)
        def multi(db: DBArrays, rows, qb: QueryArrays, qids, qcnt,
                  cdt) -> "jax.Array":
            sub = bucket(db, rows)

            def one(q, ids, cnt, t):
                return fj.batched_bounds(
                    sub, q, c_d=sparse_cd(sub.fd[:, ids], cnt) + t)
            return jax.vmap(one)(qb, qids, qcnt, cdt)
    elif layout == "packed":
        # the resident slab is the packed form; gather the bucket's packed
        # rows, decode them on device, then the usual cascade.  db.fd is a
        # (B + 1, 1) placeholder — C_D is supplied.
        def multi(words, sb, widths, db: DBArrays, rows, qb: QueryArrays,
                  qids, qcnt) -> "jax.Array":
            from repro.kernels.bitunpack.ref import unpack_rows_ref
            sub = bucket(db, rows)
            fd = unpack_rows_ref(*(jnp.take(x, rows, axis=0)
                                   for x in (words, sb, widths)))

            def one(q, ids, cnt):
                return fj.batched_bounds(sub, q,
                                         c_d=sparse_cd(fd[:, ids], cnt))
            return jax.vmap(one)(qb, qids, qcnt)
    else:
        raise ValueError(f"unknown slab layout {layout!r}")

    multi.__name__ = multi.__qualname__ = f"msq_qgram_filter_{layout}"
    return jax.jit(multi)


@functools.lru_cache(maxsize=1)
def _assign_lb_jit():
    """jit'd (Q, N) assignment-LB pass (the jax backend's stage 1.5) —
    the reference body under jit, on shape-bucketed operands, named
    ``msq_assign_lb`` (``jit_msq_assign_lb`` in the device trace)."""
    import jax

    from repro.kernels.assign_lb.ref import batched_assign_lb_ref

    def msq_assign_lb(*args):
        return batched_assign_lb_ref(*args)
    return jax.jit(msq_assign_lb)


def sparse_query_fd(qfd: np.ndarray, pad: int = 16
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """(Q, K) nonzero ids + counts of a stacked query F_D block, K rounded
    up a power-of-two ladder from ``pad`` (a raw max would retrace the jit
    pass for every distinct batch-max nonzero count — the same
    per-batch-shape churn the kernel's shape buckets kill).  Pad slots are
    id 0 with count 0 — a no-op for the min-sum."""
    qfd = np.asarray(qfd)
    nz = qfd > 0
    kmax = max(int(nz.sum(axis=1).max(initial=0)), 1)
    K = pad
    while K < kmax:
        K *= 2
    ids = np.zeros((qfd.shape[0], K), np.int32)
    cnt = np.zeros((qfd.shape[0], K), np.int32)
    for r in range(qfd.shape[0]):
        j = np.flatnonzero(nz[r])
        ids[r, :len(j)] = j
        cnt[r, :len(j)] = qfd[r, j]
    return ids, cnt


class BatchedFilterEval:
    """Stages 2+3: slab layout plus the leaf-level filter pass per bucket.

    Holds the database-side ``FilterSlab`` (built once in the configured
    layout, reused across batches) and evaluates the combined admissible
    bound for every (query, graph) pair of a bucket.  Inputs are
    bit-identical to what ``FlatMSQIndex`` feeds
    ``filters.batched_bounds_np``, so candidate sets match exactly across
    every ``slab`` layout ('dense' | 'hot' | 'packed', DESIGN.md §11) and
    every backend.

    The ``distributed`` backend additionally needs a ``mesh``; it shards
    each bucket slab over the mesh (``layout``: 'graph' | 'vocab', see
    DESIGN.md §10) and drains fixed-size per-device top-k candidate blocks
    of size ``k`` instead of materialising the full (Q, N) bounds matrix.
    The vocab-sharded layout splits the dense or hot F_D over ``'model'``;
    the packed slab shards its words rows like any graph-sharded array.
    """

    def __init__(self, db: GraphDB, enc: EncodedDB,
                 partition: RegionPartition, backend: str = "auto", *,
                 mesh=None, layout: str = "graph", k: int = _K_DEFAULT,
                 shard_pad: int = _N_PAD, slab: str = "dense",
                 hot_d: Optional[int] = None,
                 hot_mass: Optional[float] = None,
                 tile_table=None, device_cache_entries: int = 16,
                 assign_lb: bool = True, lb_hungarian: int = 0,
                 lb_tile_table=None, faults=None):
        if backend == "auto":
            backend = resolve_backend()
        if backend not in ("jax", "numpy", "pallas", "distributed"):
            raise ValueError(f"unknown backend {backend!r}")
        if backend == "distributed" and mesh is None:
            raise ValueError("backend='distributed' needs a mesh")
        self.backend = backend
        self.db = db
        self.enc = enc
        self.vocab = enc.vocab
        self.partition = partition
        self.slab = FilterSlab.build(db, enc, partition, layout=slab,
                                     hot_d=hot_d, hot_mass=hot_mass)
        self.slab_layout = self.slab.layout
        self.vmax = self.slab.vmax
        # per-bucket gathered sub-slabs + their device-resident operands,
        # shared by every backend path (DESIGN.md §13)
        self.device_cache = DeviceSlabCache(device_cache_entries)
        self._tile_table = tile_table
        # stage 1.5: batched assignment lower bounds (DESIGN.md §16)
        self.assign_lb = bool(assign_lb)
        self.lb_hungarian = int(lb_hungarian)
        self._lb_tile_table = lb_tile_table
        self._lb_dist_fn = None
        # fault injection (duck-typed: anything with .fire(point, **ctx);
        # serve.faults.FaultInjector in practice) + the per-stage health
        # machines driving the degradation ladder (DESIGN.md §18)
        self.faults = None
        self.backend_health = StageHealth("filter_backend")
        self.slab_health = StageHealth("slab_decode", fail_threshold=2)
        self._health_reg = None
        self._ladder_lock = threading.Lock()
        self.ladder_stats: Dict[str, int] = {
            "backend_fallbacks": 0, "slab_fallbacks": 0, "primary_skips": 0}
        self.set_faults(faults)
        if backend == "distributed":
            self._init_distributed(mesh, layout, k, shard_pad)

    def set_faults(self, faults) -> None:
        """(Re)attach a fault injector; threads into the device cache so
        upload builds fire ``device.cache`` too.  ``None`` disarms."""
        self.faults = faults
        self.device_cache.set_faults(faults)

    # ---- slab lifecycle ----------------------------------------------------
    def rebuild_slab(self, *, layout: Optional[str] = None,
                     hot_d: Optional[int] = None,
                     hot_mass: Optional[float] = None) -> None:
        """Rebuild the resident FilterSlab (layout / hot-width change) and
        invalidate every cached device copy of the old one — a stale
        upload must never serve another batch (DESIGN.md §13)."""
        self.slab = FilterSlab.build(
            self.db, self.enc, self.partition,
            layout=self.slab_layout if layout is None else layout,
            hot_d=hot_d, hot_mass=hot_mass)
        self.slab_layout = self.slab.layout
        self.vmax = self.slab.vmax
        self.device_cache.invalidate()

    # ---- pallas tile selection (autotuned, DESIGN.md §13) ------------------
    @property
    def tile_table(self):
        """(qb, bb, bu) per shape bucket; the persisted autotune table
        with the built-in defaults as fallback (lazy — numpy/jax paths
        never pay the load)."""
        if self._tile_table is None:
            from repro.kernels.qgram_filter import autotune
            self._tile_table = autotune.default_table()
        return self._tile_table

    def autotune_tiles(self, qs=(8, 64), save_path=None, **kw):
        """Sweep kernel tiles on this slab's real bucket shapes and adopt
        the result (``kernels.qgram_filter.autotune``)."""
        from repro.kernels.qgram_filter import autotune
        self._tile_table = autotune.autotune_slab(
            self.slab, qs=qs, save_path=save_path, **kw)
        return self._tile_table

    def _gather_cached(self, idx: np.ndarray, n_pad: int):
        """(cache key, gathered sub-slab) for one bucket; the host gather
        is cached across batches alongside the device operands."""
        key = bucket_key(idx, n_pad)
        return key, self.device_cache.get_or_build(
            key, "sub", lambda: self.slab.gather(idx, n_pad))

    # ---- stage 1.5: batched assignment lower bounds (DESIGN.md §16) -------
    @property
    def lb_tile_table(self):
        """(qb, bb) per shape bucket for the assign_lb kernel (lazy, like
        ``tile_table``)."""
        if self._lb_tile_table is None:
            from repro.kernels.assign_lb import autotune
            self._lb_tile_table = autotune.default_table()
        return self._lb_tile_table

    def bucket_assign_lbs(self, hs: Sequence[Graph],
                          cand_ids: Sequence[List[int]]
                          ) -> List[np.ndarray]:
        """Per-query assignment LBs aligned with each query's candidate
        list, computed in one batched pass over the bucket's *union* of
        surviving ids (post-filter survivors are a small fraction of the
        bucket, and coalescing the union keeps it one device launch)."""
        union = sorted(set().union(*(set(c) for c in cand_ids)))
        if not union:
            return [np.zeros(0, np.int64) for _ in cand_ids]
        uidx = np.asarray(union, np.int64)
        from repro.core.slab import branch_features
        vmq = max((h.n for h in hs), default=1)
        qv, qd, qeh = branch_features(hs, self.db.n_elabels, max(vmq, 1))
        qn = np.asarray([h.n for h in hs], np.int32)
        lbm = self._assign_lb_matrix(uidx, qv, qd, qeh, qn)
        pos = {g: i for i, g in enumerate(union)}
        out = []
        for r, ids in enumerate(cand_ids):
            out.append(np.asarray(
                lbm[r, [pos[g] for g in ids]], np.int64))
        if self.lb_hungarian > 0:
            self._hungarian_refine(hs, cand_ids, out)
        return out

    def _hungarian_refine(self, hs, cand_ids, lbs) -> None:
        """Tighten the ``lb_hungarian`` highest-LB survivors per query
        with the exact assignment relaxation (still a provable bound, so
        still recall-safe) — the pairs closest to the radius are the ones
        an exact assignment is most likely to push over it."""
        from repro.kernels.assign_lb.ops import hungarian_lb_pair
        slab = self.slab
        for r, (h, ids) in enumerate(zip(hs, cand_ids)):
            if not len(ids):
                continue
            from repro.core.slab import branch_features
            hv, hd, heh = branch_features([h], self.db.n_elabels,
                                          max(h.n, 1))
            top = np.argsort(lbs[r], kind="stable")[-self.lb_hungarian:]
            for t in top:
                g = int(ids[int(t)])
                n = int(slab.nv[g])
                hung = hungarian_lb_pair(
                    hv[0][:h.n], hd[0][:h.n], heh[0][:h.n],
                    slab.bvlab[g][:n], slab.bdeg[g][:n], slab.behist[g][:n])
                if hung is not None:
                    lbs[r][int(t)] = max(int(lbs[r][int(t)]), hung)

    def _assign_lb_matrix(self, uidx: np.ndarray, qv, qd, qeh, qn
                          ) -> np.ndarray:
        """(Q, |union|) LB matrix on the configured backend.  All
        backends compute the same integers (the bound is provable and the
        paths share one padding contract), so downstream verification
        decisions are bit-identical across backend x layout x mesh."""
        from repro.kernels.assign_lb import ops as aops
        Q, N = len(qn), len(uidx)
        if self.backend == "numpy":
            _, sub = self._gather_cached(uidx, N)
            return aops.assign_lb_np(qv, qd, qeh, qn, sub.bvlab, sub.bdeg,
                                     sub.behist, sub.nv)
        import jax.numpy as jnp
        np_ = aops.shape_bucket(max(N, 1), aops.N_BASE, aops.N_CAP)
        if self.backend == "distributed":
            np_ = _pad_to(np_, self.n_shards)
        key, sub = self._gather_cached(uidx, np_)
        qvp, qdp, qehp, qnp = aops.pad_query_block(qv, qd, qeh, qn)
        host_db = (sub.bvlab, sub.bdeg, sub.behist, sub.nv)
        host_q = (qvp, qdp, qehp, qnp)
        if self.backend == "distributed":
            from repro.core import distributed as dist
            q_specs, db_specs = dist.assign_lb_specs(self._batch_axes)
            dev = self.device_cache.get_or_build(
                key, "lb_db",
                lambda: dist.put_sharded(self.mesh, host_db, db_specs))
        else:
            dev = self.device_cache.get_or_build(
                key, "lb_db",
                lambda: tuple(jnp.asarray(x) for x in host_db))
        # query upload, dispatch, device pass and copy back
        with ambient_span("lb_device", n_queries=Q, n_graphs=N):
            if self.backend == "distributed":
                qargs = dist.put_sharded(self.mesh, host_q, q_specs)
            else:
                qargs = tuple(jnp.asarray(x) for x in host_q)
            if self.backend == "pallas":
                qb_t, bb_t = self.lb_tile_table.lookup(
                    qvp.shape[0], np_, qvp.shape[1], sub.bvlab.shape[1])
                out = aops.assign_lb_bounds_batched(*qargs, *dev,
                                                    qb=qb_t, bb=bb_t)
            elif self.backend == "distributed":
                from repro.core import jax_compat as jc
                if self._lb_dist_fn is None:
                    self._lb_dist_fn = dist.make_sharded_assign_lb(
                        self.mesh, self._batch_axes)
                with jc.set_mesh(self.mesh):
                    out = self._lb_dist_fn(*qargs, *dev)
            else:
                out = _assign_lb_jit()(*qargs, *dev)
            return np.asarray(out)[:Q, :N]

    # ---- distributed slab-shard bookkeeping -------------------------------
    def _init_distributed(self, mesh, layout: str, k: int,
                          shard_pad: int) -> None:
        from repro.core import distributed as dist
        self.mesh = mesh
        self.layout = layout
        self.k = int(k)
        self.shard_pad = int(shard_pad)
        batch_axes, model_axis = dist.layout_axes(mesh, layout)
        if model_axis is not None and self.slab_layout == "packed":
            raise ValueError(
                "the packed slab cannot split its vocabulary over 'model'; "
                "use the hot or dense slab with the vocab-sharded layout")
        self._batch_axes = batch_axes
        self._model_axis = model_axis
        self.n_shards = int(np.prod([mesh.shape[a] for a in batch_axes]))
        self._model_size = (1 if model_axis is None
                            else int(mesh.shape[model_axis]))
        self._dist_fn, self._dist_specs, _ = dist.make_sharded_multi_search(
            mesh, self.partition.x0, self.partition.y0, self.partition.l,
            self.k, batch_axes=batch_axes, model_axis=model_axis,
            slab=self.slab_layout, n_entries=self.slab.U)
        self.dist_stats: Dict[str, int] = {"blocks": 0, "overflow_blocks": 0}

    # ---- query-side arrays ------------------------------------------------
    def query_arrays(self, h: Graph, tau: int,
                     qt: Optional[QueryTuple] = None) -> QueryArrays:
        return arrays.query_arrays_from_graph(h, self.vocab, self.partition,
                                              tau, self.vmax, qt=qt)

    def stack_queries(self, qs: Sequence[QueryArrays]) -> QueryArrays:
        """(Q, ...) stacked query arrays (leading axis = query)."""
        return QueryArrays(*[np.stack([np.asarray(getattr(q, f))
                                       for q in qs])
                             for f in QueryArrays._fields])

    def graphs_in_rect(self, rect: Rect) -> np.ndarray:
        return self.slab.in_rect(rect)

    # ---- the (Q, N) pass --------------------------------------------------
    def bounds(self, idx: np.ndarray,
               qs: Sequence[QueryArrays]) -> np.ndarray:
        """(Q, len(idx)) combined lower bounds for the bucket."""
        Q, N = len(qs), len(idx)
        if Q == 0 or N == 0:
            return np.zeros((Q, N), np.int32)
        if self.backend == "distributed":
            raise ValueError("the distributed backend emits candidate "
                             "blocks, not dense bounds; use "
                             "bucket_candidates()")
        return self._bounds_ladder(idx, qs)

    def _bounds_backend(self, backend: str, idx: np.ndarray,
                        qs: Sequence[QueryArrays]) -> np.ndarray:
        if backend == "numpy":
            return self._bounds_np(idx, qs)
        if backend == "pallas":
            return self._bounds_pallas(idx, qs)
        return self._bounds_jax(idx, qs)

    # ---- the degradation ladder (DESIGN.md §18) ---------------------------
    def _attach_health(self) -> None:
        """Bind the health gauges to the ambient registry: the serving
        engines wrap every filter pass in ``use_obs``, so ladder state
        lands in the same snapshot as the serving stats."""
        obs = current_obs()
        reg = None if obs is None else obs.metrics
        if reg is not self._health_reg:
            self._health_reg = reg
            self.backend_health.attach(reg)
            self.slab_health.attach(reg)

    def _note_degrade(self, counter: str, **fields) -> None:
        with self._ladder_lock:
            self.ladder_stats[counter] += 1
        obs = current_obs()
        if obs is not None:
            obs.metrics.counter_add(f"filter.{counter}")
            if obs.spans.enabled:
                now = time.perf_counter()
                obs.spans.record("degrade", now, now, kind=counter,
                                 **fields)

    def _fire_device_faults(self, backend: str) -> None:
        if self.faults is not None and backend != "numpy":
            self.faults.fire("device.filter", backend=backend)
            if self.slab_layout in _SLAB_LADDER:
                self.faults.fire("device.decode", layout=self.slab_layout)

    def _record_ladder_failure(self, backend: str, err: BaseException,
                               primary: bool) -> None:
        """Account one rung failure; step the slab ladder when repeated
        failures attribute to the packed/hot decode path."""
        if getattr(err, "slab_decode", False):
            self.slab_health.record_failure()
            nxt = _SLAB_LADDER.get(self.slab_layout)
            if self.slab_health.state == FAILING and nxt is not None:
                # packed -> hot -> dense: rebuild the resident slab one
                # rung denser (identical F_D content, no decode step) and
                # drop the stale device uploads with it
                self.rebuild_slab(layout=nxt)
                self.slab_health.record_success()
                self._note_degrade("slab_fallbacks", to_layout=nxt)
        elif primary:
            self.backend_health.record_failure()
        self._note_degrade("backend_fallbacks", backend=backend)

    def _bounds_ladder(self, idx: np.ndarray,
                       qs: Sequence[QueryArrays]) -> np.ndarray:
        """Walk pallas→jax→numpy (or the backend's suffix) until a rung
        succeeds.  Candidates are bit-identical on every rung, so the
        ladder trades latency for availability, never recall.  A FAILING
        primary is sticky-skipped until its next probe; numpy is the
        floor and its failure propagates (nothing recall-safe is left)."""
        ladder = _BACKEND_LADDER[self.backend]
        if len(ladder) == 1:        # numpy primary: no ladder, no faults
            return self._bounds_np(idx, qs)
        self._attach_health()
        last_err: Optional[BaseException] = None
        for rung, be in enumerate(ladder):
            primary = rung == 0
            if primary and not self.backend_health.allow_primary():
                self._note_degrade("primary_skips", backend=be)
                continue
            try:
                self._fire_device_faults(be)
                out = self._bounds_backend(be, idx, qs)
            except Exception as e:      # noqa: BLE001 — ladder containment
                last_err = e
                self._record_ladder_failure(be, e, primary)
                continue
            if primary:
                self.backend_health.record_success()
            return out
        raise last_err  # type: ignore[misc]

    def bucket_candidates(self, idx: np.ndarray, qs: Sequence[QueryArrays],
                          taus: Sequence[int]
                          ) -> List[Tuple[List[int], np.ndarray]]:
        """Per-query (sorted candidate ids, aligned bounds) for one bucket.

        Single-host backends threshold the dense (Q, N) bounds; the
        distributed backend drains the all-gathered candidate blocks.
        Both sit on the degradation ladder: device failures fall back to
        the exact numpy pass (bit-identical candidates, DESIGN.md §18).
        """
        if self.backend == "distributed":
            self._attach_health()
            if self.backend_health.allow_primary():
                try:
                    self._fire_device_faults("distributed")
                    out = self._bucket_candidates_dist(idx, qs, taus)
                    self.backend_health.record_success()
                    return out
                except Exception as e:  # noqa: BLE001 — ladder containment
                    self._record_ladder_failure("distributed", e, True)
            else:
                self._note_degrade("primary_skips", backend="distributed")
            bounds = self._bounds_np(idx, qs)
        else:
            bounds = self.bounds(idx, qs)
        out: List[Tuple[List[int], np.ndarray]] = []
        with ambient_span("filter_select", n_queries=len(qs),
                          n_graphs=int(len(idx))):
            for row in range(len(qs)):
                keep = bounds[row] <= int(taus[row])
                # idx ascends (flatnonzero), so the kept ids stay sorted
                out.append(([int(g) for g in idx[keep]],
                            np.asarray(bounds[row][keep])))
        return out

    def _resident_jax(self) -> Tuple[DBArrays, Optional[Tuple]]:
        """The whole slab on the device, with its sentinel row: uploaded
        once under one fixed key and pinned in the cache until the slab
        is rebuilt or the evaluator replaced (DESIGN.md §13).  Returns
        the DBArrays and, for the packed layout, (words, sb, widths)."""
        import jax.numpy as jnp

        key = ("resident", self.slab_layout)
        slab = self.slab
        db = self.device_cache.get_or_build(
            key, "jax_db", lambda: DBArrays(*[
                jnp.asarray(x) for x in slab.base_arrays(sentinel=True)]),
            pin=True)
        packed = None
        if self.slab_layout == "packed":
            packed = self.device_cache.get_or_build(
                key, "jax_packed", lambda: tuple(
                    jnp.asarray(x) for x in slab.packed_arrays()),
                pin=True)
        return db, packed

    def _bounds_jax(self, idx: np.ndarray,
                    qs: Sequence[QueryArrays]) -> np.ndarray:
        import jax.numpy as jnp

        Q, N = len(qs), len(idx)
        qp = _pad_to(Q, _Q_PAD)
        np_ = _pad_to(N, _N_PAD)
        db, packed = self._resident_jax()
        # the bucket's slab rows, padded with the sentinel row's index
        rows = np.full(np_, self.slab.B, np.int32)
        rows[:N] = idx
        qs = list(qs) + [qs[-1]] * (qp - Q)          # pad with a repeat
        qb = self.stack_queries(qs)
        lay = self.slab_layout
        if lay == "hot":
            _, sub = self._gather_cached(idx, np_)
            cdt = sub.tail_minsum_batch(qb.fd).astype(np.int32)
            qb = qb._replace(fd=qb.fd[:, :sub.hot_d])
        qids, qcnt = sparse_query_fd(qb.fd)
        # row and query upload, dispatch, device pass and copy back
        with ambient_span("filter_device", n_queries=Q, n_graphs=N):
            args = (db, jnp.asarray(rows),
                    QueryArrays(*[jnp.asarray(x) for x in qb]),
                    jnp.asarray(qids), jnp.asarray(qcnt))
            fn = _bounds_multi_jit(lay)
            if lay == "hot":
                out = fn(*args, jnp.asarray(cdt))
            elif lay == "packed":
                out = fn(*packed, *args)
            else:
                out = fn(*args)
            return np.asarray(out)[:Q, :N]

    def _bounds_np(self, idx: np.ndarray,
                   qs: Sequence[QueryArrays]) -> np.ndarray:
        _, sub = self._gather_cached(idx, len(idx))
        db = sub.base_arrays()
        out = np.empty((len(qs), len(idx)), np.int64)
        for i, q in enumerate(qs):
            c_d = sub.cd_one(np.asarray(q.fd))
            b = filters.batched_bounds_np(
                db.nv, db.ne, db.degseq, db.vhist, db.ehist, c_d,
                int(q.nv), int(q.ne), np.asarray(q.sigma),
                np.asarray(q.vhist), np.asarray(q.ehist))
            out[i] = b["combined"]
        return out

    def _bounds_pallas(self, idx: np.ndarray,
                       qs: Sequence[QueryArrays]) -> np.ndarray:
        """One query-batched kernel launch per bucket (DESIGN.md §13): the
        padded query block rides a leading Q axis, every db-side operand
        comes from the device-resident cache, and the (qb, bb, bu) tiles
        come from the autotune table."""
        import jax.numpy as jnp

        from repro.kernels.qgram_filter import ops

        lay = self.slab_layout
        Q, N = len(qs), len(idx)
        np_ = ops.shape_bucket(max(N, 1), ops.B_BASE, ops.B_CAP)
        key, sub = self._gather_cached(idx, np_)
        if lay == "packed":
            # the cached device residency is the succinct packed form;
            # the dense F_D exists only transiently, decoded per launch
            from repro.kernels.bitunpack.ops import (flatten_packed_rows,
                                                     unpack_hybrid)

            def _upload_packed():
                words, sb, widths = flatten_packed_rows(sub.packed)
                return (jnp.asarray(words), jnp.asarray(sb),
                        jnp.asarray(widths))
            words, sb, widths = self.device_cache.get_or_build(
                key, "pallas_packed", _upload_packed)
            KB = sub.packed.sb.shape[1]
            fd_dev = unpack_hybrid(sb, widths, words).reshape(np_, KB * 128)
        else:
            fd_dev = self.device_cache.get_or_build(
                key, "pallas_fd", lambda: jnp.asarray(sub.fd))

        def _upload_small():
            aux = np.stack([sub.nv, sub.ne, sub.region_i, sub.region_j],
                           axis=1).astype(np.int32)
            return (jnp.asarray(sub.vhist), jnp.asarray(sub.ehist),
                    jnp.asarray(sub.degseq), jnp.asarray(aux))
        vhist_d, ehist_d, degseq_d, aux_d = self.device_cache.get_or_build(
            key, "pallas_small", _upload_small)

        qb = self.stack_queries(qs)
        cdt = None
        if lay == "hot":
            # sparse-tail C_D correction seeds the kernel's C_D scratch
            # (DESIGN.md §3) — per (query, graph), so it is the one
            # db-side operand rebuilt per batch
            cdt = jnp.asarray(sub.tail_minsum_batch(qb.fd).astype(np.int32))
            qb = qb._replace(fd=qb.fd[:, :sub.hot_d])
        p = self.partition
        sc = ops.make_scalars_batch(qs, p.x0, p.y0, p.l)
        qb_t, bb_t, bu_t = self.tile_table.lookup(Q, np_, fd_dev.shape[1])
        b, _ = ops.fused_filter_bounds_batched(
            jnp.asarray(sc), fd_dev, jnp.asarray(qb.fd),
            vhist_d, jnp.asarray(qb.vhist), ehist_d, jnp.asarray(qb.ehist),
            degseq_d, jnp.asarray(qb.sigma), aux_d, cdt,
            qb=qb_t, bb=bb_t, bu=bu_t)
        return np.asarray(b)[:Q, :N]

    # ---- the distributed per-bucket step ----------------------------------
    def _bucket_candidates_dist(self, idx: np.ndarray,
                                qs: Sequence[QueryArrays],
                                taus: Sequence[int]
                                ) -> List[Tuple[List[int], np.ndarray]]:
        """Shard the bucket slab, run the cascade per device, drain the
        all-gathered candidate blocks (DESIGN.md §10).

        Recall safety: a device block holds at most k ids.  ``n_pass`` is
        the true per-shard pass count, so ``n_pass > k`` (a truncated
        block) triggers an exact host-side re-evaluation of that shard's
        slab rows for that query — candidates are never silently dropped.
        """
        from repro.core import distributed as dist
        from repro.core import jax_compat as jc

        S = self.n_shards
        Q = len(qs)
        n_pad = _pad_to(max(len(idx), 1), S * self.shard_pad)
        key, sub = self._gather_cached(idx, n_pad)
        qp = _pad_to(Q, _Q_PAD)
        qb = self.stack_queries(list(qs) + [qs[-1]] * (qp - Q))
        # every upload goes straight to its shards (DESIGN.md §10)
        db_spec, q_spec, *extra_spec = self._dist_specs
        extra: Tuple = ()
        if self.slab_layout == "hot":
            # batched CSR tail correction, sharded with the slab rows —
            # per (query, graph), so rebuilt per batch (never cached)
            cdt = sub.tail_minsum_batch(qb.fd).astype(np.int32)
            qb = qb._replace(fd=qb.fd[:, :sub.hot_d])
            extra = dist.put_sharded(self.mesh, (cdt,), tuple(extra_spec))
        elif self.slab_layout == "packed":
            extra = self.device_cache.get_or_build(
                key, "dist_packed",
                lambda: dist.put_sharded(
                    self.mesh, (sub.packed.words, sub.packed.sb,
                                sub.packed.widths), tuple(extra_spec)))
        # vocab dim must divide 'model' on the vocab-sharded layout
        upad = (0 if self._model_axis is None
                else (-sub.fd.shape[1]) % self._model_size)

        def _upload_db():
            db = sub.base_arrays()
            if upad:
                db = db._replace(fd=np.pad(db.fd, [(0, 0), (0, upad)]))
            return dist.put_sharded(self.mesh, DBArrays(*db), db_spec)
        db_dev = self.device_cache.get_or_build(key, "dist_db", _upload_db)
        if upad:
            qb = qb._replace(fd=np.pad(qb.fd, [(0, 0), (0, upad)]))
        with jc.set_mesh(self.mesh):
            sids, bnds, n_pass = self._dist_fn(
                db_dev, dist.put_sharded(self.mesh, QueryArrays(*qb),
                                         q_spec), *extra)
        sids = np.asarray(sids)
        bnds = np.asarray(bnds)
        n_pass = np.asarray(n_pass)
        shard_b = n_pad // S

        # overflow fallback, batched per shard: one exact numpy pass over a
        # shard's slab rows covers every query whose block truncated there
        self.dist_stats["blocks"] += S * Q
        fallback: Dict[int, Dict[int, np.ndarray]] = {}
        for s in range(S):
            rows = [r for r in range(Q) if int(n_pass[s, r]) > self.k]
            if not rows:
                continue
            self.dist_stats["overflow_blocks"] += len(rows)
            lo, hi = s * shard_b, min((s + 1) * shard_b, len(idx))
            b = self._bounds_np(idx[lo:hi], [qs[r] for r in rows])
            fallback[s] = {r: np.asarray(b[i]) for i, r in enumerate(rows)}

        out: List[Tuple[List[int], np.ndarray]] = []
        for row in range(Q):
            tau = int(taus[row])
            pos_parts: List[np.ndarray] = []
            bnd_parts: List[np.ndarray] = []
            for s in range(S):
                fb = fallback.get(s, {}).get(row)
                if fb is not None:
                    lo = s * shard_b
                    keep = fb <= tau
                    pos_parts.append(np.arange(lo, lo + len(fb))[keep])
                    bnd_parts.append(fb[keep])
                else:
                    g = sids[s, row]
                    sel = g >= 0
                    pos_parts.append(g[sel].astype(np.int64))
                    bnd_parts.append(bnds[s, row][sel].astype(np.int64))
            pos = np.concatenate(pos_parts)
            bnd = np.concatenate(bnd_parts)
            # slab positions -> global ids: shards are disjoint contiguous
            # ranges of the ascending idx, so sorting by position restores
            # the single-host ascending-id order; pad rows never pass the
            # region mask, so every position indexes a real slab row
            order = np.argsort(pos, kind="stable")
            gids = idx[pos[order].astype(np.int64)]
            out.append(([int(g) for g in gids],
                        np.asarray(bnd[order], np.int64)))
        return out


def batched_flat_candidates(ev: BatchedFilterEval, graphs: Sequence[Graph],
                            taus: Sequence[int],
                            qtuples: Optional[Sequence[QueryTuple]] = None
                            ) -> CandidateBatch:
    """Stages 1-3 for a flat source: bucket, lay the slab out (gathered or
    sharded), one filter pass per bucket, per-query candidate lists, then
    (when ``ev.assign_lb``) the stage-1.5 assignment LB pass over each
    bucket's surviving candidates (DESIGN.md §16)."""
    obs = current_obs()
    spans_on = obs is not None and obs.spans.enabled
    Qn = len(graphs)
    ids: List[List[int]] = [[] for _ in range(Qn)]
    bnds: List[Optional[np.ndarray]] = [None] * Qn
    lbs: Optional[List[Optional[np.ndarray]]] = \
        [None] * Qn if ev.assign_lb else None
    lb_s: Optional[List[float]] = [0.0] * Qn if ev.assign_lb else None
    t_b = time.perf_counter() if spans_on else 0.0
    buckets = bucket_queries(ev.partition, graphs, taus)
    if spans_on:
        obs.spans.record("bucket", t_b, time.perf_counter(),
                         n_queries=Qn, n_buckets=len(buckets))
    for rect, qis in buckets.items():
        idx = ev.graphs_in_rect(rect)
        if len(idx) == 0:
            for qi in qis:
                ids[qi] = []
                bnds[qi] = np.zeros(0, np.int64)
                if lbs is not None:
                    lbs[qi] = np.zeros(0, np.int64)
            continue
        qs = [ev.query_arrays(graphs[qi], int(taus[qi]),
                              None if qtuples is None else qtuples[qi])
              for qi in qis]
        t_f = time.perf_counter() if spans_on else 0.0
        c_f = time.thread_time() if spans_on else 0.0
        cands = ev.bucket_candidates(idx, qs, [int(taus[qi]) for qi in qis])
        if spans_on:
            obs.spans.record("filter_bucket", t_f, time.perf_counter(),
                             n_queries=len(qis), n_graphs=int(len(idx)),
                             backend=ev.backend,
                             cpu_ms=1e3 * (time.thread_time() - c_f))
        for row, qi in enumerate(qis):
            ids[qi], bnds[qi] = cands[row]
        if lbs is not None:
            t0 = time.perf_counter()
            c0 = time.thread_time() if spans_on else 0.0
            blbs = ev.bucket_assign_lbs([graphs[qi] for qi in qis],
                                        [cands[row][0]
                                         for row in range(len(qis))])
            t1 = time.perf_counter()
            if spans_on:
                obs.spans.record("assign_lb", t0, t1, n_queries=len(qis),
                                 n_pairs=sum(len(c[0]) for c in cands),
                                 cpu_ms=1e3 * (time.thread_time() - c0))
            share = (t1 - t0) / len(qis)
            for row, qi in enumerate(qis):
                lbs[qi] = blbs[row]
                lb_s[qi] = share
    return CandidateBatch(ids=ids, bounds=bnds, lbs=lbs, lb_s=lb_s)
