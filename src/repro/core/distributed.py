"""Distributed MSQ-Index search: shard_map over the production mesh.

Layouts (DESIGN.md §5):

* **Graph-sharded** (default): the region-sorted DB slab is block-partitioned
  over the ``('pod', 'data')`` axes; query replicated; each device filters
  its shard locally and emits a fixed-size top-k candidate block; candidate
  blocks are all-gathered.  No cross-device traffic proportional to |G| —
  only k ids per device.
* **Vocab-sharded** (TP analogue): additionally the dense F_D matrix is
  sharded over the vocabulary dim on the ``'model'`` axis; the min-sum
  contraction computes partial C_D per device and psums over ``'model'``.
  This is what makes very wide q-gram vocabularies (PubChem-scale) fit.

Both paths are pure jnp + lax collectives inside shard_map, so they lower
and compile for any mesh (exercised by the multi-pod dry-run).

Two entry points share the layouts:

* ``make_sharded_search`` — one query, the dry-run / example unit;
* ``make_sharded_multi_search`` — a whole *padded query block* (Q, ...)
  replicated to every device, the batched engine's per-bucket step
  (DESIGN.md §10): every device runs the full cascade for all Q queries
  of a bucket against its slab shard and emits per-query fixed-size
  candidate blocks plus the true per-shard pass count, so the host can
  detect block overflow and fall back to exact per-device ids.

The multi-search step is FilterSlab-aware (DESIGN.md §11): the sharded
F_D carrier is the dense matrix, the hot prefix (with the batched CSR
tail correction row-sharded alongside and added to C_D after the psum),
or the hybrid bit-packed words rows (decoded per device inside
shard_map; graph-sharded only).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import filters_jax as fj
from repro.core import jax_compat as jc


def _device_bounds(db: fj.DBArrays, q: fj.QueryArrays, x0: int, y0: int,
                   l: int, model_axis: Optional[str],
                   cd_extra: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Per-shard filter cascade; psums partial C_D over the model axis and
    adds ``cd_extra`` (the hot slab's CSR tail correction) afterwards, so
    the correction lands exactly once per C_D."""
    if model_axis is not None or cd_extra is not None:
        c_d = fj.min_sum(db.fd, q.fd[None, :]).astype(jnp.int32)
        if model_axis is not None:
            # fd is vocab-sharded: partial min-sum then psum.
            c_d = jax.lax.psum(c_d, model_axis)
        if cd_extra is not None:
            c_d = c_d + cd_extra.astype(jnp.int32)
    else:
        c_d = None
    return fj.filter_pass(db, q, x0, y0, l, c_d=c_d)


def layout_axes(mesh: Mesh, layout: str) -> Tuple[Tuple[str, ...], Optional[str]]:
    """(batch_axes, model_axis) for a serving layout on this mesh.

    ``graph``: every mesh axis block-partitions the graph dim (the model
    axis, when present, just adds more graph shards).  ``vocab``: graphs
    shard over the ('pod', 'data') axes and the dense F_D vocabulary dim
    shards over 'model'.
    """
    if layout == "graph":
        return tuple(mesh.axis_names), None
    if layout == "vocab":
        if "model" not in mesh.axis_names:
            raise ValueError("vocab-sharded layout needs a 'model' mesh axis")
        return tuple(a for a in mesh.axis_names if a != "model"), "model"
    raise ValueError(f"unknown layout {layout!r} (graph | vocab)")


def multi_search_specs(batch_axes: Sequence[str], model_axis: Optional[str],
                       slab: str = "dense"
                       ) -> Tuple[fj.DBArrays, fj.QueryArrays, Tuple, Tuple]:
    """PartitionSpecs for the multi-query step: DB slab shards, the
    replicated stacked (Q, ...) query block, the per-device candidate
    blocks (ids, bounds, pass counts), and the slab layout's extra
    operands (DESIGN.md §11) — ``()`` for dense, the (Q, B) tail
    correction for ``hot``, the (B, ...) packed words/sb/widths triple
    for ``packed``.
    """
    batch_axes = tuple(batch_axes)
    spec_b = P(batch_axes)
    spec_b2 = P(batch_axes, None)
    if model_axis is not None:
        if slab == "packed":
            raise ValueError("packed slab has no vocab dim to shard over "
                             "'model'; use the hot or dense slab")
        spec_fd = P(batch_axes, model_axis)
        spec_qfd = P(None, model_axis)
    else:
        spec_fd = spec_b2
        spec_qfd = P(None, None)
    if slab == "packed":
        spec_fd = spec_b2                 # (B, 1) placeholder rides along
    db_spec = fj.DBArrays(nv=spec_b, ne=spec_b, degseq=spec_b2,
                          vhist=spec_b2, ehist=spec_b2, fd=spec_fd,
                          region_i=spec_b, region_j=spec_b)
    q_spec = fj.QueryArrays(nv=P(None), ne=P(None), sigma=P(None, None),
                            vhist=P(None, None), ehist=P(None, None),
                            fd=spec_qfd, tau=P(None))
    out_spec = (P(batch_axes, None, None), P(batch_axes, None, None),
                P(batch_axes, None))
    if slab == "hot":
        extra_spec: Tuple = (P(None, batch_axes),)
    elif slab == "packed":
        extra_spec = (spec_b2, spec_b2, spec_b2)
    else:
        extra_spec = ()
    return db_spec, q_spec, out_spec, extra_spec


def make_sharded_multi_search(mesh: Mesh, x0: int, y0: int, l: int, k: int,
                              batch_axes: Sequence[str] = ("data",),
                              model_axis: Optional[str] = None,
                              slab: str = "dense",
                              n_entries: Optional[int] = None):
    """Build the jitted per-bucket step of the sharded engine.

    ``fn(db, qb, *extra)`` takes slab-sharded ``DBArrays``, a replicated
    stacked query block (every ``QueryArrays`` field with a leading Q
    axis), and the slab layout's extra operands — nothing for ``dense``,
    the (Q, B) row-sharded CSR tail correction for ``hot``, the packed
    words/sb/widths rows for ``packed`` (``n_entries`` = decoded F_D
    width; decoded per device inside shard_map, DESIGN.md §11) — and
    returns, all-gathered over the S batch shards:

      slab_ids (S, Q, k) int32 — positions into the *padded slab* of the
               (up to) k lowest-bound passing graphs per shard (-1 = empty);
      bounds   (S, Q, k) int32 — their filter lower bounds;
      n_pass   (S, Q)    int32 — the TRUE number of passing graphs on that
               shard, so ``n_pass > k`` flags a truncated (overflowing)
               block and the host falls back to exact per-device ids
               instead of silently dropping candidates.
    """
    batch_axes = tuple(batch_axes)
    db_spec, q_spec, out_spec, extra_spec = multi_search_specs(
        batch_axes, model_axis, slab)

    def _step(db: fj.DBArrays, qb: fj.QueryArrays, cdt):
        shard_b = db.nv.shape[0]
        axis_index = jnp.int32(0)
        stride = 1
        for a in reversed(batch_axes):
            axis_index = axis_index + jax.lax.axis_index(a) * stride
            stride *= jc.axis_size(mesh, a)

        def one(q: fj.QueryArrays, t):
            mask, bounds = _device_bounds(db, q, x0, y0, l, model_axis,
                                          cd_extra=t)
            ids, bnd, _ = fj.topk_candidates(mask, bounds, k)
            pad = k - ids.shape[0]          # shard smaller than k
            if pad:
                ids = jnp.concatenate(
                    [ids, jnp.full((pad,), -1, ids.dtype)])
                bnd = jnp.concatenate(
                    [bnd, jnp.full((pad,), 2 ** 30, bnd.dtype)])
            sids = jnp.where(ids >= 0, ids + axis_index * shard_b, -1)
            return sids, bnd, mask.sum().astype(jnp.int32)

        if cdt is None:
            sids, bnd, n_pass = jax.vmap(lambda q: one(q, None))(qb)
        else:
            sids, bnd, n_pass = jax.vmap(one)(qb, cdt)
        return sids[None], bnd[None], n_pass[None]

    if slab == "hot":
        def local_step(db, qb, cdt):
            return _step(db, qb, cdt)
    elif slab == "packed":
        from repro.kernels.bitunpack.ref import unpack_rows_ref

        def local_step(db, qb, words, sb, widths):
            # the resident shard is the packed form; decode in-device
            fd = unpack_rows_ref(words, sb, widths)[:, :n_entries]
            return _step(db._replace(fd=fd), qb, None)
    else:
        def local_step(db, qb):
            return _step(db, qb, None)

    shmap = jc.shard_map(local_step, mesh=mesh,
                         in_specs=(db_spec, q_spec) + extra_spec,
                         out_specs=out_spec)
    return jax.jit(shmap), (db_spec, q_spec) + extra_spec, out_spec


def put_sharded(mesh: Mesh, tree, specs):
    """Upload a host pytree straight to its shards: each device receives
    only its own block of every leaf (``specs`` mirrors ``tree`` with a
    PartitionSpec per leaf), so nothing lands whole on one device first."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.device_put(tree, shardings)


def assign_lb_specs(batch_axes: Sequence[str]) -> Tuple[Tuple, Tuple]:
    """PartitionSpecs for the stage-1.5 assignment-LB operands
    (DESIGN.md §16): the replicated stacked query branch block
    ``(qv, qd, qeh, qn)`` and the row-sharded db branch block
    ``(dv, dd, deh, dn)`` — (N, VM) labels/degrees, the (N, VM, NE)
    incident edge-label histograms, and the (N,) vertex counts, all
    block-partitioned over the batch axes like every other slab row."""
    batch_axes = tuple(batch_axes)
    q_specs = (P(None, None), P(None, None), P(None, None, None), P(None))
    db_specs = (P(batch_axes, None), P(batch_axes, None),
                P(batch_axes, None, None), P(batch_axes))
    return q_specs, db_specs


def make_sharded_assign_lb(mesh: Mesh,
                           batch_axes: Sequence[str] = ("data",)):
    """Jitted sharded assignment-LB pass: each device prices its slab
    shard's branch rows against the replicated query block and emits its
    (Q, N/S) slice of the LB matrix — column-sharded output, no
    collectives (the min-reduce is per (query, graph) pair, so shards
    are independent).  Bit-identical to the single-host paths."""
    from repro.kernels.assign_lb.ref import batched_assign_lb_ref
    q_specs, db_specs = assign_lb_specs(batch_axes)

    def local_step(qv, qd, qeh, qn, dv, dd, deh, dn):
        return batched_assign_lb_ref(qv, qd, qeh, qn, dv, dd, deh, dn)

    shmap = jc.shard_map(local_step, mesh=mesh,
                         in_specs=q_specs + db_specs,
                         out_specs=P(None, tuple(batch_axes)))
    return jax.jit(shmap)


def make_sharded_search(mesh: Mesh, x0: int, y0: int, l: int, k: int,
                        batch_axes: Sequence[str] = ("data",),
                        model_axis: Optional[str] = None):
    """Build a jitted distributed search step for the given mesh.

    Returns (fn, in_shardings, out_shardings).  ``fn(db, q)`` returns
    (global_ids, bounds, counts): per-device top-k candidate blocks
    all-gathered to a ((devices*k),) id vector (id -1 = empty slot), with
    ids already offset into global graph numbering.
    """
    batch_axes = tuple(batch_axes)
    spec_b = P(batch_axes)                     # (B,) sharded over batch axes
    spec_b2 = P(batch_axes, None)              # (B, X) row-sharded
    if model_axis is not None:
        spec_fd = P(batch_axes, model_axis)    # (B, U) row+vocab sharded
        spec_qfd = P(model_axis)
    else:
        spec_fd = spec_b2
        spec_qfd = P(None)

    db_spec = fj.DBArrays(nv=spec_b, ne=spec_b, degseq=spec_b2,
                          vhist=spec_b2, ehist=spec_b2, fd=spec_fd,
                          region_i=spec_b, region_j=spec_b)
    q_spec = fj.QueryArrays(nv=P(), ne=P(), sigma=P(None), vhist=P(None),
                            ehist=P(None), fd=spec_qfd, tau=P())
    out_spec = (P(batch_axes, None), P(batch_axes, None), P(batch_axes))

    n_shards = int(np.prod([mesh.shape[a] for a in batch_axes]))

    def local_step(db: fj.DBArrays, q: fj.QueryArrays):
        mask, bounds = _device_bounds(db, q, x0, y0, l, model_axis)
        ids, bnd, cnt = fj.topk_candidates(mask, bounds, k)
        # globalise ids: offset by this shard's slab start.
        axis_index = jnp.int32(0)
        stride = 1
        for a in reversed(batch_axes):
            axis_index = axis_index + jax.lax.axis_index(a) * stride
            stride *= jc.axis_size(mesh, a)
        shard_b = db.nv.shape[0]
        gids = jnp.where(ids >= 0, ids + axis_index * shard_b, -1)
        return gids[None, :], bnd[None, :], cnt[None]

    shmap = jc.shard_map(
        local_step, mesh=mesh, in_specs=(db_spec, q_spec),
        out_specs=out_spec)

    in_shardings = (
        jax.tree.map(lambda s: NamedSharding(mesh, s), db_spec,
                     is_leaf=lambda x: isinstance(x, P)),
        jax.tree.map(lambda s: NamedSharding(mesh, s), q_spec,
                     is_leaf=lambda x: isinstance(x, P)),
    )
    fn = jax.jit(shmap)
    return fn, in_shardings, out_spec


def pad_db_to_shards(db: fj.DBArrays, n_shards: int) -> fj.DBArrays:
    """Pad the graph axis so it divides evenly across shards.

    Pads with impossible graphs (nv = -1) so they never pass the region
    mask or the bounds threshold.
    """
    B = db.nv.shape[0]
    pad = (-B) % n_shards
    if pad == 0:
        return db

    def pad_arr(a, fill=0):
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return np.pad(np.asarray(a), widths, constant_values=fill)

    return fj.DBArrays(
        nv=pad_arr(db.nv, -(10 ** 6)), ne=pad_arr(db.ne, -(10 ** 6)),
        degseq=pad_arr(db.degseq), vhist=pad_arr(db.vhist),
        ehist=pad_arr(db.ehist), fd=pad_arr(db.fd),
        region_i=pad_arr(db.region_i, 2 ** 30),
        region_j=pad_arr(db.region_j, 2 ** 30))


def pad_vocab(db: fj.DBArrays, q: fj.QueryArrays, multiple: int
              ) -> Tuple[fj.DBArrays, fj.QueryArrays]:
    """Pad the F_D vocabulary dim to a multiple (zero counts = no-op for
    the min-sum contraction)."""
    U = db.fd.shape[1]
    pad = (-U) % multiple
    if pad == 0:
        return db, q
    fd = np.pad(np.asarray(db.fd), [(0, 0), (0, pad)])
    qfd = np.pad(np.asarray(q.fd), [(0, pad)])
    return db._replace(fd=fd), q._replace(fd=qfd)


def gather_candidates(gids: np.ndarray, bounds: np.ndarray,
                      counts: np.ndarray) -> np.ndarray:
    """Host-side: flatten per-device candidate blocks to a sorted id list."""
    gids = np.asarray(gids).reshape(-1)
    return np.sort(gids[gids >= 0])
