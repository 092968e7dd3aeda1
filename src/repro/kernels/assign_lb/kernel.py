"""Pallas kernel for the batched Hausdorff branch lower bound
(DESIGN.md §16).

One (qb, bb) tile prices every query-vertex / db-vertex branch pair of
its block and reduces straight to the per-pair LB — a pure min-reduce,
no cross-tile accumulation, so the grid is just (Q/QB, N/BB); the row
sums and column minima build up in VMEM scratch across the query-vertex
loop.

The db-side branch operands (labels, degrees, incident edge-label
histograms) are the device-resident slab arrays, the histograms as NE
leading (BB, VM) planes.  The query block is small, so all of it sits in
SMEM — vertex counts, labels, degrees and label-major histograms — and
the kernel reads it one scalar at a time: the TPU lowering has no
strided gather along the minor dims.  Db vertex counts arrive as a
(BB, 1) VMEM column.  Pad vertices price exactly as the ε column (the
``branch_features`` padding contract), so only the two sums mask.

The loop over the query-vertex axis keeps every intermediate at rank 3 —
(QB, BB, VM) — which the TPU vector unit handles natively.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N_SCALARS = 1                 # per-query scalar block: [true vertex count]


def _lb_kernel(qn_ref,        # SMEM (QB, 1) int32: query vertex counts
               qv_ref,        # SMEM (QB, VMq) int32 query vertex labels
               qd_ref,        # SMEM (QB, VMq) int32 query degrees
               qeh_ref,       # SMEM (QB, NE*VMq) int32 hists, label-major
               dv_ref,        # (BB, VM) int32 db vertex labels (pad -1)
               dd_ref,        # (BB, VM) int32 db degrees (pad 0)
               deh_ref,       # (NE, BB, VM) int32 db incident-label hists
               dn_ref,        # (BB, 1) int32 db vertex counts
               lb_ref,        # (QB, BB) int32 out
               colmin_ref,    # VMEM (QB, BB, VM) scratch: column minima
               rowsum_ref):   # VMEM (QB, BB) scratch: row-min sums
    QB, VMq = qv_ref.shape
    NE, BB, VM = deh_ref.shape
    dv = dv_ref[...]
    dd = dd_ref[...]

    def qcol(ref, col):
        # one query-side value per query of the block, splat across that
        # query's (BB, VM) plane: scalar SMEM reads (the query block is
        # tiny), QB static; a (QB, 1, 1) vector cannot broadcast over
        # sublanes and lanes at once
        return jnp.concatenate([jnp.full((1, BB, VM), ref[r, col], jnp.int32)
                                for r in range(QB)], axis=0)

    qn = qcol(qn_ref, 0)
    colmin_ref[...] = jnp.broadcast_to((2 + dd)[None, :, :], (QB, BB, VM))
    rowsum_ref[...] = jnp.zeros((QB, BB), jnp.int32)

    @pl.loop(0, VMq)
    def _(u):
        qd_u = qcol(qd_ref, u)
        lbl = 2 * (qcol(qv_ref, u) != dv[None, :, :]).astype(jnp.int32)
        dmax = jnp.maximum(qd_u, dd[None, :, :])
        inter = jnp.zeros((QB, BB, VM), jnp.int32)
        for e in range(NE):
            inter += jnp.minimum(qcol(qeh_ref, e * VMq + u),
                                 deh_ref[e][None, :, :])
        c2 = lbl + dmax - inter                           # (QB, BB, VM)
        # row minimum over the db vertices and ε, summed over the real
        # query vertices only (c2 >= 0, so a masked row contributes 0)
        rowsum_ref[...] += jnp.where(u < qn, jnp.minimum(c2, 2 + qd_u),
                                     0).min(axis=2)
        colmin_ref[...] = jnp.minimum(colmin_ref[...], c2)

    dn = dn_ref[...][:, 0]                                # (BB,)
    vvalid = (jax.lax.broadcasted_iota(jnp.int32, (BB, VM), 1)
              < dn[:, None])
    colsum = jnp.where(vvalid[None, :, :], colmin_ref[...], 0).sum(axis=2)
    lb2 = jnp.maximum(rowsum_ref[...], colsum)
    lb_ref[...] = ((lb2 + 1) // 2).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("qb", "bb", "interpret"))
def assign_lb_call(qv, qd, qeh, qn, dv, dd, deh, dn, *, qb: int = 8,
                   bb: int = 128, interpret: bool = False):
    """Raw pallas_call; shapes must already be tile-aligned.

    qv/qd (Q, VMq); qeh (Q, VMq, NE); qn (Q,); dv/dd (N, VM);
    deh (N, VM, NE); dn (N,).  Returns (Q, N) int32 LBs.  The edge-label
    axis moves in front here (``qeh`` label-major per query row, ``deh``
    as NE planes), so the kernel reads whole (BB, VM) planes and scalar
    query entries — no strided minor-dim gather.
    """
    Q, VMq = qv.shape
    N, VM = dv.shape
    NE = deh.shape[2]
    assert Q % qb == 0 and N % bb == 0, (Q, N, qb, bb)
    scalars = jnp.asarray(qn, jnp.int32).reshape(Q, N_SCALARS)
    qeh_lm = jnp.transpose(qeh, (0, 2, 1)).reshape(Q, NE * VMq)
    deh_planes = jnp.transpose(deh, (2, 0, 1))
    dn2 = jnp.asarray(dn, jnp.int32).reshape(N, 1)
    grid = (Q // qb, N // bb)
    return pl.pallas_call(
        _lb_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((qb, N_SCALARS), lambda q, i: (q, 0),
                         memory_space=pltpu.SMEM),                # qn
            pl.BlockSpec((qb, VMq), lambda q, i: (q, 0),
                         memory_space=pltpu.SMEM),                # qv
            pl.BlockSpec((qb, VMq), lambda q, i: (q, 0),
                         memory_space=pltpu.SMEM),                # qd
            pl.BlockSpec((qb, NE * VMq), lambda q, i: (q, 0),
                         memory_space=pltpu.SMEM),                # qeh
            pl.BlockSpec((bb, VM), lambda q, i: (i, 0)),          # dv
            pl.BlockSpec((bb, VM), lambda q, i: (i, 0)),          # dd
            pl.BlockSpec((NE, bb, VM), lambda q, i: (0, i, 0)),   # deh
            pl.BlockSpec((bb, 1), lambda q, i: (i, 0)),           # dn
        ],
        out_specs=pl.BlockSpec((qb, bb), lambda q, i: (q, i)),
        out_shape=jax.ShapeDtypeStruct((Q, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((qb, bb, VM), jnp.int32),
                        pltpu.VMEM((qb, bb), jnp.int32)],
        interpret=interpret,
        name="msq_assign_lb",
    )(scalars, qv, qd, qeh_lm, dv, dd, deh_planes, dn2)
