"""Fused MSQ filter-cascade Pallas kernel.

One pass over the dense degree-q-gram frequency matrix computes, per graph:

  C_D   = sum_j min(F_D[g, j], q_D[j])           (vocab-tiled accumulation)
  C_Lv  = sum   min(vhist, q_vhist)   (vertex-label overlap)
  C_Le  = sum   min(ehist, q_ehist)
  lam   = degree-sequence term (Lemma 5, both cases)
  bound = max(number count, label q-gram, degree q-gram, degree sequence)
  mask  = in reduced query region  &  bound <= tau

Memory behaviour: F_D tiles are streamed HBM->VMEM exactly once (this is
the bandwidth-dominant operand); the small per-graph arrays (histograms,
degree sequences, sizes) live in VMEM across the whole vocab sweep — Pallas
skips re-copies when the index map is unchanged.  The filters are
memory-bound, so the fusion (vs. separate passes per filter) is the
roofline lever: every additional pass would re-read F_D.

Grid: (B / BB, U / BU); C_D accumulates in a VMEM scratch and the cascade
finalises on the last vocab tile.

Scalar parameters (query sizes, tau, region geometry) arrive via SMEM.

The query-batched variant (``fused_batched_call``, DESIGN.md §13) amortises
the F_D stream over a whole padded query block: grid (Q/QB, B/BB, U/BU),
per-query scalars as an SMEM (QB, N_SCALARS) block, query-side operands
blocked along a leading Q axis, (QB, BB) outputs and VMEM C_D scratch.  Each
F_D tile is reused by all QB queries of the block while resident in VMEM —
the single-query kernel re-reads the whole matrix once per query.  The hot
slab's per-(query, graph) CSR tail correction arrives as a dedicated
(QB, BB) operand seeding the scratch (it no longer fits in the per-graph
aux columns once queries batch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# scalar layout in the SMEM parameter vector
Q_NV, Q_NE, TAU, X0, Y0, LREG = range(6)
N_SCALARS = 6


def _kernel(scalars_ref,          # SMEM (6,) int32
            fd_ref,               # (BB, BU) int32
            qfd_ref,              # (BU,)    int32
            vhist_ref,            # (BB, NV) int32
            qvh_ref,              # (NV,)    int32
            ehist_ref,            # (BB, NE) int32
            qeh_ref,              # (NE,)    int32
            degseq_ref,           # (BB, VM) int32
            qsig_ref,             # (VM,)    int32
            aux_ref,              # (BB, 5)  int32: nv, ne, region_i, region_j,
                                  #                 cd_tail (sparse-tail C_D)
            bounds_ref,           # (BB,)    int32 out
            mask_ref,             # (BB,)    int32 out (0/1)
            cd_acc):              # VMEM (BB,) scratch
    j = pl.program_id(1)
    nu = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        # seed with the host-computed cold-vocabulary contribution so the
        # hot-prefix layout stays admissible (DESIGN.md §3)
        cd_acc[...] = aux_ref[:, 4]

    cd_acc[...] += jnp.minimum(fd_ref[...], qfd_ref[...][None, :]).sum(axis=1)

    @pl.when(j == nu - 1)
    def _finalize():
        q_nv = scalars_ref[Q_NV]
        q_ne = scalars_ref[Q_NE]
        tau = scalars_ref[TAU]
        nv = aux_ref[:, 0]
        ne = aux_ref[:, 1]
        c_d = cd_acc[...]

        overlap_v = jnp.minimum(vhist_ref[...], qvh_ref[...][None, :]).sum(axis=1)
        overlap_e = jnp.minimum(ehist_ref[...], qeh_ref[...][None, :]).sum(axis=1)
        c_l = overlap_v + overlap_e
        max_nv = jnp.maximum(nv, q_nv)
        max_ne = jnp.maximum(ne, q_ne)

        number_count = jnp.abs(nv - q_nv) + jnp.abs(ne - q_ne)
        label_qgram = max_nv + max_ne - c_l
        degree_qgram = jnp.maximum(0, (2 * max_nv - overlap_v - c_d + 1) // 2)

        d = degseq_ref[...] - qsig_ref[...][None, :]
        s1 = jnp.maximum(d, 0).sum(axis=1)
        s2 = jnp.maximum(-d, 0).sum(axis=1)
        delta = (s1 + 1) // 2 + (s2 + 1) // 2
        min_deg = jnp.minimum(degseq_ref[...], qsig_ref[...][None, :]).sum(axis=1)
        lam2 = jnp.maximum(q_ne + ne - min_deg, 0)
        lam = jnp.where(q_nv <= nv, delta, lam2)
        degree_sequence = max_nv - overlap_v + lam

        bound = jnp.maximum(jnp.maximum(number_count, label_qgram),
                            jnp.maximum(degree_qgram, degree_sequence))

        # reduced query region (formula (1)) — fused in
        x0 = scalars_ref[X0]
        y0 = scalars_ref[Y0]
        l = scalars_ref[LREG]
        s = x0 + y0
        dd = y0 - x0
        i1 = jnp.floor_divide(q_ne - tau + q_nv - s, l)
        i2 = jnp.floor_divide(q_ne + tau + q_nv - s, l)
        j1 = jnp.floor_divide(q_ne - tau - q_nv - dd, l)
        j2 = jnp.floor_divide(q_ne + tau - q_nv - dd, l)
        ri = aux_ref[:, 2]
        rj = aux_ref[:, 3]
        in_region = ((ri >= i1) & (ri <= i2) & (rj >= j1) & (rj <= j2))

        bounds_ref[...] = bound.astype(jnp.int32)
        mask_ref[...] = (in_region & (bound <= tau)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bb", "bu", "interpret"))
def fused_filter_call(scalars, fd, qfd, vhist, qvh, ehist, qeh, degseq, qsig,
                      aux, *, bb: int = 128, bu: int = 512,
                      interpret: bool = False):
    """Raw pallas_call wrapper; shapes must already be tile-aligned."""
    B, U = fd.shape
    NV = vhist.shape[1]
    NE = ehist.shape[1]
    VM = degseq.shape[1]
    assert B % bb == 0 and U % bu == 0, (B, U, bb, bu)
    grid = (B // bb, U // bu)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # scalars
            pl.BlockSpec((bb, bu), lambda i, j: (i, j)),            # fd
            pl.BlockSpec((bu,), lambda i, j: (j,)),                 # qfd
            pl.BlockSpec((bb, NV), lambda i, j: (i, 0)),            # vhist
            pl.BlockSpec((NV,), lambda i, j: (0,)),                 # qvh
            pl.BlockSpec((bb, NE), lambda i, j: (i, 0)),            # ehist
            pl.BlockSpec((NE,), lambda i, j: (0,)),                 # qeh
            pl.BlockSpec((bb, VM), lambda i, j: (i, 0)),            # degseq
            pl.BlockSpec((VM,), lambda i, j: (0,)),                 # qsig
            pl.BlockSpec((bb, 5), lambda i, j: (i, 0)),             # aux
        ],
        out_specs=[
            pl.BlockSpec((bb,), lambda i, j: (i,)),
            pl.BlockSpec((bb,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bb,), jnp.int32)],
        interpret=interpret,
    )(scalars, fd, qfd, vhist, qvh, ehist, qeh, degseq, qsig, aux)


# --------------------------------------------------------------------------
# query-batched kernel (DESIGN.md §13)
# --------------------------------------------------------------------------

def _batched_kernel(scalars_ref,      # SMEM (QB, N_SCALARS) int32
                    fd_ref,           # (BB, BU) int32
                    qfd_ref,          # (QB, BU) int32
                    vhist_ref,        # (BB, NV) int32
                    qvh_ref,          # (QB, NV) int32
                    ehist_ref,        # (BB, NE) int32
                    qeh_ref,          # (QB, NE) int32
                    degseq_ref,       # (BB, VM) int32
                    qsig_ref,         # (QB, VM) int32
                    aux_ref,          # (BB, 4)  int32: nv, ne, region_i/j
                    cdt_ref,          # (QB, BB) int32: host C_D seed (hot
                                      #          tail correction; else zeros)
                    bounds_ref,       # (QB, BB) int32 out
                    mask_ref,         # (QB, BB) int32 out (0/1)
                    cd_acc):          # VMEM (QB, BB) scratch
    j = pl.program_id(2)
    nu = pl.num_programs(2)
    QB = scalars_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        cd_acc[...] = cdt_ref[...]

    # (QB, BB, BU) broadcast min-sum: the F_D tile is read once and served
    # to every query of the block — the whole point of query batching.
    cd_acc[...] += jnp.minimum(fd_ref[...][None, :, :],
                               qfd_ref[...][:, None, :]).sum(axis=2)

    @pl.when(j == nu - 1)
    def _finalize():
        def scol(c):
            # per-query scalar column as a (QB, 1) vector; SMEM reads stay
            # scalar (TPU-safe), QB is static so the stack unrolls
            return jnp.stack([scalars_ref[r, c]
                              for r in range(QB)])[:, None]

        q_nv, q_ne, tau = scol(Q_NV), scol(Q_NE), scol(TAU)
        nv = aux_ref[:, 0][None, :]
        ne = aux_ref[:, 1][None, :]
        c_d = cd_acc[...]

        overlap_v = jnp.minimum(vhist_ref[...][None, :, :],
                                qvh_ref[...][:, None, :]).sum(axis=2)
        overlap_e = jnp.minimum(ehist_ref[...][None, :, :],
                                qeh_ref[...][:, None, :]).sum(axis=2)
        c_l = overlap_v + overlap_e
        max_nv = jnp.maximum(nv, q_nv)
        max_ne = jnp.maximum(ne, q_ne)

        number_count = jnp.abs(nv - q_nv) + jnp.abs(ne - q_ne)
        label_qgram = max_nv + max_ne - c_l
        degree_qgram = jnp.maximum(0, (2 * max_nv - overlap_v - c_d + 1) // 2)

        d = degseq_ref[...][None, :, :] - qsig_ref[...][:, None, :]
        s1 = jnp.maximum(d, 0).sum(axis=2)
        s2 = jnp.maximum(-d, 0).sum(axis=2)
        delta = (s1 + 1) // 2 + (s2 + 1) // 2
        min_deg = jnp.minimum(degseq_ref[...][None, :, :],
                              qsig_ref[...][:, None, :]).sum(axis=2)
        lam2 = jnp.maximum(q_ne + ne - min_deg, 0)
        lam = jnp.where(q_nv <= nv, delta, lam2)
        degree_sequence = max_nv - overlap_v + lam

        bound = jnp.maximum(jnp.maximum(number_count, label_qgram),
                            jnp.maximum(degree_qgram, degree_sequence))

        x0, y0, l = scol(X0), scol(Y0), scol(LREG)
        s = x0 + y0
        dd = y0 - x0
        i1 = jnp.floor_divide(q_ne - tau + q_nv - s, l)
        i2 = jnp.floor_divide(q_ne + tau + q_nv - s, l)
        j1 = jnp.floor_divide(q_ne - tau - q_nv - dd, l)
        j2 = jnp.floor_divide(q_ne + tau - q_nv - dd, l)
        ri = aux_ref[:, 2][None, :]
        rj = aux_ref[:, 3][None, :]
        in_region = ((ri >= i1) & (ri <= i2) & (rj >= j1) & (rj <= j2))

        bounds_ref[...] = bound.astype(jnp.int32)
        mask_ref[...] = (in_region & (bound <= tau)).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("qb", "bb", "bu", "interpret", "name"))
def fused_batched_call(scalars, fd, qfd, vhist, qvh, ehist, qeh, degseq,
                       qsig, aux, cdt, *, qb: int = 8, bb: int = 128,
                       bu: int = 512, interpret: bool = False,
                       name: str = "msq_qgram_filter_dense"):
    """Raw query-batched pallas_call; shapes must already be tile-aligned.

    scalars (Q, N_SCALARS); fd (B, U); qfd (Q, U); vhist (B, NV);
    qvh (Q, NV); ehist (B, NE); qeh (Q, NE); degseq (B, VM); qsig (Q, VM);
    aux (B, 4); cdt (Q, B).  Returns ((Q, B) bounds, (Q, B) mask).
    ``name`` is the kernel's name in the device trace.
    """
    Q, B, U = scalars.shape[0], fd.shape[0], fd.shape[1]
    NV = vhist.shape[1]
    NE = ehist.shape[1]
    VM = degseq.shape[1]
    assert Q % qb == 0 and B % bb == 0 and U % bu == 0, (Q, B, U, qb, bb, bu)
    grid = (Q // qb, B // bb, U // bu)
    return pl.pallas_call(
        _batched_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((qb, N_SCALARS), lambda q, i, j: (q, 0),
                         memory_space=pltpu.SMEM),                  # scalars
            pl.BlockSpec((bb, bu), lambda q, i, j: (i, j)),         # fd
            pl.BlockSpec((qb, bu), lambda q, i, j: (q, j)),         # qfd
            pl.BlockSpec((bb, NV), lambda q, i, j: (i, 0)),         # vhist
            pl.BlockSpec((qb, NV), lambda q, i, j: (q, 0)),         # qvh
            pl.BlockSpec((bb, NE), lambda q, i, j: (i, 0)),         # ehist
            pl.BlockSpec((qb, NE), lambda q, i, j: (q, 0)),         # qeh
            pl.BlockSpec((bb, VM), lambda q, i, j: (i, 0)),         # degseq
            pl.BlockSpec((qb, VM), lambda q, i, j: (q, 0)),         # qsig
            pl.BlockSpec((bb, 4), lambda q, i, j: (i, 0)),          # aux
            pl.BlockSpec((qb, bb), lambda q, i, j: (q, i)),         # cdt
        ],
        out_specs=[
            pl.BlockSpec((qb, bb), lambda q, i, j: (q, i)),
            pl.BlockSpec((qb, bb), lambda q, i, j: (q, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, B), jnp.int32),
            jax.ShapeDtypeStruct((Q, B), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((qb, bb), jnp.int32)],
        interpret=interpret,
        name=name,
    )(scalars, fd, qfd, vhist, qvh, ehist, qeh, degseq, qsig, aux, cdt)
