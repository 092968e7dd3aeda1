"""Public jit'd wrappers for the fused filter kernel.

Handles tile-padding, the scalar parameter vector, backend selection
(interpret=True off-TPU), and the optional sparse-tail C_D correction that
keeps the hot-prefix layout admissible (DESIGN.md §3).

Padded shapes round up to a shared shape-bucket ladder (``shape_bucket``,
powers of two up to the block size, then block-size multiples — the same
buckets ``core.engine`` pads the (Q, N) jax pass to), so nearby bucket
sizes share one compiled program instead of baking a fresh static block
size per distinct B (DESIGN.md §13).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.qgram_filter.kernel import (N_SCALARS, fused_batched_call,
                                               fused_filter_call)

# shared shape-bucket ladders (keep in sync with core.engine._Q_PAD/_N_PAD)
Q_BASE, Q_CAP = 8, 64
B_BASE, B_CAP = 8, 512
U_BASE, U_CAP = 128, 512


def _pad_to(x, mult, axis, value=0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def shape_bucket(n: int, base: int, cap: int) -> int:
    """Round ``n`` up to the shared shape-bucket ladder: powers of two
    times ``base`` up to ``cap``, then multiples of ``cap``.  Every ladder
    value is divisible by any power-of-two block size <= itself, so
    ``min(block, bucket)`` always tiles it evenly."""
    m = base
    while m < n and m < cap:
        m *= 2
    return m if n <= m else _next_mult(n, cap)


def _pad_and_block(n: int, base: int, blk: int) -> Tuple[int, int]:
    """(padded size, effective block) for one axis: the shared shape
    bucket when the block divides it (power-of-two blocks always do), an
    exact block multiple otherwise (explicit odd blocks keep working)."""
    pad = shape_bucket(n, base, max(blk, base))
    blk = min(blk, pad)
    if pad % blk:
        pad = _next_mult(n, blk)
    return pad, blk


def make_scalars(q_nv: int, q_ne: int, tau: int, x0: int, y0: int,
                 l: int) -> jnp.ndarray:
    return jnp.asarray([q_nv, q_ne, tau, x0, y0, l], jnp.int32)


def make_scalars_batch(qs, x0: int, y0: int, l: int) -> np.ndarray:
    """(Q, N_SCALARS) scalar rows for a stacked query block."""
    return np.asarray([[int(q.nv), int(q.ne), int(q.tau), x0, y0, l]
                       for q in qs], np.int32)


@functools.partial(jax.jit,
                   static_argnames=("bb", "bu", "interpret"))
def fused_filter_bounds(scalars, fd, qfd, vhist, qvh, ehist, qeh, degseq,
                        qsig, aux, *, bb: int = 128, bu: int = 512,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """(bounds, mask) for a database shard vs one query.

    Pads B to its shape bucket (with impossible graphs: nv = -2**20 so
    every bound is huge and the region test fails) and U to a multiple of
    the vocab tile (zero counts: no-op for min-sum).  Returns unpadded
    (B,) arrays.
    """
    if interpret is None:
        interpret = not on_tpu()
    B, U = fd.shape
    b_pad, bb = _pad_and_block(B, B_BASE, bb)
    u_pad, bu = _pad_and_block(U, U_BASE, bu)
    fd_p = _pad_to(_pad_to(fd, b_pad, 0), u_pad, 1)
    qfd_p = _pad_to(qfd, u_pad, 0)
    vhist_p = _pad_to(vhist, b_pad, 0)
    ehist_p = _pad_to(ehist, b_pad, 0)
    degseq_p = _pad_to(degseq, b_pad, 0)
    aux_p = _pad_to(aux, b_pad, 0, value=-(2 ** 20))
    bounds, mask = fused_filter_call(
        scalars, fd_p, qfd_p, vhist_p, qvh, ehist_p, qeh, degseq_p, qsig,
        aux_p, bb=bb, bu=bu, interpret=interpret)
    return bounds[:B], mask[:B]


@functools.partial(jax.jit,
                   static_argnames=("qb", "bb", "bu", "interpret"))
def fused_filter_bounds_batched(scalars, fd, qfd, vhist, qvh, ehist, qeh,
                                degseq, qsig, aux, cdt=None, *,
                                qb: int = 8, bb: int = 128, bu: int = 512,
                                interpret: Optional[bool] = None
                                ) -> Tuple[jax.Array, jax.Array]:
    """(bounds, mask), both (Q, B), for a database shard vs a whole query
    block — one kernel launch for every (query, graph) pair
    (DESIGN.md §13).

    Query-side operands carry a leading Q axis (``scalars`` (Q, 6), ``qfd``
    (Q, U), ...); ``cdt`` is the (Q, B) host-computed C_D seed (the hot
    slab's CSR tail correction; omitted/None means zeros).  Q pads by
    repeating the last scalar row (always-valid geometry — padded rows are
    sliced off), B pads with impossible graphs, U with zero counts.
    """
    if interpret is None:
        interpret = not on_tpu()
    Q = scalars.shape[0]
    B, U = fd.shape
    q_pad, qb = _pad_and_block(Q, Q_BASE, qb)
    b_pad, bb = _pad_and_block(B, B_BASE, bb)
    u_pad, bu = _pad_and_block(U, U_BASE, bu)
    sc_p = jnp.concatenate(
        [scalars] + [scalars[-1:]] * (q_pad - Q)) if q_pad > Q else scalars
    fd_p = _pad_to(_pad_to(fd, b_pad, 0), u_pad, 1)
    qfd_p = _pad_to(_pad_to(qfd, q_pad, 0), u_pad, 1)
    vhist_p = _pad_to(vhist, b_pad, 0)
    qvh_p = _pad_to(qvh, q_pad, 0)
    ehist_p = _pad_to(ehist, b_pad, 0)
    qeh_p = _pad_to(qeh, q_pad, 0)
    degseq_p = _pad_to(degseq, b_pad, 0)
    qsig_p = _pad_to(qsig, q_pad, 0)
    aux_p = _pad_to(aux[:, :4], b_pad, 0, value=-(2 ** 20))
    if cdt is None:
        cdt_p = jnp.zeros((q_pad, b_pad), jnp.int32)
    else:
        cdt_p = _pad_to(_pad_to(cdt.astype(jnp.int32), q_pad, 0), b_pad, 1)
    bounds, mask = fused_batched_call(
        sc_p, fd_p, qfd_p, vhist_p, qvh_p, ehist_p, qeh_p, degseq_p,
        qsig_p, aux_p, cdt_p, qb=qb, bb=bb, bu=bu, interpret=interpret,
        name="msq_qgram_filter_dense" if cdt is None
        else "msq_qgram_filter_hot")
    return bounds[:Q, :B], mask[:Q, :B]


def _next_mult(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def make_aux(nv, ne, region_i, region_j, cd_tail=None) -> jnp.ndarray:
    """Pack the per-graph scalar columns; cd_tail is the host-computed
    cold-vocabulary SUM(min(F_D, q_D)) when fd holds only the hot prefix
    (zeros for the full-vocab layout)."""
    if cd_tail is None:
        cd_tail = jnp.zeros_like(nv)
    return jnp.stack([nv, ne, region_i, region_j, cd_tail], axis=1
                     ).astype(jnp.int32)


def cd_tail_host(enc, q_ids: np.ndarray, q_cnt: np.ndarray, hot: int
                 ) -> np.ndarray:
    """Host CSR merge for the cold-vocabulary C_D contribution.

    Only the query's ids >= hot participate; one vectorised sweep over the
    whole CSR (``EncodedDB.tail_intersection_bulk``) regardless of |G|.
    """
    return enc.tail_intersection_bulk(np.asarray(q_ids), np.asarray(q_cnt),
                                      hot).astype(np.int32)
