"""FilterSlab: the single serving representation a bucket's filter pass
runs against (DESIGN.md §11).

The paper's headline claim is a *succinct* index, but a serving path that
materialises the full-vocab dense F_D matrix per host is the opposite.
This module makes the resident form a choice — three interchangeable
layouts behind one gather/c_d interface, so every backend (numpy / jax /
pallas / distributed) sees the same slab protocol and produces
bit-identical candidate sets:

* ``dense``  — (B, U) int32 F_D, today's behavior; fastest on narrow
  vocabularies, 32 bits per count.
* ``hot``    — dense hot prefix (B, H) over the frequency-ordered
  vocabulary plus a CSR *tail* (ids >= H).  The device computes the
  hot-prefix min-sum; the host adds the batched CSR tail correction
  (``qgrams.csr_tail_minsum``) to C_D *before* thresholding, which keeps
  the bound admissible (DESIGN.md §3).
* ``packed`` — the hybrid bit-packed rows of ``kernels/bitunpack``
  (``PackedRows``): per-128-entry blocks at the narrowest power-of-two
  width.  The resident slab is the succinct form; the filter pass decodes
  it on device (``unpack_rows_ref`` under jit/shard_map, the Pallas
  ``unpack_hybrid`` kernel on the pallas backend).

The non-F_D arrays (sizes, degree sequences, label histograms, region
coordinates) are identical across layouts; only the F_D carrier differs,
and ``size_bits()`` accounts for exactly that difference.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Dict, Optional, Tuple

import numpy as np

from repro.core.arrays import DBArrays
from repro.core.qgrams import EncodedDB

LAYOUTS = ("dense", "hot", "packed")
DEFAULT_HOT_D = 128                  # keep in sync with MSQConfig.hot_d
_IMPOSSIBLE = -(2 ** 20)


def hot_d_from_mass(enc: EncodedDB, mass: float) -> int:
    """Data-tuned hot-prefix width: the smallest H whose frequency-ordered
    columns ``[0, H)`` cover at least ``mass`` of the database's total
    degree-q-gram count mass (``MSQConfig.hot_mass``; replaces the fixed
    ``DEFAULT_HOT_D`` when set).  The vocabulary is frequency-ordered
    (most frequent id 0), so the cumulative mass curve is concave and the
    smallest covering prefix is well-defined."""
    U = max(enc.vocab.n_degree_ids, 1)
    if len(enc.d_ids) == 0 or mass <= 0.0:
        return 1
    counts = np.bincount(np.asarray(enc.d_ids, np.int64),
                         weights=np.asarray(enc.d_cnt, np.float64),
                         minlength=U)
    total = float(counts.sum())
    if total <= 0.0:
        return 1
    target = min(float(mass), 1.0) * total
    cum = np.cumsum(counts)
    # smallest H with cum[H-1] >= target (epsilon guards float equality)
    H = int(np.searchsorted(cum, target - 1e-9, side="left")) + 1
    return max(1, min(H, U))


def branch_features(graphs, n_elabels: int, vmax: int
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-vertex *branch* structures for the assignment lower bound
    (DESIGN.md §16): for every vertex its label, degree, and incident
    edge-label histogram.  Padded to ``vmax`` with label -1 / degree 0 /
    zero histograms — pad slots then price exactly like the ε
    (insert/delete) column of the branch cost matrix, so the batched
    min-reduce needs no explicit pad masking on the min axes.

    Returns ``(vlab (B, vmax) int32, deg (B, vmax) int32,
    ehist (B, vmax, n_elabels) int32)``.
    """
    B = len(graphs)
    vlab = np.full((B, vmax), -1, np.int32)
    deg = np.zeros((B, vmax), np.int32)
    eh = np.zeros((B, vmax, max(n_elabels, 1)), np.int32)
    for i, g in enumerate(graphs):
        n = min(int(g.n), vmax)
        vlab[i, :n] = np.asarray(g.vlabels[:n], np.int32)
        if g.m:
            edges = np.asarray(g.edges, np.int64)
            elab = np.asarray(g.elabels, np.int64)
            np.add.at(deg[i], edges.ravel(), 1)
            np.add.at(eh[i], (edges.ravel(), np.repeat(elab, 2)), 1)
    return vlab, deg, eh


def _pad_rows(x: np.ndarray, pad: int, fill: int = 0) -> np.ndarray:
    """``x`` with ``pad`` rows of ``fill`` appended along the graph axis."""
    if not pad:
        return x
    return np.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1),
                  constant_values=fill)


def _pad_packed(words: np.ndarray, sb: np.ndarray, widths: np.ndarray,
                pad: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Packed rows with ``pad`` rows appended that decode to zeros: zero
    words at the narrowest width (4*w words per block, so the offsets fit
    any real row width)."""
    if not pad:
        return words, sb, widths
    from repro.kernels.bitunpack.ops import WIDTHS
    KB = sb.shape[1]
    w0 = WIDTHS[0]
    zero_sb = (np.arange(KB, dtype=np.int32) * 4 * w0)[None, :]
    return (np.vstack([words, np.zeros((pad, words.shape[1]), words.dtype)]),
            np.vstack([sb, np.repeat(zero_sb, pad, axis=0)]),
            np.vstack([widths, np.full((pad, KB), w0, widths.dtype)]))


def _ragged_take(off: np.ndarray, ids: np.ndarray, cnt: np.ndarray,
                 rows: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Gather CSR rows: new (off, ids, cnt) for ``rows`` in order."""
    rows = np.asarray(rows, np.int64)
    lengths = (off[rows + 1] - off[rows]).astype(np.int64)
    new_off = np.zeros(len(rows) + 1, np.int64)
    np.cumsum(lengths, out=new_off[1:])
    pos = (np.repeat(off[rows], lengths)
           + np.arange(int(new_off[-1]), dtype=np.int64)
           - np.repeat(new_off[:-1], lengths))
    return new_off, ids[pos], cnt[pos]


@dataclass
class FilterSlab:
    """One bucket-servable database slab in a chosen F_D layout.

    Always-dense per-graph arrays (the filter cascade's small operands)
    plus exactly one F_D carrier: ``fd`` (dense (B, U) or hot (B, H)),
    the ``hot`` tail CSR (``t_off``/``t_ids``/``t_cnt``, ids >= hot_d),
    or ``packed`` (``PackedRows``).
    """

    layout: str
    nv: np.ndarray
    ne: np.ndarray
    degseq: np.ndarray
    vhist: np.ndarray
    ehist: np.ndarray
    region_i: np.ndarray
    region_j: np.ndarray
    U: int                       # full degree-vocabulary width
    hot_d: int                   # == U for dense/packed
    vmax: int
    fd: Optional[np.ndarray] = None
    t_off: Optional[np.ndarray] = None
    t_ids: Optional[np.ndarray] = None
    t_cnt: Optional[np.ndarray] = None
    packed: Optional["PackedRows"] = None        # noqa: F821
    # per-vertex branch structures for the stage-1.5 assignment lower
    # bound (DESIGN.md §16) — layout-independent, like nv/degseq
    bvlab: Optional[np.ndarray] = None           # (B, vmax), pad -1
    bdeg: Optional[np.ndarray] = None            # (B, vmax), pad 0
    behist: Optional[np.ndarray] = None          # (B, vmax, NE), pad 0
    _fd_cache: Optional[np.ndarray] = None       # lazy packed host decode
    _t_rows: Optional[np.ndarray] = None         # lazy tail entry -> row map

    # ---- construction -----------------------------------------------------
    @classmethod
    def build(cls, db, enc: EncodedDB, partition, *, layout: str = "dense",
              hot_d: Optional[int] = None,
              hot_mass: Optional[float] = None) -> "FilterSlab":
        if layout not in LAYOUTS:
            raise ValueError(f"unknown slab layout {layout!r} "
                             f"(one of {LAYOUTS})")
        from repro.graphs.batching import PaddedGraphBatch
        nv, ne = db.sizes()
        vmax = int(max(nv.max(), 1)) if len(nv) else 1
        batch = PaddedGraphBatch.from_db(db, vmax=vmax)
        U = max(enc.vocab.n_degree_ids, 1)
        ri, rj = partition.region_of(nv, ne)
        slab = cls(
            layout=layout,
            nv=batch.nv.astype(np.int32), ne=batch.ne.astype(np.int32),
            degseq=batch.degseq.astype(np.int32),
            vhist=batch.vlabel_hist.astype(np.int32),
            ehist=batch.elabel_hist.astype(np.int32),
            region_i=ri.astype(np.int32), region_j=rj.astype(np.int32),
            U=U, hot_d=U, vmax=vmax)
        slab.bvlab, slab.bdeg, slab.behist = branch_features(
            db.graphs, db.n_elabels, vmax)
        if layout == "dense":
            fd, _ = enc.dense_hot(U)
            slab.fd = fd.astype(np.int32)
        elif layout == "hot":
            # explicit hot_d wins; else a hot_mass target picks H from the
            # data; else the fixed default — hot without any width must
            # not silently degenerate to the dense slab
            if hot_d is not None:
                H = int(hot_d)
            elif hot_mass is not None:
                H = hot_d_from_mass(enc, hot_mass)
            else:
                H = DEFAULT_HOT_D
            H = max(1, min(H, U))
            slab.hot_d = H
            fd, _ = enc.dense_hot(H)
            slab.fd = fd.astype(np.int32)
            mask = enc.d_ids >= H
            row_of = np.repeat(np.arange(len(enc)), np.diff(enc.d_off))
            slab.t_ids = enc.d_ids[mask].astype(np.int32)
            slab.t_cnt = enc.d_cnt[mask].astype(np.int32)
            t_off = np.zeros(len(enc) + 1, np.int64)
            np.cumsum(np.bincount(row_of[mask], minlength=len(enc)),
                      out=t_off[1:])
            slab.t_off = t_off
        else:  # packed
            from repro.kernels.bitunpack.ops import pack_hybrid_rows
            fd, _ = enc.dense_hot(U)
            slab.packed = pack_hybrid_rows(fd)
        return slab

    @property
    def B(self) -> int:
        return len(self.nv)

    # ---- bucket gather ----------------------------------------------------
    def gather(self, idx: np.ndarray,
               n_pad: Optional[int] = None) -> "FilterSlab":
        """Row-gather a bucket sub-slab, optionally padded to ``n_pad``
        with impossible graphs (never in-region, zero F_D)."""
        idx = np.asarray(idx, np.int64)
        n_pad = len(idx) if n_pad is None else int(n_pad)
        pad = n_pad - len(idx)

        def take(x, fill=0):
            return _pad_rows(np.asarray(x)[idx], pad, fill)

        sub = replace(
            self,
            _fd_cache=None, _t_rows=None,
            nv=take(self.nv), ne=take(self.ne), degseq=take(self.degseq),
            vhist=take(self.vhist), ehist=take(self.ehist),
            region_i=take(self.region_i, _IMPOSSIBLE),
            region_j=take(self.region_j, _IMPOSSIBLE),
            bvlab=None if self.bvlab is None else take(self.bvlab, -1),
            bdeg=None if self.bdeg is None else take(self.bdeg),
            behist=None if self.behist is None else take(self.behist))
        if self.fd is not None:
            sub.fd = take(self.fd)
        if self.layout == "hot":
            t_off, t_ids, t_cnt = _ragged_take(self.t_off, self.t_ids,
                                               self.t_cnt, idx)
            if pad:          # pad rows have empty tails
                t_off = np.concatenate(
                    [t_off, np.full(pad, t_off[-1], np.int64)])
            sub.t_off, sub.t_ids, sub.t_cnt = t_off, t_ids, t_cnt
        if self.layout == "packed":
            from repro.kernels.bitunpack.ops import PackedRows
            pk = self.packed
            words, sb, widths = _pad_packed(pk.words[idx], pk.sb[idx],
                                            pk.widths[idx], pad)
            sub.packed = PackedRows(words=words, sb=sb, widths=widths,
                                    n_entries=pk.n_entries)
        return sub

    def in_rect(self, rect: Tuple[int, int, int, int]) -> np.ndarray:
        i1, i2, j1, j2 = rect
        m = ((self.region_i >= i1) & (self.region_i <= i2)
             & (self.region_j >= j1) & (self.region_j <= j2))
        return np.flatnonzero(m)

    # ---- device-side view -------------------------------------------------
    def base_arrays(self, sentinel: bool = False) -> DBArrays:
        """The DBArrays a filter pass consumes.  ``fd`` is the layout's
        dense carrier: full matrix (dense), hot prefix (hot), or a (B, 1)
        placeholder (packed — the pass decodes ``self.packed`` itself and
        supplies C_D explicitly).

        ``sentinel`` appends one row at index ``B`` with the fills
        ``gather`` pads a bucket with (never in-region, zero F_D, sizes
        and histograms): the whole-slab copy a device pass gathers a
        bucket from by row index, padding with ``B``."""
        fd = self.fd
        if fd is None:
            fd = np.zeros((self.B, 1), np.int32)
        db = DBArrays(nv=self.nv, ne=self.ne, degseq=self.degseq,
                      vhist=self.vhist, ehist=self.ehist, fd=fd,
                      region_i=self.region_i, region_j=self.region_j)
        if not sentinel:
            return db
        fills = {"region_i": _IMPOSSIBLE, "region_j": _IMPOSSIBLE}
        return DBArrays(**{f: _pad_rows(x, 1, fills.get(f, 0))
                           for f, x in db._asdict().items()})

    def packed_arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The packed layout's ``(words, sb, widths)`` with one sentinel
        row appended that decodes to zeros, as ``base_arrays(sentinel=True)``
        for the device pass to gather from."""
        pk = self.packed
        return _pad_packed(pk.words, pk.sb, pk.widths, 1)

    # ---- host C_D (numpy backend + overflow fallback) ---------------------
    def fd_dense_np(self) -> np.ndarray:
        """Full-width dense F_D (decodes packed once per gathered slab;
        rebuilding hot tails is the caller's job via ``cd_one`` — hot
        keeps no dense tail on purpose)."""
        if self.layout == "packed":
            if self._fd_cache is None:
                from repro.kernels.bitunpack.ops import unpack_rows_np
                self._fd_cache = unpack_rows_np(self.packed)
            return self._fd_cache
        return self.fd

    def cd_one(self, qfd: np.ndarray) -> np.ndarray:
        """(B,) exact C_D against one full-width dense query F_D.

        Query-sparse (DESIGN.md §13): only the query's nonzero columns are
        gathered — ``min(F_D, 0) = 0`` makes the rest a guaranteed no-op,
        so this is bit-identical to the full-width sweep at a fraction of
        the work (queries touch a few dozen of potentially thousands of
        vocabulary columns)."""
        qfd = np.asarray(qfd, np.int64)
        if self.layout == "hot":
            ids = np.flatnonzero(qfd[:self.hot_d] > 0)
            hot = np.minimum(self.fd[:, ids].astype(np.int64),
                             qfd[ids][None, :]).sum(axis=1)
            return hot + self.tail_minsum_one(qfd)
        fd = self.fd_dense_np()
        ids = np.flatnonzero(qfd[:fd.shape[1]] > 0)
        return np.minimum(fd[:, ids].astype(np.int64),
                          qfd[ids][None, :]).sum(axis=1)

    def tail_minsum_one(self, qfd: np.ndarray) -> np.ndarray:
        """(B,) batched CSR tail correction for one dense query F_D.

        The tail CSR already holds only ids >= hot_d, and the query is
        dense, so this is one gather + bincount over the tail nnz; the
        query-independent entry->row map is computed once per slab.
        """
        if self._t_rows is None:
            self._t_rows = np.repeat(np.arange(self.B),
                                     np.diff(self.t_off))
        qfd = np.asarray(qfd, np.int64)
        contrib = np.minimum(self.t_cnt.astype(np.int64),
                             qfd[self.t_ids])
        return np.bincount(self._t_rows, weights=contrib,
                           minlength=self.B).astype(np.int64)

    def tail_minsum_batch(self, qfds: np.ndarray) -> np.ndarray:
        """(Q, B) tail corrections for a stacked query block."""
        return np.stack([self.tail_minsum_one(q) for q in qfds])

    # ---- size accounting (DESIGN.md §11) ----------------------------------
    def size_bits(self) -> Dict[str, int]:
        """Bits of the layout-specific F_D carrier (the slab parts shared
        by every layout are excluded — they don't differentiate)."""
        if self.layout == "dense":
            fd_bits = self.fd.size * 32
            return {"fd": fd_bits, "total": fd_bits}
        if self.layout == "hot":
            fd_bits = self.fd.size * 32
            tail_bits = (len(self.t_ids) * 32 + len(self.t_cnt) * 32
                         + len(self.t_off) * 64)
            return {"fd": fd_bits, "tail": tail_bits,
                    "total": fd_bits + tail_bits}
        from repro.kernels.bitunpack.ops import packed_rows_size_bits
        s = packed_rows_size_bits(self.packed)
        return {"words": s["words"], "sb": s["sb"], "widths": s["widths"],
                "ragged_payload": s["ragged_payload"], "total": s["total"]}

    def bits_per_graph(self) -> float:
        return self.size_bits()["total"] / max(self.B, 1)
