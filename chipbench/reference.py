"""Plain reference for range-tau graph similarity search, and its control.

The reference answers the same question as the served path with none of
its code: for a query graph h and a radius tau,

* the candidates are every database graph whose combined lower bound is at
  most tau.  The bound is the largest of the filters the MSQ-Index paper
  (arXiv:1612.09155) combines: number count, label count (equal to the
  label-based q-gram count of Sec. 3.2), degree-based q-gram count
  (Lemma 2), and the degree-sequence filter (Lemma 5), whose case
  |V_h| > |V_g| takes the closed-form relaxation
  lambda_e >= |E_h| + |E_g| - sum_i min(sigma_g[i], sigma_h[i]);
* the matches are the candidates whose exact graph edit distance (unit
  costs for the six primitive edits) is at most tau, with that distance.
  The filter never dismisses a true match, so searching the candidates is
  enough.

The exact distance is a depth-first branch and bound over mappings of the
query's vertices onto the database graph's vertices or deletion, pruned by
a label-multiset bound on the unmapped rest.  ``ReferenceIndex`` builds
its own per-graph features from the plain graphs; it takes no table the
program made.

``greedy_ged`` is the control: the same search without backtracking, an
upper bound standing in for an approximate verifier.  Reporting it as the
distance breaks the exactness the configurations state.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from data import PlainGraph

_R = 64                        # radix of the adjacent-edge-label counts


def _degrees(g: PlainGraph) -> np.ndarray:
    d = np.zeros(g.n, np.int64)
    if g.m:
        np.add.at(d, g.edges[:, 0], 1)
        np.add.at(d, g.edges[:, 1], 1)
    return d


def degree_qgram_keys(g: PlainGraph, n_elabels: int) -> np.ndarray:
    """One integer per vertex for its degree-based q-gram: the vertex
    label and the multiset of adjacent edge labels (the degree is that
    multiset's size)."""
    cnt = np.zeros((g.n, n_elabels), np.int64)
    if g.m:
        np.add.at(cnt, (g.edges[:, 0], g.elabels), 1)
        np.add.at(cnt, (g.edges[:, 1], g.elabels), 1)
    key = g.vlabels.astype(np.int64)
    for j in range(n_elabels):
        key = key * _R + cnt[:, j]
    return key


class ReferenceIndex:
    """Per-graph features for the reference filter, built from the data."""

    def __init__(self, graphs: Sequence[PlainGraph], n_vlabels: int,
                 n_elabels: int):
        self.graphs = list(graphs)
        self.n_vlabels, self.n_elabels = int(n_vlabels), int(n_elabels)
        B = len(self.graphs)
        self.nv = np.array([g.n for g in self.graphs], np.int64)
        self.ne = np.array([g.m for g in self.graphs], np.int64)
        self.vmax = int(self.nv.max(initial=1))
        voff = np.concatenate([[0], np.cumsum(self.nv)])
        gid_v = np.repeat(np.arange(B), self.nv)
        gid_e = np.repeat(np.arange(B), self.ne)
        vl = np.concatenate([g.vlabels for g in self.graphs]).astype(np.int64)
        el = np.concatenate([g.elabels for g in self.graphs]
                            + [np.zeros(0, np.int32)]).astype(np.int64)
        ends = (np.concatenate([g.edges for g in self.graphs]
                               + [np.zeros((0, 2), np.int32)])
                .astype(np.int64) + voff[gid_e][:, None])
        self.vhist = np.zeros((B, self.n_vlabels), np.int64)
        np.add.at(self.vhist, (gid_v, vl), 1)
        self.ehist = np.zeros((B, self.n_elabels), np.int64)
        np.add.at(self.ehist, (gid_e, el), 1)
        # per vertex: adjacent edge-label counts -> degree-q-gram key, degree
        cnt = np.zeros((len(vl), self.n_elabels), np.int64)
        np.add.at(cnt, (ends[:, 0], el), 1)
        np.add.at(cnt, (ends[:, 1], el), 1)
        key = vl.copy()
        for j in range(self.n_elabels):
            key = key * _R + cnt[:, j]
        self.vkey, self.vgid = key, gid_v
        # degree sequences, non-increasing, zero-padded
        deg = cnt.sum(axis=1)
        order = np.lexsort((-deg, gid_v))
        rank = np.arange(len(vl)) - voff[gid_v]
        self.degseq = np.zeros((B, self.vmax), np.int64)
        self.degseq[gid_v, rank] = deg[order]

    def bounds(self, h: PlainGraph) -> np.ndarray:
        """Combined lower bound on ged(g, h) for every database graph g."""
        qn, qm = h.n, h.m
        qv = np.bincount(h.vlabels, minlength=self.n_vlabels)
        qe = np.bincount(h.elabels, minlength=self.n_elabels)
        ov = np.minimum(self.vhist, qv[None, :]).sum(axis=1)
        oe = np.minimum(self.ehist, qe[None, :]).sum(axis=1)
        max_v = np.maximum(self.nv, qn)
        max_e = np.maximum(self.ne, qm)
        number = np.abs(self.nv - qn) + np.abs(self.ne - qm)
        label = max_v - ov + max_e - oe
        # degree-based q-grams: |D(g) ∩ D(h)| as a multiset intersection
        qk, qc = np.unique(degree_qgram_keys(h, self.n_elabels),
                           return_counts=True)
        pos = np.searchsorted(qk, self.vkey)
        pos = np.minimum(pos, len(qk) - 1)
        hit = qk[pos] == self.vkey
        per = np.zeros((len(self.graphs), len(qk)), np.int64)
        np.add.at(per, (self.vgid[hit], pos[hit]), 1)
        c_d = np.minimum(per, qc[None, :]).sum(axis=1)
        num = 2 * max_v - ov - c_d
        degree_q = np.maximum(0, -(-num // 2))
        # degree sequence (Lemma 5)
        width = max(self.vmax, qn)
        ds = np.pad(self.degseq, [(0, 0), (0, width - self.vmax)])
        sh = np.zeros(width, np.int64)
        sh[:qn] = np.sort(_degrees(h))[::-1]
        diff = ds - sh[None, :]
        s1 = np.maximum(diff, 0).sum(axis=1)
        s2 = np.maximum(-diff, 0).sum(axis=1)
        case1 = -(-s1 // 2) + -(-s2 // 2)
        case2 = np.maximum(
            qm + self.ne - np.minimum(ds, sh[None, :]).sum(axis=1), 0)
        lam = np.where(qn <= self.nv, case1, case2)
        degseq = max_v - ov + lam
        return np.maximum.reduce([number, label, degree_q, degseq])

    def candidates(self, h: PlainGraph, tau: int) -> List[int]:
        return [int(g) for g in np.flatnonzero(self.bounds(h) <= tau)]

    def answer(self, h: PlainGraph, tau: int, ged=None
               ) -> Tuple[List[int], List[Tuple[int, int]]]:
        """(candidates, sorted matches as (graph id, distance))."""
        ged = exact_ged if ged is None else ged
        cand = self.candidates(h, tau)
        matches = []
        for gid in cand:
            d = ged(h, self.graphs[gid], tau)
            if d is not None:
                matches.append((gid, d))
        return cand, sorted(matches)


# ---------------------------------------------------------------------------
# graph edit distance
# ---------------------------------------------------------------------------

class _Pair:
    """Dense adjacency of both graphs and the query's vertex order."""

    def __init__(self, q: PlainGraph, g: PlainGraph):
        self.q, self.g = q, g
        self.aq = np.full((q.n, q.n), -1, np.int64)
        self.ag = np.full((g.n, g.n), -1, np.int64)
        for a, e, el in ((self.aq, q.edges, q.elabels),
                         (self.ag, g.edges, g.elabels)):
            if len(e):
                a[e[:, 0], e[:, 1]] = el
                a[e[:, 1], e[:, 0]] = el
        self.aq_l = self.aq.tolist()
        self.ag_l = self.ag.tolist()
        self.vq = q.vlabels.tolist()
        self.vg = g.vlabels.tolist()
        self.order = self._order()

    def _order(self) -> List[int]:
        """Breadth-first from the highest-degree vertex, so that each new
        vertex closes edges to vertices already mapped."""
        n = self.q.n
        deg = (self.aq >= 0).sum(axis=1)
        seen, order = set(), []
        while len(order) < n:
            root = max((v for v in range(n) if v not in seen),
                       key=lambda v: (deg[v], -v))
            seen.add(root)
            frontier = [root]
            while frontier:
                v = frontier.pop(0)
                order.append(v)
                nb = sorted((w for w in range(n)
                             if self.aq_l[v][w] >= 0 and w not in seen),
                            key=lambda w: (-deg[w], w))
                for w in nb:
                    seen.add(w)
                    frontier.append(w)
        return order

    def rest_bound(self, k: int, used: List[bool]) -> int:
        """Lower bound on the cost of everything not yet costed after the
        first k query vertices are mapped (``used`` marks images)."""
        rest_q = self.order[k:]
        rest_g = [a for a in range(self.g.n) if not used[a]]
        lq: Dict[int, int] = {}
        for v in rest_q:
            lq[self.vq[v]] = lq.get(self.vq[v], 0) + 1
        common = 0
        for a in rest_g:
            c = lq.get(self.vg[a], 0)
            if c:
                lq[self.vg[a]] = c - 1
                common += 1
        vb = max(len(rest_q), len(rest_g)) - common
        in_rest = [False] * self.q.n
        for v in rest_q:
            in_rest[v] = True
        eq: Dict[int, int] = {}
        n_eq = 0
        for u, w in self.q.edges.tolist():
            if in_rest[u] or in_rest[w]:
                lab = self.aq_l[u][w]
                eq[lab] = eq.get(lab, 0) + 1
                n_eq += 1
        n_eg = common_e = 0
        for a, b in self.g.edges.tolist():
            if not used[a] or not used[b]:
                n_eg += 1
                lab = self.ag_l[a][b]
                c = eq.get(lab, 0)
                if c:
                    eq[lab] = c - 1
                    common_e += 1
        return vb + max(n_eq, n_eg) - common_e

    def step_cost(self, k: int, img: List[int], a: int) -> int:
        """Cost of mapping the k-th query vertex to ``a`` (-1: delete it),
        with the edges it closes to the vertices mapped before it."""
        v = self.order[k]
        c = 1 if a < 0 else int(self.vq[v] != self.vg[a])
        row_q = self.aq_l[v]
        row_g = self.ag_l[a] if a >= 0 else None
        for j in range(k):
            w = self.order[j]
            b = img[j]
            lq = row_q[w]
            lg = row_g[b] if (row_g is not None and b >= 0) else -1
            if lq != lg:
                c += 1
        return c

    def final_cost(self, used: List[bool]) -> int:
        """Insert every database vertex left unmapped, and each database
        edge that touches one."""
        c = sum(1 for a in range(self.g.n) if not used[a])
        for a, b in self.g.edges.tolist():
            if not used[a] or not used[b]:
                c += 1
        return c


def exact_ged(q: PlainGraph, g: PlainGraph, tau: int) -> Optional[int]:
    """The exact edit distance if it is at most ``tau``, else None."""
    p = _Pair(q, g)
    n = q.n
    best = [tau + 1]
    img = [0] * n
    used = [False] * g.n

    def dfs(k: int, cost: int) -> None:
        if k == n:
            total = cost + p.final_cost(used)
            if total < best[0]:
                best[0] = total
            return
        children = []
        for a in list(range(g.n)) + [-1]:
            if a >= 0 and used[a]:
                continue
            c = cost + p.step_cost(k, img, a)
            if c < best[0]:
                children.append((c, a))
        children.sort()
        for c, a in children:
            if c >= best[0]:
                break
            img[k] = a
            if a >= 0:
                used[a] = True
            if c + p.rest_bound(k + 1, used) < best[0]:
                dfs(k + 1, c)
            if a >= 0:
                used[a] = False

    if p.rest_bound(0, used) <= tau:
        dfs(0, 0)
    return best[0] if best[0] <= tau else None


def greedy_ged(q: PlainGraph, g: PlainGraph, tau: int) -> Optional[int]:
    """The control: one descent of the same search, taking at each query
    vertex the cheapest image by step cost plus the bound on the rest.
    Its cost is an upper bound on the distance, not the distance."""
    p = _Pair(q, g)
    img = [0] * q.n
    used = [False] * g.n
    cost = 0
    for k in range(q.n):
        best = None
        for a in list(range(g.n)) + [-1]:
            if a >= 0 and used[a]:
                continue
            c = cost + p.step_cost(k, img, a)
            if a >= 0:
                used[a] = True
            f = c + p.rest_bound(k + 1, used)
            if a >= 0:
                used[a] = False
            if best is None or (f, c) < best[:2]:
                best = (f, c, a)
        _, cost, img[k] = best
        if img[k] >= 0:
            used[img[k]] = True
    total = cost + p.final_cost(used)
    return total if total <= tau else None
