"""The benchmark's own plain graphs, random graphs and query edits.

Copied from the program's ``graphs/generators.py`` so that a later change
to the program cannot change the data a cell runs on; the database
generators built on them live in ``generators/<name>.py``, one file each,
named by the configuration.  For the same arguments the copies emit the
same graphs as the originals: the random draws happen in the same order,
and ``PlainGraph`` normalises its edge list exactly as the program's
``Graph`` does (endpoints ordered, rows sorted), which the perturbation's
draws depend on.

Nothing here imports the program: the reference and the traffic generator
build on these plain graphs, and the harness converts them once.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import numpy as np


class PlainGraph(NamedTuple):
    """A labelled simple undirected graph: ``edges`` is (m, 2) int32 with
    ``u < v``, rows sorted lexicographically; ``elabels`` aligned."""
    n: int
    vlabels: np.ndarray
    edges: np.ndarray
    elabels: np.ndarray

    @property
    def m(self) -> int:
        return int(self.edges.shape[0])


def make_graph(n: int, vlabels, edges, elabels) -> PlainGraph:
    vl = np.asarray(vlabels, np.int32).reshape(-1)
    e = np.asarray(edges, np.int32).reshape(-1, 2)
    el = np.asarray(elabels, np.int32).reshape(-1)
    if vl.shape[0] != n or e.shape[0] != el.shape[0]:
        raise ValueError("inconsistent graph arrays")
    if e.size:
        lo = np.minimum(e[:, 0], e[:, 1])
        hi = np.maximum(e[:, 0], e[:, 1])
        order = np.lexsort((hi, lo))
        e = np.stack([lo, hi], axis=1)[order]
        el = el[order]
    return PlainGraph(int(n), vl, e, el)


def _zipf_probs(k: int, s: float = 1.3) -> np.ndarray:
    w = 1.0 / np.arange(1, k + 1) ** s
    return w / w.sum()


def random_graph(rng: np.random.Generator, n: int, m: int, n_vlabels: int,
                 n_elabels: int, vlabel_probs: Optional[np.ndarray] = None,
                 elabel_probs: Optional[np.ndarray] = None,
                 connected: bool = True,
                 max_degree: Optional[int] = None) -> PlainGraph:
    n = max(int(n), 1)
    max_m = n * (n - 1) // 2
    if max_degree is not None:
        max_m = min(max_m, n * max_degree // 2)
    m = int(min(max(m, 0), max_m))
    vlabels = rng.choice(n_vlabels, size=n, p=vlabel_probs).astype(np.int32)
    chosen: set = set()
    edges: List[Tuple[int, int]] = []
    deg = np.zeros(n, np.int32)

    def can(u: int, v: int) -> bool:
        if max_degree is None:
            return True
        return deg[u] < max_degree and deg[v] < max_degree

    if connected and n > 1 and m >= n - 1:
        perm = rng.permutation(n)
        for i in range(1, n):
            u = int(perm[i])
            for _try in range(16):
                v = int(perm[rng.integers(0, i)])
                if can(u, v):
                    break
            a, b = (u, v) if u < v else (v, u)
            if (a, b) in chosen:
                continue
            chosen.add((a, b))
            edges.append((a, b))
            deg[u] += 1
            deg[v] += 1
    tries = 0
    while len(edges) < m and tries < 50 * m + 100:
        tries += 1
        u = int(rng.integers(0, n))
        v = int(rng.integers(0, n))
        if u == v or not can(u, v):
            continue
        a, b = (u, v) if u < v else (v, u)
        if (a, b) in chosen:
            continue
        chosen.add((a, b))
        edges.append((a, b))
        deg[u] += 1
        deg[v] += 1
    e = np.array(edges, np.int32).reshape(-1, 2)
    el = rng.choice(n_elabels, size=len(edges), p=elabel_probs).astype(np.int32)
    return make_graph(n, vlabels, e, el)


def perturb_graph(g: PlainGraph, k: int, rng: np.random.Generator,
                  n_vlabels: int, n_elabels: int) -> PlainGraph:
    """``k`` random primitive edit operations applied to ``g``: the result
    lies within edit distance ``k`` of it."""
    n = g.n
    vlabels = g.vlabels.copy().tolist()
    edict = {(int(u), int(v)): int(l) for (u, v), l in zip(g.edges, g.elabels)}
    for _ in range(k):
        ops = ["vsub", "esub", "eins", "edel", "vins", "vdel"]
        rng.shuffle(ops)
        for op in ops:
            if op == "vsub" and n > 0:
                v = int(rng.integers(0, n))
                new = int(rng.integers(0, n_vlabels))
                if new != vlabels[v]:
                    vlabels[v] = new
                    break
            elif op == "esub" and edict:
                key = list(edict)[int(rng.integers(0, len(edict)))]
                new = int(rng.integers(0, n_elabels))
                if new != edict[key]:
                    edict[key] = new
                    break
            elif op == "eins" and n >= 2:
                for _try in range(10):
                    u = int(rng.integers(0, n)); v = int(rng.integers(0, n))
                    if u == v:
                        continue
                    a, b = (u, v) if u < v else (v, u)
                    if (a, b) not in edict:
                        edict[(a, b)] = int(rng.integers(0, n_elabels))
                        break
                else:
                    continue
                break
            elif op == "edel" and edict:
                key = list(edict)[int(rng.integers(0, len(edict)))]
                del edict[key]
                break
            elif op == "vins":
                vlabels.append(int(rng.integers(0, n_vlabels)))
                n += 1
                break
            elif op == "vdel" and n > 1:
                deg = np.zeros(n, np.int64)
                for (a, b) in edict:
                    deg[a] += 1
                    deg[b] += 1
                iso = np.flatnonzero(deg == 0)
                if len(iso) == 0:
                    continue
                v = int(iso[int(rng.integers(0, len(iso)))])
                vlabels.pop(v)
                remap = {}
                for old in range(n):
                    if old == v:
                        continue
                    remap[old] = old - (1 if old > v else 0)
                edict = {(remap[a], remap[b]): l for (a, b), l in edict.items()}
                n -= 1
                break
    edges = np.array(sorted(edict), np.int32).reshape(-1, 2)
    elabels = np.array([edict[tuple(e)] for e in edges], np.int32)
    return make_graph(n, np.array(vlabels, np.int32), edges, elabels)


def generator(name: str):
    """The database generator ``generators/<name>.py``."""
    import registry
    return registry.load("generators", name)


def build_database(cfg: dict) -> List[PlainGraph]:
    """The deployment's database, from the generator and the data seed
    that the configuration names."""
    return generator(cfg["data"]["generator"]).build(cfg)
