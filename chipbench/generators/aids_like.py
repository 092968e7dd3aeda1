"""Molecule-like graphs matched to AIDS (MSQ-Index Table 1).

Copied from the program's ``graphs/generators.py`` (``aids_like_db``);
the same arguments give the same graphs.  Each base graph is stored with
``family_size - 1`` copies 1-4 edits away, so a query made from one of
them finds its near-duplicates among the candidates.
"""
from __future__ import annotations

from typing import List

import numpy as np

from data import PlainGraph, _zipf_probs, perturb_graph, random_graph


def aids_like_db(num_graphs: int, seed: int = 0, mean_v: float = 25.6,
                 std_v: float = 8.0, n_vlabels: int = 62,
                 n_elabels: int = 3, family_size: int = 4
                 ) -> List[PlainGraph]:
    """Molecule-like graphs matched to AIDS (MSQ-Index Table 1)."""
    rng = np.random.default_rng(seed)
    vprobs = _zipf_probs(n_vlabels, 1.6)
    eprobs = np.array([0.85, 0.13, 0.02])[:n_elabels]
    eprobs = eprobs / eprobs.sum()
    graphs: List[PlainGraph] = []
    while len(graphs) < num_graphs:
        n = int(np.clip(round(rng.normal(mean_v, std_v)), 4, 64))
        extra = rng.binomial(max(n // 6, 1), 0.55)
        m = (n - 1) + extra
        base = random_graph(rng, n, m, n_vlabels, n_elabels, vprobs,
                            eprobs, max_degree=4)
        graphs.append(base)
        for _ in range(min(family_size - 1, num_graphs - len(graphs))):
            k = int(rng.integers(1, 5))
            graphs.append(perturb_graph(base, k, rng, n_vlabels, n_elabels))
    perm = rng.permutation(len(graphs))
    return [graphs[i] for i in perm]


def build(cfg: dict) -> List[PlainGraph]:
    d = cfg["data"]
    return aids_like_db(cfg["num_graphs"], seed=d["seed"], mean_v=d["mean_v"],
                        std_v=d["std_v"], n_vlabels=cfg["n_vlabels"],
                        n_elabels=cfg["n_elabels"],
                        family_size=d["family_size"])
