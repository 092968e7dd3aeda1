"""GraphGen-style synthetic graphs (the S-series of MSQ-Index Table 1).

Copied from the program's ``graphs/generators.py`` (``graphgen_db``); the
same arguments give the same graphs.
"""
from __future__ import annotations

from typing import List

import numpy as np

from data import PlainGraph, random_graph


def graphgen_db(num_graphs: int, num_edges: int = 30, density: float = 0.5,
                n_vlabels: int = 5, n_elabels: int = 2, seed: int = 0
                ) -> List[PlainGraph]:
    """GraphGen-style graphs, e.g. S100K.E30.D50.L5; |V| from the density
    rho = 2|E| / (|V|(|V|-1))."""
    rng = np.random.default_rng(seed)
    n_target = (1.0 + np.sqrt(1.0 + 8.0 * num_edges / density)) / 2.0
    graphs = []
    for _ in range(num_graphs):
        n = int(np.clip(round(rng.normal(n_target, 0.75)), 3, 64))
        graphs.append(random_graph(rng, n, num_edges, n_vlabels, n_elabels,
                                   connected=False))
    return graphs


def build(cfg: dict) -> List[PlainGraph]:
    d = cfg["data"]
    return graphgen_db(cfg["num_graphs"], num_edges=d["num_edges"],
                       density=d["density"], n_vlabels=cfg["n_vlabels"],
                       n_elabels=cfg["n_elabels"], seed=d["seed"])
