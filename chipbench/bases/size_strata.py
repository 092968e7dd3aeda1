"""Bases drawn one from each of ``n`` equal size strata: the database in
(|V|, |E|, id) order cut into ``n`` runs of equal length, and one graph
drawn uniformly from each.  Every seed gets the same spread of sizes, and
no base twice."""
import numpy as np


def draw(n: int, db_order: np.ndarray, rng: np.random.Generator
         ) -> np.ndarray:
    """Database ids, the k-th from the k-th stratum."""
    db_order = np.asarray(db_order, np.int64)
    if n > len(db_order):
        raise ValueError(f"{n} distinct bases asked of {len(db_order)} "
                         "graphs")
    bounds = np.linspace(0, len(db_order), n + 1).astype(np.int64)
    picks = bounds[:-1] + (rng.random(n) * np.diff(bounds)).astype(np.int64)
    return db_order[picks]
