"""Traffic: one general generator driven by a mix file, and the log of a
window's queries.

A mix file (``chipbench/workloads/<traffic>.json``) holds parameters
only.  It names the pieces that read them, each a module found by name
(``registry.py``):

* ``loop``  ``loops/<loop>.py``: how many queries a window draws, their
  due times, and how they are sent (open loop at due times, or closed
  loop with a fixed number of clients);
* ``bases`` ``bases/<bases>.py``: which database graphs the queries are
  made from;
* ``kind``  ``queries/<kind>.py``: what a query asks (range-tau), the
  radii and edits it is drawn with, and how its answer is judged.

For a seed the generator fixes the amounts (the count, the gaps, the
spread of sizes, the shares of radii and edits) and lets the seed choose
the order and the graphs.  A mix that states ``pool_seed`` draws its
queries from that seed instead, a fixed query set as the field's papers
use, in a fixed cyclic order, and lets the run's seed choose only where
in that cycle the window starts: where a few queries cost as much as the
rest together, as exact edit distance allows, only a fixed set gives
every seed the same amount of work, and only a fixed cycle gives every
seed the same neighbours in flight beside each query, so that the seed
moves no more than the part-cycle the window ends in.

Adapted from the program's ``serve/traffic.py``, with its timing fixed:
latency runs from the due time, not from when the generator got round to
sending, so a stalled generator shows up as latency and as lateness.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field, replace
from typing import List, Optional, Sequence

import numpy as np

import registry
from data import PlainGraph, perturb_graph

WINDOW_STREAM, SAMPLE_STREAM, WARM_STREAM, ORDER_STREAM = 0, 1, 2, 3


@dataclass(frozen=True)
class Arrival:
    t: float                       # due time, seconds from the window start
    base: int                      # database graph the query perturbs
    edits: int
    qseed: int
    tau: int


def pieces(mix: dict):
    """(loop, bases, kind) modules the mix names; refuses traffic this
    benchmark does not generate."""
    if mix.get("repeats") or mix.get("deadline_s") is not None:
        raise ValueError("traffic with repeats or deadlines is not "
                         "generated: answers have to be exact")
    try:
        return (registry.load("loops", mix["loop"]),
                registry.load("bases", mix["bases"]),
                registry.load("queries", mix["kind"]))
    except (KeyError, registry.Missing) as e:
        raise ValueError(f"traffic not generated here: {e}") from None


def schedule(mix: dict, db_order: Sequence[int], seconds: float,
             seed: int) -> List[Arrival]:
    """The window's queries for one seed over a database whose ids in
    (|V|, |E|) order are ``db_order`` (``size_order``)."""
    loop, bases, kind = pieces(mix)
    pool_seed = mix.get("pool_seed")
    rng = np.random.default_rng(
        [int(seed if pool_seed is None else pool_seed), WINDOW_STREAM])
    n = loop.count(mix, seconds)
    times = loop.times(mix, n, seconds, rng)
    ids = bases.draw(n, db_order, rng)
    taus, edits = kind.radii(mix, n, rng)
    slot = rng.permutation(n)
    qseeds = rng.integers(0, 2 ** 63 - 1, size=n, dtype=np.int64)
    out = [Arrival(float(t), int(b), int(e), int(s), int(tau))
           for t, b, e, s, tau in zip(times, ids[slot], edits[slot],
                                      qseeds, taus[slot])]
    if pool_seed is not None:
        # the run's seed picks where the fixed cycle starts; the due times
        # stay in place
        start = int(np.random.default_rng([int(seed), ORDER_STREAM])
                    .integers(n))
        out = [replace(out[(k + start) % n], t=out[k].t) for k in range(n)]
    return out


def size_order(db: Sequence[PlainGraph]) -> np.ndarray:
    """Database ids in (|V|, |E|, id) order: the strata's order."""
    nv = np.array([g.n for g in db])
    ne = np.array([g.m for g in db])
    return np.lexsort((np.arange(len(db)), ne, nv))


def materialise(arrivals: Sequence[Arrival], db: Sequence[PlainGraph],
                n_vlabels: int, n_elabels: int) -> List[PlainGraph]:
    return [perturb_graph(db[a.base], a.edits,
                          np.random.default_rng(a.qseed), n_vlabels,
                          n_elabels) for a in arrivals]


def check_sample(n_queries: int, k: int, seed: int,
                 must: Sequence[int] = ()) -> List[int]:
    """Indices of the queries the reference checks: ``k`` drawn from the
    seed, plus ``must`` (the heaviest ones)."""
    rng = np.random.default_rng([int(seed), SAMPLE_STREAM])
    pick = rng.choice(n_queries, size=min(k, n_queries), replace=False)
    return sorted(set(int(i) for i in pick) | set(int(i) for i in must))


@dataclass
class ReplayLog:
    """What a loop saw, per query sent (host perf_counter seconds).
    ``index[k]`` is the arrival the k-th query sent was made from."""
    t0: float = 0.0                # window start
    seconds: float = 0.0
    due: List[float] = field(default_factory=list)
    issued: List[float] = field(default_factory=list)
    done: List[Optional[float]] = field(default_factory=list)
    index: List[int] = field(default_factory=list)
    tickets: List = field(default_factory=list)
    lock: threading.Lock = field(default_factory=threading.Lock)

    def stamp(self, k: int, clock):
        """A done-callback that records when the k-th query was answered."""
        def cb(_res) -> None:
            t = clock()
            with self.lock:
                self.done[k] = t
        return cb

    def latencies(self) -> List[Optional[float]]:
        """Due time to answer, seconds; None where no answer came."""
        return [None if d is None else d - u
                for u, d in zip(self.due, self.done)]

    def lateness(self) -> List[float]:
        """How late each query was sent, seconds."""
        return [i - u for u, i in zip(self.due, self.issued)]

    def completed_by(self, t: float) -> int:
        return sum(1 for d in self.done if d is not None and d <= t)


def percentile(xs: Sequence[float], p: float) -> float:
    """Nearest-rank percentile over every value (no interpolation)."""
    s = sorted(xs)
    if not s:
        raise ValueError("percentile of nothing")
    i = min(len(s) - 1, max(0, int(np.ceil(p / 100.0 * len(s))) - 1))
    return float(s[i])
