"""Range-tau queries: every database graph within edit distance tau of
the query graph, with that distance.

A mix of this kind states ``tau`` (the radii) and ``edits`` (``[lo, hi]``
random edits turn a base graph into a query).  Every (tau, edits) pair
comes in equal shares, dealt evenly over the size strata of the bases.
"""
import numpy as np


def radii(mix: dict, n: int, rng: np.random.Generator):
    """(taus, edits) for ``n`` queries, aligned with the bases' strata:
    each run of consecutive strata gets every pair once, in a seeded
    order."""
    lo, hi = mix["edits"]
    pairs = [(int(t), e) for t in mix["tau"] for e in range(lo, hi + 1)]
    combo = np.concatenate([rng.permutation(len(pairs))
                            for _ in range(-(-n // len(pairs)))])[:n]
    return (np.array([pairs[c][0] for c in combo]),
            np.array([pairs[c][1] for c in combo]))


def request(graph, arrival, verify: bool = True):
    """The program's request for one query."""
    from repro.serve import GraphQuery
    return GraphQuery(graph, int(arrival.tau), verify=verify)


def expected(ref, qplain, arrival, ged=None):
    """The reference's (candidates, matches) for one query."""
    return ref.answer(qplain, int(arrival.tau), ged=ged)


def compare(res, exp) -> tuple:
    """(candidates differ, matches or distances differ), each 0 or 1; an
    answer that never came differs in both."""
    if res is None:
        return 1, 1
    cand, matches = exp
    got_c = [int(g) for g in res.candidates]
    got_m = sorted((int(g), int(d)) for g, d in res.matches)
    return int(got_c != list(cand)), int(got_m != list(matches))
