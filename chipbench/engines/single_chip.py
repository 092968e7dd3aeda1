"""The program served on one chip: ``FlatMSQIndex`` over the whole
database, a ``GraphQueryEngine`` and an ``AsyncGraphQueryEngine`` over
it, each given the values the configuration's ``serving`` block states
(everything else is the constructors' defaults).
"""


def to_program_graph(g):
    from repro.graphs.graph import Graph
    return Graph(g.n, g.vlabels, g.edges, g.elabels)


def build_index(plain_db, cfg: dict):
    from repro.core.search import FlatMSQIndex
    from repro.graphs.graph import GraphDB
    db = GraphDB([to_program_graph(g) for g in plain_db], cfg["n_vlabels"],
                 cfg["n_elabels"])
    return FlatMSQIndex(db, l=cfg["index"]["subregion_l"])


def engine(index, cfg: dict, spans: bool = False):
    from repro.obs import Observability
    from repro.serve import GraphQueryEngine
    s = cfg["serving"]
    eng = GraphQueryEngine(
        index, backend=s["backend"], slab_layout=s["slab_layout"],
        assign_lb=s["assign_lb"], lb_hungarian=s["lb_hungarian"],
        result_cache_size=s["result_cache_size"],
        obs=Observability(spans=spans))
    if eng.backend != s["device_backend"]:
        raise RuntimeError(f"backend {s['backend']!r} resolved to "
                           f"{eng.backend!r}, not {s['device_backend']!r}")
    return eng


def pipeline(eng, cfg: dict):
    from repro.serve import AsyncGraphQueryEngine
    s = cfg["serving"]
    return AsyncGraphQueryEngine(eng, num_workers=s["num_workers"],
                                 verify_executor=s["verify_executor"])


def evaluator(index, eng):
    """The filter evaluator the engine's filter stage runs on (one per
    index and settings, shared by every engine over the index)."""
    return index.filter_eval(eng.backend, slab=eng.slab_layout,
                             assign_lb=eng.assign_lb,
                             lb_hungarian=eng.lb_hungarian)


def forget_device_state(index, eng) -> None:
    """Drop what earlier engines left on the device (the evaluator's
    per-bucket slab cache), so a window starts as a fresh server would."""
    evaluator(index, eng).device_cache.invalidate()
