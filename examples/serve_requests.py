"""Batched LM serving with the MSQ-Index as a retrieval pre-filter
(DESIGN.md §6c), now through the async pipelined engine (DESIGN.md §12):
each request carries a molecule graph; ``AsyncGraphQueryEngine`` forms
dynamic batches, runs the bucketed device filter pass while its verifier
pool drains earlier queries' GED worklists, and streams matches out
cheapest-first; retrieved ids condition the prompt; the LM decodes
batched.

    PYTHONPATH=src python examples/serve_requests.py
"""
import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced
from repro.core.search import MSQIndex
from repro.graphs.generators import aids_like_db, perturb_graph
from repro.models import build_params
from repro.obs import Observability
from repro.serve import (AsyncGraphQueryEngine, GraphQuery,
                         GraphQueryEngine, Request, ServeEngine,
                         as_completed)


def main() -> None:
    enable_compile_cache()
    # retrieval side: molecule database + index + pipelined query engine,
    # with per-query span recording on (DESIGN.md §17)
    db = aids_like_db(1000, seed=2)
    index = MSQIndex(db)
    retriever = GraphQueryEngine(index, obs=Observability(spans=True))

    # serving side: small LM
    cfg = reduced(get_config("granite-moe-1b-a400m"))
    params = build_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, batch_size=4, max_len=64)

    rng = np.random.default_rng(0)
    mols = [perturb_graph(db[int(rng.integers(0, len(db)))], 2, rng,
                          db.n_vlabels, db.n_elabels) for _ in range(8)]
    with AsyncGraphQueryEngine(retriever, max_batch=4, max_delay_s=0.002,
                               num_workers=2) as apipe:
        # one verified request streams its matches as A* confirms them,
        # while the filter passes for the rest are still pipelining
        probe = apipe.submit(GraphQuery(mols[0], 1, verify=True))
        tickets = apipe.submit_many([GraphQuery(m, 3, verify=False)
                                     for m in mols])
        for gid, d in probe.stream(timeout=120):
            print(f"probe: streamed match graph {gid} at ged {d}")
        print(f"probe: {len(probe.result().candidates)} candidates, "
              f"{len(probe.result().matches)} matches "
              f"(stats {probe.result().stats})")
        retrieved = [None] * len(tickets)
        for i, res in as_completed(tickets, timeout=120):
            retrieved[i] = res        # arrive as their worklists finish
    requests = []
    for i, res in enumerate(retrieved):
        neighbours = res.candidates[:4]
        # prompt = [BOS=1] + retrieved neighbour ids folded into vocab
        prompt = np.array([1] + [2 + (g % (cfg.vocab_size - 2))
                                 for g in neighbours], np.int32)
        requests.append(Request(prompt=prompt, max_new_tokens=8))
        print(f"req{i}: |candidates|={len(res.candidates)} "
              f"-> prompt {prompt.tolist()}")
    print(f"retrieval: {retriever.stats['filter_s']:.3f}s filter for "
          f"{retriever.stats['queries']} queries "
          f"(backend={retriever.backend})")
    # per-stage breakdown from the recorded spans (DESIGN.md §17)
    print("stage breakdown (spans):")
    print(f"  {'stage':<14} {'count':>6} {'total_ms':>9}")
    for name, (count, total_s) in sorted(
            retriever.obs.spans.aggregate().items(),
            key=lambda kv: -kv[1][1]):
        print(f"  {name:<14} {count:>6} {total_s * 1e3:>9.2f}")
    engine.run(requests)
    for i, r in enumerate(requests):
        print(f"req{i}: generated {r.out_tokens}")
    print(f"prefill {engine.stats['prefill_s']:.2f}s, "
          f"decode {engine.stats['decode_s']:.2f}s, "
          f"{engine.stats['tokens']} tokens")


if __name__ == "__main__":
    main()
