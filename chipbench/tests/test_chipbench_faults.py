"""A whole run on the CPU at a tiny size, past the harness's look for a
chip, with the served path broken underneath: ``correct`` has to come out
false for each fault a range-query cell can have, and true without one."""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import bench  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny_run(trace=False):
    spec = bench.load_spec()
    cell, cfg, mix = bench.find_cell(spec, "aids.range.closed")
    cfg = dict(cfg, num_graphs=300,
               serving=dict(cfg["serving"], backend="jax"))
    mix = dict(mix, clients=4, pool_qps=16.0, warmup_s=0.3, warmup_windows=1)
    counter = bench.CompileCounter().install()
    return bench.run_cell(spec, cell, cfg, mix, 4_000_000_007, 1.5, trace,
                          time.perf_counter(), CPU, counter, sample_k=18)


def patch_assemble(monkeypatch, alter):
    from repro.serve.graph_engine import GraphQueryEngine
    real = GraphQueryEngine._assemble

    def assemble(*a, **kw):
        res = real(*a, **kw)
        alter(res)
        return res
    monkeypatch.setattr(GraphQueryEngine, "_assemble",
                        staticmethod(assemble))


def test_a_sound_run_is_correct_and_reports_its_metrics():
    out = tiny_run(trace=True)
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 4
    for name in ("queue_ms.closed", "filter_ms.closed", "verify_ms.closed"):
        assert out["metrics"][name]["value"] >= 0
    out = tiny_run(trace=False)
    assert out["correct"] is True
    assert set(out["metrics"]) >= {"throughput_qps", "setup_s"}


def _wrong_candidate(res):
    res.candidates = sorted(set(res.candidates) | {0, 1, 2})


def _wrong_distance(res):
    res.matches = [(g, d + 1) for g, d in res.matches]


def _missing_match(res):
    res.matches = res.matches[1:]


def _partial(res):
    res.stats["partial"] = 1


@pytest.mark.parametrize("alter,number", [
    (_wrong_candidate, "wrong_candidates"),
    (_wrong_distance, "wrong_matches"),
    (_missing_match, "wrong_matches"),
    (_partial, "failed_queries"),
])
def test_an_answer_altered_where_it_is_produced_fails(monkeypatch, alter,
                                                      number):
    patch_assemble(monkeypatch, alter)
    out = tiny_run()
    assert out["correct"] is False
    assert out["checks"][number]["value"] > out["checks"][number]["limit"]


def test_half_the_queries_left_out_fails(monkeypatch):
    """Every other query is dropped on admission and never answered: the
    clients that sent them wait, and the check counts each as failed."""
    from repro.serve import pipeline
    real = pipeline.AsyncGraphQueryEngine.submit
    calls = []

    def submit(self, request):
        calls.append(1)
        if len(calls) % 2:
            return pipeline.QueryTicket(request)       # never resolved
        return real(self, request)
    monkeypatch.setattr(pipeline.AsyncGraphQueryEngine, "submit", submit)
    monkeypatch.setattr(bench, "ANSWER_WAIT_S", 0.5)
    out = tiny_run()
    assert out["correct"] is False
    assert out["failed"] >= 2
