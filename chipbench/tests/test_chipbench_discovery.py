"""Discovery by name, and BENCHMARK.json against the benchmark contract."""
import json
import os
import re
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import pytest  # noqa: E402

import bench  # noqa: E402
import data  # noqa: E402
import registry  # noqa: E402
import traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_in_benchmark_json_has_its_file():
    s = spec()
    for w in s["workloads"]:
        cell, cfg, mix = bench.find_cell(s, w["name"])
        assert cfg["name"] == w["config"]
        loop, bases, kind = traffic.pieces(mix)
        assert callable(loop.drive) and callable(bases.draw)
        assert callable(kind.expected) and callable(kind.compare)
        assert callable(data.generator(cfg["data"]["generator"]).build)
        assert callable(bench.serving(cfg).pipeline)
    for m in s["end_to_end"] + s["per_layer"]:
        assert callable(bench.load_reader(m["name"]))
    for c in s["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] == []
        assert cfg["source"] == c["source"]


def test_benchmark_json_keeps_to_the_contract():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert s["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= s["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in s[k]]
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in s["end_to_end"]}
    assert "setup_s" in e2e
    for m in s["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in s["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["layer"], []).append(m["name"])
    cells = {w["name"] for w in s["workloads"]}
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert bench.cell_metrics(s, w["name"], trace=True)
    for m in s["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def copy_bench(tmp_path):
    bdir = tmp_path / "chipbench"
    shutil.copytree(BENCH, bdir, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    return bdir


def test_a_new_cell_and_a_new_metric_are_found_by_name(tmp_path):
    """A later change adds files and entries only: the harness finds them
    without an edit to any file it already has."""
    s = spec()
    bdir = copy_bench(tmp_path)
    mix = json.loads((bdir / "workloads" / "aids.range.closed.json")
                     .read_text())
    (bdir / "workloads" / "aids.tau1.closed.json").write_text(
        json.dumps({**mix, "tau": [1], "clients": 16}))
    (bdir / "metrics" / "spans_per_query.closed.py").write_text(
        "def read(run):\n"
        "    return len(run.spans) / run.n_queries if run.spans else None\n")
    s["workloads"].append({"name": "aids.tau1.closed", "config": "msq_aids",
                           "traffic": "aids.tau1.closed", "chips": 1,
                           "why": "test"})
    s["per_layer"].append({"name": "spans_per_query.closed", "unit": "1",
                           "better": "lower", "source": "program_span",
                           "layer": "encode and bucket",
                           "moves": "throughput_qps",
                           "workloads": ["aids.tau1.closed"]})
    cell, cfg, mix2 = bench.find_cell(s, "aids.tau1.closed", str(bdir))
    assert mix2["tau"] == [1] and cfg["name"] == "msq_aids"
    names = [m["name"] for m in bench.cell_metrics(s, "aids.tau1.closed",
                                                   trace=True)]
    assert names == ["spans_per_query.closed"]
    e2e = [m["name"] for m in bench.cell_metrics(s, "aids.tau1.closed",
                                                 trace=False)]
    assert "setup_s" in e2e
    read = bench.load_reader("spans_per_query.closed", str(bdir))
    rec = bench.RunRecord(n_queries=4, spans=[object()] * 8)
    assert read(rec) == 2.0
    with pytest.raises(bench.Refusal):
        bench.find_cell(s, "no.such.cell", str(bdir))


NEW_PIECES = {
    "generators": ("rings", "def build(cfg):\n    return ['ring']\n",
                   lambda m: m.build({}) == ["ring"]),
    "engines": ("sharded4", "def pipeline(eng, cfg):\n    return 'p4'\n",
                lambda m: m.pipeline(None, {}) == "p4"),
    "loops": ("bursty", "def count(mix, seconds):\n    return 7\n",
              lambda m: m.count({}, 1.0) == 7),
    "arrivals": ("poisson", "def times(n, s, rng):\n    return [0.0] * n\n",
                 lambda m: m.times(2, 1.0, None) == [0.0, 0.0]),
    "bases": ("zipf_hot", "def draw(n, order, rng):\n    return order[:n]\n",
              lambda m: m.draw(2, [5, 6, 7], None) == [5, 6]),
    "queries": ("top_k", "def compare(res, exp):\n    return (0, 0)\n",
                lambda m: m.compare(None, None) == (0, 0)),
}


@pytest.mark.parametrize("kind", sorted(NEW_PIECES))
def test_a_new_piece_of_each_kind_is_found_by_name(tmp_path, kind):
    """A generator, an engine, a loop, arrivals, bases or a kind of query
    that a later change adds is one new file, found by the name a
    configuration or a mix gives it."""
    bdir = copy_bench(tmp_path)
    name, source, works = NEW_PIECES[kind]
    with pytest.raises(registry.Missing):
        registry.load(kind, name, str(bdir))
    (bdir / kind / f"{name}.py").write_text(source)
    assert works(registry.load(kind, name, str(bdir)))
    with pytest.raises(registry.Missing):
        registry.load(kind, "../configs/msq_aids", str(bdir))


def test_readers_return_nothing_where_there_is_nothing_to_read():
    s = spec()
    empty = bench.RunRecord(seconds=1.0, n_queries=0)
    for m in s["per_layer"]:
        assert bench.load_reader(m["name"])(empty) is None
