"""The readers of the filter's and verify's inner spans and the slab-cache
counters, on synthetic runs; and the clock that puts host spans and the
device trace on one time line."""
import os
import sys
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

import bench  # noqa: E402
import tracing  # noqa: E402

NEW = ("filter_device_ms.closed", "slab_build_ms.closed",
       "slab_cache_hit_share.closed", "filter_cpu_ms.closed",
       "verify_cpu_ms.closed")


def span(name, t0, t1, **args):
    return SimpleNamespace(name=name, t0=t0, t1=t1, args=args)


def program_run():
    """Four queries sent; two filter buckets, two A* slices, one miss of
    each kind, and the cache counters of a program that has them."""
    spans = [
        span("filter_bucket", 0.0, 0.030, cpu_ms=12.0),
        span("slab_gather", 0.001, 0.005, field="sub", cpu_ms=3.5),
        span("slab_upload", 0.005, 0.011, field="jax_db", cpu_ms=1.0),
        span("filter_device", 0.012, 0.020, cpu_ms=2.0),
        span("filter_bucket", 0.040, 0.050, cpu_ms=4.0),
        span("filter_device", 0.041, 0.045, cpu_ms=1.0),
        span("verify", 0.050, 0.090, cpu_ms=30.0),
        span("verify", 0.060, 0.070, cpu_ms=6.0),
        span("queue", 0.0, 1.0),
    ]
    counters = {"slab_cache.jax_db.hits": 3, "slab_cache.jax_db.misses": 1,
                "slab_cache.lb_db.misses": 2, "slab_cache.evictions": 1}
    return bench.RunRecord(seconds=1.0, n_queries=4, spans=spans,
                           counters=counters)


def parent_run():
    """What a program without the inner spans and counters leaves: the
    layer spans with no ``cpu_ms``, and no ``slab_cache`` counters."""
    spans = [span("filter_bucket", 0.0, 0.03), span("verify", 0.05, 0.09),
             span("assign_lb", 0.03, 0.04, n_pairs=3)]
    return bench.RunRecord(seconds=1.0, n_queries=4, spans=spans,
                           counters={"lb_pruned": 1, "queries": 4})


EXPECT = {
    "filter_device_ms.closed": (0.008 + 0.004) / 4 * 1e3,
    "slab_build_ms.closed": (0.004 + 0.006) / 4 * 1e3,
    "slab_cache_hit_share.closed": 75.0,
    "filter_cpu_ms.closed": (12.0 + 4.0) / 4,
    "verify_cpu_ms.closed": (30.0 + 6.0) / 4,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_values_per_query_sent(name):
    assert bench.load_reader(name)(program_run()) == \
        pytest.approx(EXPECT[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_from_a_program_without_it(name):
    assert bench.load_reader(name)(parent_run()) is None
    assert bench.load_reader(name)(bench.RunRecord(seconds=1.0)) is None


def test_slab_build_reads_zero_in_a_window_without_misses():
    run = program_run()
    run.spans = [s for s in run.spans if not s.name.startswith("slab_")]
    assert bench.load_reader("slab_build_ms.closed")(run) == 0.0


def test_new_readers_are_listed_for_both_cells():
    spec = bench.load_spec()
    for cell in ("aids.range.closed", "s100k.range.closed"):
        names = {m["name"] for m in bench.cell_metrics(spec, cell, True)}
        assert set(NEW) <= names


def test_read_xspace_maps_annotations_back_to_perf_counter(tmp_path,
                                                           monkeypatch):
    """Two annotations opened at known ``perf_counter`` times 5 s apart
    come back from the trace within 1 ms of those times.  On a host with
    no chip the trace has no device plane, so the host's annotations are
    offered to ``read_xspace`` as the ops of one."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sync_t = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracing.SYNC_ANNOTATION):
            pass
        known = []
        for k in range(2):
            if k:
                time.sleep(5.0)
            known.append(time.perf_counter())
            with jax.profiler.TraceAnnotation(f"chipbench.probe{k}"):
                pass
    finally:
        jax.profiler.stop_trace()

    class AsDevice:
        @staticmethod
        def from_file(path):
            pd = ProfileData.from_file(path)
            probes = [e for p in pd.planes if p.name.startswith("/host:")
                      for line in p.lines for e in line.events
                      if e.name.startswith("chipbench.probe")]
            ops = SimpleNamespace(name="XLA Ops", events=probes)
            dev = SimpleNamespace(name="/device:TPU:0", lines=[ops])
            return SimpleNamespace(planes=list(pd.planes) + [dev])

    monkeypatch.setattr(jax.profiler, "ProfileData", AsDevice)
    ops, _, n_dev, _ = tracing.read_xspace(str(tmp_path), sync_t)
    assert n_dev == 1
    got = {name: a for a, _, name in ops}
    assert set(got) == {"chipbench.probe0", "chipbench.probe1"}
    for k, t in enumerate(known):
        assert abs(got[f"chipbench.probe{k}"] - t) < 1e-3
