"""The trace reduction: busy-interval union, idle share, gap labels."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

import bench  # noqa: E402
import tracing  # noqa: E402

idle_share_reader = bench.load_reader("device_idle_share.closed")


def idle_share(ops, lo, hi):
    busy = tracing.busy_seconds(ops, lo, hi)
    return idle_share_reader(bench.RunRecord(device_busy_s=busy,
                                             device_window_s=hi - lo))

TRACE = os.path.join(HERE, "data", "small_trace.json")


def load():
    with open(TRACE) as f:
        t = json.load(f)
    ops = [tuple(e) for e in t["device_ops"]]
    spans = [tuple(s) for s in t["host_spans"]]
    return t, ops, spans


def test_merge_unions_overlaps_and_clips_to_the_window():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6), (9.0, 11.0)]
    assert tracing.merge(iv, 0.0, 10.0) == [(0.0, 2.0), (3.0, 4.0),
                                            (9.0, 10.0)]
    assert tracing.busy_seconds(iv, 0.0, 10.0) == pytest.approx(4.0)
    assert idle_share(iv, 0.0, 10.0) == pytest.approx(60.0)
    assert idle_share(iv, 5.0, 5.0) is None


def test_recorded_trace_busy_and_idle_share():
    t, ops, _ = load()
    lo, hi = t["window"]
    busy = tracing.busy_seconds([(a, b) for a, b, _ in ops], lo, hi)
    assert busy == pytest.approx(t["expect"]["busy_s"], rel=1e-9)
    assert idle_share([(a, b) for a, b, _ in ops], lo, hi) == \
        pytest.approx(100 * (1 - t["expect"]["busy_s"] / (hi - lo)))


def test_recorded_trace_gaps_are_labelled_by_the_host_span_open():
    t, ops, spans = load()
    lo, hi = t["window"]
    gaps = tracing.idle_gaps([(a, b) for a, b, _ in ops], lo, hi, spans)
    assert [g[0] for g in gaps[:len(t["expect"]["gap_labels"])]] == \
        t["expect"]["gap_labels"]
    lens = [g[1] for g in gaps]
    assert lens == sorted(lens, reverse=True)
    assert len(gaps) <= 10


def test_top_modules_sums_device_time_per_module():
    ev = [(0.0, 1.0, "a"), (1.0, 1.5, "b"), (2.0, 3.0, "a"), (9.5, 12, "b")]
    assert tracing.top_modules(ev, 0.0, 10.0) == [["a", 2.0], ["b", 1.0]]


def test_label_at_takes_the_innermost_span():
    spans = [(0.0, 10.0, "query"), (2.0, 4.0, "verify"), (5.0, 6.0, "x")]
    assert tracing.label_at(3.0, spans) == "verify"
    assert tracing.label_at(4.5, spans) == "query"
    assert tracing.label_at(11.0, spans) == "no span"


def test_monitor_records_how_late_a_sleeping_thread_wakes():
    import time
    mon = bench.Monitor(period=0.002)
    mon.start()
    time.sleep(0.05)
    mon.finish()
    assert mon.stalls and all(s > -0.002 for s in mon.stalls)
    assert "over 100 ms" in mon.summary()
