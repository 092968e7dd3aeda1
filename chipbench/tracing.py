"""Reduction of a device trace and the program's host spans to numbers.

The device side is a list of (start, end, module) intervals in host
seconds; the host side is the program's spans on the same clock.  From
them: the busy time (the union of the device intervals; the idle share
of the window follows from it), the jitted functions that took most
device time, and the longest idle gaps, each labelled with the host span
open at its middle.

``read_xspace`` turns a ``jax.profiler`` trace into such intervals.  The
trace's clock is tied to the host's ``perf_counter`` through one
annotation whose host time is known.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

SYNC_ANNOTATION = "chipbench.clock_sync"


def merge(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(intervals: Sequence[Interval], lo: float, hi: float
                 ) -> float:
    return sum(b - a for a, b in merge(intervals, lo, hi))


def module_name(name: str) -> str:
    """``jit_multi(6417903127139779808)`` -> ``jit_multi``: one entry per
    jitted function, over all its compiled shapes."""
    return name.split("(", 1)[0]


def top_modules(events: Sequence[Tuple[float, float, str]], lo: float,
                hi: float, n: int = 10) -> List[List]:
    """[[module, seconds], ...]: the device time of each jitted function
    inside the window, over all its shapes, largest first."""
    tot: Dict[str, float] = {}
    for a, b, name in events:
        d = min(b, hi) - max(a, lo)
        if d > 0:
            key = module_name(name)
            tot[key] = tot.get(key, 0.0) + d
    return [[k, v] for k, v in
            sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:n]]


def label_at(t: float, spans: Sequence[Tuple[float, float, str]]) -> str:
    """The innermost (shortest) host span open at ``t``."""
    best = None
    for a, b, name in spans:
        if a <= t <= b and (best is None or b - a < best[0]):
            best = (b - a, name)
    return "no span" if best is None else best[1]


def idle_gaps(intervals: Sequence[Interval], lo: float, hi: float,
              spans: Sequence[Tuple[float, float, str]], n: int = 10
              ) -> List[List]:
    """[[label, seconds], ...]: the ``n`` longest gaps in which the device
    ran nothing, each labelled with the host span open at its middle."""
    merged = merge(intervals, lo, hi)
    gaps, t = [], lo
    for a, b in merged:
        if a > t:
            gaps.append((t, a))
        t = b
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label_at((a + b) / 2, spans), b - a] for a, b in gaps[:n]]


def read_xspace(trace_dir: str, sync_host_t: float
                ) -> Tuple[List[Tuple[float, float, str]],
                           List[Tuple[float, float, str]], int,
                           Dict[str, int]]:
    """(device op events, device module events, device planes, events
    per device line name) from the
    newest ``*.xplane.pb`` under ``trace_dir``, in host perf_counter
    seconds.  ``sync_host_t`` is the host time at which the
    ``SYNC_ANNOTATION`` annotation opened."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    sync_ns = None
    ops: List[Tuple[int, int, str]] = []
    mods: List[Tuple[int, int, str]] = []
    n_dev = 0
    lines: Dict[str, int] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            n_dev += 1
            for line in plane.lines:
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events]
                lines[line.name] = lines.get(line.name, 0) + len(evs)
                if line.name == "XLA Ops":
                    ops += evs
                elif line.name == "XLA Modules":
                    mods += evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == SYNC_ANNOTATION:
                        sync_ns = e.start_ns
    if sync_ns is None:
        raise ValueError("the clock-sync annotation is not in the trace")

    def host(evs):
        return [(sync_host_t + (a - sync_ns) * 1e-9,
                 sync_host_t + (b - sync_ns) * 1e-9, name)
                for a, b, name in evs]
    return host(ops), host(mods), n_dev, lines
