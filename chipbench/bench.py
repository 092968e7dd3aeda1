"""The benchmark harness: one cell, one seed, one run, in this process.

Everything that belongs to one configuration, one traffic mix or one
metric sits in a file of its own and is found by the name that
``BENCHMARK.json`` or the file above it gives (``registry.py``):

* ``configs/<config>.json``    the deployment: data, sizes, serving
  settings; it names its generator and its engine
* ``workloads/<traffic>.json`` the traffic mix, read by ``traffic.py``;
  it names its loop, its bases and its kind of query
* ``metrics/<metric>.py``      a reader: ``read(run) -> float | None``

A run builds the deployment, warms every shape the window's queries use,
drives the window through ``AsyncGraphQueryEngine.submit`` with the mix's
loop, checks a seeded sample of the answers against the plain reference
(``reference.py``), and prints one JSON line.
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import registry

BENCH_DIR = registry.BENCH_DIR
ROOT = os.path.dirname(BENCH_DIR)
ANSWER_WAIT_S = 60.0           # how long past the close an answer may come


class Refusal(Exception):
    """The run cannot be made here; no result is printed."""


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------

def load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        raise Refusal(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def find_cell(spec: dict, name: str, bench_dir: str = BENCH_DIR):
    """(workload entry, configuration, traffic mix) of cell ``name``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refusal(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = load_json(bench_dir, "configs", f"{cell['config']}.json")
    mix = load_json(bench_dir, "workloads", f"{cell['traffic']}.json")
    return cell, cfg, mix


def cell_metrics(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end ones with
    tracing off, and with tracing on the per-layer ones that list the cell
    (or, with no list, move one of its end-to-end metrics)."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def load_reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    try:
        return registry.load("metrics", name, bench_dir).read
    except registry.Missing as e:
        raise Refusal(str(e)) from None


def serving(cfg: dict, bench_dir: str = BENCH_DIR):
    """The ``engines/<name>.py`` the configuration's serving block names."""
    return registry.load("engines", cfg["serving"]["engine"], bench_dir)


# ---------------------------------------------------------------------------
# what a run hands to the readers
# ---------------------------------------------------------------------------

@dataclass
class RunRecord:
    """Everything a metric reader may read about one run."""
    seconds: float = 0.0
    setup_s: float = 0.0
    n_queries: int = 0             # queries sent in the window
    num_graphs: int = 0
    latencies_s: List[Optional[float]] = field(default_factory=list)
    completed_in_window: int = 0
    # traced runs only
    spans: List = field(default_factory=list)     # program Span objects
    counters: Dict[str, float] = field(default_factory=dict)
    device_busy_s: Optional[float] = None
    device_window_s: Optional[float] = None

    def span_sum(self, *names: str) -> float:
        return sum(s.t1 - s.t0 for s in self.spans if s.name in names)


# ---------------------------------------------------------------------------
# the compile counter, and the host's stalls during the window
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts executables JAX builds (``backend_compile``, persistent-cache
    hits included) and persistent-cache misses, from ``jax.monitoring``."""

    def __init__(self):
        self.built = 0
        self.misses = 0
        self._lock = threading.Lock()

    def install(self) -> "CompileCounter":
        from jax import monitoring

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self.built += 1

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_misses":
                with self._lock:
                    self.misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        return self

    def read(self):
        with self._lock:
            return self.built, self.misses


class Monitor(threading.Thread):
    """How late a thread that sleeps ``period`` seconds wakes: the host
    stalls the program's threads see (the interpreter lock held, or the
    process off the CPU)."""

    def __init__(self, period: float = 0.01):
        super().__init__(name="chipbench-monitor", daemon=True)
        self.period = period
        self.stalls: List[float] = []
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.is_set():
            t = time.perf_counter()
            time.sleep(self.period)
            self.stalls.append(time.perf_counter() - t - self.period)

    def finish(self) -> None:
        self._halt.set()
        self.join()

    def summary(self) -> str:
        s = sorted(self.stalls) or [0.0]
        return (f"host wake-up lateness over {len(self.stalls)} "
                f"{1e3 * self.period:.0f}-ms sleeps: p50 "
                f"{1e3 * s[len(s) // 2]:.3f} ms, max {1e3 * s[-1]:.3f} ms, "
                f"{sum(x > 0.1 for x in s)} over 100 ms")


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------

def require_devices(chips: int) -> dict:
    """The accelerator JAX sees; refuses anything but enough TPU chips."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise Refusal(f"JAX's backend is {backend!r}, not 'tpu': this "
                      "benchmark measures the chip only")
    devs = jax.devices()
    if len(devs) < chips:
        raise Refusal(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peak_bytes() -> Optional[int]:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclass
class Outcome:
    """The answers to the queries sent, in the order sent, and what the
    harness saw of them."""
    results: list
    errors: list
    fallbacks: int


@dataclass
class Deployment:
    plain_db: list
    order: object                  # ids in (|V|, |E|) order
    index: object
    serve: object                  # the engines/<name>.py module
    gen_s: float
    index_s: float


def build_deployment(cfg: dict) -> Deployment:
    import data
    import traffic
    serve = serving(cfg)
    t0 = time.perf_counter()
    plain_db = data.build_database(cfg)
    t1 = time.perf_counter()
    index = serve.build_index(plain_db, cfg)
    return Deployment(plain_db, traffic.size_order(plain_db), index, serve,
                      t1 - t0, time.perf_counter() - t1)


@dataclass
class Queries:
    """A window's queries: the mix's arrivals, the plain graphs the
    reference reads, and the program's requests."""
    arrivals: list
    plain: list
    requests: list
    kind: object                   # the queries/<kind>.py module


def window_queries(dep: Deployment, cfg: dict, mix: dict, seconds: float,
                   seed: int) -> Queries:
    import traffic
    arrivals = traffic.schedule(mix, dep.order, seconds, seed)
    _, _, kind = traffic.pieces(mix)
    plain = traffic.materialise(arrivals, dep.plain_db, cfg["n_vlabels"],
                                cfg["n_elabels"])
    requests = [kind.request(dep.serve.to_program_graph(g), a)
                for g, a in zip(plain, arrivals)]
    return Queries(arrivals, plain, requests, kind)


def qgram_count(g, n_elabels: int) -> int:
    """Distinct degree-based q-grams of a graph (MSQ-Index Sec. 3.2)."""
    import numpy as np

    import reference
    return len(np.unique(reference.degree_qgram_keys(g, n_elabels)))


def warm_up(dep: Deployment, cfg: dict, mix: dict, qs: Queries,
            counter: CompileCounter, seed: int) -> Dict[str, object]:
    """Build every program the window's queries use, in an engine of its
    own, then drop what it left on the device:

    1. the filter and the assignment LB for each query alone, one query
       of each (tau, |V|, |E|, q-gram count), no A*;
    2. short windows of the mix's own loop over the same queries, with
       A*: the batches the loop forms; repeated until a window builds
       nothing.  Each starts at a point of the window's cycle drawn from
       the seed's warm-up stream, so that the same queries are in flight
       together as in the window."""
    import numpy as np

    import traffic
    serve = dep.serve
    eng = serve.engine(dep.index, cfg)
    loop, _, kind = traffic.pieces(mix)
    first = counter.read()
    seen = set()
    order = sorted(range(len(qs.plain)), key=lambda i: (
        qs.arrivals[i].tau, qs.plain[i].n, qs.plain[i].m, i))
    for i in order:
        g = qs.plain[i]
        sig = (qs.arrivals[i].tau, g.n, g.m, qgram_count(g, cfg["n_elabels"]))
        if sig in seen:
            continue
        seen.add(sig)
        eng.submit([kind.request(qs.requests[i].graph, qs.arrivals[i],
                                 verify=False)])
    singles = counter.read()[0] - first[0]
    rng = np.random.default_rng([int(seed), traffic.WARM_STREAM])
    warm_s = float(mix["warmup_s"])
    arrivals = [a for a in qs.arrivals if a.t < warm_s]
    windows, unanswered = [], 0
    for _ in range(int(mix["warmup_windows"])):
        before = counter.read()[0]
        n = len(qs.requests)
        perm = np.roll(np.arange(n), -int(rng.integers(n)))
        pipe = serve.pipeline(eng, cfg)
        wlog = loop.drive(mix, arrivals,
                          lambda i: pipe.submit(qs.requests[perm[i]]),
                          warm_s)
        t_end = wlog.t0 + warm_s + ANSWER_WAIT_S
        for tk in wlog.tickets:
            try:
                tk.result(timeout=max(t_end - time.perf_counter(), 0.01))
            except Exception:  # noqa: BLE001 — the window's check judges
                unanswered += 1
        pipe.close()
        windows.append(counter.read()[0] - before)
        if windows[-1] == 0:
            break
    serve.forget_device_state(dep.index, eng)
    built, misses = counter.read()
    return {"singles": len(seen), "singles_built": singles,
            "windows_built": windows, "unanswered": unanswered,
            "built": built - first[0],
            "cache_misses": misses - first[1]}


@dataclass
class Window:
    """What one measured window produced."""
    log: object                    # traffic.ReplayLog
    outcome: Outcome
    spans: list
    counters: Dict[str, float]
    built: int                     # executables built inside the window
    misses: int                    # of them, persistent-cache misses
    peak: Optional[int]
    t_close: float
    monitor: Monitor
    trace_window: Optional[tuple] = None
    sync_t: Optional[float] = None   # host time of the trace's clock sync


def measure(dep: Deployment, cfg: dict, mix: dict, qs: Queries,
            seconds: float, counter: CompileCounter, trace: bool = False,
            profiler_dir: Optional[str] = None,
            monitor: Optional[Monitor] = None) -> Window:
    """Drive the window through a fresh engine and pipeline with the
    mix's loop, wait for every answer, and close the pipeline."""
    import traffic
    serve = dep.serve
    engine = serve.engine(dep.index, cfg, spans=trace)
    ev = serve.evaluator(dep.index, engine)
    ladder0 = dict(ev.ladder_stats)
    loop, _, _ = traffic.pieces(mix)
    pipe = serve.pipeline(engine, cfg)
    stats0 = dict(pipe.stats)
    monitor = monitor or Monitor()
    sync_t = None
    if trace:
        import jax

        import tracing
        # no Python tracer: it would slow every host call of the program
        # several-fold; the program's own spans attribute host time
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(profiler_dir, profiler_options=opts)
        sync_t = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracing.SYNC_ANNOTATION):
            pass
    built0, miss0 = counter.read()
    monitor.start()
    log_ = loop.drive(mix, qs.arrivals, lambda i: pipe.submit(qs.requests[i]),
                      seconds)
    t_close = log_.t0 + seconds
    wait = t_close - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    monitor.finish()
    trace_window = None
    if trace:
        import jax
        trace_window = (log_.t0, time.perf_counter())
        jax.profiler.stop_trace()
    results, errors = [], []
    for tk in log_.tickets:
        left = max(t_close + ANSWER_WAIT_S - time.perf_counter(), 0.01)
        try:
            results.append(tk.result(timeout=left))
            errors.append(None)
        except Exception as e:  # noqa: BLE001 — a failed query, counted
            results.append(None)
            errors.append(e)
    built1, miss1 = counter.read()
    peak = peak_bytes()
    stats1 = dict(pipe.stats)
    fallbacks = sum(v - ladder0.get(k, 0)
                    for k, v in ev.ladder_stats.items())
    spans = engine.obs.spans.spans() if trace else []
    pipe.close()
    return Window(log_, Outcome(results, errors, fallbacks), spans,
                  {k: stats1[k] - stats0.get(k, 0) for k in stats1
                   if isinstance(stats1[k], (int, float))},
                  built1 - built0, miss1 - miss0, peak, t_close, monitor,
                  trace_window, sync_t)


def run_cell(spec: dict, cell: dict, cfg: dict, mix: dict, seed: int,
             seconds: float, trace: bool, t_start: float, device: dict,
             counter: CompileCounter, sample_k: Optional[int] = None,
             monitor: Optional[Monitor] = None) -> dict:
    """Set up, measure, check; returns the result line as a dict."""
    import traffic

    # -- set-up: the deployment, the window's queries, the warm-up --------
    dep = build_deployment(cfg)
    t_idx = time.perf_counter()
    qs = window_queries(dep, cfg, mix, seconds, seed)
    warm = warm_up(dep, cfg, mix, qs, counter, seed)
    profiler_dir = (tempfile.mkdtemp(prefix="chipbench-trace-")
                    if trace else None)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: generate {dep.gen_s:.3f} s, index "
        f"{dep.index_s:.3f} s, queries and warm-up "
        f"{time.perf_counter() - t_idx:.3f} s {warm}")

    # -- the window ---------------------------------------------------------
    w = measure(dep, cfg, mix, qs, seconds, counter, trace, profiler_dir,
                monitor)
    late = sorted(w.log.lateness()) or [0.0]
    log(f"window {seconds} s, {mix['loop']} loop: {len(w.log.tickets)} "
        f"queries sent of {len(qs.arrivals)} drawn, "
        f"{w.log.completed_by(w.t_close)} answered inside it; sender "
        f"lateness p50 {1e3 * late[len(late) // 2]:.3f} ms max "
        f"{1e3 * late[-1]:.3f} ms")
    log(f"compiles in window: {w.built} executables built, {w.misses} "
        f"persistent-cache misses")
    log(w.monitor.summary())
    rec = RunRecord(seconds=seconds, setup_s=setup_s,
                    n_queries=len(w.log.tickets),
                    num_graphs=len(dep.plain_db),
                    latencies_s=w.log.latencies(),
                    completed_in_window=w.log.completed_by(w.t_close),
                    spans=w.spans, counters=w.counters)
    breakdown = (read_device_trace(rec, profiler_dir, w.trace_window,
                                   w.sync_t) if trace else None)

    # -- the check, after the window and with the program's state closed ---
    t_ref = time.perf_counter()
    k = mix["check_sample"] if sample_k is None else sample_k
    sample = traffic.check_sample(len(w.log.tickets), k, seed,
                                  must=heaviest(w.outcome.results))
    checks = check_answers(w.outcome, w.log.index, qs, dep.plain_db, cfg,
                           sample)
    log(f"reference check of {len(sample)} queries took "
        f"{time.perf_counter() - t_ref:.3f} s")

    metrics = {}
    for m in cell_metrics(spec, cell["name"], trace):
        v = load_reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values()
                  if "limit" in c)
    dev = dict(device, memory_peak_bytes=w.peak)
    if trace:
        dev["busy_s"] = rec.device_busy_s
        dev["window_s"] = rec.device_window_s
    out = {"correct": bool(correct), "attempted": len(w.log.tickets),
           "failed": int(checks["failed_queries"]["value"]),
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def read_device_trace(rec: RunRecord, profiler_dir: str, window,
                      sync_t: float) -> dict:
    """Fill the record's device numbers from the profiler trace; returns
    the breakdown."""
    import shutil

    import tracing
    try:
        ops, mods, n_dev, lines = tracing.read_xspace(profiler_dir,
                                                      sync_t)
    finally:
        shutil.rmtree(profiler_dir, ignore_errors=True)
    log(f"trace: {n_dev} device planes, lines {lines}")
    lo, hi = window
    rec.device_window_s = hi - lo
    if not n_dev:
        return {"device_ops": [], "idle_gaps": []}
    rec.device_busy_s = tracing.busy_seconds(
        [(a, b) for a, b, _ in ops], lo, hi)
    host = [(s.t0, s.t1, s.name) for s in rec.spans]
    return {"device_ops": tracing.top_modules(mods or ops, lo, hi),
            "idle_gaps": tracing.idle_gaps([(a, b) for a, b, _ in ops],
                                           lo, hi, host)}


def heaviest(results) -> List[int]:
    """The query with the most candidates (the longest to verify)."""
    sizes = [len(r.candidates) if r is not None else -1 for r in results]
    if not sizes:
        return []
    return [max(range(len(sizes)), key=lambda i: (sizes[i], -i))]


def check_answers(outcome: Outcome, index: Sequence[int], qs: Queries,
                  plain_db, cfg: dict, sample: Sequence[int]
                  ) -> Dict[str, dict]:
    """Compare the sampled answers with the reference.  ``outcome`` holds
    the answers to the queries sent, the k-th made from arrival
    ``index[k]``.  Every number has its limit; any query that failed,
    came back partial, or was served after a fallback-ladder step counts
    as failed."""
    import reference
    failed = sum(1 for r, e in zip(outcome.results, outcome.errors)
                 if e is not None or r is None
                 or r.stats.get("partial"))
    failed += outcome.fallbacks
    ref = reference.ReferenceIndex(plain_db, cfg["n_vlabels"],
                                   cfg["n_elabels"])
    wrong_c = wrong_m = 0
    for k in sample:
        i = index[k]
        exp = qs.kind.expected(ref, qs.plain[i], qs.arrivals[i])
        c2, m2 = qs.kind.compare(outcome.results[k], exp)
        wrong_c += c2
        wrong_m += m2
    return {"checked": {"value": len(sample)},
            "failed_queries": {"value": failed, "limit": 0},
            "wrong_candidates": {"value": wrong_c, "limit": 0},
            "wrong_matches": {"value": wrong_m, "limit": 0}}


def format_checks(checks: Dict[str, dict]) -> List[str]:
    return [f"check {n}: {c['value']}" + (f" limit {c['limit']}"
                                           if "limit" in c else "")
            for n, c in checks.items()]
