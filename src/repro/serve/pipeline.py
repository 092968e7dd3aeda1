"""Async pipelined serving: overlap device filtering with the host GED
worklist and stream matches cheapest-first (DESIGN.md §12).

``GraphQueryEngine.submit`` is strictly serial: the device sits idle while
host A* drains the verification worklist, and callers see nothing until
the whole batch completes — yet verification dominates end-to-end time on
every benchmarked config.  ``AsyncGraphQueryEngine`` decomposes serving
into pipelined stages, each on its own thread(s), none blocking another:

    submit() ──► admission inbox ──► dynamic batch former (size/deadline)
             ──► device filter pass (the wrapped engine's stages 1-3: any
                 backend / FilterSlab layout / ShardedGraphQueryEngine's
                 shard_map path)  [one admission+filter thread]
             ──► shared VerifyScheduler worklist (cheapest filter bound
                 first, budgeted/resumable A*)  [N verifier threads]
             ──► per-query QueryTicket futures + incremental match streams

While the verifier pool drains batch k's worklist, the filter thread is
already running batch k+1's device pass.  With no deadlines, a completed
ticket's result is **bit-identical** to ``engine.submit`` (same
candidates, same matches): the filter path and the A* are shared code and
match *sets* don't depend on worker count or completion order — only the
timing stats differ.  Per-query deadlines produce recall-safe partials:
candidates are never truncated, unverified pairs are counted and the
result is flagged ``partial`` (DESIGN.md §12).
"""
from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from repro.core.search import QueryResult
from repro.obs import use_obs
from repro.serve.errors import (AdmissionError, FilterStageError,
                                QueryError)
from repro.serve.graph_engine import (GraphQuery, GraphQueryEngine,
                                      TopKState, VerifyScheduler)

_DONE = object()                     # stream sentinel


def _ticket_nbytes(r: GraphQuery) -> int:
    """Rough inbox footprint of one queued request: the query graph's
    arrays (vlabels + edge endpoints/labels at int64) plus fixed ticket
    overhead — an admission-accounting bound, not a measurement."""
    g = r.graph
    # defensive: a malformed request (g=None) must still admit and fail
    # *typed* at the filter stage, not blow up the submitter
    n = int(getattr(g, "n", 0) or 0)
    m = int(getattr(g, "m", 0) or 0)
    return 96 + 8 * (n + 3 * m)


class QueryTicket:
    """Per-query future plus an incremental match stream."""

    def __init__(self, request: GraphQuery):
        self.request = request
        self._events: "queue.Queue" = queue.Queue()
        self._done = threading.Event()
        # _result/_error are published under _lock by _resolve and only
        # read after _done is set (or inside _lock) — the Event is the
        # memory barrier, so they carry no guarded_by annotation
        self._result: Optional[QueryResult] = None
        self._error: Optional[BaseException] = None
        self._lock = threading.Lock()
        self._resolved = False            # guarded_by: self._lock
        self._callbacks: List = []        # guarded_by: self._lock
        self._streamed_live = False
        # observability context (engine-internal, DESIGN.md §17):
        # _t_submit pins the root query span's start, _t_enq the current
        # batch-former entry (reset on top-k re-entry), _queue_s the
        # accumulated former wait across rounds, _qid the engine query id
        self._t_submit: Optional[float] = None
        self._t_enq: Optional[float] = None
        self._queue_s = 0.0
        self._qid: Optional[int] = None
        # top-k escalation context (engine-internal, DESIGN.md §15): the
        # ticket re-enters the batch former once per widened-τ round, so
        # its state/encoding ride along instead of being recomputed
        self._topk: Optional[TopKState] = None
        self._topk_counted = False
        self._topk_key = None
        self._topk_qt = None
        # admission accounting (DESIGN.md §18): estimated inbox bytes,
        # stamped at submit and released when the batch former pops it
        self._nbytes = 0

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> QueryResult:
        """Block until the query completes (its last candidate pair is
        verified, expired, or it resolved from cache).  Re-raises the
        pipeline-stage exception if this query's batch failed."""
        if not self._done.wait(timeout):
            raise TimeoutError("query still in the pipeline past timeout")
        if self._error is not None:
            raise self._error
        return self._result  # type: ignore[return-value]

    def stream(self, timeout: Optional[float] = None
               ) -> Iterator[Tuple[int, int]]:
        """Yield ``(graph id, ged)`` matches as A* confirms them —
        cheapest filter bound first, before the query completes.  Ends
        when the query resolves; ``timeout`` bounds each wait
        (``TimeoutError``, same contract as ``result``)."""
        while True:
            try:
                ev = self._events.get(timeout=timeout)
            except queue.Empty:
                raise TimeoutError(
                    "no match or completion within timeout") from None
            if ev is _DONE:
                if self._error is not None:
                    raise self._error
                return
            yield ev

    def add_done_callback(self, fn) -> None:
        """``fn(result)`` on the resolving thread (immediately if done;
        ``result`` is None when the query's batch failed)."""
        self._add_callback(lambda res, err: fn(res))

    # ---- resolution (engine-internal) --------------------------------------
    def _add_callback(self, fn) -> None:
        with self._lock:
            if not self._resolved:
                self._callbacks.append(fn)
                return
        fn(self._result, self._error)

    def _push_match(self, gid: int, d: int) -> None:
        self._streamed_live = True
        self._events.put((gid, d))

    def _resolve(self, result: Optional[QueryResult],
                 error: Optional[BaseException] = None) -> bool:
        """First resolution wins (idempotent — a failed batch's blanket
        error resolution must not fight a scheduler completion)."""
        with self._lock:
            if self._resolved:
                return False
            self._resolved = True
            self._result = result
            self._error = error
            callbacks, self._callbacks = self._callbacks, []
        if error is None and not self._streamed_live:
            # cache hit / alias / verify=False: stream the final matches
            for m in result.matches:
                self._events.put(tuple(m))
        self._events.put(_DONE)
        self._done.set()
        for fn in callbacks:
            try:
                fn(result, error)
            except Exception:        # lint: disable=SRV001
                pass                 # a raising user callback must not
                                     # kill the delivering verifier
                                     # thread (the ticket is already
                                     # resolved by this point)
        return True


def as_completed(tickets: Sequence[QueryTicket],
                 timeout: Optional[float] = None
                 ) -> Iterator[Tuple[int, QueryResult]]:
    """Yield ``(index, result)`` in completion order (earliest-finished
    first — typically the cheapest worklists).  ``timeout`` bounds each
    wait (``TimeoutError``); a failed ticket re-raises its error when
    reached."""
    q: "queue.Queue" = queue.Queue()
    for idx, t in enumerate(tickets):
        t._add_callback(lambda res, err, i=idx: q.put((i, res, err)))
    for _ in tickets:
        try:
            i, res, err = q.get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(
                "no query completed within timeout") from None
        if err is not None:
            raise err
        yield i, res


class AsyncGraphQueryEngine:
    """Pipelined front-end over a ``GraphQueryEngine`` (incl. the sharded
    engine): request queue, dynamic batch former, device filter stage,
    verifier worker pool, streaming delivery (DESIGN.md §12).

    The wrapped engine supplies the source, backend, FilterSlab layout,
    and both LRU caches — the async path reuses its ``_admit`` /
    ``_batched_candidates`` / ``_assemble`` stages verbatim, which is what
    makes the no-deadline bit-identical invariant hold by construction.
    Don't call ``engine.submit`` concurrently with an open pipeline; wrap
    it instead.

    * ``max_batch`` / ``max_delay_s``: admission — a batch forms when
      ``max_batch`` requests are waiting or the oldest has waited
      ``max_delay_s``, whichever is first.
    * ``num_workers``: verifier threads draining the shared worklist.
    * ``verify_executor``: ``"thread"`` (default) runs A* slices on the
      verifier threads; ``"process"`` offloads each slice to the
      scheduler's ``ProcessPoolExecutor`` (``num_workers`` processes) so
      GED verification stops sharing the GIL with the numpy filter pass
      — bit-identical results either way (DESIGN.md §12).
    * ``slice_expansions``: A* timeslice (heap pops) per worklist run;
      undecided searches re-queue at their improved frontier bound.
    * ``default_deadline_s``: verification deadline applied to requests
      that don't carry their own ``deadline_s``.
    * ``inbox_limit`` / ``inbox_bytes``: admission control (DESIGN.md
      §18) — the inbox is bounded by queued tickets and/or estimated
      bytes; an arrival past either bound triggers ``shed_policy``:
      ``"reject"`` resolves the *new* ticket with ``AdmissionError``,
      ``"shed_oldest"`` evicts the oldest queued ticket of the most
      over-weight tenant (per ``tenant_weights``, default weight 1.0)
      and admits the arrival.  Rejections are fast typed outcomes, never
      hangs; in-flight top-k escalation rounds bypass the bound (they
      re-enter, they are not new load).
    * ``faults``: a ``serve.faults.FaultInjector`` threaded through every
      stage's injection points (defaults to the wrapped engine's).
    """

    def __init__(self, engine: GraphQueryEngine, *, max_batch: int = 32,
                 max_delay_s: float = 0.005, num_workers: int = 2,
                 verify_executor: str = "thread",
                 slice_expansions: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 name: str = "apipe",
                 inbox_limit: Optional[int] = None,
                 inbox_bytes: Optional[int] = None,
                 shed_policy: str = "reject",
                 tenant_weights: Optional[Dict[str, float]] = None,
                 faults=None):
        if shed_policy not in ("reject", "shed_oldest"):
            raise ValueError(f"unknown shed_policy {shed_policy!r} "
                             "(reject | shed_oldest)")
        self.engine = engine
        self.max_batch = max(1, int(max_batch))
        self.max_delay_s = float(max_delay_s)
        self.default_deadline_s = default_deadline_s
        self.inbox_limit = None if inbox_limit is None else int(inbox_limit)
        self.inbox_bytes = None if inbox_bytes is None else int(inbox_bytes)
        self.shed_policy = shed_policy
        self.tenant_weights = dict(tenant_weights or {})
        # one injector for the whole pipeline: the engine threads it to
        # the filter evaluator, the scheduler to the verify points
        self.faults = faults if faults is not None else engine.faults
        engine.faults = self.faults
        self.obs = engine.obs           # one ring/registry per pipeline
        self.scheduler = VerifyScheduler(
            engine.source.db, slice_expansions=slice_expansions,
            # map the thread alias; anything unknown reaches the
            # scheduler's own validation instead of silently degrading
            executor={"thread": "inline"}.get(verify_executor,
                                              verify_executor),
            workers=num_workers, obs=engine.obs, faults=self.faults)
        self._cv = threading.Condition()
        self._inbox: "deque[Tuple[float, QueryTicket]]" = \
            deque()                 # guarded_by: self._cv
        self._inbox_nbytes = 0      # guarded_by: self._cv
        # admission counters + high-water marks, merged into ``stats``
        self.pstats = engine.obs.metrics.view("pipe", initial={
            "rejected": 0, "shed": 0, "inbox_hwm": 0,
            "inbox_bytes_hwm": 0})  # guarded_by: self._cv
        self._outstanding = 0       # guarded_by: self._cv
        self._topk_pending = 0      # guarded_by: self._cv
        self._closing = False       # guarded_by: self._cv
        self._closed = False        # guarded_by: self._cv
        self._filter_thread = threading.Thread(
            target=self._filter_loop, name=f"{name}-filter", daemon=True)
        self._workers = [
            threading.Thread(target=self.scheduler.worker_loop,
                             name=f"{name}-verify-{w}", daemon=True)
            for w in range(max(1, int(num_workers)))]
        self._filter_thread.start()
        for w in self._workers:
            w.start()

    # ---- submission --------------------------------------------------------
    def submit(self, request: GraphQuery) -> QueryTicket:
        return self.submit_many([request])[0]

    def submit_many(self, requests: Sequence[GraphQuery]
                    ) -> List[QueryTicket]:
        """Admit requests into the bounded inbox.  Over capacity, the
        configured ``shed_policy`` fires per arrival: rejected arrivals
        and shed victims resolve immediately with ``AdmissionError`` —
        a fast typed outcome, never a queued-forever ticket."""
        tickets = [QueryTicket(r) for r in requests]
        now = time.perf_counter()
        rejected: List[QueryTicket] = []
        shed: List[QueryTicket] = []
        failed: List[Tuple[QueryTicket, AdmissionError]] = []
        admitting = tickets
        if self.faults is not None:
            # the ``admit`` point fires outside _cv (a delay fault must
            # not stall concurrent submitters); a raise fails only the
            # struck ticket, typed, before it ever occupies the inbox
            admitting = []
            for t in tickets:
                try:
                    self.faults.fire("admit", tenant=t.request.tenant)
                    admitting.append(t)
                except Exception as e:  # noqa: BLE001 — typed containment
                    failed.append((t, AdmissionError(
                        f"admission fault: {e!r}",
                        tenant=t.request.tenant, cause=e)))
        with self._cv:
            if self._closing:
                raise RuntimeError("AsyncGraphQueryEngine is closed")
            for t in admitting:
                t._t_submit = t._t_enq = now
                t._nbytes = _ticket_nbytes(t.request)
                if self._over_locked(t._nbytes) \
                        and self.shed_policy == "shed_oldest":
                    while self._over_locked(t._nbytes):
                        victim = self._pick_victim_locked()
                        if victim is None:
                            break
                        shed.append(victim)
                        self.pstats["shed"] += 1
                if self._over_locked(t._nbytes):
                    self.pstats["rejected"] += 1
                    rejected.append(t)
                    continue
                self._inbox.append((now, t))
                self._inbox_nbytes += t._nbytes
                self._outstanding += 1
                if len(self._inbox) > self.pstats["inbox_hwm"]:
                    self.pstats["inbox_hwm"] = len(self._inbox)
                if self._inbox_nbytes > self.pstats["inbox_bytes_hwm"]:
                    self.pstats["inbox_bytes_hwm"] = self._inbox_nbytes
            self._cv.notify_all()
        # resolutions run outside _cv: _resolve takes the ticket lock and
        # fires user callbacks — never under the pipeline lock
        for t, err in failed:
            t._resolve(None, err)
        for t in rejected:
            t._resolve(None, AdmissionError(
                "inbox full: arrival rejected under overload",
                policy=self.shed_policy, tenant=t.request.tenant))
        for t in shed:
            # victims were admitted earlier (outstanding): _finish keeps
            # drain()/close() accounting exact
            self._finish(t, None, AdmissionError(
                "shed from inbox under overload", policy="shed_oldest",
                tenant=t.request.tenant, shed=True))
        return tickets

    def _over_locked(self, nbytes: int) -> bool:    # guarded_by: self._cv
        """Would admitting ``nbytes`` more exceed a bound?  An empty inbox
        always admits (one oversized request must proceed, not livelock)."""
        if not self._inbox:
            return False
        if self.inbox_limit is not None \
                and len(self._inbox) >= self.inbox_limit:
            return True
        return (self.inbox_bytes is not None
                and self._inbox_nbytes + nbytes > self.inbox_bytes)

    def _pick_victim_locked(self    # guarded_by: self._cv
                            ) -> Optional[QueryTicket]:
        """Evict the oldest queued ticket of the most over-weight tenant
        (queued count / tenant weight, ties by tenant name).  In-flight
        top-k rounds are never victims — shedding a half-escalated query
        would strand its worklist accounting."""
        occ: Dict[Optional[str], int] = {}
        for _, t in self._inbox:
            if t._topk is None:
                ten = t.request.tenant
                occ[ten] = occ.get(ten, 0) + 1
        if not occ:
            return None
        victim_tenant = max(
            occ, key=lambda ten: (occ[ten] / max(
                self.tenant_weights.get(ten, 1.0), 1e-9), str(ten)))
        for i, (_, t) in enumerate(self._inbox):
            if t._topk is None and t.request.tenant == victim_tenant:
                del self._inbox[i]
                self._inbox_nbytes -= t._nbytes
                return t
        return None

    # ---- lifecycle ---------------------------------------------------------
    def drain(self, timeout: Optional[float] = None) -> None:
        """Block until every submitted query has resolved."""
        end = None if timeout is None else time.perf_counter() + timeout
        with self._cv:
            while self._outstanding > 0:
                left = None if end is None else end - time.perf_counter()
                if left is not None and left <= 0:
                    raise TimeoutError(
                        f"{self._outstanding} queries still in flight")
                self._cv.wait(left)

    def close(self, timeout: float = 60.0) -> None:
        """Stop admission, drain in-flight work, stop every thread.  Even
        when the drain times out, the scheduler is closed and workers are
        joined (``finally``) so a wedged pipeline never parks verifier
        threads forever; ``close`` stays retryable until every thread has
        actually exited."""
        with self._cv:
            if self._closed:
                return
            self._closing = True
            self._cv.notify_all()
        try:
            self._filter_thread.join(timeout)
            self.drain(timeout)
        finally:
            self.scheduler.close()   # workers exit once the heap is empty
            for w in self._workers:
                w.join(timeout)
            closed = not any(
                t.is_alive() for t in [self._filter_thread, *self._workers])
            with self._cv:
                self._closed = closed
            # tear the pool down even on a timed-out close: a wedged
            # worker's later dispatch falls back to in-process slices
            # (never wrong), whereas a leaked spawn pool lives forever
            self.scheduler.shutdown(wait=closed)

    def __enter__(self) -> "AsyncGraphQueryEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def stats(self) -> dict:
        """Wrapped-engine counters plus the shared worklist's.  Each side
        is copied under its own lock — sequentially, never nested, so no
        lock-order edge between the pipeline and the scheduler exists."""
        with self._cv:
            s = dict(self.engine.stats)
            s.update(dict(self.pstats))
        s.update(self.scheduler.stats_snapshot())
        return s

    # ---- stage: dynamic batch former + device filter -----------------------
    def _filter_loop(self) -> None:
        while True:
            batch = self._next_batch()
            if batch is None:
                return
            try:
                self._process_batch(batch)
            except Exception as e:      # noqa: BLE001 — stage containment
                # a failed admission/filter pass must not kill the filter
                # thread (that would hang every future ticket): fail this
                # batch's unresolved tickets with a *typed* error and keep
                # going — other batches and in-flight queries are untouched
                err = e if isinstance(e, QueryError) else FilterStageError(
                    f"filter stage failed: {e!r}", cause=e)
                for t in batch:
                    self._finish(t, None, err)

    def _next_batch(self) -> Optional[List[QueryTicket]]:
        """Size/deadline admission: wait for ``max_batch`` requests or an
        oldest-request age of ``max_delay_s`` (close flushes what's left)."""
        with self._cv:
            while True:
                if self._inbox:
                    age = time.perf_counter() - self._inbox[0][0]
                    if (len(self._inbox) >= self.max_batch
                            or age >= self.max_delay_s or self._closing):
                        n = min(len(self._inbox), self.max_batch)
                        out = []
                        for _ in range(n):
                            _, t = self._inbox.popleft()
                            self._inbox_nbytes -= t._nbytes
                            out.append(t)
                        return out
                    self._cv.wait(self.max_delay_s - age)
                elif self._closing:
                    if self._topk_pending == 0:
                        return None
                    # in-flight top-k queries may still re-enter for a
                    # wider-τ round — the filter stage must outlive them
                    self._cv.wait()
                else:
                    self._cv.wait()

    def _process_batch(self, tickets: List[QueryTicket]) -> None:
        eng = self.engine
        if self.faults is not None:
            # per-batch injection point: a raise here fails exactly this
            # batch's tickets via _filter_loop's containment
            self.faults.fire("filter.batch", n=len(tickets))
        spans_on = eng.obs.spans.enabled
        # batch-former wait becomes a visible queue span (DESIGN.md §17):
        # submission (or top-k re-entry) -> this batch picking the ticket
        t_formed = time.perf_counter()
        for t in tickets:
            if t._t_enq is not None:
                t._queue_s += t_formed - t._t_enq
                if spans_on and t._qid is not None:   # top-k re-entry
                    eng.obs.spans.record("queue", t._t_enq, t_formed,
                                         qid=t._qid)
                t._t_enq = None
        # a re-entered top-k ticket is already admitted (cache checked,
        # encoding cached, state attached): it only needs its next filter
        # round at the widened τ, batched with fresh arrivals
        reenter = [t for t in tickets if t._topk is not None]
        new = [t for t in tickets if t._topk is None]
        # rows: (ticket, request, filter τ, qtuple, key, top-k state)
        rows: List[tuple] = []
        # the wrapped engine's counters are shared with _on_done (verifier
        # threads) and the stats property — mutate them under _cv only
        with self._cv:
            eng.stats["batches"] += 1
            eng.stats["queries"] += len(new)
        if new:
            requests = [t.request for t in new]
            results, fresh, aliases, keys, qtuples, qids = \
                eng._admit(requests)
            for i, t in enumerate(new):
                t._qid = qids[i]
            if spans_on:
                for t in new:
                    eng.obs.spans.record("queue", t._t_submit, t_formed,
                                         qid=t._qid)
            # cache hits resolve immediately — no pipeline latency at all
            for i, res in enumerate(results):
                if res is not None:
                    self._finish(new[i], res)
            # in-batch duplicates follow their source ticket (errors incl.)
            for i, src in aliases:
                new[src]._add_callback(
                    lambda res, err, t=new[i]: self._finish(t, res, err))
            now = time.perf_counter()
            for i in fresh:
                r, t = requests[i], new[i]
                if r.top_k is not None:
                    dl_s = (r.deadline_s if r.deadline_s is not None
                            else self.default_deadline_s)
                    st = TopKState(
                        int(r.top_k), int(r.tau),
                        None if dl_s is None else now + float(dl_s))
                    t._topk = st
                    t._topk_key = keys[i]
                    t._topk_qt = qtuples[i]
                    with self._cv:
                        self._topk_pending += 1
                        t._topk_counted = True
                    rows.append((t, r, st.tau, qtuples[i], keys[i], st))
                else:
                    rows.append((t, r, int(r.tau), qtuples[i], keys[i],
                                 None))
        for t in reenter:
            rows.append((t, t.request, t._topk.tau, t._topk_qt,
                         t._topk_key, t._topk))
        if not rows:
            return

        graphs = [r.graph for _, r, _, _, _, _ in rows]
        taus = [tau for _, _, tau, _, _, _ in rows]
        t0 = time.perf_counter()
        with use_obs(eng.obs):
            batch = eng._batched_candidates(
                graphs, taus, [qt for _, _, _, qt, _, _ in rows])
        t1 = time.perf_counter()
        with self._cv:
            eng.stats["filter_s"] += t1 - t0
        if spans_on:
            eng.obs.spans.record("filter", t0, t1, rows=len(rows),
                                 backend=eng.backend)
            c1 = time.thread_time()

        n_db = len(eng.source.db)
        per_q_filter = (t1 - t0) / len(rows)
        now = time.perf_counter()
        for row, (ticket, r, tau, _qt, key, st) in enumerate(rows):
            cand = batch.ids[row]
            lb_share = eng._job_lb_share(batch, row)
            with self._cv:
                eng.stats["lb_s"] += lb_share
            if st is not None:
                st.rounds += 1
                st.filter_s += per_q_filter
                st.lb_s += lb_share
                with self._cv:
                    eng.stats["topk_rounds"] += 1
                bounds = eng._job_bounds(batch, row)
                lbs = eng._job_lbs(batch, row)
                keep = [c for c, g in enumerate(cand)
                        if int(g) not in st.seen]
                new_ids = [int(cand[c]) for c in keep]
                st.seen.update(new_ids)   # lb-pruned gids stay "seen":
                # decided (GED >= lb > cap), never resubmitted (§16)
                w_ids, w_bounds, n_pr, n_tt = eng._merge_lb(
                    new_ids, [bounds[c] for c in keep],
                    None if lbs is None else [int(lbs[c]) for c in keep],
                    st.cap)
                # pairs run at the query CAP, not the round τ: decisions
                # stay final, frontiers stay resumable in the shared heap
                # across escalation rounds (DESIGN.md §15)
                self.scheduler.add_job(
                    r.graph, st.cap, w_ids, w_bounds, deadline=st.deadline,
                    token=(ticket, key, r, st),
                    on_match=self._on_topk_match,
                    on_done=self._on_topk_round_done,
                    should_skip=st.should_skip,
                    n_lb_pruned=n_pr, n_lb_tightened=n_tt,
                    qid=ticket._qid)
                continue
            if not r.verify:
                res = eng._assemble(cand, None, n_db, per_q_filter,
                                    lb_s=lb_share)
                res.stats["queue_s"] = ticket._queue_s
                eng._cache_result(key, r, res)
                self._finish(ticket, res)
                continue
            dl_s = (r.deadline_s if r.deadline_s is not None
                    else self.default_deadline_s)
            deadline = None if dl_s is None else now + float(dl_s)
            # candidate list in the token stays the *full* row — the
            # stage-1.5 LB prunes verification work, never recall (§16)
            w_ids, w_bounds, n_pr, n_tt = eng._merge_lb(
                cand, eng._job_bounds(batch, row),
                eng._job_lbs(batch, row), tau)
            self.scheduler.add_job(
                r.graph, tau, w_ids, w_bounds, deadline=deadline,
                token=(ticket, key, r, cand, n_db, per_q_filter, lb_share),
                on_match=self._on_match, on_done=self._on_done,
                n_lb_pruned=n_pr, n_lb_tightened=n_tt, qid=ticket._qid)
        if spans_on:
            # the host work between the batch's filter and its worklist
            eng.obs.spans.record("enqueue", t1, time.perf_counter(),
                                 rows=len(rows),
                                 cpu_ms=1e3 * (time.thread_time() - c1))

    # ---- stage: top-k escalation (runs on verifier threads) ----------------
    def _reenter(self, ticket: QueryTicket) -> None:
        """Queue a top-k query's next widened-τ filter round.  Bypasses
        ``submit_many``: escalation of an in-flight query must proceed
        even while admission is closing (close() waits for it)."""
        now = time.perf_counter()
        with self._cv:
            ticket._t_enq = now        # next round's queue-wait starts now
            self._inbox.append((now, ticket))
            self._inbox_nbytes += ticket._nbytes
            self._cv.notify_all()

    def _on_topk_match(self, job, gid: int, d: int) -> None:
        # matches feed the state (so should_skip prunes live), not the
        # ticket stream: only the final k-best may be streamed, and those
        # are known only at resolution
        job.token[3].record_match(gid, d)

    def _on_topk_round_done(self, job) -> None:
        """One escalation round drained: finish the query (satisfied /
        deadline) or widen τ and re-enter the batch former."""
        ticket, key, request, st = job.token
        eng = self.engine
        try:
            st.absorb_round(job)
            if eng.obs.spans.enabled:
                eng.obs.spans.record("topk_round", job.t_enq,
                                     time.perf_counter(), qid=ticket._qid,
                                     tau=st.tau, round=st.rounds)
            with self._cv:
                eng.stats["verify_s"] += job.verify_s
            if st.unverified or (st.deadline is not None
                                 and time.perf_counter() >= st.deadline):
                st.deadline_hit = True
            if st.deadline_hit or st.satisfied():
                res = eng._assemble_topk(st, len(eng.source.db))
                res.stats["queue_s"] = ticket._queue_s
                # deadline partials are never cached (DESIGN.md §15)
                if not (st.unverified or st.deadline_hit):
                    eng._cache_result(key, request, res)
                self._finish(ticket, res)
            else:
                st.escalate()
                self._reenter(ticket)
        except Exception as e:       # noqa: BLE001 — resolve, don't kill
            self._finish(ticket, None, e)

    # ---- stage: delivery (runs on verifier threads) ------------------------
    def _on_match(self, job, gid: int, d: int) -> None:
        job.token[0]._push_match(gid, d)

    def _on_done(self, job) -> None:
        ticket, key, request, cand, n_db, per_q_filter, lb_share = job.token
        eng = self.engine
        try:
            res = eng._assemble(cand, job, n_db, per_q_filter,
                                lb_s=lb_share)
            # queue time is per-*ticket*, stamped before caching so the
            # cached entry never carries another query's wait (replays
            # zero it regardless — DESIGN.md §17)
            res.stats["queue_s"] = ticket._queue_s
            with self._cv:
                eng.stats["verify_s"] += job.verify_s
            if not job.unverified:   # deadline partials are never cached
                eng._cache_result(key, request, res)
        except Exception as e:       # noqa: BLE001 — resolve, don't kill
            self._finish(ticket, None, e)
            return
        self._finish(ticket, res)

    def _finish(self, ticket: QueryTicket, res: Optional[QueryResult],
                error: Optional[BaseException] = None) -> None:
        if not ticket._resolve(res, error):
            return                       # already resolved — keep accounting
        obs = self.engine.obs
        if obs.spans.enabled and ticket._qid is not None \
                and ticket._t_submit is not None \
                and not (res is not None and res.stats.get("cache_hit")):
            # the async root span: submission -> resolution (cache hits
            # already got theirs from _admit, zero-length by design)
            obs.spans.record(
                "query", ticket._t_submit, time.perf_counter(),
                qid=ticket._qid, error=int(error is not None),
                partial=int(bool(res is not None
                                 and res.stats.get("partial"))))
        with self._cv:
            self._outstanding -= 1
            if ticket._topk_counted:     # escalation over — release close()
                ticket._topk_counted = False
                self._topk_pending -= 1
            self._cv.notify_all()
