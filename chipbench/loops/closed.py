"""Closed loop: ``clients`` clients, each sending its next query the
moment its last one is answered, for the whole window.

The load is whatever the system can take with that many queries in
flight, so the answers completed in the window measure its capacity.
Mix parameters: ``clients``, and either ``pool_queries``, the number of
queries the window draws, or ``pool_qps``: it draws
``round(pool_qps * seconds)``.  The clients send them in order, and start
again from the first when they have sent them all.  Each query is timed
from when it was sent.
"""
import queue
import time

from traffic import ReplayLog


def count(mix: dict, seconds: float) -> int:
    clients = int(mix["clients"])
    n = (int(mix["pool_queries"]) if "pool_queries" in mix
         else int(round(float(mix["pool_qps"]) * seconds)))
    if clients < 1 or n < 1:
        raise ValueError("clients and the pool must be positive")
    return max(clients, n)


def times(mix: dict, n: int, seconds: float, rng):
    return [0.0] * n


def drive(mix: dict, arrivals, submit, seconds: float,
          clock=time.perf_counter, sleep=None) -> ReplayLog:
    """Keep ``clients`` queries in flight through ``submit(i)`` until the
    window closes; sends nothing after the close."""
    log = ReplayLog(seconds=float(seconds))
    n = len(arrivals)
    answered: "queue.SimpleQueue[int]" = queue.SimpleQueue()

    def send(k: int) -> None:
        t = clock()
        with log.lock:
            log.due.append(t)
            log.issued.append(t)
            log.done.append(None)
            log.index.append(k % n)
        ticket = submit(k % n)
        log.tickets.append(ticket)
        stamp = log.stamp(k, clock)

        def cb(res) -> None:
            stamp(res)
            answered.put(k)
        ticket.add_done_callback(cb)

    log.t0 = clock()
    end = log.t0 + seconds
    sent = 0
    for _ in range(int(mix["clients"])):
        send(sent)
        sent += 1
    while True:
        left = end - clock()
        if left <= 0:
            break
        try:
            answered.get(timeout=left)
        except queue.Empty:
            break
        if clock() >= end:
            break
        send(sent)
        sent += 1
    return log
