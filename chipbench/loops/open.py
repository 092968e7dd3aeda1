"""Open loop: queries sent at due times fixed in advance, answered or not.

Mix parameters: ``rate_qps`` (mean offered rate) and ``arrivals`` (the
``arrivals/<name>.py`` that spaces them).  A window draws
``round(rate_qps * seconds)`` queries.  Each is timed from its due time,
so a sender that falls behind shows as latency and as lateness.
"""
import time

import registry
from traffic import ReplayLog


def count(mix: dict, seconds: float) -> int:
    if float(mix["rate_qps"]) <= 0:
        raise ValueError("rate_qps must be positive")
    return max(1, int(round(float(mix["rate_qps"]) * seconds)))


def times(mix: dict, n: int, seconds: float, rng):
    return registry.load("arrivals", mix["arrivals"]).times(n, seconds, rng)


def drive(mix: dict, arrivals, submit, seconds: float,
          clock=time.perf_counter, sleep=time.sleep) -> ReplayLog:
    """Send arrival ``i`` through ``submit(i)`` at its due time.  The
    returned ticket's ``add_done_callback`` stamps its answer time."""
    log = ReplayLog(seconds=float(seconds))
    n = len(arrivals)
    log.done = [None] * n
    log.t0 = clock()
    for i, a in enumerate(arrivals):
        due = log.t0 + a.t
        wait = due - clock()
        if wait > 0:
            sleep(wait)
        log.due.append(due)
        log.issued.append(clock())
        log.index.append(i)
        ticket = submit(i)
        log.tickets.append(ticket)
        ticket.add_done_callback(log.stamp(i, clock))
    return log
