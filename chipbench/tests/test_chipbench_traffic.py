"""The traffic generator and the loops that send it (CPU only)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import registry  # noqa: E402
import traffic  # noqa: E402

MIX = {"loop": "open", "arrivals": "exponential_gaps", "rate_qps": 20.0,
       "kind": "range", "tau": [1, 2, 3], "edits": [1, 2],
       "bases": "size_strata", "repeats": False, "deadline_s": None}
CLOSED = {**MIX, "loop": "closed", "clients": 3, "pool_qps": 20.0}
OPEN_LOOP = registry.load("loops", "open")
CLOSED_LOOP = registry.load("loops", "closed")


@pytest.mark.parametrize("mix", [MIX, CLOSED], ids=["open", "closed"])
def test_schedule_is_deterministic_for_a_seed(mix):
    big = 2 ** 31 + 12345
    a = traffic.schedule(mix, np.arange(5000), 10.0, big)
    b = traffic.schedule(mix, np.arange(5000), 10.0, big)
    c = traffic.schedule(mix, np.arange(5000), 10.0, big + 1)
    assert a == b
    assert a != c


@pytest.mark.parametrize("mix", [MIX, CLOSED], ids=["open", "closed"])
def test_schedule_fixes_count_and_deals_tau_and_edits_in_equal_shares(mix):
    for seed in (1, 2, 3):
        arr = traffic.schedule(mix, np.arange(5000), 10.0, seed)
        assert len(arr) == 200
        assert sorted(x.t for x in arr) == [x.t for x in arr]
        assert all(0.0 <= x.t < 10.0 for x in arr)
        taus = [x.tau for x in arr]
        assert all(66 <= taus.count(t) <= 68 for t in (1, 2, 3))
        edits = [x.edits for x in arr]
        assert 99 <= edits.count(1) <= 101
        assert len({x.base for x in arr}) == len(arr)       # no repeats
        assert {x.edits for x in arr} <= {1, 2}


def test_schedule_refuses_traffic_it_cannot_make():
    for bad in ({"loop": "polled"}, {"repeats": True}, {"deadline_s": 0.1},
                {"bases": "zipf"}, {"kind": "top_k"}, {"rate_qps": 0.0}):
        with pytest.raises(ValueError):
            traffic.schedule({**MIX, **bad}, np.arange(100), 1.0, 0)
    with pytest.raises(ValueError):
        traffic.schedule(CLOSED, np.arange(100), 10.0, 0)   # 200 of 100


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, d):
        self.t += d


class Ticket:
    def __init__(self):
        self.cbs = []
        self.fired = False

    def add_done_callback(self, fn):
        if self.fired:
            fn(object())
        else:
            self.cbs.append(fn)

    def fire(self):
        self.fired = True
        for fn in self.cbs:
            fn(object())


def test_replay_times_each_query_from_its_due_time():
    """A submit that stalls the open loop makes later queries late; their
    latency still counts from when they were due."""
    clock = FakeClock()
    arr = [traffic.Arrival(t, 0, 1, 0, 1) for t in (0.0, 0.1, 0.2, 0.3)]
    tickets = []

    def submit(i):
        if i == 1:
            clock.t += 0.25          # the sender stalls in this submit
        tk = Ticket()
        tickets.append(tk)
        return tk

    log = OPEN_LOOP.drive(MIX, arr, submit, 1.0, clock=clock,
                          sleep=clock.sleep)
    clock.t = 100.0 + 0.5
    for tk in tickets:
        tk.fire()
    lat = log.latencies()
    assert lat == pytest.approx([0.5, 0.4, 0.3, 0.2])
    late = log.lateness()
    assert late[0] == pytest.approx(0.0)
    assert late[2] == pytest.approx(0.15)    # due at 0.2, sent at 0.35
    assert log.completed_by(log.t0 + 1.0) == 4
    assert log.completed_by(log.t0 + 0.4) == 0
    assert log.index == [0, 1, 2, 3]


def test_replay_leaves_unanswered_queries_unanswered():
    clock = FakeClock()
    arr = [traffic.Arrival(0.0, 0, 1, 0, 1), traffic.Arrival(0.5, 0, 1, 0, 1)]
    log = OPEN_LOOP.drive(MIX, arr, lambda i: Ticket(), 1.0, clock=clock,
                          sleep=clock.sleep)
    assert log.latencies() == [None, None]


def test_closed_loop_keeps_its_clients_busy_until_the_close():
    """Each answer sends the next query; nothing is sent after the close,
    and the pool starts again from the first query when it runs out."""
    clock = FakeClock()
    arr = [traffic.Arrival(0.0, i, 1, 0, 1) for i in range(5)]
    sent = []

    def submit(i):
        sent.append(i)
        tk = Ticket()
        clock.t += 0.125             # each answer takes 0.125 s
        tk.fire()
        return tk

    log = CLOSED_LOOP.drive(CLOSED, arr, submit, 1.0, clock=clock)
    assert len(sent) == 8            # 3 at the start, then one an answer
    assert log.index == [k % 5 for k in range(8)]
    assert log.latencies() == pytest.approx([0.125] * 8)
    assert log.lateness() == [0.0] * 8
    assert log.completed_by(log.t0 + 1.0) == 8
    assert all(t < log.t0 + 1.0 for t in log.issued)


def test_closed_loop_leaves_unanswered_queries_unanswered():
    clock = FakeClock()
    arr = [traffic.Arrival(0.0, i, 1, 0, 1) for i in range(5)]
    log = CLOSED_LOOP.drive(CLOSED, arr, lambda i: Ticket(), 0.05,
                            clock=clock)
    assert len(log.tickets) == 3     # one a client, none answered
    assert log.latencies() == [None, None, None]


def test_percentile_is_nearest_rank_over_all_values():
    xs = list(np.arange(1, 201, dtype=float))
    assert traffic.percentile(xs, 95) == 190.0
    assert traffic.percentile(xs, 50) == 100.0
    with pytest.raises(ValueError):
        traffic.percentile([], 50)


def test_check_sample_is_seeded_and_keeps_the_heaviest():
    a = traffic.check_sample(100, 10, 7, must=[99])
    assert a == traffic.check_sample(100, 10, 7, must=[99])
    assert 99 in a and len(a) in (10, 11)


@pytest.mark.parametrize("mix", [MIX, CLOSED], ids=["open", "closed"])
def test_every_seed_gets_the_same_gaps_and_size_strata(mix):
    order = np.arange(5000)[::-1]
    runs = [traffic.schedule(mix, order, 10.0, s) for s in (4, 5)]
    gaps = [sorted(np.diff([0.0] + [x.t for x in r])) for r in runs]
    assert gaps[0] == pytest.approx(gaps[1])
    assert runs[0][-1].t < 10.0
    for r in runs:
        rank = sorted(int(np.flatnonzero(order == x.base)[0]) for x in r)
        assert [k // 25 for k in rank] == list(range(200))   # one a stratum


def test_a_fixed_query_set_is_only_reordered_by_the_seed():
    fixed = {**CLOSED, "pool_queries": 60, "pool_seed": 77}
    runs = [traffic.schedule(fixed, np.arange(5000), 10.0, s)
            for s in (2 ** 31 + 1, 2 ** 31 + 2)]
    assert len(runs[0]) == 60
    assert runs[0] != runs[1]
    key = lambda a: (a.qseed, a.base)                      # noqa: E731
    assert sorted(runs[0], key=key) == sorted(runs[1], key=key)
    # one cycle for every seed: the second run is the first rotated
    first = [key(a) for a in runs[0]]
    start = first.index(key(runs[1][0]))
    assert [key(a) for a in runs[1]] == first[start:] + first[:start]
    assert runs[0] == traffic.schedule(fixed, np.arange(5000), 10.0,
                                       2 ** 31 + 1)
