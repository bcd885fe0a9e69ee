"""The open-loop generator against a fake server."""

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench.harness import loadgen

PRICES = np.linspace(50.0, 60.0, 4000).astype(np.float32)


class FakeServer:
    """Answers every request after ``delay`` seconds from one worker thread;
    ``stall`` (start, length) holds all answers back for a while."""

    def __init__(self, delay=0.001, stall=None, refuse_every=0):
        self.delay, self.stall, self.refuse_every = delay, stall, refuse_every
        self.t0 = time.perf_counter()
        self.queue, self.seen = [], 0
        self.lock = threading.Lock()
        self.stop = False
        self.thread = threading.Thread(target=self._work, daemon=True)
        self.thread.start()

    def submit(self, sid, obs, callback=None):
        self.seen += 1
        if self.refuse_every and self.seen % self.refuse_every == 0:
            raise RuntimeError("refused")
        with self.lock:
            self.queue.append((time.perf_counter() + self.delay, callback))

    def _work(self):
        while not self.stop:
            now = time.perf_counter()
            if self.stall and self.stall[0] <= now - self.t0 < sum(self.stall):
                time.sleep(0.001)
                continue
            with self.lock:
                ready = [q for q in self.queue if q[0] <= now]
                self.queue = [q for q in self.queue if q[0] > now]
            for _, cb in ready:
                cb(SimpleNamespace(action=2, logits=np.zeros(3, np.float32)))
            time.sleep(0.0005)


def sessions(n=16, seed=5):
    return loadgen.make_sessions(PRICES, 12, n, seed, 2400.0, max_steps=500)


def test_every_seed_gives_the_same_number_of_arrivals_in_another_order():
    a = loadgen.arrival_times(1, 200.0, 2.0)
    b = loadgen.arrival_times(2 ** 31 + 9, 200.0, 2.0)
    assert len(a) == len(b) == 400 and not np.array_equal(a, b)
    assert np.array_equal(a, loadgen.arrival_times(1, 200.0, 2.0))
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 2.0
    assert np.std(np.diff(a)) > 0.5 * np.mean(np.diff(a))   # not evenly spaced


def test_sessions_are_drawn_from_the_seed_and_follow_the_trade_rules():
    one, two = sessions(seed=5), sessions(seed=5)
    assert [s.start for s in one] == [s.start for s in two]
    assert [s.start for s in one] != [s.start for s in sessions(seed=6)]
    s = one[0]
    obs = s.observation()
    assert obs.shape == (14,) and obs[-2] == 2400.0 and obs[-1] == 0.0
    price = float(PRICES[s.start + 12])
    s.advance(loadgen.BUY, np.zeros(3))
    assert s.shares == 1.0 and s.budget == pytest.approx(2400.0 - price)
    s.advance(loadgen.SELL, np.zeros(3))
    s.advance(loadgen.SELL, np.zeros(3))      # nothing left to sell: a hold
    assert s.shares == 0.0 and s.t == 3 and len(s.steps) == 3


def test_latency_runs_from_the_due_instant_through_a_stall():
    due = loadgen.arrival_times(3, 400.0, 1.0)
    server = FakeServer(delay=0.001, stall=(0.3, 0.3))
    gen = loadgen.OpenLoop(server, sessions(256), due, seed=3)
    gen.run()
    assert gen.wait_idle(10.0)
    server.stop = True
    assert gen.attempted == len(due) and gen.failed == 0
    assert len(gen.latency_ms) == len(due)
    # arrivals due inside the stall waited for its end: up to 300 ms, though
    # the server's own time per request is 1 ms
    assert max(gen.latency_ms) > 200.0
    assert np.median(gen.latency_ms) < 50.0
    # and the generator reports its own lateness, which stays small: it
    # never waited for an answer
    assert len(gen.late_ms) == len(due) and np.median(gen.late_ms) < 5.0


def test_an_arrival_that_finds_every_session_busy_is_attempted_and_failed():
    due = loadgen.arrival_times(4, 200.0, 0.5)
    server = FakeServer(delay=0.001, stall=(0.0, 0.4))
    gen = loadgen.OpenLoop(server, sessions(8), due, seed=4)
    gen.run()
    gen.wait_idle(10.0)
    server.stop = True
    assert gen.attempted == len(due)
    assert gen.failed >= len(due) - 8 - 30 and gen.failed > 0
    assert len(gen.latency_ms) + gen.failed == gen.attempted


def test_a_refused_submit_is_failed_and_the_session_returns():
    due = loadgen.arrival_times(5, 200.0, 0.5)
    server = FakeServer(refuse_every=10)
    gen = loadgen.OpenLoop(server, sessions(8), due, seed=5)
    gen.run()
    assert gen.wait_idle(10.0)
    server.stop = True
    assert gen.failed == len(due) // 10
    assert len(gen.free) == 8
