"""Open-loop load for the serving cells: one general generator that reads a
traffic mix's parameters (sessions, rate, arrival law) and drives anything
with ``submit(session_id, obs, callback)``.

A copy of ``sharetrade_tpu/serve/driver.py``'s idea with its two faults for
a benchmark corrected: latency runs from the instant an arrival was DUE, not
from ``submit``, and arrivals are a Poisson process's points, not evenly
spaced. Every seed gives the same number of arrivals in the window (the
points of a Poisson process given their count are uniform), in another
order and to other sessions.
"""

from __future__ import annotations

import threading
import time

import numpy as np

BUY, SELL = 0, 1


class Session:
    """One portfolio session: a cursor into the price series and a wallet
    that obeys the served actions (the env's trade rules, on the host).
    ``steps`` keeps what the comparison needs of every served request."""

    def __init__(self, sid: str, prices: np.ndarray, window: int, start: int,
                 budget: float):
        self.sid, self.prices, self.window = sid, prices, window
        self.start, self.t = int(start), 0
        self.budget, self.shares = float(budget), 0.0
        self.steps: list[tuple[float, float, int, np.ndarray]] = []

    def observation(self) -> np.ndarray:
        lo = self.start + self.t
        return np.concatenate(
            [self.prices[lo:lo + self.window],
             np.asarray([self.budget, self.shares], np.float32)]
        ).astype(np.float32)

    def advance(self, action: int, logits) -> None:
        self.steps.append((self.budget, self.shares, int(action),
                           np.asarray(logits, np.float32)))
        price = float(self.prices[self.start + self.t + self.window])
        if action == BUY and self.budget >= price:
            self.budget -= price
            self.shares += 1.0
        elif action == SELL and self.shares > 0:
            self.budget += price
            self.shares -= 1.0
        self.t += 1


def make_sessions(prices: np.ndarray, window: int, n: int, seed: int,
                  budget: float, max_steps: int) -> list[Session]:
    """``n`` sessions at staggered offsets drawn from the seed, each with
    room for ``max_steps`` requests before its series ends."""
    rng = np.random.default_rng([seed, 1])
    room = len(prices) - window - 1 - max_steps
    if room < 1:
        raise ValueError("price series too short for the sessions' steps")
    starts = rng.integers(0, room, size=n)
    return [Session(f"s{i}", prices, window, starts[i], budget)
            for i in range(n)]


def arrival_times(seed: int, rate: float, seconds: float) -> np.ndarray:
    """``round(rate * seconds)`` arrival instants in [0, seconds), the
    points of a Poisson process given their count."""
    rng = np.random.default_rng([seed, 2])
    return np.sort(rng.uniform(0.0, seconds, size=int(round(rate * seconds))))


class OpenLoop:
    """Issues each arrival when it is due, to a session drawn uniformly
    from those with no request in flight. An arrival that finds none, and
    any refused or failed submit, is attempted and failed. Latencies run
    from the due instant to the callback."""

    def __init__(self, server, sessions: list[Session], due: np.ndarray,
                 seed: int):
        self.server, self.due = server, due
        self.free = list(sessions)
        self.rng = np.random.default_rng([seed, 3])
        self.lock = threading.Lock()
        self.latency_ms: list[float] = []     # completed, due in the window
        self.done_at: list[float] = []        # completion instants
        self.late_ms: list[float] = []        # issue time minus due time
        self.attempted = self.failed = self.in_flight = 0
        self.idle = threading.Event()
        self.idle.set()

    def _callback(self, sess: Session, t_due: float):
        def cb(result):
            now = time.perf_counter()
            with self.lock:
                if result is None:
                    self.failed += 1
                else:
                    self.latency_ms.append((now - t_due) * 1e3)
                    self.done_at.append(now)
                    sess.advance(result.action, result.logits)
                self.free.append(sess)
                self.in_flight -= 1
                if self.in_flight == 0:
                    self.idle.set()
        return cb

    def run(self) -> float:
        """Blocks for the schedule's length; returns its first instant on
        the ``perf_counter`` clock."""
        t0 = time.perf_counter()
        for offset in self.due:
            t_due = t0 + float(offset)
            while True:
                wait = t_due - time.perf_counter()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.0005))
            with self.lock:
                self.attempted += 1
                if not self.free:
                    self.failed += 1
                    continue
                pick = int(self.rng.integers(len(self.free)))
                self.free[pick], self.free[-1] = self.free[-1], self.free[pick]
                sess = self.free.pop()
                self.in_flight += 1
                self.idle.clear()
            self.late_ms.append((time.perf_counter() - t_due) * 1e3)
            try:
                self.server.submit(sess.sid, sess.observation(),
                                   self._callback(sess, t_due))
            except Exception:       # noqa: BLE001 - a refused submit fails
                with self.lock:
                    self.failed += 1
                    self.free.append(sess)
                    self.in_flight -= 1
                    if self.in_flight == 0:
                        self.idle.set()
        return t0

    def wait_idle(self, timeout: float = 60.0) -> bool:
        """Wait for every answer that is due, a minute past the close."""
        return self.idle.wait(timeout)


def first_requests(server, sessions: list[Session], wave: int) -> int:
    """Every session's first request (the cold prefill) in a closed loop of
    ``wave`` in flight; returns how many failed."""
    failed = 0
    for lo in range(0, len(sessions), wave):
        handles = [(s, server.submit(s.sid, s.observation()))
                   for s in sessions[lo:lo + wave]]
        for sess, handle in handles:
            result = handle.wait(120.0)
            if result is None:
                failed += 1
            else:
                sess.advance(result.action, result.logits)
    return failed
