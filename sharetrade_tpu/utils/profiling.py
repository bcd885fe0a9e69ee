"""Tracing/profiling — the subsystem the reference lacks entirely.

Reference status (SURVEY.md §5): no tracing of any kind; TF's SummarySaver is
imported but never used (QDecisionPolicyActor.scala:8); the only timing
signal is a progress log every 200 fold steps. Here:

- :class:`Tracer` starts and stops ``jax.profiler`` device traces (XPlane
  output, viewable in TensorBoard/XProf) gated by config; the host spans
  that show up in its timeline are ``obs/trace.py``'s (``host_span``);
- :class:`StepTimer` measures per-chunk wall time and derives steps/sec,
  feeding the metrics registry (the throughput series BASELINE.md needs).
"""

from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field

import jax

from sharetrade_tpu.utils.logging import get_logger

log = get_logger("utils.profiling")


class Tracer:
    """Device tracing around training chunks.

    ``profile_dir=None`` disables everything at zero cost (the config
    default, RuntimeConfig.profile_dir).
    """

    def __init__(self, profile_dir: str | None = None):
        self.profile_dir = profile_dir
        self._active = False

    def start(self) -> None:
        if self.profile_dir and not self._active:
            jax.profiler.start_trace(self.profile_dir)
            self._active = True
            log.info("profiler trace started -> %s", self.profile_dir)

    def stop(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            log.info("profiler trace written to %s", self.profile_dir)

    @contextlib.contextmanager
    def trace(self):
        self.start()
        try:
            yield self
        finally:
            self.stop()


@dataclass
class StepTimer:
    """Per-chunk wall-clock accounting → steps/sec metrics."""

    chunk_steps: int
    num_agents: int
    _last: float | None = None
    # (elapsed seconds, chunks covered) per tick: the orchestrator's sampled
    # metrics cadence ticks once per SAMPLE, covering several dispatched
    # chunks, so each entry carries its own chunk count. Bounded by
    # ``max_history`` (a ring; soak runs previously grew this without
    # limit) — summary() stays EXACT under eviction via the running totals.
    history: list[tuple[float, int]] = field(default_factory=list)
    max_history: int | None = None
    _total_seconds: float = 0.0
    _total_chunks: int = 0

    def __post_init__(self) -> None:
        if self.max_history:
            self.history = deque(self.history, maxlen=int(self.max_history))

    def tick(self, chunks: int = 1) -> dict[str, float]:
        """Call once per completed chunk — or once per metrics sample with
        ``chunks`` = the number of chunks dispatched since the last tick;
        returns throughput metrics averaged over that span."""
        now = time.perf_counter()
        if self._last is None:
            self._last = now
            return {}
        dt = now - self._last
        self._last = now
        self.history.append((dt, chunks))
        self._total_seconds += dt
        self._total_chunks += chunks
        agent_steps = self.chunk_steps * self.num_agents * chunks
        return {
            "chunk_seconds": dt / chunks,
            "env_steps_per_sec":
                self.chunk_steps * chunks / dt if dt > 0 else 0.0,
            "agent_steps_per_sec": agent_steps / dt if dt > 0 else 0.0,
        }

    def rebase(self) -> None:
        """Restart the interval clock without recording anything — called
        after a supervision recovery so the failed chunk, the backoff
        sleep, and the checkpoint restore don't pollute the next sample's
        throughput metrics."""
        self._last = time.perf_counter()

    def summary(self) -> dict[str, float]:
        if not self._total_chunks:
            return {}
        # Running totals, not the (possibly ring-evicted) history: the
        # whole-run aggregates stay exact no matter how long the soak.
        total = self._total_seconds
        chunks = self._total_chunks
        return {
            "chunks_timed": float(chunks),
            "total_seconds": total,
            "mean_chunk_seconds": total / chunks,
            "mean_agent_steps_per_sec":
                self.chunk_steps * self.num_agents * chunks / total,
        }
