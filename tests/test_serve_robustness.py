"""Overload-safe, self-healing serving (serve/ — ISSUE 10).

The load-bearing contracts, on top of tests/test_serve.py's PR-8 suite
(which pins that DEFAULT-config behavior is unchanged):

- **Admission control**: the ingress queue is bounded at
  ``serve.max_queue``; a flood is shed (``shed_policy="oldest"``) or
  rejected (``"reject"``) with explicit ``ServeRejected`` terminal
  outcomes — the caller's thread is never blocked silently and host
  memory never grows without bound.
- **Deadlines**: expired requests complete with ``ServeDeadlineExceeded``
  BEFORE batch collection (never occupying a padded device row), and the
  batch-coalescing wait is clamped to the earliest surviving deadline.
- **Supervision**: with ``serve.max_restarts > 0`` a dispatch fault fails
  its batch and then REBUILDS the engine (fresh programs + fresh arena —
  previously-warm sessions re-enter cold and answer as fresh sessions
  do); a consecutive-fault storm trips a terminal failed state
  that fails queued work loudly and makes submits raise.
- **Swap breaker**: repeated verified-restore failures stop the watcher
  from polling a wedged tag for a cooldown, with gauge + counters.
- **Shutdown honesty**: ``stop()`` returns False when a thread survived
  its join timeout; ``drain()``'s timeout path returns False.
- **Tooling**: lint check 10 (no unbounded queues / stray sleeps in
  serve/) and the serve chaos soak's quick profile run in tier-1; the
  full >= 20-injection soak is ``slow``.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from sharetrade_tpu.agents.base import TrainState
from sharetrade_tpu.checkpoint.manager import CheckpointManager
from sharetrade_tpu.config import ConfigError, ModelConfig, ServeConfig
from sharetrade_tpu.models import build_model
from sharetrade_tpu.models.transformer_episode import (
    episode_transformer_policy,
)
from sharetrade_tpu.serve import (
    ServeDeadlineExceeded,
    ServeEngine,
    ServeEngineFailed,
    ServeRejected,
    WeightSwapWatcher,
)
from sharetrade_tpu.utils.metrics import MetricsRegistry

from serving_parity import (
    SequentialReference,
    assert_other_answer,
    assert_same_answer,
)

WINDOW = 8
OBS_DIM = WINDOW + 2


@pytest.fixture(scope="module")
def mlp_model():
    return build_model(ModelConfig(kind="mlp", hidden_dim=16), OBS_DIM,
                       head="ac")


@pytest.fixture(scope="module")
def mlp_params(mlp_model):
    return mlp_model.init(jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def episode_model():
    return episode_transformer_policy(obs_dim=OBS_DIM, num_layers=2,
                                      num_heads=2, head_dim=8)


@pytest.fixture(scope="module")
def episode_params(episode_model):
    return episode_model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def prices():
    rng = np.random.default_rng(7)
    return rng.uniform(10.0, 20.0, 256).astype(np.float32)


def obs_at(prices, start, t, *, budget=2400.0, shares=0.0):
    lo = start + t
    return np.concatenate(
        [prices[lo:lo + WINDOW],
         np.asarray([budget, shares], np.float32)]).astype(np.float32)


def _stalled_engine(model, params, *, max_queue, shed_policy,
                    registry=None, stall_s=0.4, prices=None, **cfg_kw):
    """Engine with a SHALLOW pipeline (done_depth=1) whose consumer is
    stalled by one sleeping-callback request: the deterministic way to
    make later submits pile into the bounded ingress queue. Returns
    (engine, stall_handle) with the stall already engaged."""
    engine = ServeEngine(
        model,
        ServeConfig(max_batch=2, slots=4, batch_timeout_ms=1.0,
                    max_queue=max_queue, shed_policy=shed_policy,
                    **cfg_kw),
        params, registry=registry, done_depth=1)
    engine.warmup()
    engaged = threading.Event()

    def stall_cb(_result):
        engaged.set()
        time.sleep(stall_s)

    handle = engine.submit("stall", obs_at(prices, 0, 0),
                           callback=stall_cb)
    assert engaged.wait(20.0), "stall request never dispatched"
    return engine, handle


# ---------------------------------------------------------------------------
# config validation


def test_new_knob_validation(mlp_model, mlp_params):
    with pytest.raises(ConfigError):
        ServeEngine(mlp_model,
                    ServeConfig(max_batch=1, slots=1, max_queue=0),
                    mlp_params)
    with pytest.raises(ConfigError):
        ServeEngine(mlp_model,
                    ServeConfig(max_batch=1, slots=1,
                                shed_policy="brownout"), mlp_params)
    with pytest.raises(ConfigError):
        ServeEngine(mlp_model,
                    ServeConfig(max_batch=1, slots=1,
                                default_deadline_ms=-1.0), mlp_params)
    with pytest.raises(ConfigError):
        ServeEngine(mlp_model,
                    ServeConfig(max_batch=1, slots=1, max_restarts=-1),
                    mlp_params)
    with pytest.raises(ConfigError):
        ServeEngine(mlp_model,
                    ServeConfig(max_batch=1, slots=1,
                                restart_backoff_s=0.0), mlp_params)


# ---------------------------------------------------------------------------
# admission control / load shedding


def test_flood_rejects_with_explicit_outcome(mlp_model, mlp_params,
                                             prices):
    """shed_policy='reject': a flood past max_queue completes the excess
    with ServeRejected — immediately (wait() does not block out its
    timeout), counted exactly, queue depth bounded — and the engine
    serves normally afterward."""
    registry = MetricsRegistry()
    engine, stall = _stalled_engine(mlp_model, mlp_params, max_queue=4,
                                    shed_policy="reject",
                                    registry=registry, prices=prices)
    try:
        handles = [engine.submit(f"f{i}", obs_at(prices, i % 32, 0))
                   for i in range(64)]
        assert engine.queue_depth() <= 4
        rejected = []
        for handle in handles:
            t0 = time.perf_counter()
            result = handle.wait(30.0)
            if result is None:
                assert isinstance(handle.error, ServeRejected)
                assert handle.error.reason == "queue_full"
                rejected.append(handle)
                # A rejected handle completed at submit time: waiting on
                # it returns instantly, not after a timeout.
                assert time.perf_counter() - t0 < 1.0
        assert rejected, "a 64-request flood past max_queue=4 with a "\
            "stalled consumer rejected nothing"
        counters = registry.counters()
        assert counters["serve_queue_rejected_total"] == len(rejected)
        assert "serve_shed_total" not in counters
        # Recovery: the engine still answers.
        result = engine.submit("after", obs_at(prices, 40, 0)).wait(30.0)
        assert result is not None
        assert registry.latest("serve_overload") == 1.0
    finally:
        assert stall.wait(10.0) is not None
        engine.stop()


def test_flood_shed_oldest_admits_newest(mlp_model, mlp_params, prices):
    """shed_policy='oldest': the brownout sheds QUEUED work to admit new
    arrivals — the newest submit survives to completion, shed victims
    carry ServeRejected(reason='shed_oldest'), and the shed counter
    matches the victims exactly."""
    registry = MetricsRegistry()
    engine, stall = _stalled_engine(mlp_model, mlp_params, max_queue=4,
                                    shed_policy="oldest",
                                    registry=registry, prices=prices)
    try:
        handles = [engine.submit(f"o{i}", obs_at(prices, i % 32, 0))
                   for i in range(64)]
        assert engine.queue_depth() <= 4
        shed = [h for h in handles if h.wait(30.0) is None]
        for handle in shed:
            assert isinstance(handle.error, ServeRejected)
            assert handle.error.reason == "shed_oldest"
        assert shed, "the flood shed nothing"
        # Under 'oldest' the LAST submit is always admitted (it evicts
        # an older victim), so it must have been served.
        assert handles[-1].result is not None
        assert registry.counters()["serve_shed_total"] == len(shed)
    finally:
        assert stall.wait(10.0) is not None
        engine.stop()


def test_wait_on_shed_request_returns_none_with_error(mlp_model,
                                                      mlp_params, prices):
    """Satellite: wait(timeout) on a request whose batch was shed is a
    prompt None + error, indistinguishable from neither a timeout (error
    set) nor a served result (result None)."""
    engine, stall = _stalled_engine(mlp_model, mlp_params, max_queue=2,
                                    shed_policy="oldest", prices=prices)
    try:
        handles = [engine.submit(f"w{i}", obs_at(prices, i, 0))
                   for i in range(16)]
        shed = [h for h in handles if h.wait(20.0) is None]
        assert shed
        handle = shed[0]
        assert handle.wait(0.001) is None       # already terminal
        assert handle.result is None
        assert isinstance(handle.error, ServeRejected)
    finally:
        assert stall.wait(10.0) is not None
        engine.stop()


# ---------------------------------------------------------------------------
# per-request deadlines


def test_deadline_expires_before_batch_collection(mlp_model, mlp_params,
                                                  prices):
    """Requests queued behind a stalled consumer whose deadline passes
    must complete with ServeDeadlineExceeded, matching the counter
    exactly; later requests are unaffected."""
    registry = MetricsRegistry()
    engine, stall = _stalled_engine(mlp_model, mlp_params, max_queue=8,
                                    shed_policy="reject",
                                    registry=registry, prices=prices)
    try:
        handles = [engine.submit(f"d{i}", obs_at(prices, i, 0),
                                 deadline_ms=20.0) for i in range(8)]
        outcomes = [h.wait(30.0) for h in handles]
        expired = [h for h, r in zip(handles, outcomes) if r is None]
        for handle in expired:
            assert isinstance(handle.error, ServeDeadlineExceeded)
        assert expired, "no deadline expiries behind a stalled consumer"
        assert registry.counters()["serve_deadline_expired_total"] == len(
            expired)
        # The engine serves deadline-free traffic normally afterward.
        assert engine.submit("ok", obs_at(prices, 50, 0)).wait(30.0)
    finally:
        assert stall.wait(10.0) is not None
        engine.stop()


def test_default_deadline_from_config(mlp_model, mlp_params, prices):
    """serve.default_deadline_ms applies when submit() passes none."""
    registry = MetricsRegistry()
    engine, stall = _stalled_engine(mlp_model, mlp_params, max_queue=8,
                                    shed_policy="reject",
                                    registry=registry, prices=prices,
                                    default_deadline_ms=15.0)
    try:
        handles = [engine.submit(f"dd{i}", obs_at(prices, i, 0))
                   for i in range(8)]
        expired = [h for h in handles if h.wait(30.0) is None]
        assert expired
        assert all(isinstance(h.error, ServeDeadlineExceeded)
                   for h in expired)
        # Explicit deadline_ms=0 overrides the default to NO deadline.
        assert engine.submit("nodl", obs_at(prices, 60, 0),
                             deadline_ms=0).wait(30.0) is not None
    finally:
        assert stall.wait(10.0) is not None
        engine.stop()


def test_deadline_anchors_batch_coalescing(mlp_model, mlp_params, prices):
    """A lone tightly-deadlined request under a LONG batch_timeout_ms
    must dispatch at its deadline, not the coalescing timeout: the
    collection wait is clamped to the earliest surviving deadline."""
    engine = ServeEngine(
        mlp_model,
        ServeConfig(max_batch=8, slots=8, batch_timeout_ms=2000.0,
                    max_queue=8),
        mlp_params)
    engine.warmup()
    try:
        t0 = time.perf_counter()
        result = engine.submit("anchor", obs_at(prices, 0, 0),
                               deadline_ms=50.0).wait(10.0)
        elapsed = time.perf_counter() - t0
        assert result is not None, "anchored request expired instead of "\
            "dispatching at its deadline"
        assert elapsed < 1.5, (
            f"request waited {elapsed:.2f}s: the coalescing deadline "
            "ignored the request's own deadline")
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# dispatch supervision


def test_supervised_restart_rebuilds_arena(episode_model, episode_params,
                                           prices):
    """With max_restarts > 0 a dispatch fault rebuilds the engine: the
    formerly-warm session re-enters COLD and answers as a fresh session
    does, not as its continuation (the rebuild discarded its slot carry),
    and the restart counter advances by exactly one."""
    registry = MetricsRegistry()
    engine = ServeEngine(
        episode_model,
        ServeConfig(max_batch=4, slots=8, batch_timeout_ms=2.0,
                    max_restarts=2, restart_backoff_s=0.01,
                    restart_backoff_max_s=0.05),
        episode_params, registry=registry)
    engine.warmup()
    ref = SequentialReference(episode_model, episode_params)
    try:
        for t in range(2):                       # warm session A
            assert engine.submit("A", obs_at(prices, 0, t)).wait(30.0)
            ref.step("A-kept", obs_at(prices, 0, t))
        bad = engine.submit("bad", np.ones(3, np.float32))
        assert bad.wait(30.0) is None and bad.error is not None
        # Post-rebuild: A is cold; its next answer is a FRESH session's
        # (NOT the warm continuation the PR-8 default preserves).
        obs = obs_at(prices, 0, 2)
        result = engine.submit("A", obs).wait(60.0)
        assert result is not None, "engine did not heal after the fault"
        assert_same_answer(
            result.logits, ref.step("A-fresh", obs)[1],
            "post-restart response is not a fresh-session response: the "
            "rebuild kept a stale arena")
        assert_other_answer(result.logits, ref.step("A-kept", obs)[1])
        assert registry.counters()["serve_restarts_total"] == 1.0
    finally:
        engine.stop()


def test_restart_storm_trips_terminal_failed(mlp_model, mlp_params,
                                             prices):
    """More than max_restarts CONSECUTIVE faults: the engine enters the
    terminal failed state — queued work fails loudly, submits raise
    ServeEngineFailed, stop() still shuts down cleanly."""
    registry = MetricsRegistry()
    engine = ServeEngine(
        mlp_model,
        ServeConfig(max_batch=2, slots=2, batch_timeout_ms=1.0,
                    max_restarts=1, restart_backoff_s=0.01,
                    restart_backoff_max_s=0.02),
        mlp_params, registry=registry)
    engine.warmup()
    try:
        first = engine.submit("s1", np.ones(3, np.float32))
        assert first.wait(30.0) is None          # fault 1 -> restart 1
        second = engine.submit("s2", np.ones(3, np.float32))
        assert second.wait(30.0) is None         # fault 2 -> terminal
        deadline = time.monotonic() + 10.0
        while engine.failed is None and time.monotonic() < deadline:
            time.sleep(0.01)
        assert engine.failed is not None, "restart storm did not trip "\
            "the terminal failed state"
        with pytest.raises(ServeEngineFailed):
            engine.submit("late", obs_at(prices, 0, 0))
        assert registry.counters()["serve_restarts_total"] == 1.0
        assert registry.latest("serve_failed") == 1.0
    finally:
        assert engine.stop(drain=False) is True


# ---------------------------------------------------------------------------
# shutdown honesty (satellites)


def test_drain_timeout_returns_false(mlp_model, mlp_params, prices):
    """Satellite: drain(timeout_s) with work still in flight is an
    honest False; once the pipeline clears it flips to True."""
    engine, stall = _stalled_engine(mlp_model, mlp_params, max_queue=8,
                                    shed_policy="reject", prices=prices,
                                    stall_s=0.5)
    try:
        assert engine.drain(timeout_s=0.05) is False
        assert engine.drain(timeout_s=20.0) is True
    finally:
        assert stall.wait(10.0) is not None
        assert engine.stop() is True


def test_stop_reports_hung_thread(mlp_model, mlp_params, prices):
    """Satellite: a consumer wedged past the join timeout makes stop()
    return False (the cli exits nonzero on it) instead of lying."""
    engine, stall = _stalled_engine(mlp_model, mlp_params, max_queue=8,
                                    shed_policy="reject", prices=prices,
                                    stall_s=1.2)
    # The consumer thread is mid-sleep inside the stall callback: a stop
    # with a short join timeout must say so.
    assert engine.stop(drain=False, timeout_s=0.2) is False
    # After the stall clears, the threads exit and stop() is honest again.
    assert stall.wait(10.0) is not None
    assert engine.stop(drain=False, timeout_s=10.0) is True


# ---------------------------------------------------------------------------
# the dispatcher's two device slots: no path may leak one


def _until(predicate, timeout_s=20.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while not predicate() and time.monotonic() < deadline:
        time.sleep(0.002)
    return predicate()


def _in_flight(engine) -> int:
    return engine._ticks_dispatched - engine._ticks_completed


def test_stop_while_waiting_on_a_slot(mlp_model, mlp_params, prices):
    """stop(drain=False) with the consumer stalled and the dispatcher
    waiting for a slot: the wait ends on the stop at once (the request it
    held back fails as never dispatched, well before the stall clears),
    and stop() returns True within its timeout."""
    engine, stall = _stalled_engine(mlp_model, mlp_params, max_queue=8,
                                    shed_policy="reject", prices=prices,
                                    stall_s=3.0)
    second = engine.submit("second", obs_at(prices, 1, 0))
    assert _until(lambda: _in_flight(engine) == 2)
    failed_at = []
    held = engine.submit("held", obs_at(prices, 2, 0),
                         callback=lambda r: failed_at.append(
                             time.perf_counter()))
    time.sleep(0.05)
    assert engine.queue_depth() == 1, "the dispatcher did not wait"
    t_stop = time.perf_counter()
    assert engine.stop(drain=False, timeout_s=10.0) is True
    assert time.perf_counter() - t_stop < 10.0
    assert held.wait(1.0) is None and isinstance(held.error, RuntimeError)
    assert failed_at and failed_at[0] - t_stop < 1.0
    assert stall.result is not None and second.result is not None


@pytest.mark.parametrize("max_restarts", [0, 1])
def test_a_faulted_completion_frees_its_slot(mlp_model, mlp_params, prices,
                                             max_restarts):
    """A completion that raises still frees its tick's slot: with every
    slot held by faulting ticks the engine would wedge. Later requests
    are served, after the supervised rebuild where max_restarts is 1."""
    registry = MetricsRegistry()
    engine, stall = _stalled_engine(
        mlp_model, mlp_params, max_queue=8, shed_policy="reject",
        prices=prices, registry=registry, stall_s=0.3,
        max_restarts=max_restarts, restart_backoff_s=0.01,
        restart_backoff_max_s=0.02)
    complete = engine._complete_batch

    def faulting(done):
        if any(str(req.session_id).startswith("bad")
               for reqs, *_ in done.groups for req in reqs):
            raise RuntimeError("injected readback fault")
        complete(done)
    engine._complete_batch = faulting
    try:
        # Two faulting ticks where a restart cannot trip the terminal
        # state, one where the streak allows a single rebuild.
        bad = []
        for i in range(2 if max_restarts == 0 else 1):
            bad.append(engine.submit(f"bad{i}", obs_at(prices, i, 0)))
            assert _until(lambda: engine._ticks_dispatched == 2 + i)
        assert all(h.wait(20.0) is None for h in bad)
        assert all(isinstance(h.error, RuntimeError) for h in bad)
        for i in range(4):
            result = engine.submit(f"good{i}", obs_at(prices, i, 1)).wait(60.0)
            assert result is not None, "a faulted tick leaked its slot"
        assert _in_flight(engine) == 0
        assert registry.counters().get("serve_restarts_total", 0.0) \
            == float(max_restarts)
    finally:
        assert stall.wait(10.0) is not None
        assert engine.stop(drain=False) is True


def test_a_dispatch_that_raises_takes_no_slot(mlp_model, mlp_params, prices):
    """A malformed request fails in dispatch, before any launch: it takes
    no slot, so with the stalled tick holding one the next request still
    launches while the stall lasts."""
    engine, stall = _stalled_engine(mlp_model, mlp_params, max_queue=8,
                                    shed_policy="reject", prices=prices,
                                    stall_s=3.0)
    try:
        bad = engine.submit("bad", np.ones(3, np.float32))
        assert bad.wait(10.0) is None and bad.error is not None
        assert engine._ticks_dispatched == 1
        good = engine.submit("good", obs_at(prices, 1, 0))
        assert _until(lambda: engine._ticks_dispatched == 2, 2.0)
        assert engine._ticks_completed == 0, "the stall ended first"
        assert good.wait(20.0) is not None
    finally:
        assert stall.wait(10.0) is not None
        assert engine.stop(drain=False) is True
    assert _in_flight(engine) == 0


def test_a_spill_nudge_is_not_a_tick(mlp_model, mlp_params, prices):
    """The spill sentinel that nudges the consumer sits in the done queue
    but holds no slot: with it queued behind the stalled tick, the next
    request still launches, and both counters end level."""
    engine, stall = _stalled_engine(mlp_model, mlp_params, max_queue=8,
                                    shed_policy="reject", prices=prices,
                                    stall_s=3.0)
    try:
        engine._kick_consumer()
        assert engine._done_q.qsize() == 1
        good = engine.submit("good", obs_at(prices, 1, 0))
        assert _until(lambda: engine._ticks_dispatched == 2, 2.0)
        assert engine._ticks_completed == 0, "the stall ended first"
        assert good.wait(20.0) is not None
    finally:
        assert stall.wait(10.0) is not None
        assert engine.stop(drain=False) is True
    assert engine._ticks_dispatched == engine._ticks_completed == 2
    inflight = engine.registry.histograms()["serve_inflight_ticks"]
    assert inflight["count"] == 2 and sum(inflight["counts"][2:]) == 0


def test_the_slot_bound_holds_under_thread_switch_pressure(
        mlp_model, mlp_params, prices):
    """More submitting threads than cores and a 10 µs switch interval: no
    tick launches behind two unconsumed ones, every request is answered,
    and the two counters end level, so no slot was lost or leaked."""
    engine = ServeEngine(mlp_model,
                         ServeConfig(max_batch=2, slots=64,
                                     batch_timeout_ms=0.0),
                         mlp_params)
    engine.warmup()
    answered, workers = [], (os.cpu_count() or 1) + 1

    def client(w):
        for i in range(24):
            result = engine.submit(f"w{w}", obs_at(prices, w, i)).wait(30.0)
            answered.append(result is not None)

    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(w,))
                   for w in range(workers)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(60.0)
        assert not any(thread.is_alive() for thread in threads)
    finally:
        sys.setswitchinterval(old_interval)
        assert engine.stop(drain=False) is True
    assert len(answered) == 24 * workers and all(answered)
    inflight = engine.registry.histograms()["serve_inflight_ticks"]
    assert sum(inflight["counts"][2:]) == 0
    assert inflight["count"] == engine._ticks_dispatched \
        == engine._ticks_completed


# ---------------------------------------------------------------------------
# swap circuit breaker


def _train_state(params, updates: int) -> TrainState:
    return TrainState(params=params, opt_state=(), carry=(),
                      env_state=(), rng=jax.random.PRNGKey(0),
                      env_steps=jnp.int32(0), updates=jnp.int32(updates))


def _corrupt_tag(tmp_path) -> None:
    state_path = tmp_path / "ckpt" / "tag_best" / "state.msgpack"
    raw = bytearray(state_path.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    state_path.write_bytes(bytes(raw))


def test_swap_breaker_opens_and_recovers(mlp_model, prices, tmp_path):
    """Consecutive refused candidates open the breaker (gauge 1, polls
    skipped without re-verifying); after the cooldown a genuine candidate
    probes through, swaps, and closes it (gauge 0)."""
    v1 = mlp_model.init(jax.random.PRNGKey(31))
    manager = CheckpointManager(str(tmp_path / "ckpt"), fsync=False)
    registry = MetricsRegistry()
    engine = ServeEngine(
        mlp_model, ServeConfig(max_batch=2, slots=4, batch_timeout_ms=1.0),
        v1, params_step=1, registry=registry)
    engine.warmup()
    watcher = WeightSwapWatcher(engine, manager, _train_state(v1, 1),
                                tag="best", poll_s=60.0,
                                breaker_failures=2,
                                breaker_cooldown_s=0.2)
    try:
        for k in (2, 3):                     # two corrupt candidates
            manager.save_tagged("best",
                                _train_state(mlp_model.init(
                                    jax.random.PRNGKey(40 + k)), k),
                                metadata={"updates": k})
            _corrupt_tag(tmp_path)
            assert watcher.poll_once() is False
        assert watcher.rejected == 2
        assert watcher.breaker_opens == 1
        assert watcher.breaker_open is True
        assert registry.latest("serve_swap_breaker_open") == 1.0
        assert registry.counters()["serve_swap_breaker_opens_total"] == 1.0
        # While open: a fresh candidate is NOT verified (no new reject).
        manager.save_tagged("best",
                            _train_state(mlp_model.init(
                                jax.random.PRNGKey(44)), 4),
                            metadata={"updates": 4})
        _corrupt_tag(tmp_path)
        assert watcher.poll_once() is False
        assert watcher.rejected == 2, "breaker-open poll still verified "\
            "the wedged tag"
        # Cooldown over: a GENUINE candidate probes through and closes it.
        time.sleep(0.25)
        v5 = mlp_model.init(jax.random.PRNGKey(45))
        manager.save_tagged("best", _train_state(v5, 5),
                            metadata={"updates": 5})
        assert watcher.poll_once() is True
        assert engine.params_step == 5
        assert watcher.breaker_open is False
        assert registry.latest("serve_swap_breaker_open") == 0.0
        # Serving continued on the old weights the whole time.
        assert engine.submit("up", obs_at(prices, 0, 0)).wait(30.0)
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# chaos soak / lint / obs satellites


def test_serve_chaos_quick_profile(tmp_path):
    """The 2-injection quick profile of the chaos soak (also wired into
    `make check`): engine never wedges, queue stays bounded, counters
    reconcile. The full >= 20-injection soak across all five fault
    classes is the `slow` test below."""
    import serve_chaos

    summary = serve_chaos.run_chaos(injections=2, seed=0,
                                    workdir=str(tmp_path),
                                    verbose=False)
    assert summary["injections"] == 2
    assert summary["max_queue_depth_seen"] <= 16
    assert summary["requests_total"] > 0


@pytest.mark.slow
def test_serve_chaos_full_soak(tmp_path):
    """ISSUE 10 acceptance: >= 20 seeded injections covering all five
    fault classes, every invariant asserted after each."""
    import serve_chaos

    summary = serve_chaos.run_chaos(injections=20, seed=0,
                                    workdir=str(tmp_path),
                                    verbose=False)
    assert all(summary["by_class"][c] >= 1
               for c in serve_chaos.FAULT_CLASSES), summary["by_class"]
    assert summary["restarts_total"] == summary["by_class"][
        "dispatch_exception"]
    assert summary["shed_total"] + summary["queue_rejected_total"] > 0
    assert summary["deadline_expired_total"] > 0
    assert summary["swap_breaker_opens_total"] >= 1


def test_lint_serve_overload_safety_clean():
    """Check 10 on the shipped tree: serve/ has no unbounded queues and
    no unmarked sleeps outside the backoff helper."""
    import lint_hot_loop

    hits = lint_hot_loop.lint_serve_overload_safety()
    assert hits == [], f"serve overload-safety lint hits: {hits}"


def test_lint_serve_overload_safety_semantics(tmp_path):
    """Pattern semantics on a fixture: unbounded Queue() (including the
    literal maxsize=0) and EVERY time.sleep are flagged — there is no
    function allowlist, the real backoff helper waits on the stop event
    — while bounded queues and marked lines are not."""
    import lint_hot_loop

    (tmp_path / "engine.py").write_text(
        "import queue\nimport time\nfrom time import sleep\n\n"
        "def bad():\n"
        "    q = queue.Queue()\n"
        "    z = queue.Queue(maxsize=0)\n"   # maxsize=0 IS unbounded
        "    y = queue.Queue(0)\n"
        "    time.sleep(1.0)\n\n"
        "def also_bad():\n"
        "    sleep(2.0)\n\n"          # bare form must be caught too
        "def _backoff_sleep(d):\n"
        "    time.sleep(d)\n\n"       # NOT exempt: no allowlist
        "def fine():\n"
        "    q = queue.Queue(maxsize=8)\n"
        "    r = queue.Queue(4)\n"
        "    other.sleep(9)\n"        # non-time dotted receiver: legal
        "    time.sleep(0.1)  # serve-block-ok: fixture\n")
    hits = lint_hot_loop.lint_serve_overload_safety(root=tmp_path)
    assert {(rel, ln) for rel, ln, _text in hits} == {
        ("serve/engine.py", 6), ("serve/engine.py", 7),
        ("serve/engine.py", 8), ("serve/engine.py", 9),
        ("serve/engine.py", 12), ("serve/engine.py", 15)}


def test_obs_serve_section_includes_overload_block(tmp_path):
    """`cli obs`'s serve section surfaces the shed/deadline/restart/
    breaker counters and the overload gauge in the same block (the PR 9
    'replay' section style)."""
    from sharetrade_tpu.config import FrameworkConfig
    from sharetrade_tpu.obs import build_obs, summarize_run_dir

    cfg = FrameworkConfig()
    cfg.obs.enabled = True
    cfg.obs.dir = str(tmp_path / "run")
    registry = MetricsRegistry()
    bundle = build_obs(cfg, registry)
    registry.record_many({"serve_qps": 100.0, "serve_overload": 1.0,
                          "serve_swap_breaker_open": 0.0})
    registry.inc("serve_requests_total", 64)
    registry.inc("serve_shed_total", 5)
    registry.inc("serve_queue_rejected_total", 3)
    registry.inc("serve_deadline_expired_total", 2)
    registry.inc("serve_restarts_total", 1)
    registry.inc("serve_swap_breaker_opens_total", 1)
    bundle.flush()
    bundle.close()
    summary = summarize_run_dir(cfg.obs.dir)
    serve = summary["serve"]
    assert serve["shed_total"] == 5.0
    assert serve["queue_rejected_total"] == 3.0
    assert serve["deadline_expired_total"] == 2.0
    assert serve["restarts_total"] == 1.0
    assert serve["overload"] == 1.0
    assert serve["swap_breaker_open"] == 0.0
    assert serve["swap_breaker_opens_total"] == 1.0
    prom = (tmp_path / "run" / "metrics.prom").read_text()
    assert "sharetrade_serve_shed_total" in prom
    assert "sharetrade_serve_overload" in prom
