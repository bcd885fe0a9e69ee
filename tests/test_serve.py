"""Serving tier (serve/ — ISSUE 8): continuous batching, slot pool, hot
weight swaps, SLO telemetry.

The load-bearing contracts:

- **Parity**: for a fixed request trace, continuous-batched serving gives
  the SAME ANSWER as threading each session one at a time through
  ``model.apply`` (fp32): same action, logits equal within the written
  tolerance of ``serving_parity.assert_same_answer`` (the engine's batched
  programs and the one-row reference are different XLA programs) — mixed
  prefill/incremental batches included. Batching is a scheduling
  optimization, never a numerics change: bf16 compute fails the tolerance.
- **Slot pool**: LRU admission/eviction; an evicted session re-enters COLD
  through the batched prefill and from then on behaves exactly like a
  fresh session fed the same requests (the documented eviction contract).
- **Hot swap**: under load with repeated ``tag_best`` updates every
  response is attributable to exactly ONE checkpoint step (it recomputes
  within the parity tolerance under that step's params and no other's — a
  torn batch cannot pass), and a corrupt candidate is refused without
  interrupting serving.
- **SLO surface**: serve gauges land in ``metrics.prom`` and the ``cli
  obs`` summary grows a serve section.
- **Tooling**: lint check 8 (no blocking host ops in the dispatch
  closure) and the soak's quick profile run in tier-1; the full
  3x-acceptance soak is ``slow``.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
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
from sharetrade_tpu.precision import PrecisionPolicy
from sharetrade_tpu.serve import ServeEngine, SlotPool, WeightSwapWatcher
from sharetrade_tpu.serve.engine import _gather_rows
from sharetrade_tpu.utils.metrics import MetricsRegistry

from serving_parity import (
    SequentialReference,
    assert_other_answer,
    assert_same_answer,
)

WINDOW = 8
OBS_DIM = WINDOW + 2


@pytest.fixture(scope="module")
def episode_model():
    return episode_transformer_policy(obs_dim=OBS_DIM, num_layers=2,
                                      num_heads=2, head_dim=8)


@pytest.fixture(scope="module")
def episode_params(episode_model):
    return episode_model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mlp_model():
    return build_model(ModelConfig(kind="mlp", hidden_dim=16), OBS_DIM,
                       head="ac")


@pytest.fixture(scope="module")
def mlp_params(mlp_model):
    return mlp_model.init(jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def prices():
    rng = np.random.default_rng(7)
    return rng.uniform(10.0, 20.0, 256).astype(np.float32)


def obs_at(prices, start, t, *, budget=2400.0, shares=0.0):
    lo = start + t
    return np.concatenate(
        [prices[lo:lo + WINDOW],
         np.asarray([budget, shares], np.float32)]).astype(np.float32)


# ---------------------------------------------------------------------------
# construction / slot pool


def test_config_validation(mlp_model, mlp_params):
    with pytest.raises(ConfigError):
        ServeEngine(mlp_model, ServeConfig(max_batch=8, slots=4),
                    mlp_params)
    with pytest.raises(ConfigError):
        ServeEngine(mlp_model, ServeConfig(max_batch=0), mlp_params)
    with pytest.raises(ConfigError):
        ServeEngine(mlp_model,
                    ServeConfig(max_batch=1, slots=1,
                                batch_timeout_ms=-1.0), mlp_params)


def test_slot_pool_lru_and_pinning():
    pool = SlotPool(3)
    slots = {s: pool.admit(s, set())[0] for s in "abc"}
    assert len(set(slots.values())) == 3 and len(pool) == 3
    # 'a' is LRU; touching it promotes it, so 'b' becomes the victim.
    assert pool.lookup("a") == slots["a"]
    slot_d, evicted = pool.admit("d", set())
    assert evicted == "b" and slot_d == slots["b"]
    assert pool.evictions == 1
    assert pool.lookup("b") is None          # evicted sessions are cold
    # Pinning protects the current batch: 'c' is LRU but pinned.
    _, evicted = pool.admit("e", {"c"})
    assert evicted == "a"


# ---------------------------------------------------------------------------
# parity (the acceptance criterion)


def test_parity_mixed_prefill_incremental_episode(episode_model,
                                                  episode_params, prices):
    """Sessions join at staggered ticks, so most ticks mix a cold prefill
    sub-batch with a warm incremental sub-batch at heterogeneous episode
    clocks — every response must be the one-at-a-time reference's answer
    (``assert_same_answer``)."""
    registry = MetricsRegistry()
    engine = ServeEngine(
        episode_model,
        ServeConfig(max_batch=8, slots=16, batch_timeout_ms=5.0,
                    swap_poll_s=0.0),
        episode_params, registry=registry)
    engine.warmup()
    ref = SequentialReference(episode_model, episode_params)
    try:
        sessions = [(f"s{i}", 3 * i) for i in range(6)]   # staggered starts
        clock: dict[str, int] = {}
        for tick in range(10):
            live = sessions[: 2 + tick]                   # joiners per tick
            pending = []
            for sid, start in live:
                t = clock.get(sid, 0)
                obs = obs_at(prices, start, t, shares=float(t % 3))
                pending.append((sid, obs, engine.submit(sid, obs)))
                clock[sid] = t + 1
            for sid, obs, handle in pending:
                result = handle.wait(30.0)
                assert result is not None, "serve timeout"
                ref_action, ref_logits = ref.step(sid, obs)
                assert result.action == ref_action
                assert_same_answer(result.logits, ref_logits, (sid, tick))
    finally:
        engine.stop()
    counters = registry.counters()
    # The trace really did mix paths: prefills for every join, plus warm
    # incremental traffic.
    assert counters["serve_prefills_total"] == len(sessions)
    assert counters["serve_responses_total"] > counters[
        "serve_prefills_total"]


def test_parity_generic_path_mlp(mlp_model, mlp_params, prices):
    engine = ServeEngine(
        mlp_model, ServeConfig(max_batch=4, slots=8, batch_timeout_ms=5.0),
        mlp_params)
    engine.warmup()
    ref = SequentialReference(mlp_model, mlp_params)
    try:
        for tick in range(5):
            pending = []
            for i in range(6):                # > max_batch: multiple ticks
                obs = obs_at(prices, 5 * i, tick, shares=float(i))
                pending.append((f"u{i}", obs,
                                engine.submit(f"u{i}", obs)))
            for sid, obs, handle in pending:
                result = handle.wait(30.0)
                assert result is not None
                action, logits = ref.step(sid, obs)
                assert result.action == action
                assert_same_answer(result.logits, logits, (sid, tick))
    finally:
        engine.stop()


def test_same_session_requests_stay_sequential(episode_model,
                                               episode_params, prices):
    """Two in-flight requests for one session must not share a batch: the
    second sees the first's carry (deferred to the next tick), matching
    the sequential reference (a second step from a fresh carry would differ
    in the first digits)."""
    engine = ServeEngine(
        episode_model,
        ServeConfig(max_batch=8, slots=8, batch_timeout_ms=2.0),
        episode_params)
    engine.warmup()
    ref = SequentialReference(episode_model, episode_params)
    try:
        obs0 = obs_at(prices, 0, 0)
        obs1 = obs_at(prices, 0, 1)
        h0 = engine.submit("dup", obs0)
        h1 = engine.submit("dup", obs1)
        r0, r1 = h0.wait(30.0), h1.wait(30.0)
        assert r0 is not None and r1 is not None
        a0, l0 = ref.step("dup", obs0)
        a1, l1 = ref.step("dup", obs1)
        assert (r0.action, r1.action) == (a0, a1)
        assert_same_answer(r0.logits, l0, "first")
        assert_same_answer(r1.logits, l1, "second")
    finally:
        engine.stop()


def test_parity_tolerance_refuses_bf16_compute(episode_model,
                                               episode_params, prices):
    """The written tolerance sees a precision change (ROADMAP D1's
    condition): the same session stepped by an engine built on the
    ``bf16_mixed`` policy's bf16 copy of the params fails
    ``assert_same_answer`` against the float32 reference at every step,
    while the float32 engine beside it passes."""
    cfg = ServeConfig(max_batch=4, slots=8, batch_timeout_ms=2.0)
    engines = {
        "fp32": ServeEngine(episode_model, cfg, episode_params),
        "bf16": ServeEngine(episode_model, cfg, episode_params,
                            precision=PrecisionPolicy(mode="bf16_mixed")),
    }
    ref = SequentialReference(episode_model, episode_params)
    try:
        for engine in engines.values():
            engine.warmup()
        for t in range(3):
            obs = obs_at(prices, 0, t)
            _, logits = ref.step("p", obs)
            got = {name: engine.submit("p", obs).wait(30.0)
                   for name, engine in engines.items()}
            assert all(r is not None for r in got.values())
            assert_same_answer(got["fp32"].logits, logits, ("fp32", t))
            assert_other_answer(got["bf16"].logits, logits, ("bf16", t))
    finally:
        for engine in engines.values():
            engine.stop()


def test_steady_state_is_one_program_per_tick(episode_model,
                                              episode_params, prices):
    """Once every session is warm, a full tick is ONE batched program:
    batches_total advances by one per tick and prefills stay flat."""
    registry = MetricsRegistry()
    engine = ServeEngine(
        episode_model,
        ServeConfig(max_batch=4, slots=8, batch_timeout_ms=50.0),
        episode_params, registry=registry)
    engine.warmup()
    try:
        sids = [f"w{i}" for i in range(4)]
        for tick in range(2):                 # admit + warm everyone
            handles = [engine.submit(s, obs_at(prices, 4 * i, tick))
                       for i, s in enumerate(sids)]
            assert all(h.wait(30.0) for h in handles)
        counters = registry.counters()
        batches0 = counters["serve_batches_total"]
        prefills0 = counters["serve_prefills_total"]
        for tick in range(2, 5):
            handles = [engine.submit(s, obs_at(prices, 4 * i, tick))
                       for i, s in enumerate(sids)]
            assert all(h.wait(30.0) for h in handles)
        counters = registry.counters()
        assert counters["serve_prefills_total"] == prefills0
        assert counters["serve_batches_total"] == batches0 + 3
    finally:
        engine.stop()


def _carry_of(kind):
    """One session's carry of ``kind``: the episode transformer's with K/V
    in bf16 over a 21-wide window (no multiple of the bf16 tile's 16
    sublanes: the case whose gather sliced the whole arena on the chip),
    an LSTM's pair of vectors, or the MLP's empty tree."""
    if kind == "episode_bf16_w21":
        carry = episode_transformer_policy(
            obs_dim=23, num_layers=2, num_heads=2, head_dim=8).init_carry()
        return dict(carry, k=carry["k"].astype(jnp.bfloat16),
                    v=carry["v"].astype(jnp.bfloat16))
    return build_model(ModelConfig(kind=kind, hidden_dim=8), OBS_DIM,
                       head="ac").init_carry()


@pytest.mark.parametrize("idx", [
    pytest.param([7, 0, 11, 15, 16], id="unique_with_scratch_rows"),
    pytest.param([4, 9, 12, 12, 12], id="park_padding_repeated"),
])
@pytest.mark.parametrize("kind", ["episode_bf16_w21", "lstm", "mlp"])
def test_gather_rows_is_x_idx_bit_for_bit(kind, idx):
    """The arena's gather moves bytes: every leaf of any carry tree, for
    the tick's unique indices (live slots, then scratch rows ``slots + i``
    for padding) and for the park program's repeated padding index, is
    bit for bit what ``x[idx]`` returns."""
    rows, rng = 12 + 5, np.random.default_rng(29)     # slots + max_batch

    def distinct_rows(x):
        shape = (rows,) + np.shape(x)
        if jnp.issubdtype(x.dtype, jnp.integer):
            return jnp.asarray(rng.integers(0, 1 << 20, shape), x.dtype)
        return jnp.asarray(rng.standard_normal(shape), x.dtype)

    pool = jax.tree.map(distinct_rows, _carry_of(kind))
    idx = jnp.asarray(idx, jnp.int32)
    got = jax.jit(_gather_rows)(pool, idx)
    want = jax.tree.map(lambda x: x[idx], pool)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_serve_step_writes_only_each_rows_ring_slot(episode_model,
                                                    episode_params, prices):
    """``apply_serve_batch`` at heterogeneous episode clocks: every row's
    new K/V differs from its old one in the ring slot of ITS clock, in
    every layer, and nowhere else (bit for bit), and its clock advances."""
    rows = 5
    obs = jnp.stack([jnp.asarray(obs_at(prices, 4 * i, 0))
                     for i in range(rows)])
    _, carry = episode_model.apply_prefill(episode_params, obs)
    t = carry["t"] + jnp.asarray([0, 3, WINDOW - 1, WINDOW, 2 * WINDOW + 1],
                                 carry["t"].dtype)
    carry = dict(carry, t=t)
    _, new = jax.jit(episode_model.apply_serve_batch)(
        episode_params, obs + 1.0, carry)
    window = carry["k"].shape[3]
    slots = np.mod(np.asarray(t) - 1, window)
    assert np.array_equal(np.asarray(new["t"]), np.asarray(t) + 1)
    for name in ("k", "v"):
        before, after = np.asarray(carry[name]), np.asarray(new[name])
        changed = (before != after).any(axis=(2, 4))      # (rows, L, W)
        want = np.zeros_like(changed)
        want[np.arange(rows), :, slots] = True
        assert np.array_equal(changed, want), name


def test_warm_tick_touches_only_the_rows_it_names(episode_model,
                                                  episode_params, prices):
    """A warm tick rewrites the arena rows of the sessions it serves (and
    scratch rows for its padding) and leaves every other row, resident or
    free, bit for bit as it was."""
    slots = 8
    engine = ServeEngine(
        episode_model,
        ServeConfig(max_batch=4, slots=slots, batch_timeout_ms=50.0),
        episode_params)
    engine.warmup()
    try:
        sids = [f"r{i}" for i in range(5)]
        for tick in range(2):                 # admit + warm everyone
            handles = [engine.submit(s, obs_at(prices, 4 * i, tick))
                       for i, s in enumerate(sids)]
            assert all(h.wait(30.0) for h in handles)
        before = jax.tree.map(np.asarray, engine._pool)
        served = sids[:2]
        handles = [engine.submit(s, obs_at(prices, 4 * i, 2))
                   for i, s in enumerate(served)]
        assert all(h.wait(30.0) for h in handles)
        after = jax.tree.map(np.asarray, engine._pool)
        named = sorted(engine._slots.lookup(s) for s in served)
        others = [r for r in range(slots) if r not in named]
        assert len(others) == slots - 2
        for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            assert np.array_equal(b[others], a[others])
        assert np.array_equal(after["t"][named], before["t"][named] + 1)
        assert not np.array_equal(after["k"][named], before["k"][named])
    finally:
        engine.stop()


def test_serve_tick_gather_bytes_gauge(episode_model, episode_params,
                                       mlp_model, mlp_params):
    """``serve_tick_gather_bytes``: what one tick's gather must move,
    ``max_batch`` rows of every leaf of the arena, exported at build."""
    for model, params in ((episode_model, episode_params),
                          (mlp_model, mlp_params)):
        registry = MetricsRegistry()
        engine = ServeEngine(model, ServeConfig(max_batch=4, slots=8),
                             params, registry=registry)
        try:
            row_bytes = sum(x[0].nbytes
                            for x in jax.tree.leaves(engine._pool))
            assert (row_bytes > 0) == (model is episode_model)
            assert (registry.latest("serve_tick_gather_bytes")
                    == 4 * row_bytes)
        finally:
            engine.stop()


def test_dispatch_fault_fails_batch_not_engine(episode_model,
                                               episode_params, prices):
    """A malformed request (wrong obs length) fails ITS batch — waiters
    unblock with ``error`` set, callbacks fire with None — and the engine
    keeps serving the reference's answers afterward (the donated arena must
    survive the fault)."""
    engine = ServeEngine(
        episode_model,
        ServeConfig(max_batch=4, slots=8, batch_timeout_ms=2.0),
        episode_params)
    engine.warmup()
    ref = SequentialReference(episode_model, episode_params)
    try:
        # Warm a healthy session first (its slot carry must survive).
        assert engine.submit("ok", obs_at(prices, 0, 0)).wait(30.0)
        failed_cb: list = []
        bad = engine.submit("bad", np.ones(3, np.float32),
                            callback=failed_cb.append)
        assert bad.wait(30.0) is None
        assert bad.error is not None
        assert failed_cb == [None]
        # The engine is still up, and the warm session's state is intact:
        # its next step matches the sequential reference stepped twice.
        ref.step("ok", obs_at(prices, 0, 0))
        obs = obs_at(prices, 0, 1)
        result = engine.submit("ok", obs).wait(30.0)
        assert result is not None
        action, logits = ref.step("ok", obs)
        assert result.action == action
        assert_same_answer(result.logits, logits, "after the fault")
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# slot eviction / re-prefill


def test_eviction_reprefill_resumes_as_cold_session(episode_model,
                                                    episode_params, prices):
    """Evict a session by admitting others past capacity, then bring it
    back: from re-admission on, its responses are those of a FRESH session
    fed the same request suffix, and not the continuation's — the documented
    slot-pool contract (eviction restarts the episode from the request's
    window)."""
    registry = MetricsRegistry()
    engine = ServeEngine(
        episode_model,
        ServeConfig(max_batch=2, slots=2, batch_timeout_ms=2.0),
        episode_params, registry=registry)
    engine.warmup()
    ref = SequentialReference(episode_model, episode_params)
    try:
        # Warm session A for three steps.
        for t in range(3):
            assert engine.submit("A", obs_at(prices, 0, t)).wait(30.0)
            ref.step("A-kept", obs_at(prices, 0, t))
        # Evict A: two other sessions take both slots.
        for sid, start in (("B", 40), ("C", 80)):
            assert engine.submit(sid, obs_at(prices, start, 0)).wait(30.0)
        assert registry.counters()["serve_evictions_total"] >= 1
        # A returns at episode step 3..5; the reference is a FRESH session
        # fed the same suffix (cold restart semantics).
        for t in range(3, 6):
            obs = obs_at(prices, 0, t)
            result = engine.submit("A", obs).wait(30.0)
            assert result is not None
            action, logits = ref.step("A-fresh", obs)
            assert result.action == action
            assert_same_answer(result.logits, logits, ("A", t))
            # ... and a carry that survived eviction would not pass.
            assert_other_answer(result.logits, ref.step("A-kept", obs)[1])
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# hot weight swaps


def _train_state(params, updates: int) -> TrainState:
    return TrainState(params=params, opt_state=(), carry=(),
                      env_state=(), rng=jax.random.PRNGKey(0),
                      env_steps=jnp.int32(0), updates=jnp.int32(updates))


def test_hot_swap_atomicity_under_load(mlp_model, prices, tmp_path):
    """Sustained load while ``tag_best`` advances four times: every
    response must be attributable to exactly one published step, and its
    logits must recompute under that step's params within the parity
    tolerance, which no other step's params meet — a batch that mixed two
    param versions cannot pass."""
    versions = {k: mlp_model.init(jax.random.PRNGKey(10 + k))
                for k in range(1, 5)}
    manager = CheckpointManager(str(tmp_path / "ckpt"), fsync=False)
    manager.save_tagged("best", _train_state(versions[1], 1),
                        metadata={"updates": 1})
    registry = MetricsRegistry()
    engine = ServeEngine(
        mlp_model, ServeConfig(max_batch=4, slots=8, batch_timeout_ms=1.0),
        versions[1], params_step=1, registry=registry)
    engine.warmup()
    watcher = WeightSwapWatcher(
        engine, manager, _train_state(versions[1], 1), tag="best",
        poll_s=60.0, seen_meta={"updates": 1, "saved_at": 0.0})
    results: list = []
    results_lock = threading.Lock()
    stop = threading.Event()

    def load():
        i = 0
        while not stop.is_set():
            obs = obs_at(prices, (i * 3) % 100, 0, shares=float(i % 5))
            handle = engine.submit(f"load{i % 16}", obs)
            result = handle.wait(10.0)
            if result is not None:
                with results_lock:
                    results.append((obs, result))
            i += 1

    threads = [threading.Thread(target=load, daemon=True)
               for _ in range(3)]
    for thread in threads:
        thread.start()
    try:
        for k in range(2, 5):
            time.sleep(0.15)
            manager.save_tagged("best", _train_state(versions[k], k),
                                metadata={"updates": k})
            assert watcher.poll_once()
            assert engine.params_step == k
        time.sleep(0.15)
    finally:
        stop.set()
        for thread in threads:
            thread.join(10.0)
        engine.stop()
    assert registry.counters()["serve_swaps_total"] == 3.0
    apply_fn = jax.jit(mlp_model.apply)
    seen_steps = set()
    assert len(results) > 50
    # The tolerance tells any two published steps apart (their logits
    # differ in the first digits), so recomputing within it attributes a
    # response to exactly one of them.
    probe = {k: apply_fn(versions[k], results[0][0], ())[0].logits
             for k in versions}
    for k in versions:
        for other in range(1, k):
            assert_other_answer(probe[k], probe[other], (k, other))
    for obs, result in results:
        assert result.params_step in versions, (
            f"response attributed to unpublished step {result.params_step}")
        seen_steps.add(result.params_step)
        out, _ = apply_fn(versions[result.params_step], obs, ())
        assert_same_answer(
            result.logits, out.logits,
            "response does not recompute under its claimed step — torn "
            "or mixed-params batch")
    assert len(seen_steps) >= 2, "load never spanned a swap"


def test_corrupt_swap_candidate_refused_serving_continues(
        mlp_model, prices, tmp_path):
    v1 = mlp_model.init(jax.random.PRNGKey(21))
    v2 = mlp_model.init(jax.random.PRNGKey(22))
    manager = CheckpointManager(str(tmp_path / "ckpt"), fsync=False)
    registry = MetricsRegistry()
    engine = ServeEngine(
        mlp_model, ServeConfig(max_batch=2, slots=4, batch_timeout_ms=1.0),
        v1, params_step=1, registry=registry)
    engine.warmup()
    watcher = WeightSwapWatcher(engine, manager, _train_state(v1, 1),
                                tag="best", poll_s=60.0)
    # Publish a candidate, then corrupt its payload in place.
    manager.save_tagged("best", _train_state(v2, 2),
                        metadata={"updates": 2})
    state_path = tmp_path / "ckpt" / "tag_best" / "state.msgpack"
    raw = bytearray(state_path.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    state_path.write_bytes(bytes(raw))

    assert watcher.poll_once() is False
    assert watcher.rejected == 1
    assert registry.counters()["serve_swap_rejected_total"] == 1.0
    assert engine.params_step == 1          # serving weights untouched
    # ... and the engine still answers, on the old weights.
    obs = obs_at(prices, 0, 0)
    result = engine.submit("still-up", obs).wait(30.0)
    assert result is not None and result.params_step == 1
    apply_fn = jax.jit(mlp_model.apply)
    assert_same_answer(result.logits, apply_fn(v1, obs, ())[0].logits,
                       "old weights")
    assert_other_answer(result.logits, apply_fn(v2, obs, ())[0].logits,
                        "the refused candidate's")
    # The corrupt candidate was quarantined, not deleted.
    assert any(name.startswith("corrupt_")
               for name in os.listdir(tmp_path / "ckpt"))
    engine.stop()


# ---------------------------------------------------------------------------
# SLO telemetry


def test_slo_gauges_reach_metrics_prom(mlp_model, mlp_params, prices,
                                       tmp_path):
    from sharetrade_tpu.config import FrameworkConfig
    from sharetrade_tpu.obs import build_obs, summarize_run_dir

    cfg = FrameworkConfig()
    cfg.obs.enabled = True
    cfg.obs.dir = str(tmp_path / "run")
    cfg.obs.export_interval_s = 0.1
    registry = MetricsRegistry()
    obs_bundle = build_obs(cfg, registry)
    engine = ServeEngine(
        mlp_model,
        ServeConfig(max_batch=4, slots=8, batch_timeout_ms=1.0,
                    stats_interval_s=0.05),
        mlp_params, registry=registry, obs=obs_bundle)
    engine.warmup()
    try:
        for tick in range(6):
            handles = [engine.submit(f"m{i}", obs_at(prices, 4 * i, tick))
                       for i in range(4)]
            assert all(h.wait(30.0) for h in handles)
            time.sleep(0.06)
    finally:
        engine.stop()
        obs_bundle.flush()
        obs_bundle.close()
    prom = (tmp_path / "run" / "metrics.prom").read_text()
    for gauge in ("serve_qps", "serve_p50_ms", "serve_p99_ms",
                  "serve_batch_occupancy", "serve_queue_depth"):
        assert f"sharetrade_{gauge}" in prom, f"{gauge} missing from prom"
    assert "sharetrade_serve_requests_total" in prom
    summary = summarize_run_dir(cfg.obs.dir)
    assert "serve" in summary
    assert summary["serve"]["requests_total"] == 24.0
    assert summary["serve"]["qps"] is not None


# ---------------------------------------------------------------------------
# soak / lint satellites


def test_serve_soak_quick_profile():
    """Seconds-scale soak profile: all three phases run and produce sane
    numbers. (The 3x acceptance itself is the slow full-scale soak —
    speed assertions at toy scale measure the CI host, not the engine.)"""
    import serve_soak

    result = serve_soak.run_soak(duration_s=0.5, sessions=32,
                                 rates=(2.0,), max_batch=8, slots=32,
                                 window=WINDOW, length=512, mlp=True)
    assert result["baseline_b1"]["completed"] > 0
    assert result["engine_saturation"]["completed"] > 0
    assert result["rate_sweep"][0]["engine"]["completed"] > 0
    assert result["baseline_b1"]["qps"] > 0
    assert "accepted" in result


@pytest.mark.slow
def test_serve_soak_full_acceptance():
    """The ISSUE 8 acceptance row: on CPU, continuous batching sustains
    >= 3x the batch=1 closed-loop QPS at equal-or-better p99 than the
    batch=1 server under the same offered rate."""
    import serve_soak

    result = serve_soak.run_soak(duration_s=3.0, sessions=2000,
                                 rates=(2.0, 4.0, 8.0), max_batch=64,
                                 mlp=True)
    sweep = [(p["rate_multiple"], round(p["engine"]["qps"]))
             for p in result["rate_sweep"]]
    assert result["accepted"], (
        f"3x acceptance failed: baseline {result['baseline_b1']['qps']:.0f}"
        f" QPS, sweep {sweep}")
    assert result["speedup_saturation"] >= 3.0


def test_lint_serve_dispatch_clean():
    """Check 8 on the shipped tree: the dispatch closure is clean and the
    consumer-side functions exist (a rename must update the lint)."""
    import lint_hot_loop

    hits, found = lint_hot_loop.lint_serve_dispatch()
    assert hits == [], f"serve dispatch lint hits: {hits}"
    required = (set(lint_hot_loop.SERVE_DISPATCH_FUNCS)
                | set(lint_hot_loop.SERVE_CONSUMER_FUNCS))
    assert required <= found


# ---------------------------------------------------------------------------
# cli serve preemption contract


def test_cli_serve_sigterm_drains_and_exits_75(tmp_path):
    """``cli serve`` installs the train-style preemption handling: SIGTERM
    drains in-flight requests, flushes metrics, prints its summary, and
    exits 75 (EX_TEMPFAIL)."""
    env = dict(os.environ)
    run_dir = str(tmp_path / "obs")
    proc = subprocess.Popen(
        [sys.executable, "-m", "sharetrade_tpu.cli", "serve",
         "--set", "data.synthetic_length=600",
         "--set", "env.window=32",
         "--set", "serve.max_batch=8", "--set", "serve.slots=16",
         "--set", "serve.stats_interval_s=0.2",
         "--set", "obs.enabled=true", "--set", f"obs.dir={run_dir}",
         "--set", "obs.export_interval_s=0.2",
         "--set", f"runtime.checkpoint_dir={tmp_path / 'ckpt'}",
         "--duration", "60", "--sessions", "32"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env, cwd=REPO)
    try:
        ready = json.loads(proc.stdout.readline())
        assert ready["event"] == "serving_ready"
        assert ready["device"]["platform"] == "cpu"
        time.sleep(1.0)                       # let traffic flow
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=90)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 75, f"expected 75, got {proc.returncode}"
    summary = json.loads(out.strip().splitlines()[-1])
    assert summary["preempted"] is True
    assert summary["drained"] is True
    assert summary["completed"] > 0
    assert summary["device"] == ready["device"]
    # Metrics were flushed on the way out.
    assert os.path.isfile(os.path.join(run_dir, "metrics.prom"))
