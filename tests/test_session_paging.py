"""Tiered session paging + fleet autoscaler (ISSUE 18).

The load-bearing contracts:

- **Warm bitwise oracle**: a session evicted to the host-RAM warm tier
  and paged back in CONTINUES — its responses are bit-identical to those
  of an identically built engine that never evicted it (device_get →
  host numpy → device_put → batched scatter install is an exact byte
  round trip, and the same program then reads the same bytes), and the
  one-row reference's uninterrupted answers within the written tolerance
  (``serving_parity.assert_same_answer``). This is the tier's whole
  claim; the PR-8 cold-restart contract stays pinned for everything the
  warm tier does not hold.
- **Bounded warm store**: byte-budgeted + session-bounded LRU; overflow
  demotes stalest-first to cold, an over-budget carry is refused (that
  session pages straight to cold), and demoted/refused sessions resume
  under the documented COLD semantics (a fresh session's answers).
- **Autoscaler discipline**: the membership controller is the PR-14
  pattern applied to ``EnginePool.scale`` — windowed signals out of the
  telemetry history ring, asymmetric hysteresis (one noisy window scales
  up, 2x quiet windows scale down, dead band holds), bounded ±1 steps
  under a cooldown, config floor/ceiling — all driven here with stubbed
  rows, a stub pool, and a fake clock (no subprocesses).
- **Tooling**: lint check 17 (warm tier bounded in code; the dispatch-
  thread paging functions inherit the host-op ban) fixture-tested like
  checks 10-16; the ``cli obs`` "sessions" section; EnginePool.scale's
  spawn/retire mechanics on stub children.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from sharetrade_tpu.config import ConfigError, ModelConfig, ServeConfig
from sharetrade_tpu.models import build_model
from sharetrade_tpu.models.transformer_episode import (
    episode_transformer_policy,
)
from sharetrade_tpu.serve import ServeEngine
from sharetrade_tpu.serve.engine import WarmStore
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
def prices():
    rng = np.random.default_rng(7)
    return rng.uniform(10.0, 20.0, 256).astype(np.float32)


def obs_at(prices, start, t, *, budget=2400.0, shares=0.0):
    lo = start + t
    return np.concatenate(
        [prices[lo:lo + WINDOW],
         np.asarray([budget, shares], np.float32)]).astype(np.float32)


def _engine(model, params, *, slots=2, max_batch=2, warm_bytes=1 << 20,
            warm_max_sessions=4096, registry=None):
    engine = ServeEngine(
        model,
        ServeConfig(max_batch=max_batch, slots=slots, batch_timeout_ms=2.0,
                    warm_bytes=warm_bytes,
                    warm_max_sessions=warm_max_sessions),
        params, registry=registry or MetricsRegistry())
    engine.warmup()
    return engine


def _carry_nbytes(model) -> int:
    return sum(int(np.asarray(leaf).size) * np.asarray(leaf).dtype.itemsize
               for leaf in jax.tree.leaves(model.init_carry()))


# ---------------------------------------------------------------------------
# WarmStore unit semantics (single-owner LRU, bytes + session bounds)


class TestWarmStore:
    def test_lru_demotes_stalest_first_and_hits_refresh(self):
        store = WarmStore(max_bytes=300, max_sessions=64)
        for i, sid in enumerate(("a", "b", "c")):
            assert store.put(sid, rows=sid.upper(), nbytes=100,
                             steps=i + 1) == []
        assert store.bytes == 300 and len(store) == 3
        # A hit removes the entry and hands back the carry WITH its
        # park-time step stamp (the adoption clock travels with the
        # carry — ISSUE 20)...
        assert store.pop("a") == ("A", 1)
        assert store.bytes == 200
        # ...and re-parking makes it the FRESHEST: the next overflow
        # demotes b (now stalest) as a full (sid, rows, nbytes, steps)
        # entry — exactly what the spill tier seals to disk.
        assert store.put("a", "A2", 100, steps=4) == []
        assert store.put("d", "D", 100) == [("b", "B", 100, 2)]
        assert store.demotions == 1
        assert store.pop("b") is None           # demoted = cold
        assert store.pop("a") == ("A2", 4)

    def test_byte_budget_refuses_oversize_carry(self):
        store = WarmStore(max_bytes=100, max_sessions=64)
        assert store.put("big", "X", 101) == []
        assert store.refusals == 1
        assert len(store) == 0 and store.bytes == 0
        assert store.put("junk", "Y", 0) == []  # degenerate size: refused
        assert store.refusals == 2

    def test_session_bound_demotes_even_under_byte_budget(self):
        store = WarmStore(max_bytes=1 << 20, max_sessions=2)
        store.put("a", "A", 10)
        store.put("b", "B", 10)
        assert store.put("c", "C", 10) == [("a", "A", 10, 0)]
        assert len(store) == 2 and store.bytes == 20

    def test_reput_same_session_replaces_bytes(self):
        store = WarmStore(max_bytes=250, max_sessions=64)
        store.put("a", "A", 100)
        store.put("a", "A2", 200)               # replace, not accumulate
        assert store.bytes == 200 and len(store) == 1
        assert store.pop("a") == ("A2", 0)


def test_slot_pool_lru_order_and_pinned_exemption():
    """The hot tier's eviction choice feeds the warm tier: admit picks
    the OLDEST unpinned session — a session pinned by the current batch
    is never the victim even when it is the LRU — so the sid handed to
    the page-out path is exactly the LRU-order victim."""
    from sharetrade_tpu.serve.engine import SlotPool
    pool = SlotPool(capacity=3)
    for sid in ("a", "b", "c"):
        slot, evicted = pool.admit(sid, pinned=set())
        assert evicted is None
    pool.lookup("a")                            # refresh: order b, c, a
    _slot, evicted = pool.admit("d", pinned=set())
    assert evicted == "b"                       # oldest unpinned
    # 'c' is now the LRU but sits in the current batch: exempt.
    _slot, evicted = pool.admit("e", pinned={"c"})
    assert evicted == "a"
    assert pool.evictions == 2


# ---------------------------------------------------------------------------
# engine-level paging (the warm and cold oracles)


def test_config_validation():
    model = build_model(ModelConfig(kind="mlp", hidden_dim=16), OBS_DIM,
                        head="ac")
    params = model.init(jax.random.PRNGKey(1))
    with pytest.raises(ConfigError):
        ServeEngine(model, ServeConfig(warm_bytes=-1), params)
    with pytest.raises(ConfigError):
        ServeEngine(model, ServeConfig(warm_max_sessions=0), params)


def test_warm_unpark_is_bitwise_uninterrupted(episode_model,
                                              episode_params, prices):
    """THE acceptance oracle: evict a session into the warm tier, page
    it back in, and its continuation is BIT FOR BIT that of an
    identically built engine (the same programs) in which it was never
    evicted: device_get → host numpy → device_put → batched scatter
    install hands the warm program the bytes it would have read anyway.
    Against the one-row reference (another program) it is the
    uninterrupted session's answer within the written tolerance — NOT
    the cold fresh-restart the PR-8 contract gives demoted sessions."""
    registry = MetricsRegistry()
    engine = _engine(episode_model, episode_params, registry=registry)
    kept = _engine(episode_model, episode_params)   # serves A alone
    ref = SequentialReference(episode_model, episode_params)

    def step_a(t):
        obs = obs_at(prices, 0, t)
        result = engine.submit("A", obs).wait(30.0)
        same = kept.submit("A", obs).wait(30.0)
        assert result is not None and same is not None
        action, logits = ref.step("A", obs)
        assert result.action == action
        assert np.array_equal(result.logits, same.logits), t
        assert_same_answer(result.logits, logits, ("A", t))
        return obs, result

    try:
        for t in range(3):
            step_a(t)
        # Evict A: B and C take both slots; A's carry pages out through
        # the consumer readback into the warm store.
        for sid, start in (("B", 40), ("C", 80)):
            assert engine.submit(sid, obs_at(prices, start, 0)).wait(30.0)
        # A returns: warm hit, batched scatter re-install, and steps 3..5
        # CONTINUE the uninterrupted session.
        for t in range(3, 6):
            obs, result = step_a(t)
            assert_other_answer(result.logits, ref.step("A-fresh", obs)[1],
                                "the cold restart's")
        counters = registry.counters()
        assert counters["serve_warm_parks_total"] >= 1
        assert counters["serve_warm_hits_total"] >= 1
    finally:
        engine.stop()
        kept.stop()


def test_warm_overflow_demotes_to_cold_restart(episode_model,
                                               episode_params, prices):
    """A warm store sized for exactly ONE carry: the second park demotes
    the first session to cold, which then resumes under the documented
    cold contract (a fresh session's answers); the still-warm session
    continues uninterrupted."""
    nbytes = _carry_nbytes(episode_model)
    registry = MetricsRegistry()
    engine = _engine(episode_model, episode_params, warm_bytes=nbytes,
                     registry=registry)
    ref = SequentialReference(episode_model, episode_params)
    try:
        for t in range(3):
            obs = obs_at(prices, 0, t)
            assert engine.submit("A", obs).wait(30.0)
            ref.step("A", obs)
        obs_b = obs_at(prices, 40, 0)
        assert engine.submit("B", obs_b).wait(30.0)
        ref.step("B", obs_b)
        # C evicts A (parked: warm holds A); D evicts B (parked: A is
        # demoted — one-carry budget).
        assert engine.submit("C", obs_at(prices, 80, 0)).wait(30.0)
        assert engine.submit("D", obs_at(prices, 120, 0)).wait(30.0)
        # B pages back WARM: continues the uninterrupted reference.
        obs = obs_at(prices, 40, 1)
        result = engine.submit("B", obs).wait(30.0)
        assert result is not None
        _, logits = ref.step("B", obs)
        assert_same_answer(result.logits, logits, "B warm")
        # A was demoted: returns COLD — a fresh session fed the same
        # suffix.
        for t in range(3, 5):
            obs = obs_at(prices, 0, t)
            result = engine.submit("A", obs).wait(30.0)
            assert result is not None
            action, logits = ref.step("A-fresh", obs)
            assert result.action == action
            assert_same_answer(result.logits, logits, ("A cold", t))
        assert registry.counters()["serve_warm_demotions_total"] >= 1
    finally:
        engine.stop()


def test_undersized_budget_refuses_and_stays_cold(episode_model,
                                                  episode_params, prices):
    """``warm_bytes`` smaller than one carry: every park is refused, and
    eviction keeps the exact PR-8 cold-restart behavior."""
    registry = MetricsRegistry()
    engine = _engine(episode_model, episode_params, warm_bytes=1,
                     registry=registry)
    ref = SequentialReference(episode_model, episode_params)
    try:
        for t in range(3):
            assert engine.submit("A", obs_at(prices, 0, t)).wait(30.0)
        for sid, start in (("B", 40), ("C", 80)):
            assert engine.submit(sid, obs_at(prices, start, 0)).wait(30.0)
        for t in range(3, 5):
            obs = obs_at(prices, 0, t)
            result = engine.submit("A", obs).wait(30.0)
            assert result is not None
            action, logits = ref.step("A-fresh", obs)
            assert result.action == action
            assert_same_answer(result.logits, logits, ("A cold", t))
        assert engine._warm.refusals >= 1
        assert registry.counters().get("serve_warm_hits_total", 0) == 0
    finally:
        engine.stop()


def test_warm_disabled_for_stateless_model(prices):
    """A stateless (empty-carry) model never enables the warm tier even
    with a budget configured — there is nothing to park."""
    model = build_model(ModelConfig(kind="mlp", hidden_dim=16), OBS_DIM,
                        head="ac")
    params = model.init(jax.random.PRNGKey(1))
    registry = MetricsRegistry()
    engine = _engine(model, params, warm_bytes=1 << 20, registry=registry)
    try:
        assert engine._warm_enabled is False
        for sid, start in (("A", 0), ("B", 40), ("C", 80)):
            assert engine.submit(sid, obs_at(prices, start, 0)).wait(30.0)
        counters = registry.counters()
        assert counters.get("serve_warm_parks_total", 0) == 0
        assert counters.get("serve_warm_misses_total", 0) == 0
    finally:
        engine.stop()


def test_sessions_gauges_published(episode_model, episode_params, prices):
    """The paging surface publishes its population/economics gauges
    through the registry (the Prometheus/`cli obs` surface)."""
    registry = MetricsRegistry()
    engine = _engine(episode_model, episode_params, registry=registry)
    try:
        for sid, start in (("A", 0), ("B", 40), ("C", 80)):
            assert engine.submit(sid, obs_at(prices, start, 0)).wait(30.0)
        # A's park rides the consumer readback into the inbox; the NEXT
        # dispatch commits it to the warm store — drive one hot request.
        assert engine.submit("C", obs_at(prices, 80, 1)).wait(30.0)
        engine._publish_stats(force=True)
        gauges = {k: registry.latest(k)
                  for k in ("serve_sessions_hot", "serve_warm_sessions",
                            "serve_warm_bytes", "serve_warm_budget_bytes",
                            "serve_warm_econ_ms_per_mb")}
        assert gauges["serve_sessions_hot"] == 2.0      # slots=2, full
        assert gauges["serve_warm_sessions"] == 1.0     # A parked
        assert gauges["serve_warm_bytes"] > 0
        assert gauges["serve_warm_budget_bytes"] == float(1 << 20)
        assert gauges["serve_warm_econ_ms_per_mb"] is not None
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# autoscaler decision discipline (stubbed rows, stub pool, fake clock)


class StubPool:
    def __init__(self, target=2, live=2):
        self.target = target
        self._live = live
        self.scaled: list[int] = []

    def live_count(self):
        return self._live

    def scale(self, n):
        self.scaled.append(n)
        self.target = n


def _fleet_cfg(tmp_path, **kw):
    from sharetrade_tpu.config import FrameworkConfig
    cfg = FrameworkConfig()
    cfg.fleet.dir = str(tmp_path / "fleet")
    cfg.fleet.num_engines = kw.pop("num_engines", 4)
    cfg.fleet.autoscale = True
    cfg.fleet.min_engines = kw.pop("min_engines", 1)
    cfg.fleet.autoscale_interval_s = kw.pop("interval", 0.01)
    cfg.fleet.autoscale_cooldown_s = kw.pop("cooldown", 0.0)
    cfg.fleet.autoscale_window = kw.pop("window", 3)
    for k, v in kw.items():
        setattr(cfg.fleet, k, v)
    return cfg


def _rows(n, *, burn=0.0, depth=0.0, engines=2.0, overload=0.0):
    return [{"ts": float(i), "fleet_slo_availability_burn": burn,
             "fleet_queue_depth": depth, "fleet_engines_live": engines,
             "fleet_overload": overload} for i in range(n)]


class TestAutoscalerDecide:
    def _scaler(self, tmp_path, pool=None, **kw):
        from sharetrade_tpu.fleet.autoscale import EngineAutoscaler
        clock = {"t": 1000.0}
        scaler = EngineAutoscaler(pool or StubPool(),
                                  _fleet_cfg(tmp_path, **kw).fleet,
                                  clock=lambda: clock["t"])
        return scaler, clock

    def test_validation(self, tmp_path):
        from sharetrade_tpu.fleet.autoscale import EngineAutoscaler
        with pytest.raises(ConfigError):
            EngineAutoscaler(StubPool(),
                             _fleet_cfg(tmp_path, min_engines=0).fleet)
        with pytest.raises(ConfigError):
            EngineAutoscaler(StubPool(),
                             _fleet_cfg(tmp_path, num_engines=2,
                                        min_engines=3).fleet)
        with pytest.raises(ConfigError):
            EngineAutoscaler(StubPool(),
                             _fleet_cfg(tmp_path, interval=0.0).fleet)

    def test_dead_band_holds(self, tmp_path):
        scaler, _ = self._scaler(tmp_path)
        # Between the low and high thresholds: neither up nor down.
        rows = _rows(6, burn=0.5, depth=2.0)
        assert scaler.decide(rows, current=2) is None

    def test_up_on_sustained_burn_bounded_step(self, tmp_path):
        scaler, _ = self._scaler(tmp_path)
        rows = _rows(3, burn=1.5)
        decision = scaler.decide(rows, current=2)
        assert decision is not None
        target, reason = decision
        assert target == 3                      # ONE engine, never more
        assert "burn" in reason

    def test_one_bad_poll_is_noise(self, tmp_path):
        """Windowed MEAN smooths a transient: one above-threshold poll
        in an otherwise-quiet window holds (a spike big enough to drag
        the whole mean over the line is, by definition, not noise)."""
        scaler, _ = self._scaler(tmp_path)
        rows = _rows(2) + _rows(1, burn=2.0)    # mean 0.67 < burn_high 1.0
        assert scaler.decide(rows, current=2) is None

    def test_up_on_queue_depth_per_engine(self, tmp_path):
        scaler, _ = self._scaler(tmp_path)
        # Aggregate depth 20 over 2 engines = 10/engine >= 8.0 default.
        rows = _rows(3, depth=20.0, engines=2.0)
        target, reason = scaler.decide(rows, current=2)
        assert target == 3 and "queue" in reason

    def test_up_on_overload_majority(self, tmp_path):
        scaler, _ = self._scaler(tmp_path)
        rows = _rows(1) + _rows(2, overload=1.0)
        target, reason = scaler.decide(rows, current=2)
        assert target == 3 and "overload" in reason

    def test_ceiling_and_floor_clamp(self, tmp_path):
        scaler, _ = self._scaler(tmp_path, num_engines=4)
        assert scaler.decide(_rows(3, burn=5.0), current=4) is None
        assert scaler.decide(_rows(6), current=1) is None   # at floor

    def test_down_needs_double_quiet_window(self, tmp_path):
        scaler, _ = self._scaler(tmp_path)
        assert scaler.decide(_rows(3), current=2) is None   # 3 < 2*3 rows
        target, reason = scaler.decide(_rows(6), current=2)
        assert target == 1 and "quiet" in reason

    def test_down_vetoed_by_any_noisy_row(self, tmp_path):
        scaler, _ = self._scaler(tmp_path)
        rows = _rows(5) + _rows(1, burn=0.5)    # one row above burn_low
        assert scaler.decide(rows, current=2) is None

    def test_missing_gauges_read_as_quiet(self, tmp_path):
        scaler, _ = self._scaler(tmp_path)
        rows = [{"ts": float(i)} for i in range(6)]
        target, _reason = scaler.decide(rows, current=2)
        assert target == 1

    def test_step_applies_cooldown_and_writes_state(self, tmp_path):
        pool = StubPool(target=2, live=2)
        scaler, clock = self._scaler(tmp_path, pool=pool, cooldown=10.0)
        rows = _rows(3, burn=2.0)
        clock["t"] += 1.0
        applied = scaler.step(rows=rows)
        assert applied is not None and applied.target == 3
        assert pool.scaled == [3]
        # Within the cooldown: pressure persists but no second apply.
        clock["t"] += 1.0
        assert scaler.step(rows=rows) is None
        assert pool.scaled == [3]
        # Past the cooldown the next bounded step lands.
        clock["t"] += 10.0
        applied = scaler.step(rows=rows)
        assert applied is not None and applied.target == 4
        with open(os.path.join(scaler.dir, "fleet_autoscale.json"),
                  encoding="utf-8") as f:
            state = json.load(f)
        assert state["target"] == 4 and state["decisions"] == 2
        assert state["last_decision"]["action"] == "up"

    def test_interval_rate_limits_reads(self, tmp_path):
        pool = StubPool()
        scaler, clock = self._scaler(tmp_path, pool=pool, interval=5.0)
        rows = _rows(3, burn=2.0)
        clock["t"] += 1.0                       # < interval since init
        assert scaler.step(rows=rows) is None
        clock["t"] += 5.0
        assert scaler.step(rows=rows) is not None

    def test_reads_history_ring_from_disk(self, tmp_path):
        from sharetrade_tpu.fleet.autoscale import EngineAutoscaler
        from sharetrade_tpu.obs.tsdb import FLEET_HISTORY_FILE, TsdbRing
        cfg = _fleet_cfg(tmp_path, window=2)
        os.makedirs(cfg.fleet.dir, exist_ok=True)
        ring = TsdbRing(os.path.join(cfg.fleet.dir, FLEET_HISTORY_FILE))
        for row in _rows(4, burn=3.0, engines=2.0):
            ring.append(row)
        ring.close()
        pool = StubPool()
        clock = {"t": 1000.0}
        scaler = EngineAutoscaler(pool, cfg.fleet, clock=lambda: clock["t"])
        clock["t"] += 1.0
        applied = scaler.step()
        assert applied is not None and applied.action == "up"
        assert pool.scaled == [3]


# ---------------------------------------------------------------------------
# EnginePool.scale mechanics (stub children, no jax bring-up)


_HEALTHY_STUB = r"""
import json, sys, time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
class H(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    def log_message(self, *a): pass
    def do_GET(self):
        body = json.dumps({"ok": True, "queue_depth": 0, "overload": 0,
                           "params_step": 1, "swaps_total": 0}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)
srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
print(json.dumps({"event": "engine_listening", "host": "127.0.0.1",
                  "port": srv.server_address[1]}), flush=True)
srv.serve_forever()
"""


def _stub_spawn(script: str):
    def spawn(engine_id: str, log_path: str):
        with open(log_path, "ab") as log_f:
            return subprocess.Popen([sys.executable, "-c", script],
                                    stdout=log_f,
                                    stderr=subprocess.STDOUT)
    return spawn


def _pump(pool, predicate, timeout_s=15.0, desc="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        pool.poll_once()
        if predicate():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {desc}")


def test_engine_pool_scale_up_down(tmp_path):
    """scale() grows by spawning supervised engines and shrinks by
    retiring the NEWEST members (drain via SIGTERM, classified retired
    — not crashed — by the reaper); scale_events counts both."""
    from sharetrade_tpu.fleet import EnginePool
    cfg = _fleet_cfg(tmp_path, num_engines=1)
    pool = EnginePool(cfg, spawn_fn=_stub_spawn(_HEALTHY_STUB))
    pool.target = 1
    with pool._lock:
        pool._spawn_new_locked()
    try:
        _pump(pool, lambda: "e0" in pool.endpoints(), desc="e0 listening")
        pool.scale(3)
        assert pool.target == 3
        _pump(pool, lambda: len(pool.endpoints()) == 3,
              desc="scale-up to 3 listening")
        restarts_before = pool.restarts_total
        pool.scale(1)
        _pump(pool, lambda: pool.counts()["alive"] == 1
              and pool.counts().get("retired", 0) == 2,
              desc="scale-down retires the two newest")
        # Retirements are NOT crashes: no respawn, no restart count.
        assert pool.restarts_total == restarts_before
        assert pool.scale_events == 2
        assert "e0" in pool.endpoints()
    finally:
        pool.kill_all()
        pool.stop(grace_s=2.0)


def test_engine_pool_scale_refused_when_quiesced(tmp_path):
    from sharetrade_tpu.fleet import EnginePool
    cfg = _fleet_cfg(tmp_path, num_engines=1)
    pool = EnginePool(cfg, spawn_fn=_stub_spawn(_HEALTHY_STUB))
    try:
        pool.quiesce()
        pool.scale(3)
        assert pool.target != 3 or pool.counts()["alive"] == 0
        assert pool.scale_events == 0
    finally:
        pool.kill_all()
        pool.stop(grace_s=2.0)


# ---------------------------------------------------------------------------
# lint check 17 fixture semantics


def test_lint_warm_tier_semantics(tmp_path):
    """Fixture semantics: an unbounded WarmStore (no popitem loop
    conditioned on the budget) is flagged unless the class carries
    ``warm-tier-ok``; the dispatch-thread paging functions inherit the
    check-8 host-op ban with the ``serve-host-ok`` escape; bounded +
    clean code passes."""
    import pathlib

    import lint_hot_loop

    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "engine.py").write_text(
        "class WarmStore:\n"
        "    def put(self, sid, rows, nbytes):\n"
        "        self._lru[sid] = rows\n"        # no eviction at all
        "        return []\n\n"
        "def _drain_park_inbox(self):\n"
        "    x = jax.device_get(rows)\n"          # host op on dispatch
        "def _install_parked(self, rows, slots):\n"
        "    print('installing')\n")
    hits, found = lint_hot_loop.lint_warm_tier(
        target=bad / "engine.py")
    assert found == {"WarmStore", "_drain_park_inbox", "_install_parked"}
    assert {(name, ln) for name, ln, _ in hits} == {
        ("WarmStore", 1), ("_drain_park_inbox", 7),
        ("_install_parked", 9)}

    good = tmp_path / "good"
    good.mkdir()
    (good / "engine.py").write_text(
        "class WarmStore:\n"
        "    def put(self, sid, rows, nbytes):\n"
        "        self._lru[sid] = (rows, nbytes)\n"
        "        while (self.bytes > self.max_bytes\n"
        "               or len(self._lru) > self.max_sessions):\n"
        "            self._lru.popitem(last=False)\n"
        "        return []\n\n"
        "def _drain_park_inbox(self):\n"
        "    self._warm.put('s', 1, 2)\n"
        "def _install_parked(self, rows, slots):\n"
        "    return self._install_fn(self._pool, rows, slots)\n")
    hits, found = lint_hot_loop.lint_warm_tier(target=good / "engine.py")
    assert hits == []

    marked = tmp_path / "marked"
    marked.mkdir()
    (marked / "engine.py").write_text(
        "# warm-tier-ok: bound lives in the caller's byte ledger\n"
        "class WarmStore:\n"
        "    def put(self, sid, rows, nbytes):\n"
        "        self._lru[sid] = rows\n\n"
        "def _drain_park_inbox(self):\n"
        "    x = jax.device_get(r)  # serve-host-ok: fixture\n"
        "def _install_parked(self):\n"
        "    pass\n")
    hits, _found = lint_hot_loop.lint_warm_tier(
        target=marked / "engine.py")
    assert hits == []


def test_lint_check17_clean_on_real_engine():
    import lint_hot_loop
    hits, found = lint_hot_loop.lint_warm_tier()
    assert hits == []
    assert {"WarmStore", "_drain_park_inbox", "_install_parked"} <= found


# ---------------------------------------------------------------------------
# cli obs "sessions" section


def test_obs_sessions_section(tmp_path):
    """`cli obs` grows a sessions section: tier populations, warm
    hit/miss, bytes vs budget, economics gauge — plus the autoscaler
    state file folded in as sessions.autoscaler."""
    from sharetrade_tpu.config import FrameworkConfig
    from sharetrade_tpu.obs import build_obs, summarize_run_dir

    cfg = FrameworkConfig()
    cfg.obs.enabled = True
    cfg.obs.dir = str(tmp_path / "run")
    registry = MetricsRegistry()
    bundle = build_obs(cfg, registry)
    registry.record_many({
        "serve_sessions_hot": 16.0, "serve_warm_sessions": 48.0,
        "serve_warm_bytes": 6144.0, "serve_warm_budget_bytes": 65536.0,
        "serve_warm_econ_ms_per_mb": 12.5})
    registry.inc("serve_warm_parks_total", 80)
    registry.inc("serve_warm_hits_total", 60)
    registry.inc("serve_warm_misses_total", 20)
    registry.inc("serve_warm_demotions_total", 4)
    registry.inc("serve_prefills_total", 24)
    bundle.flush()
    bundle.close()
    with open(os.path.join(cfg.obs.dir, "fleet_autoscale.json"), "w",
              encoding="utf-8") as f:
        json.dump({"ts": 0.0, "target": 3, "actual": 3, "floor": 1,
                   "ceiling": 4, "decisions": 2,
                   "last_decision": {"action": "up", "from": 2, "to": 3,
                                     "reason": "burn"}}, f)
    summary = summarize_run_dir(cfg.obs.dir)
    sessions = summary["sessions"]
    assert sessions["hot"] == 16.0
    assert sessions["warm"] == 48.0
    assert sessions["warm_hit_rate"] == 0.75
    assert sessions["warm_demotions_total"] == 4.0
    assert sessions["econ_ms_per_mb"] == 12.5
    assert sessions["autoscaler"]["target"] == 3
    assert sessions["autoscaler"]["last_decision"]["action"] == "up"
