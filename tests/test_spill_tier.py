"""Crash-consistent disk spill tier + warm-carry migration (ISSUE 20).

The load-bearing contracts:

- **Record discipline**: a SpillArena record is sealed atomically
  (tmp → fsync → rename), carries a CRC over meta+payload and the
  session's step stamp, and is consumed on take — a torn, corrupt,
  wrong-model, or digest-colliding record NEVER hands back bytes, it
  demotes to cold; a stale stamp (or, with no fleet clock, a foreign
  incarnation) likewise. Injected corruption can change latency, never
  the answer.
- **Adoption bitwise oracle**: engine A drains (stop → page_out_all →
  sealed arena), engine B adopts every session via the router-carried
  ``session_clock`` — B's responses are bit-identical to those of a
  single uninterrupted engine built the same way (the same programs on
  the bytes the disk round trip preserved) fed the same requests, and the
  one-row reference's answers within the written tolerance
  (``serving_parity.assert_same_answer``).
- **Drain ordering**: ``page_out_all()`` REFUSES while the worker
  threads are alive (drain → stop() → page_out_all() → exit 75) and,
  post-stop, seals every surviving carry — hot slots, RAM-warm, and
  in-flight inbox rows.
- **Router half of the contract**: the session clock ticks only on a
  200, survives engine death (affinity detached, clock kept), and the
  engine-side spill counters fold into same-named ``fleet_`` counters
  that the kill soak reconciles exactly (restart rebases at zero).
- **Tooling**: lint check 19 fixture semantics (arena I/O confinement,
  CRC'd publishes, no in-memory record index) and the ``cli obs``
  sessions.spill section.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from sharetrade_tpu.config import (
    ConfigError,
    FleetConfig,
    ModelConfig,
    ServeConfig,
)
from sharetrade_tpu.fleet import FleetRouter, StaticEndpoints
from sharetrade_tpu.fleet import wire
from sharetrade_tpu.fleet.router import _EngineView
from sharetrade_tpu.models.transformer_episode import (
    episode_transformer_policy,
)
from sharetrade_tpu.serve import ServeEngine
from sharetrade_tpu.serve.engine import WarmStore
from sharetrade_tpu.serve.spill import (
    SPILL_SUFFIX,
    SpillArena,
    record_name,
    sweep_debris,
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


def obs_at(prices, start, t):
    lo = start + t
    return np.concatenate(
        [prices[lo:lo + WINDOW],
         np.asarray([2400.0, 0.0], np.float32)]).astype(np.float32)


def _carry_nbytes(model) -> int:
    return sum(int(np.asarray(leaf).size) * np.asarray(leaf).dtype.itemsize
               for leaf in jax.tree.leaves(model.init_carry()))


def _spill_engine(model, params, spill_dir, *, warm_carries=1, slots=2,
                  max_batch=2, registry=None):
    engine = ServeEngine(
        model,
        ServeConfig(max_batch=max_batch, slots=slots, batch_timeout_ms=2.0,
                    warm_bytes=warm_carries * _carry_nbytes(model),
                    warm_max_sessions=4096,
                    spill_dir=str(spill_dir), spill_bytes=1 << 26),
        params, registry=registry or MetricsRegistry())
    engine.warmup()
    return engine


def _sealed(spill_dir) -> list[str]:
    return sorted(f for f in os.listdir(spill_dir)
                  if f.endswith(SPILL_SUFFIX))


# ---------------------------------------------------------------------------
# SpillArena unit semantics (record discipline, no engine)


def _leaves(nbytes: int, seed: int = 0) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.random(nbytes // 8, dtype=np.float64).view(np.float64)]


def _arena(root, *, nbytes=64, incarnation="inc-a", max_bytes=1 << 20):
    return SpillArena(str(root), max_bytes=max_bytes,
                      record_nbytes=nbytes, incarnation=incarnation)


class TestSpillArena:
    def test_put_take_roundtrip_consumes(self, tmp_path):
        arena = _arena(tmp_path)
        leaves = _leaves(64)
        assert arena.put("s0", leaves, steps=7)
        assert arena.probe("s0")
        payload, steps, reason, foreign = arena.take("s0", expected_steps=7)
        assert reason == "hit" and not foreign and steps == 7
        assert payload == b"".join(
            np.ascontiguousarray(x).tobytes() for x in leaves)
        # Consume-on-take: adopted at most once.
        assert not arena.probe("s0")
        assert arena.take("s0", expected_steps=7)[2] == "miss"
        assert arena.takes == 1 and arena.sessions == 0

    def test_stale_stamp_consumed_and_demotes(self, tmp_path):
        arena = _arena(tmp_path)
        arena.put("s0", _leaves(64), steps=7)
        payload, steps, reason, _ = arena.take("s0", expected_steps=6)
        assert payload is None and reason == "stale" and steps == 7
        # The safe direction: the record is gone, the session lands cold
        # and can never read this stamp again.
        assert not arena.probe("s0")
        assert arena.stale == 1

    def test_no_clock_accepts_own_incarnation_only(self, tmp_path):
        writer = _arena(tmp_path, incarnation="inc-a")
        writer.put("s0", _leaves(64), steps=3)
        # A clock-less take from a DIFFERENT incarnation is stale (the
        # supervised-restart contract: a rebuilt engine serves only cold
        # re-entries without the fleet clock vouching for the record).
        other = _arena(tmp_path, incarnation="inc-b")
        payload, _steps, reason, foreign = other.take("s0")
        assert payload is None and reason == "stale" and foreign
        # Same incarnation, no clock: the engine-local warm continuation.
        writer.put("s1", _leaves(64, seed=1), steps=5)
        payload, steps, reason, foreign = writer.take("s1")
        assert reason == "hit" and not foreign and steps == 5

    def test_foreign_record_with_matching_clock_adopts(self, tmp_path):
        _arena(tmp_path, incarnation="inc-a").put("s0", _leaves(64), steps=9)
        payload, steps, reason, foreign = _arena(
            tmp_path, incarnation="inc-b").take("s0", expected_steps=9)
        assert reason == "hit" and foreign and steps == 9
        assert payload is not None

    def test_corrupt_record_consumed(self, tmp_path):
        from soak_common import flip_byte

        arena = _arena(tmp_path)
        arena.put("s0", _leaves(64), steps=1)
        flip_byte(str(tmp_path / record_name("s0")), offset_frac=0.9)
        payload, _steps, reason, _ = arena.take("s0", expected_steps=1)
        assert payload is None and reason == "corrupt"
        assert not arena.probe("s0")
        assert arena.corrupt == 1

    def test_torn_record_consumed(self, tmp_path):
        arena = _arena(tmp_path)
        arena.put("s0", _leaves(64), steps=1)
        path = tmp_path / record_name("s0")
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
        assert arena.take("s0", expected_steps=1)[2] == "corrupt"
        # Zero-length (crashed writer raced the rename): same demotion.
        arena.put("s1", _leaves(64, seed=1), steps=1)
        with open(tmp_path / record_name("s1"), "r+b") as f:
            f.truncate(0)
        assert arena.take("s1", expected_steps=1)[2] == "corrupt"

    def test_wrong_model_footprint(self, tmp_path):
        # Writer refuses a payload that is not ITS record size...
        arena = _arena(tmp_path, nbytes=64)
        assert not arena.put("s0", _leaves(32), steps=1)
        assert arena.put_refusals == 1 and not arena.probe("s0")
        # ...and a reader with a different carry template fails the
        # length check — a different model/precision simply lands cold.
        arena.put("s0", _leaves(64), steps=1)
        reader = _arena(tmp_path, nbytes=128)
        assert reader.take("s0", expected_steps=1)[2] == "corrupt"

    def test_digest_rendezvous_never_crosses_sessions(self, tmp_path):
        arena = _arena(tmp_path)
        arena.put("s0", _leaves(64), steps=1)
        # A record renamed onto another session's slot (the digest-
        # collision stand-in) must read corrupt, never as s1's state.
        os.replace(tmp_path / record_name("s0"),
                   tmp_path / record_name("s1"))
        assert arena.take("s1", expected_steps=1)[2] == "corrupt"

    def test_byte_budget_refuses(self, tmp_path):
        arena = _arena(tmp_path, max_bytes=200)   # header+meta+64 > 200/2
        assert arena.put("s0", _leaves(64), steps=1)
        assert not arena.put("s1", _leaves(64, seed=1), steps=1)
        assert arena.put_refusals == 1
        assert _sealed(tmp_path) == [record_name("s0")]

    def test_scan_usage_reanchors_counters(self, tmp_path):
        arena = _arena(tmp_path)
        arena.put("s0", _leaves(64), steps=1)
        arena.put("s1", _leaves(64, seed=1), steps=2)
        total, count = arena.scan_usage()
        assert count == 2
        assert total == sum(
            os.path.getsize(tmp_path / f) for f in _sealed(tmp_path))
        # A peer's out-of-band delete drifts the incremental counters;
        # the next scan re-anchors them.
        os.unlink(tmp_path / record_name("s0"))
        assert arena.scan_usage()[1] == 1
        assert arena.sessions == 1

    def test_sweep_debris_only_tmp(self, tmp_path):
        arena = _arena(tmp_path)
        arena.put("s0", _leaves(64), steps=1)
        (tmp_path / "abc.spill.tmp-111").write_bytes(b"torn")
        (tmp_path / "def.spill.tmp-222").write_bytes(b"torn")
        # Pid-specific sweep (pool reaping one dead engine)...
        assert sweep_debris(str(tmp_path), pid=111) == 1
        # ...then the fleet-start full sweep; sealed records untouched.
        assert sweep_debris(str(tmp_path)) == 1
        assert _sealed(tmp_path) == [record_name("s0")]
        assert arena.probe("s0")

    def test_record_name_is_the_rendezvous(self, tmp_path):
        # Any engine computes the same name from the session id alone.
        assert record_name("s0") == record_name("s0")
        assert record_name("s0") != record_name("s1")
        assert record_name("s0").endswith(SPILL_SUFFIX)


# ---------------------------------------------------------------------------
# WarmStore: the spill tier's RAM half (drop-while-parked)


def test_warm_discard_while_parked_never_resurrects():
    store = WarmStore(max_bytes=1000, max_sessions=8)
    store.put("a", "A", 100, steps=3)
    store.discard("a")
    assert store.pop("a") is None and len(store) == 0 and store.bytes == 0
    # Idempotent on a miss.
    store.discard("a")
    assert store.bytes == 0


# ---------------------------------------------------------------------------
# engine-level: adoption bitwise oracle + corruption/stale demotion


def test_spill_adoption_is_bitwise_uninterrupted(episode_model,
                                                 episode_params, prices,
                                                 tmp_path):
    """Engine A thrashes 4 sessions through a one-carry warm budget (the
    overflow spills to disk), then drains: stop → page_out_all seals the
    whole population. Engine B — a different process stand-in with its
    own incarnation — adopts every session via the router-carried
    session clock, and its responses are BIT FOR BIT those of ONE
    uninterrupted engine built the same way (the same programs reading
    the bytes the disk round trip preserved) fed the same requests, and
    the one-row reference's answers within the written tolerance."""
    model, params = episode_model, episode_params
    ref = SequentialReference(model, params)
    arena = tmp_path / "handed_over"
    whole = _spill_engine(model, params, tmp_path / "whole")
    sids = [(f"s{i}", i * 3) for i in range(4)]
    clock: dict = {}

    def send(engine, sid, t0, t):
        obs = obs_at(prices, t0, t)
        stamp = clock.get(sid) or None
        result = engine.submit(sid, obs, session_clock=stamp).wait(30)
        same = whole.submit(sid, obs, session_clock=stamp).wait(30)
        assert np.array_equal(result.logits, same.logits), (sid, t)
        assert_same_answer(result.logits, ref.step(sid, obs)[1], (sid, t))
        clock[sid] = clock.get(sid, 0) + 1

    reg_a = MetricsRegistry()
    a = _spill_engine(model, params, arena, registry=reg_a)
    for rnd in range(3):
        for sid, t0 in sids:
            send(a, sid, t0, rnd)
    a.stop(timeout_s=30.0)
    out = a.page_out_all()
    assert out["refused"] == 0
    # Warm handoff: one sealed record per session, none lost.
    assert len(_sealed(arena)) == len(sids)

    reg_b = MetricsRegistry()
    b = _spill_engine(model, params, arena, registry=reg_b)
    try:
        for rnd in range(3, 5):
            for sid, t0 in sids:
                send(b, sid, t0, rnd)
        counters = reg_b.counters()
        # Every session's first request on B was a clocked foreign-
        # incarnation disk hit — a warm ADOPTION, counted exactly once.
        assert counters.get("serve_adopt_warm_total", 0) == len(sids)
        assert counters.get("serve_adopt_cold_total", 0) == 0
        assert counters.get("serve_spill_hits_total", 0) >= len(sids)
    finally:
        b.stop(drain=False, timeout_s=30.0)
        whole.stop(drain=False, timeout_s=30.0)


def test_corrupt_and_stale_records_land_cold_bitwise_fresh(
        episode_model, episode_params, prices, tmp_path):
    """Injected corruption (and a stale clock) can change LATENCY, never
    the answer: the adopting engine demotes the session to the
    cold-restart path and its response is a fresh session's first step
    (and not the sealed carry's continuation) — with the per-reason
    counters naming what happened."""
    from soak_common import flip_byte

    model, params = episode_model, episode_params
    ref = SequentialReference(model, params)
    a = _spill_engine(model, params, tmp_path)
    for sid, t0 in (("c0", 0), ("s0", 8)):
        for t in range(3):
            obs = obs_at(prices, t0, t)
            result = a.submit(sid, obs).wait(30)
            assert_same_answer(result.logits, ref.step(sid, obs)[1],
                               (sid, t))
    a.stop(timeout_s=30.0)
    assert a.page_out_all()["written"] == 2
    flip_byte(str(tmp_path / record_name("c0")), offset_frac=0.99)

    reg_b = MetricsRegistry()
    b = _spill_engine(model, params, tmp_path, registry=reg_b)
    try:
        # c0: record exists, clock matches, CRC does not → corrupt →
        # cold restart: a fresh session's first step, not step 4 of the
        # sealed carry.
        obs = obs_at(prices, 0, 3)
        result = b.submit("c0", obs, session_clock=3).wait(30)
        assert_same_answer(result.logits, ref.step("c0-fresh", obs)[1], "c0")
        assert_other_answer(result.logits, ref.step("c0", obs)[1], "c0")
        # s0: record intact but the clock disagrees with the stamp (the
        # router saw fewer completions than the seal) → stale → cold.
        obs = obs_at(prices, 8, 3)
        result = b.submit("s0", obs, session_clock=2).wait(30)
        assert_same_answer(result.logits, ref.step("s0-fresh", obs)[1], "s0")
        assert_other_answer(result.logits, ref.step("s0", obs)[1], "s0")
        counters = reg_b.counters()
        assert counters.get("serve_spill_corrupt_total", 0) == 1
        assert counters.get("serve_spill_stale_total", 0) == 1
        assert counters.get("serve_adopt_warm_total", 0) == 0
        # Both clocked re-entries that missed warm are cold adoptions.
        assert counters.get("serve_adopt_cold_total", 0) == 2
        # Consumed either way: nothing left to adopt.
        assert _sealed(tmp_path) == []
    finally:
        b.stop(drain=False, timeout_s=30.0)


def test_park_inbox_commit_races_eviction_bitwise(episode_model,
                                                  episode_params, prices):
    """Two sessions ping-pong through ONE slot: every request evicts the
    other session, whose page-out readback races the next admission.
    The park-inbox commit points (collect-top and pre-admission) must
    make every parked carry visible before its session re-enters — the
    whole exchange gives the uninterrupted reference's answers."""
    model, params = episode_model, episode_params
    reg = MetricsRegistry()
    engine = ServeEngine(
        model,
        ServeConfig(max_batch=1, slots=1, batch_timeout_ms=2.0,
                    warm_bytes=2 * _carry_nbytes(model),
                    warm_max_sessions=4096),
        params, registry=reg)
    engine.warmup()
    try:
        ref = SequentialReference(model, params)
        for t in range(6):
            for sid, t0 in (("a", 0), ("b", 16)):
                obs = obs_at(prices, t0, t)
                result = engine.submit(sid, obs).wait(30)
                assert_same_answer(result.logits, ref.step(sid, obs)[1],
                                   (sid, t))
        counters = reg.counters()
        # The race was real: the loop parked and unparked repeatedly.
        assert counters.get("serve_warm_parks_total", 0) >= 10
        assert counters.get("serve_warm_hits_total", 0) >= 10
    finally:
        engine.stop(drain=False, timeout_s=30.0)


def test_page_out_all_refuses_until_stopped(episode_model, episode_params,
                                            prices, tmp_path):
    """The drain ORDERING contract (satellite of ISSUE 20): drain →
    stop() → page_out_all() → exit 75. A live dispatcher/consumer still
    owns the session stores, so the page-out refuses loudly; after
    stop() it seals the full surviving population — hot AND warm."""
    model, params = episode_model, episode_params
    engine = _spill_engine(model, params, tmp_path, warm_carries=2,
                           slots=2)
    # 3 sessions on 2 slots: two stay hot, one is parked RAM-warm.
    for sid, t0 in (("h0", 0), ("h1", 8), ("w0", 16)):
        engine.submit(sid, obs_at(prices, t0, 0)).wait(30)
    with pytest.raises(RuntimeError, match="page_out_all\\(\\) before "
                                           "stop\\(\\)"):
        engine.page_out_all()
    assert _sealed(tmp_path) == []      # refused means NOTHING written
    assert engine.stop(timeout_s=30.0)
    out = engine.page_out_all()
    assert out["written"] == 3 and out["refused"] == 0
    assert len(_sealed(tmp_path)) == 3
    for sid in ("h0", "h1", "w0"):
        assert record_name(sid) in _sealed(tmp_path)


def test_spill_config_validation(tmp_path):
    mlp = ModelConfig(kind="mlp", hidden_dim=8, num_layers=1)
    from sharetrade_tpu.models import build_model

    model = build_model(mlp, OBS_DIM)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ConfigError, match="spill_bytes"):
        ServeEngine(model, ServeConfig(spill_bytes=-1), params)
    with pytest.raises(ConfigError, match="spill_dir requires the warm"):
        ServeEngine(model, ServeConfig(spill_dir=str(tmp_path),
                                       warm_bytes=0), params)


# ---------------------------------------------------------------------------
# router: the session clock (the adoption stamp's fleet half)


def _router(reg=None):
    return FleetRouter(StaticEndpoints({}), FleetConfig(),
                       reg or MetricsRegistry(), workdir="")


class TestRouterSessionClock:
    def test_clock_ticks_on_200_only(self):
        router = _router()
        assert router.session_clock("s") == 0
        router.finish_relay("s", "e0", False, wire.STATUS_OK, b"{}")
        router.finish_relay("s", "e0", False, wire.STATUS_OK, b"{}")
        assert router.session_clock("s") == 2
        # A protocol refusal never touched the carry: clock holds.
        router.finish_relay("s", "e0", False, wire.STATUS_UNAVAILABLE,
                            b"{}")
        assert router.session_clock("s") == 2

    def test_clock_survives_engine_death(self):
        router = _router()
        router.finish_relay("s", "e0", False, wire.STATUS_OK, b"{}")
        router._drop_engine_affinity("e0")
        # Detached from the dead engine, clock kept — the key that
        # unlocks warm adoption on the next engine.
        assert router._affinity["s"] == (None, 1)
        assert router.session_clock("s") == 1

    def test_engine_id_spliced_into_reply(self):
        router = _router()
        status, reply = router.finish_relay(
            "s", "e7", False, wire.STATUS_OK, b'{"logits":[1]}')
        assert status == wire.STATUS_OK
        assert json.loads(reply)["engine"] == "e7"

    def test_counter_deltas_fold_and_restart_rebase(self):
        reg = MetricsRegistry()
        router = _router(reg)
        view = _EngineView("e0", ("h", 1))

        def metrics(total, warm, corrupt=0.0):
            return {"counters": {
                "sharetrade_serve_requests_total": total,
                "sharetrade_serve_adopt_warm_total": warm,
                "sharetrade_serve_spill_corrupt_total": corrupt}}

        # First scrape of a new engine folds everything since boot.
        router._counter_deltas(view, metrics(10.0, 3.0))
        assert reg.counters()["fleet_adopt_warm_total"] == 3
        # Steady state folds the window delta.
        router._counter_deltas(view, metrics(20.0, 5.0, corrupt=1.0))
        counters = reg.counters()
        assert counters["fleet_adopt_warm_total"] == 5
        assert counters["fleet_spill_corrupt_total"] == 1
        # A restart (total shrank) rebases at zero: the fresh counters
        # ARE the window — nothing double-counted, nothing lost.
        router._counter_deltas(view, metrics(2.0, 2.0))
        assert reg.counters()["fleet_adopt_warm_total"] == 7


# ---------------------------------------------------------------------------
# lint check 19 fixture semantics


def test_lint_spill_arena_semantics(tmp_path):
    """Fixture semantics: arena record I/O outside serve/spill.py is
    flagged unless marked ``spill-io-ok``; a SpillArena method that
    publishes via os.replace without a crc32 call is flagged; an
    in-memory container assigned in __init__ needs ``spill-index-ok``;
    a compliant module passes all three."""
    import lint_hot_loop

    root = tmp_path / "bad"
    (root / "serve").mkdir(parents=True)
    (root / "other.py").write_text(
        "import os\n"
        "def sneaky(root, sid):\n"
        "    return open(os.path.join(root, record_name(sid)))\n")
    (root / "serve" / "spill.py").write_text(
        "import os, zlib\n"
        "class SpillArena:\n"
        "    def __init__(self):\n"
        "        self._index = {}\n"
        "    def put(self, sid, data):\n"
        "        os.replace('a.tmp', 'a')\n")
    io_bad, crc_bad, index_bad, found = lint_hot_loop.lint_spill_arena(
        root=root)
    assert found == {"SpillArena"}
    assert [(path, ln) for path, ln, _ in io_bad] == [("other.py", 3)]
    assert len(crc_bad) == 1 and "without calling crc32" in crc_bad[0][2]
    assert [(ln, text) for _, ln, text in index_bad] == [
        (4, "self._index = {}")]

    good = tmp_path / "good"
    (good / "serve").mkdir(parents=True)
    (good / "pool.py").write_text(
        "# spill-io-ok: the supervisor's debris sweep\n"
        "def sweep(root, sid):\n"
        "    return record_name(sid)\n")
    (good / "serve" / "spill.py").write_text(
        "import os, zlib\n"
        "class SpillArena:\n"
        "    def __init__(self):\n"
        "        # counters only  # spill-index-ok\n"
        "        self.stats = dict(puts=0)\n"
        "    def put(self, sid, data):\n"
        "        crc = zlib.crc32(data)\n"
        "        os.replace('a.tmp', 'a')\n")
    io_bad, crc_bad, index_bad, _found = lint_hot_loop.lint_spill_arena(
        root=good)
    assert io_bad == [] and crc_bad == [] and index_bad == []

    # No sealed publish at all is ALSO a finding (the crash-consistency
    # claim rests on the rename), and a missing module even more so.
    sealed_less = tmp_path / "sealedless"
    (sealed_less / "serve").mkdir(parents=True)
    (sealed_less / "serve" / "spill.py").write_text(
        "class SpillArena:\n"
        "    def put(self, sid, data):\n"
        "        open('a', 'wb').write(data)\n")
    _io, crc_bad, _idx, _found = lint_hot_loop.lint_spill_arena(
        root=sealed_less)
    assert any("no os.replace publish" in text for _, _, text in crc_bad)
    _io, crc_bad, _idx, found = lint_hot_loop.lint_spill_arena(
        root=tmp_path / "void")
    assert found == set()
    assert any("missing" in text for _, _, text in crc_bad)


def test_lint_check19_clean_on_real_repo():
    import lint_hot_loop

    io_bad, crc_bad, index_bad, found = lint_hot_loop.lint_spill_arena()
    assert io_bad == [] and crc_bad == [] and index_bad == []
    assert "SpillArena" in found


# ---------------------------------------------------------------------------
# cli obs: the sessions.spill section


def test_obs_spill_section(tmp_path):
    from sharetrade_tpu.config import FrameworkConfig
    from sharetrade_tpu.obs import build_obs, summarize_run_dir

    cfg = FrameworkConfig()
    cfg.obs.enabled = True
    cfg.obs.dir = str(tmp_path / "run")
    registry = MetricsRegistry()
    bundle = build_obs(cfg, registry)
    registry.record_many({
        "serve_sessions_hot": 2.0, "serve_warm_sessions": 3.0,
        "serve_warm_bytes": 4096.0, "serve_warm_budget_bytes": 8192.0,
        "serve_spill_sessions": 5.0, "serve_spill_bytes": 20480.0,
        "serve_spill_budget_bytes": 1048576.0})
    registry.inc("serve_warm_hits_total", 6)
    registry.inc("serve_spill_puts_total", 9)
    registry.inc("serve_spill_hits_total", 4)
    registry.inc("serve_spill_corrupt_total", 1)
    registry.inc("serve_adopt_warm_total", 4)
    registry.inc("serve_adopt_cold_total", 2)
    bundle.flush()
    bundle.close()
    spill = summarize_run_dir(cfg.obs.dir)["sessions"]["spill"]
    assert spill["sessions"] == 5.0
    assert spill["bytes"] == 20480.0
    assert spill["budget_bytes"] == 1048576.0
    assert spill["puts_total"] == 9.0
    assert spill["hits_total"] == 4.0
    assert spill["corrupt_total"] == 1.0
    assert spill["adopt_warm_total"] == 4.0
    assert spill["adopt_cold_total"] == 2.0


def test_obs_no_spill_section_without_tier(tmp_path):
    from sharetrade_tpu.config import FrameworkConfig
    from sharetrade_tpu.obs import build_obs, summarize_run_dir

    cfg = FrameworkConfig()
    cfg.obs.enabled = True
    cfg.obs.dir = str(tmp_path / "run")
    registry = MetricsRegistry()
    bundle = build_obs(cfg, registry)
    registry.record_many({"serve_sessions_hot": 2.0,
                          "serve_warm_sessions": 3.0,
                          "serve_warm_bytes": 1.0,
                          "serve_warm_budget_bytes": 2.0})
    registry.inc("serve_warm_hits_total", 1)
    bundle.flush()
    bundle.close()
    assert "spill" not in summarize_run_dir(cfg.obs.dir)["sessions"]
