"""Replay data plane (ISSUE 9): device-resident prioritized replay
(ops/sum_tree.py + the DQN PER mode), bounded journal (segment rotation +
retirement), streaming ingest, and their guards.

The pinned claims:

1. **Uniform default is bit-identical to pre-PR** — the golden trajectory
   captured at the pre-data-plane commit
   (tests/golden/replay_uniform_golden.json) reproduces EXACTLY, the same
   contract (and capture recipe) as the precision PR's fp32 golden.
2. **The sum-tree is exact** — after any batched update sequence every
   internal node equals the sum of its two children bit-for-bit (so the
   root IS the total mass), sampled frequencies track priorities, and
   massless (masked / never-written) leaves are never sampled.
3. **Rotation keeps the torn-tail contract per segment** — a crash at ANY
   byte offset of the newest segment recovers an exact record prefix;
   sealed segments are immutable and retirement never touches the
   replay-capacity horizon.
4. **Streaming ingest converges to the batch load** — consuming a feed
   incrementally (partial lines included) yields exactly the series a
   one-shot CSV load of the final file returns.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sharetrade_tpu.agents import build_agent
from sharetrade_tpu.config import ConfigError, FrameworkConfig
from sharetrade_tpu.data.journal import Journal, segment_paths
from sharetrade_tpu.data.synthetic import synthetic_price_series
from sharetrade_tpu.data.transitions import (
    append_transitions,
    count_transition_rows,
    read_tail_transitions,
    retire_transition_segments,
)
from sharetrade_tpu.env import trading
from sharetrade_tpu.ops import sum_tree

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "replay_uniform_golden.json")


def _tree_digest(tree):
    h = hashlib.sha256()
    for path, leaf in sorted(
            jax.tree_util.tree_flatten_with_path(tree)[0],
            key=lambda kv: str(kv[0])):
        a = np.asarray(leaf)
        h.update(str(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _golden_cfg(mode: str = "uniform") -> FrameworkConfig:
    cfg = FrameworkConfig()
    cfg.learner.algo = "dqn"
    cfg.parallel.num_workers = 4
    cfg.env.window = 16
    cfg.runtime.chunk_steps = 25
    cfg.model.hidden_dim = 16
    cfg.learner.replay_capacity = 512
    cfg.learner.replay_batch = 32
    cfg.learner.target_update_every = 10
    cfg.learner.replay_priority = mode
    return cfg


def _golden_env(cfg):
    series = synthetic_price_series(length=256, seed=7)
    return trading.env_from_prices(series.prices, window=cfg.env.window,
                                   initial_budget=cfg.env.initial_budget)


def _tbatch(n, obs_dim=5, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, obs_dim)).astype(np.float32),
            rng.integers(0, 3, n).astype(np.int32),
            rng.standard_normal(n).astype(np.float32),
            rng.standard_normal((n, obs_dim)).astype(np.float32))


# ---------------------------------------------------------------------------
# sum-tree properties
# ---------------------------------------------------------------------------

class TestSumTree:
    def test_leaf_count_power_of_two(self):
        assert sum_tree.leaf_count(1) == 1
        assert sum_tree.leaf_count(2) == 2
        assert sum_tree.leaf_count(3) == 4
        assert sum_tree.leaf_count(4096) == 4096
        assert sum_tree.leaf_count(4097) == 8192
        with pytest.raises(ValueError):
            sum_tree.leaf_count(0)

    def test_total_mass_exact_under_batched_updates(self):
        """After ANY update sequence — duplicates and masks included —
        every internal node equals the sum of its two children
        bit-for-bit, and the whole tree equals a from-scratch rebuild of
        its own leaves. (Exactness is what makes the stratified descent's
        residual-mass arithmetic safe.)"""
        rng = np.random.default_rng(0)
        cap = 256
        tree = sum_tree.from_leaves(
            jnp.asarray(rng.random(cap, dtype=np.float32)))
        for it in range(6):
            b = 32
            idx = rng.integers(0, cap, b).astype(np.int32)
            vals = (rng.random(b) * 3).astype(np.float32).copy()
            for i in range(b):   # duplicate indices carry identical values
                vals[i] = vals[np.flatnonzero(idx == idx[i])[0]]
            mask = jnp.asarray(rng.random(b) > 0.3)
            tree = sum_tree.set_priorities(
                tree, jnp.asarray(idx), jnp.asarray(vals), mask)
            levels = [np.asarray(l) for l in tree.levels]
            for k in range(1, len(levels)):
                paired = levels[k - 1].reshape(-1, 2)
                np.testing.assert_array_equal(
                    levels[k], paired[:, 0] + paired[:, 1],
                    err_msg=f"iteration {it}, level {k}")
            rebuilt = sum_tree.from_leaves(tree.leaves)
            for a, b2 in zip(tree.levels, rebuilt.levels):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b2))

    def test_masked_rows_leave_slots_untouched(self):
        tree = sum_tree.from_leaves(jnp.arange(1.0, 9.0))
        before = np.asarray(tree.leaves).copy()
        tree = sum_tree.set_priorities(
            tree, jnp.asarray([2, 5]), jnp.asarray([100.0, 200.0]),
            mask=jnp.asarray([False, True]))
        after = np.asarray(tree.leaves)
        assert after[2] == before[2]            # masked: untouched
        assert after[5] == 200.0                # unmasked: written
        assert float(tree.total) == float(after.sum())

    def test_sampled_frequencies_match_priorities(self):
        """Empirical stratified-sample frequencies converge to the
        normalized priorities (the PER sampling contract)."""
        priorities = np.zeros(64, np.float32)
        priorities[:16] = np.linspace(0.5, 8.0, 16, dtype=np.float32)
        tree = sum_tree.from_leaves(jnp.asarray(priorities))
        counts = np.zeros(64)
        batch, draws = 32, 300
        sample = jax.jit(lambda t, k: sum_tree.sample_stratified(t, k, batch))
        for d in range(draws):
            idx, probs = sample(tree, jax.random.PRNGKey(d))
            np.add.at(counts, np.asarray(idx), 1)
        freq = counts / counts.sum()
        expect = priorities / priorities.sum()
        # Within-band: absolute 2% everywhere, relative 15% on the
        # heavier-than-average leaves.
        np.testing.assert_allclose(freq, expect, atol=0.02)
        heavy = expect > expect.mean()
        np.testing.assert_allclose(freq[heavy], expect[heavy], rtol=0.15)

    def test_masked_leaves_never_sampled(self):
        """Zero-priority leaves — masked or never written — carry no mass
        and must never come back from the descent (the invalid-slot
        guarantee the replay buffer's size bound relies on)."""
        priorities = np.zeros(128, np.float32)
        live = np.asarray([1, 7, 31, 64, 100])
        priorities[live] = [1.0, 0.25, 3.0, 0.5, 2.0]
        tree = sum_tree.from_leaves(jnp.asarray(priorities))
        sample = jax.jit(lambda t, k: sum_tree.sample_stratified(t, k, 64))
        for d in range(50):
            idx, probs = sample(tree, jax.random.PRNGKey(d))
            assert np.isin(np.asarray(idx), live).all()
            assert (np.asarray(probs) > 0).all()

    def test_empty_tree_samples_gate_to_zero_prob(self):
        tree = sum_tree.create(32)
        idx, probs = sum_tree.sample_stratified(tree, jax.random.PRNGKey(0),
                                                8)
        assert (np.asarray(probs) == 0).all()

    def test_is_weights_normalized_and_zero_safe(self):
        probs = jnp.asarray([0.5, 0.25, 0.0, 0.125])
        w = np.asarray(sum_tree.is_weights(probs, jnp.int32(100),
                                           jnp.float32(0.5)))
        assert w.max() == pytest.approx(1.0)
        assert w[2] == 0.0                      # zero-prob row: 0, not inf
        # Lower probability -> larger weight (the bias correction).
        assert w[3] > w[1] > w[0]


# ---------------------------------------------------------------------------
# uniform default: bit-identical to the pre-data-plane commit
# ---------------------------------------------------------------------------

class TestUniformGolden:
    def test_trajectory_matches_pre_data_plane_golden(self):
        """The golden was captured at the commit BEFORE the replay data
        plane landed (same container, same jax): the default uniform
        sampler must reproduce params/opt/metrics EXACTLY."""
        with open(GOLDEN) as f:
            golden = json.load(f)["dqn"]
        cfg = _golden_cfg("uniform")
        env = _golden_env(cfg)
        agent = build_agent(cfg, env)
        step = jax.jit(agent.step)
        ts = agent.init(jax.random.PRNGKey(0))
        for i in range(2):
            ts, metrics = step(ts)
            got = {k: float(np.asarray(v))
                   for k, v in sorted(metrics.items())
                   if np.asarray(v).ndim == 0}
            assert got == golden["metrics"][i]
        assert _tree_digest(ts.params) == golden["params_sha256"]
        assert _tree_digest(ts.opt_state) == golden["opt_state_sha256"]
        assert _tree_digest(ts) == golden["state_sha256"]


# ---------------------------------------------------------------------------
# PER mode
# ---------------------------------------------------------------------------

class TestPerMode:
    def test_unknown_replay_priority_rejected(self):
        cfg = _golden_cfg("prioritized")   # not a valid value
        with pytest.raises(ConfigError, match="replay_priority"):
            build_agent(cfg, _golden_env(cfg))

    def test_capacity_at_most_batch_rejected(self):
        """A push spanning the whole circular buffer has implementation-
        defined slot winners (masked rows alias pos-1) — config error,
        both samplers."""
        for mode in ("uniform", "per"):
            cfg = _golden_cfg(mode)
            cfg.learner.replay_capacity = 4   # == num_workers
            with pytest.raises(ConfigError, match="replay_capacity"):
                build_agent(cfg, _golden_env(cfg))

    def test_per_step_invariants(self):
        """PER training runs: finite loss, the PER gauges in the metric
        dict, live slots carry positive priority, empty slots none, and
        the tree stays exactly consistent after real traced updates."""
        cfg = _golden_cfg("per")
        env = _golden_env(cfg)
        agent = build_agent(cfg, env)
        step = jax.jit(agent.step)
        ts = agent.init(jax.random.PRNGKey(0))
        for _ in range(3):
            ts, metrics = step(ts)
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["per_max_priority"]) >= 1.0
        assert 0.0 < float(metrics["per_beta"]) <= 1.0
        size = int(ts.extras.replay.size)
        leaves = np.asarray(ts.extras.per.tree.leaves)
        assert size > 0
        assert (leaves[:size] > 0).all()
        assert (leaves[size:] == 0).all()
        rebuilt = sum_tree.from_leaves(ts.extras.per.tree.leaves)
        for a, b in zip(ts.extras.per.tree.levels, rebuilt.levels):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_per_diverges_from_uniform(self):
        """The prioritized sampler must actually change training (same
        seed, same data — different sample distribution)."""
        outs = {}
        for mode in ("uniform", "per"):
            cfg = _golden_cfg(mode)
            agent = build_agent(cfg, _golden_env(cfg))
            step = jax.jit(agent.step)
            ts = agent.init(jax.random.PRNGKey(0))
            for _ in range(2):
                ts, _m = step(ts)
            outs[mode] = _tree_digest(ts.params)
        assert outs["uniform"] != outs["per"]

    def test_reseed_per_priorities(self):
        """The resume warm-start path: an out-of-band buffer fill reseeds
        live slots at max priority, empty slots at zero."""
        from sharetrade_tpu.agents.dqn import (
            fill_replay_from_arrays, reseed_per_priorities)
        cfg = _golden_cfg("per")
        agent = build_agent(cfg, _golden_env(cfg))
        ts = agent.init(jax.random.PRNGKey(0))
        obs, act, rew, nxt = _tbatch(40, obs_dim=cfg.env.window + 2)
        warm = fill_replay_from_arrays(ts.extras.replay, obs, act, rew, nxt)
        extras = reseed_per_priorities(ts.extras.replace(replay=warm))
        leaves = np.asarray(extras.per.tree.leaves)
        assert (leaves[:40] == float(extras.per.max_priority)).all()
        assert (leaves[40:] == 0).all()
        # Uniform extras pass through untouched.
        cfg_u = _golden_cfg("uniform")
        agent_u = build_agent(cfg_u, _golden_env(cfg_u))
        ts_u = agent_u.init(jax.random.PRNGKey(0))
        assert reseed_per_priorities(ts_u.extras) is ts_u.extras

    def test_per_beta_schedule(self):
        from sharetrade_tpu.agents.base import per_beta
        cfg = FrameworkConfig().learner
        assert float(per_beta(jnp.int32(0), cfg)) == pytest.approx(
            cfg.per_beta0)
        assert float(per_beta(jnp.int32(cfg.per_beta_steps), cfg)) == 1.0
        assert float(per_beta(jnp.int32(10 ** 9), cfg)) == 1.0

    def test_per_checkpoint_roundtrip_exact(self, tmp_path):
        from sharetrade_tpu.checkpoint import CheckpointManager
        cfg = _golden_cfg("per")
        agent = build_agent(cfg, _golden_env(cfg))
        step = jax.jit(agent.step)
        ts = agent.init(jax.random.PRNGKey(0))
        ts, _ = step(ts)
        mgr = CheckpointManager(str(tmp_path / "ck"), keep=2)
        mgr.save(1, ts)
        restored, _step = mgr.restore(agent.init(jax.random.PRNGKey(0)))
        for a, b in zip(jax.tree.leaves(ts), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# bounded journal: rotation, bounded tail reads, retirement
# ---------------------------------------------------------------------------

class TestSegmentRotation:
    def test_rotation_and_replay_order(self, tmp_journal_path):
        """Events split across sealed segments + the active file replay
        in exact append order."""
        with Journal(tmp_journal_path, segment_records=3) as j:
            for n in range(10):
                j.append({"n": n})
        assert len(segment_paths(tmp_journal_path)) == 3
        with Journal(tmp_journal_path, segment_records=3) as j:
            assert [e["n"] for e in j.replay()] == list(range(10))
            assert len(j) == 10

    def test_tail_reader_walks_only_tail_segments(self, tmp_journal_path):
        j = Journal(tmp_journal_path, segment_records=2)
        for i in range(10):
            append_transitions(j, *_tbatch(2, seed=i), env_steps=i + 1)
        j.flush()
        tail = read_tail_transitions(tmp_journal_path, 4, journal=j)
        obs, act, rew, nxt, high = tail
        assert obs.shape[0] == 4               # newest two records only
        assert high == 10
        # Unbounded read still sees everything, oldest-first.
        full = read_tail_transitions(tmp_journal_path, 0, journal=j)
        assert full[0].shape[0] == 20
        np.testing.assert_array_equal(full[0][-2:], obs[-2:])
        # Cutoff filtering splits across segment boundaries.
        cut = read_tail_transitions(tmp_journal_path, 0,
                                    cutoff_env_steps=5, journal=j)
        assert cut[0].shape[0] == 10 and cut[4] == 10
        j.close()

    def test_retirement_keeps_horizon_and_frees_bytes(self, tmp_journal_path):
        j = Journal(tmp_journal_path, segment_records=2)
        for i in range(12):
            append_transitions(j, *_tbatch(2, seed=i), env_steps=i + 1)
        j.flush()
        seals_before = segment_paths(tmp_journal_path)
        retired, freed = retire_transition_segments(j, keep_rows=6)
        assert retired > 0 and freed > 0
        kept = segment_paths(tmp_journal_path)
        # Never a segment newer than the horizon: the kept set is a
        # SUFFIX of the pre-retirement order, covering >= keep_rows.
        assert kept == seals_before[len(seals_before) - len(kept):]
        rows_kept = (count_transition_rows(tmp_journal_path)
                     + sum(count_transition_rows(p) for p in kept))
        assert rows_kept >= 6
        # The tail (and its high-water) still reads cleanly.
        tail = read_tail_transitions(tmp_journal_path, 0, journal=j)
        assert tail[4] == 12
        # Idempotent once within budget.
        assert retire_transition_segments(j, keep_rows=6)[0] == 0
        j.close()

    def test_compact_payloads_removes_sealed_segments(self, tmp_journal_path):
        """Whole-journal compaction (the orchestrator's fresh-run
        truncation) supersedes sealed segments too."""
        with Journal(tmp_journal_path, segment_records=2) as j:
            for n in range(7):
                j.append({"n": n})
            assert segment_paths(tmp_journal_path)
            j.compact([])
            assert segment_paths(tmp_journal_path) == []
            assert list(j.replay()) == []
            j.append({"n": "post"})
            assert [e["n"] for e in j.replay()] == ["post"]

    def test_torn_tail_property_in_newest_segment(self, tmp_journal_path):
        """Crash the journal at EVERY byte offset of the NEWEST (active)
        segment: recovery must always yield the sealed segments' records
        plus an exact prefix of the active segment — never garbage, never
        a lost sealed record — and appends must continue cleanly."""
        events = [{"n": n, "pad": "x" * (n * 7 % 23)} for n in range(11)]
        with Journal(tmp_journal_path, segment_records=4,
                     fsync_every_records=3) as j:
            for e in events:
                j.append(e)
        seals = segment_paths(tmp_journal_path)
        assert seals                       # rotation actually happened
        # Count sealed records by walking only the sealed files.
        from sharetrade_tpu.data.journal import iter_framed_records
        sealed_records = sum(1 for p in seals
                             for _ in iter_framed_records(p))
        blob = open(tmp_journal_path, "rb").read()
        for cut in range(len(blob) + 1):
            with open(tmp_journal_path, "wb") as f:
                f.write(blob[:cut])
            with Journal(tmp_journal_path, segment_records=4,
                         fsync_every_records=3) as j:
                recovered = list(j.replay())
                # Exact prefix: all sealed events, then a prefix of the
                # active segment's.
                assert recovered == events[:len(recovered)]
                assert len(recovered) >= sealed_records
                j.append({"n": "post-crash"})
                j.flush()
                assert list(j.replay())[-1] == {"n": "post-crash"}

    def test_compact_transitions_on_segmented_journal_retires(
            self, tmp_journal_path):
        """The public compact_transitions must not destroy sealed history:
        on a segmented journal it delegates to segment retirement (the
        keep_rows horizon holds; the active-file-only rewrite would have
        deleted every sealed segment)."""
        from sharetrade_tpu.data.transitions import compact_transitions
        j = Journal(tmp_journal_path, segment_records=2)
        for i in range(10):
            append_transitions(j, *_tbatch(2, seed=i), env_steps=i + 1)
        j.flush()
        assert compact_transitions(j, keep_rows=6)
        tail = read_tail_transitions(tmp_journal_path, 0, journal=j)
        assert tail[0].shape[0] >= 6          # horizon survived
        assert tail[4] == 10
        j.close()

    def test_legacy_json_events_survive_rotation(self, tmp_journal_path):
        """Migration path: a pre-rotation journal holding legacy JSON
        'transitions' events gets sealed into a segment once rotation is
        enabled — the warm-start scan must still find them."""
        from sharetrade_tpu.agents.dqn import (ReplayBuffer,
                                               fill_replay_from_journal)
        with Journal(tmp_journal_path) as j:      # legacy, no rotation
            j.append({"type": "transitions", "env_steps": 5,
                      "obs": [[1.0, 2.0]], "action": [1],
                      "reward": [0.5], "next_obs": [[2.0, 3.0]]})
        j2 = Journal(tmp_journal_path, segment_records=1)
        j2.append({"type": "other"})              # triggers a seal
        j2.flush()
        assert segment_paths(tmp_journal_path)
        warm = fill_replay_from_journal(ReplayBuffer.create(8, 2), j2)
        assert int(warm.size) == 1
        np.testing.assert_allclose(np.asarray(warm.obs[0]), [1.0, 2.0])
        j2.close()

    def test_reopen_continues_rotation(self, tmp_journal_path):
        j = Journal(tmp_journal_path, segment_records=2)
        for n in range(3):
            j.append({"n": n})
        j.close()
        j2 = Journal(tmp_journal_path, segment_records=2)
        for n in range(3, 6):
            j2.append({"n": n})
        j2.close()
        assert len(segment_paths(tmp_journal_path)) >= 2
        with Journal(tmp_journal_path) as j3:
            assert [e["n"] for e in j3.replay()] == list(range(6))


# ---------------------------------------------------------------------------
# streaming ingest
# ---------------------------------------------------------------------------

class TestStreamingIngest:
    def test_tail_parity_with_batch_csv_load(self, tmp_path):
        """Consuming the feed in arbitrary chunks — mid-line cuts
        included — converges to exactly the one-shot CSV load."""
        from sharetrade_tpu.data.ingest import load_price_csv
        from sharetrade_tpu.data.service import (FileTailFeed,
                                                 PriceDataService)
        series = synthetic_price_series(symbol="MSFT", length=80, seed=3)
        feed_path = str(tmp_path / "MSFT.feed")
        blob = "".join(f"{float(p)}, {d}\n"
                       for d, p in zip(series.dates,
                                       series.prices)).encode()
        svc = PriceDataService(
            journal=Journal(str(tmp_path / "ev.journal")),
            provider=lambda s, a, b: series)
        svc.attach_feed("MSFT", FileTailFeed(feed_path))
        cuts = sorted({0, 7, 33, 120, 456, len(blob) // 2, len(blob)})
        rows = 0
        for a, b in zip(cuts, cuts[1:]):
            with open(feed_path, "ab") as f:
                f.write(blob[a:b])
            rows += len(svc.tail("MSFT").series)
        assert len(svc.tail("MSFT").series) == 0   # quiet feed: no delta
        merged = svc.request("MSFT").series
        batch = load_price_csv(feed_path, symbol="MSFT")
        np.testing.assert_array_equal(merged.dates, batch.dates)
        np.testing.assert_allclose(merged.prices, batch.prices)
        assert rows == len(batch)
        svc.close()
        # Recovery: the streamed rows came back from the JOURNAL, with
        # no feed and a provider that must not be called.
        def no_fetch(s, a, b):
            raise AssertionError("recovery must not fetch")
        svc2 = PriceDataService(
            journal=Journal(str(tmp_path / "ev.journal")),
            provider=no_fetch)
        np.testing.assert_array_equal(
            svc2.request("MSFT").series.dates, batch.dates)
        svc2.close()

    def test_restart_does_not_reingest_recovered_rows(self, tmp_path):
        """A restarted consumer's feed offset resets to zero, but rows
        the journal already recovered must NOT come back as delta (nor
        be re-journaled) — only rows appended while the process was
        down do."""
        from sharetrade_tpu.data.service import (FileTailFeed,
                                                 PriceDataService,
                                                 append_feed_rows)
        series = synthetic_price_series(symbol="MSFT", length=30, seed=3)
        feed_path = str(tmp_path / "MSFT.feed")
        jpath = str(tmp_path / "ev.journal")
        first, rest = series.range(end=str(series.dates[19])), series.range(
            start=str(series.dates[20]))
        append_feed_rows(feed_path, first)
        svc = PriceDataService(journal=Journal(jpath),
                               provider=lambda s, a, b: None)
        svc.attach_feed("MSFT", FileTailFeed(feed_path))
        assert len(svc.tail("MSFT").series) == 20
        svc.close()
        # "Restart": new process state, same journal, fresh feed reader;
        # ten new rows landed while it was down.
        append_feed_rows(feed_path, rest)
        svc2 = PriceDataService(journal=Journal(jpath),
                                provider=lambda s, a, b: None)
        svc2.attach_feed("MSFT", FileTailFeed(feed_path))
        delta = svc2.tail("MSFT").series
        assert len(delta) == 10                   # only the new rows
        np.testing.assert_array_equal(delta.dates, rest.dates)
        assert len(svc2.tail("MSFT").series) == 0
        merged = svc2.request("MSFT").series
        np.testing.assert_array_equal(merged.dates, series.dates)
        svc2.close()

    def test_missing_feed_and_unattached_symbol(self, tmp_path):
        from sharetrade_tpu.data.service import (FileTailFeed,
                                                 PriceDataService)
        svc = PriceDataService(journal=Journal(str(tmp_path / "j")),
                               provider=lambda s, a, b: None)
        with pytest.raises(ValueError, match="feed"):
            svc.tail("MSFT")
        svc.attach_feed("MSFT", FileTailFeed(str(tmp_path / "nope.feed")))
        assert len(svc.tail("MSFT").series) == 0   # absent file: empty delta
        svc.close()

    def test_feed_path_config_substitutes_symbol(self, tmp_path):
        from sharetrade_tpu.config import DataConfig
        from sharetrade_tpu.data.service import (PriceDataService,
                                                 append_feed_rows)
        series = synthetic_price_series(symbol="GOOG", length=10, seed=5)
        append_feed_rows(str(tmp_path / "GOOG.feed"), series)
        cfg = DataConfig(feed_path=str(tmp_path / "{symbol}.feed"),
                         journal_dir=str(tmp_path))
        svc = PriceDataService(journal=Journal(str(tmp_path / "j")),
                               provider=lambda s, a, b: None, config=cfg)
        delta = svc.tail("GOOG")
        assert len(delta.series) == 10
        np.testing.assert_allclose(delta.series.prices, series.prices)
        svc.close()


# ---------------------------------------------------------------------------
# orchestrator integration: journaled DQN with rotation, bounded resume
# ---------------------------------------------------------------------------

class TestOrchestratorReplayPlane:
    def _cfg(self, tmp_path, mode):
        cfg = FrameworkConfig()
        cfg.learner.algo = "dqn"
        cfg.learner.journal_replay = True
        cfg.learner.replay_priority = mode
        cfg.learner.replay_capacity = 128
        cfg.learner.replay_batch = 16
        cfg.parallel.num_workers = 4
        cfg.env.window = 8
        cfg.model.hidden_dim = 8
        cfg.runtime.chunk_steps = 8
        cfg.runtime.episodes = 3
        cfg.runtime.checkpoint_every_updates = 32
        cfg.runtime.checkpoint_dir = str(tmp_path / f"ck-{mode}")
        cfg.runtime.keep_best_eval = False
        cfg.data.journal_dir = str(tmp_path / f"journal-{mode}")
        cfg.data.use_native_journal = False
        cfg.data.async_transition_writer = False
        cfg.data.journal_segment_records = 4
        cfg.data.journal_fsync_every_records = 1
        return cfg

    @pytest.mark.parametrize("mode", ["uniform", "per"])
    def test_rotation_resume_and_gauges(self, tmp_path, mode):
        from sharetrade_tpu.runtime.orchestrator import Orchestrator
        cfg = self._cfg(tmp_path, mode)
        prices = synthetic_price_series(length=72, seed=1).prices
        orch = Orchestrator(cfg)
        orch.send_training_data(prices)
        orch.start_training(background=False)
        from sharetrade_tpu.runtime.lifecycle import Phase
        assert orch.lifecycle.phase is Phase.COMPLETED
        jpath = os.path.join(cfg.data.journal_dir, "transitions.journal")
        assert segment_paths(jpath), "rotation never sealed a segment"
        assert (orch.metrics.latest("journal_segments") or 0) >= 1
        orch.stop()

        # Resume: the warm start reads only the tail segments and (in per
        # mode) reseeds the sum-tree over the recovered rows.
        orch2 = Orchestrator(cfg)
        orch2.send_training_data(prices, resume=True)
        size = int(orch2._ts.extras.replay.size)
        assert size > 0
        if mode == "per":
            leaves = np.asarray(orch2._ts.extras.per.tree.leaves)
            assert (leaves[:size] > 0).all()
            assert (leaves[size:] == 0).all()
        orch2.stop()


# ---------------------------------------------------------------------------
# guards: lint check 9, cli obs section
# ---------------------------------------------------------------------------

class TestGuards:
    def test_lint_replay_device_path_clean(self):
        import lint_hot_loop
        hits, found = lint_hot_loop.lint_replay_device_path()
        assert hits == [], f"replay device-path lint hits: {hits}"
        required = (set(lint_hot_loop.REPLAY_TREE_FUNCS)
                    | set(lint_hot_loop.REPLAY_DQN_FUNCS)
                    | set(lint_hot_loop.REPLAY_CONSUMER_FUNCS))
        assert required <= found

    def test_lint_replay_pattern_semantics(self):
        import lint_hot_loop
        pat = lint_hot_loop.REPLAY_BLOCK_PATTERN
        assert pat.search("os.fsync(fd)")
        assert pat.search("np.random.uniform(0, 1)")
        assert pat.search("random.random()")
        assert pat.search("journal.append({})")
        assert pat.search("j.append_bytes(payload)")
        assert pat.search("open(path)")
        # jax.random stays legal; dotted open too.
        assert not pat.search("jax.random.split(key)")
        assert not pat.search("k = jax.random.uniform(key, (3,))")

    def test_cli_obs_replay_section(self, tmp_path):
        from sharetrade_tpu.obs import summarize_run_dir
        run_dir = tmp_path / "obs"
        run_dir.mkdir()
        record = {"ts": 0.0,
                  "gauges": {"replay_size": 128.0, "per_max_priority": 2.5,
                             "per_beta": 0.6, "journal_segments": 3.0},
                  "counters": {"journal_compacted_bytes_total": 4096.0,
                               "journal_segments_retired_total": 2.0}}
        (run_dir / "metrics.jsonl").write_text(json.dumps(record) + "\n")
        summary = summarize_run_dir(str(run_dir))
        replay = summary["replay"]
        assert replay["replay_size"] == 128.0
        assert replay["per_max_priority"] == 2.5
        assert replay["journal_segments"] == 3.0
        assert replay["journal_compacted_bytes_total"] == 4096.0
        assert replay["journal_segments_retired_total"] == 2.0
