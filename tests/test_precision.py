"""Precision policy (precision.py, ops/fused_update.py): the bf16-compute /
fp32-master tier and its contracts.

The four pinned claims:

1. **fp32 default is bit-identical to the pre-policy code** — the policy
   helpers are structural identities, and a fixed-seed qlearn/PPO
   trajectory reproduces the golden captured at the commit BEFORE the
   policy landed (tests/golden/precision_fp32_golden.json) exactly.
2. **bf16_mixed keeps fp32 masters** — params and optimizer state stay
   f32 through training and checkpoints; the reference MLP converges
   within a pinned band of the fp32 run.
3. **Checkpoints hold fp32 masters and refuse mode mismatches** — the
   round-trip is exact, and a store saved under one precision.mode
   raises a loud ValueError under another (flax from_bytes would
   otherwise silently deserialize wrong-dtype leaves).
4. **The fused optimizer update is optax-exact** — bitwise in fp32 for
   adagrad/adam/sgd; bf16 gradients differ only by their quantization.
"""

from __future__ import annotations

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sharetrade_tpu.agents import build_agent
from sharetrade_tpu.config import ConfigError, FrameworkConfig
from sharetrade_tpu.data.synthetic import synthetic_price_series
from sharetrade_tpu.env import trading
from sharetrade_tpu.ops.fused_update import fused_apply
from sharetrade_tpu.precision import FP32, PrecisionPolicy, policy_from_config

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "precision_fp32_golden.json")


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


def _small_cfg(algo: str, mode: str = "fp32") -> FrameworkConfig:
    cfg = FrameworkConfig()
    cfg.learner.algo = algo
    cfg.precision.mode = mode
    cfg.parallel.num_workers = 4
    cfg.env.window = 16
    cfg.runtime.chunk_steps = 25
    cfg.learner.unroll_len = 25
    cfg.model.hidden_dim = 16
    return cfg


def _small_env(cfg):
    series = synthetic_price_series(length=256, seed=7)
    return trading.env_from_prices(series.prices, window=cfg.env.window,
                                   initial_budget=cfg.env.initial_budget)


# ---------------------------------------------------------------------------
# the policy object
# ---------------------------------------------------------------------------

class TestPolicy:
    def test_fp32_helpers_are_object_identities(self):
        """The structural bit-identity guarantee: fp32 mode returns THE
        SAME OBJECT, so the traced program cannot differ from pre-policy
        code even by a no-op cast."""
        tree = {"w": jnp.ones((3, 2)), "n": jnp.int32(4)}
        assert FP32.cast_compute(tree) is tree
        assert FP32.grads_to_master(tree) is tree
        assert FP32.cast_carry(tree) is tree
        assert not FP32.mixed and not FP32.use_fused_update

    def test_bf16_casts_float_leaves_only(self):
        pol = PrecisionPolicy(mode="bf16_mixed")
        tree = {"w": jnp.ones((3, 2)), "n": jnp.int32(4)}
        cast = pol.cast_compute(tree)
        assert cast["w"].dtype == jnp.bfloat16
        assert cast["n"].dtype == jnp.int32
        back = pol.grads_to_master(cast)
        assert back["w"].dtype == jnp.float32
        assert pol.mixed and pol.use_fused_update

    def test_model_carry_hook_wins(self):
        """The episode transformer's mixed-dtype carry: K/V follow the
        compute dtype, ``hist`` (raw prices) stays f32."""
        from sharetrade_tpu.models.transformer_episode import (
            episode_transformer_policy)
        pol = PrecisionPolicy(mode="bf16_mixed")
        model = episode_transformer_policy(10, 3, num_layers=2, num_heads=2,
                                           head_dim=8)
        carry = pol.cast_carry(model.init_carry(), model)
        assert carry["k"].dtype == jnp.bfloat16
        assert carry["v"].dtype == jnp.bfloat16
        assert carry["hist"].dtype == jnp.float32
        assert carry["t"].dtype == jnp.int32

    def test_unknown_mode_rejected(self):
        with pytest.raises(ConfigError, match="precision.mode"):
            PrecisionPolicy(mode="fp16")
        with pytest.raises(ConfigError, match="fused_update"):
            PrecisionPolicy(fused_update="maybe")

    def test_policy_from_config(self):
        cfg = FrameworkConfig()
        assert not policy_from_config(cfg.precision).mixed
        cfg.precision.mode = "bf16_mixed"
        assert policy_from_config(cfg.precision).mixed

    def test_old_dtype_knob_raises_migration_error(self):
        """Satellite: model.dtype='bfloat16' (the whole-model cast that
        silently put optimizer state in bf16) must fail loudly, naming the
        replacement knob."""
        from sharetrade_tpu.models import build_model
        cfg = FrameworkConfig()
        cfg.model.dtype = "bfloat16"
        with pytest.raises(ConfigError, match="precision.mode"):
            build_model(cfg.model, 18)
        cfg.model.dtype = "float16"
        with pytest.raises(ConfigError, match="unknown model.dtype"):
            build_model(cfg.model, 18)


# ---------------------------------------------------------------------------
# fp32 default: bit-identical to the pre-policy commit (golden trajectory)
# ---------------------------------------------------------------------------

class TestFp32Golden:
    @pytest.mark.parametrize("algo,chunks", [("qlearn", 2), ("ppo", 1)])
    def test_trajectory_matches_pre_policy_golden(self, algo, chunks):
        """The golden file was captured at the commit BEFORE the precision
        policy landed (same container, same jax): the default fp32 mode
        must reproduce params/opt/metrics EXACTLY — not approximately."""
        with open(GOLDEN) as f:
            golden = json.load(f)[algo]
        cfg = _small_cfg(algo)
        env = _small_env(cfg)
        agent = build_agent(cfg, env)
        step = jax.jit(agent.step)
        ts = agent.init(jax.random.PRNGKey(0))
        for i in range(chunks):
            ts, metrics = step(ts)
            got = {k: float(np.asarray(v)) for k, v in sorted(metrics.items())
                   if np.asarray(v).ndim == 0}
            assert got == golden["metrics"][i]
        assert _tree_digest(ts.params) == golden["params_sha256"]
        assert _tree_digest(ts.opt_state) == golden["opt_state_sha256"]
        assert _tree_digest(ts) == golden["state_sha256"]


# ---------------------------------------------------------------------------
# bf16_mixed: masters stay fp32; reference MLP converges within a band
# ---------------------------------------------------------------------------

class TestBf16Mixed:
    def test_masters_stay_fp32_and_convergence_band(self):
        """The reference-shape MLP (hidden 200 — the real architecture,
        shortened series) trained one 200-update chunk in both modes on
        one seed: masters stay f32, and the bf16 run's loss curve and
        final portfolio stats sit within a pinned band of fp32 — the
        bf16 quantization moves rounding, not the learning dynamics."""
        results = {}
        for mode in ("fp32", "bf16_mixed"):
            cfg = FrameworkConfig()
            cfg.learner.algo = "qlearn"
            cfg.precision.mode = mode
            cfg.parallel.num_workers = 4
            cfg.env.window = 32
            cfg.model.hidden_dim = 200
            cfg.runtime.chunk_steps = 200
            series = synthetic_price_series(length=300, seed=3)
            env = trading.env_from_prices(series.prices,
                                          window=cfg.env.window)
            agent = build_agent(cfg, env)
            ts = agent.init(jax.random.PRNGKey(0))
            ts, metrics = jax.jit(agent.step)(ts)
            for leaf in jax.tree.leaves(ts.params):
                assert leaf.dtype == jnp.float32
            for leaf in jax.tree.leaves(ts.opt_state):
                if jnp.issubdtype(leaf.dtype, jnp.floating):
                    assert leaf.dtype == jnp.float32
            results[mode] = (ts, {k: float(np.asarray(v))
                                  for k, v in metrics.items()
                                  if np.asarray(v).ndim == 0})
        m32, m16 = results["fp32"][1], results["bf16_mixed"][1]
        assert np.isfinite(m16["loss"])
        # Loss scale tracks squared portfolio-value errors (large); the
        # band is generous but pins "same training dynamics" — a wrong
        # master/update dtype diverges by orders of magnitude, not 20%.
        assert m16["loss"] == pytest.approx(m32["loss"], rel=0.2)
        assert m16["portfolio_mean"] == pytest.approx(
            m32["portfolio_mean"], rel=0.05)
        # Master weights stay close leaf-by-leaf (bf16 rounding noise
        # accumulated over 200 adagrad updates, not a different optimum).
        for a, b in zip(jax.tree.leaves(results["fp32"][0].params),
                        jax.tree.leaves(results["bf16_mixed"][0].params)):
            denom = np.maximum(np.abs(np.asarray(a)), 1e-3)
            rel = np.abs(np.asarray(a) - np.asarray(b)) / denom
            assert float(np.median(rel)) < 0.05

    def test_bf16_megachunk_parity(self):
        """K fused chunks == K host chunks under bf16_mixed (the same
        traced-body guarantee megachunks pin for fp32)."""
        from sharetrade_tpu.agents.base import megachunk_step
        cfg = _small_cfg("qlearn", "bf16_mixed")
        env = _small_env(cfg)
        agent = build_agent(cfg, env)
        single = jax.jit(agent.step)
        fused = jax.jit(megachunk_step(agent.step, 2))
        ts_a = agent.init(jax.random.PRNGKey(0))
        ts_b = agent.init(jax.random.PRNGKey(0))
        for _ in range(2):
            ts_a, _ = single(ts_a)
        ts_b, _ = fused(ts_b)
        for a, b in zip(jax.tree.leaves(ts_a), jax.tree.leaves(ts_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_compute_copy_drives_forward_dtype(self):
        """models compute in the dtype of the params they are HANDED:
        fp32 masters -> f32 activations; the policy's bf16 copy -> bf16
        internals with f32 heads (the ops/attention.py accumulation
        convention extended to models/*)."""
        from sharetrade_tpu.models.core import compute_dtype
        from sharetrade_tpu.models.mlp import ac_mlp
        pol = PrecisionPolicy(mode="bf16_mixed")
        model = ac_mlp(18, 16)
        params = model.init(jax.random.PRNGKey(0))
        assert compute_dtype(params) == jnp.float32
        params_c = pol.cast_compute(params)
        assert compute_dtype(params_c) == jnp.bfloat16
        out, _ = model.apply(params_c, jnp.ones((18,)), ())
        assert out.logits.dtype == jnp.float32   # heads stay f32
        assert np.isfinite(np.asarray(out.logits)).all()


# ---------------------------------------------------------------------------
# fused optimizer update vs the optax pair
# ---------------------------------------------------------------------------

def _opt_pair(name):
    return {"adagrad": optax.adagrad(0.01), "adam": optax.adam(0.01),
            "sgd": optax.sgd(0.01)}[name]


#: The leaf kinds of the d=1024 training tree (chip_smoke.wide_agent):
#: a block's 2-D weight, a 1-D bias, the ``(3, d)`` embedding.
D1024_LEAVES = {"weight": (1024, 3072), "bias": (3072,), "embed": (3, 1024)}
OPTIMIZERS = ["adagrad", "adam", "sgd"]


class TestFusedUpdate:
    params = {
        "a": jax.random.normal(jax.random.PRNGKey(0), (37, 13)),
        "b": {"w": jax.random.normal(jax.random.PRNGKey(1), (200,)),
              "s": jnp.float32(0.5)},
    }
    grads = jax.tree.map(lambda x: x * 0.37 + 0.01, params)

    @pytest.mark.parametrize("name,leaf", [
        *(pytest.param(n, None, id=n) for n in OPTIMIZERS),
        *(pytest.param(n, kind, id=f"{n}-{kind}")
          for n in OPTIMIZERS for kind in D1024_LEAVES),
    ])
    def test_fp32_bitwise_vs_optax(self, name, leaf):
        """Bit for bit the optax pair, over the small mixed tree and over
        each leaf kind of the d=1024 tree at its own shape."""
        params, grads = self.params, self.grads
        if leaf is not None:
            params = {"w": jax.random.normal(jax.random.PRNGKey(2),
                                             D1024_LEAVES[leaf])}
            grads = jax.tree.map(lambda x: x * 0.37 + 0.01, params)
        opt = _opt_pair(name)
        st = opt.init(params)
        p_ref, st_ref = params, st
        p_f, st_f = params, st
        for _ in range(3):       # counts/moments exercise multi-step state
            u, st_ref = opt.update(grads, st_ref, p_ref)
            p_ref = optax.apply_updates(p_ref, u)
            p_f, st_f = fused_apply(name, 0.01, grads, st_f, p_f)
        for ref, got in zip(jax.tree.leaves((p_ref, st_ref)),
                            jax.tree.leaves((p_f, st_f))):
            np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    @pytest.mark.parametrize("name", OPTIMIZERS)
    def test_bf16_grads_within_tolerance(self, name):
        """bf16 gradients: the fused update (upcast inside the pass)
        equals the optax pair fed explicitly-upcast grads — the only
        divergence is the gradient's own bf16 quantization upstream."""
        opt = _opt_pair(name)
        st = opt.init(self.params)
        g16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), self.grads)
        p_f, st_f = fused_apply(name, 0.01, g16, st, self.params,
                                compute_dtype=jnp.bfloat16)
        u, _ = opt.update(jax.tree.map(lambda x: x.astype(jnp.float32), g16),
                          st, self.params)
        p_ref = optax.apply_updates(self.params, u)
        for a, b in zip(jax.tree.leaves(p_f), jax.tree.leaves(p_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    def test_emit_compute_is_recast_of_new_masters(self):
        st = optax.adagrad(0.01).init(self.params)
        p_new, _, p_c = fused_apply("adagrad", 0.01, self.grads, st,
                                    self.params,
                                    compute_dtype=jnp.bfloat16,
                                    emit_compute=True)
        for m, c in zip(jax.tree.leaves(p_new), jax.tree.leaves(p_c)):
            assert c.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(m, dtype=np.float32).astype(jnp.bfloat16),
                np.asarray(c))

    def test_in_jit_trace(self):
        """The fused path must trace inside the agents' jitted steps (the
        real call context) — counts as traced scalars included. Tolerance
        is ~1 ulp, not bitwise: XLA may FMA-contract the jitted fused
        chain differently from the eagerly-dispatched optax reference
        (op-for-op identity is pinned by test_fp32_bitwise_vs_optax,
        where both sides run under the same execution regime)."""
        opt = optax.adam(0.01)
        st = opt.init(self.params)

        @jax.jit
        def step(p, s, g):
            return fused_apply("adam", 0.01, g, s, p)

        p1, s1 = step(self.params, st, self.grads)
        u, s_ref = opt.update(self.grads, st, self.params)
        p_ref = optax.apply_updates(self.params, u)
        for a, b in zip(jax.tree.leaves((p1, s1)),
                        jax.tree.leaves((p_ref, s_ref))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-7, atol=1e-7)

    def test_unsupported_optimizer_raises(self):
        with pytest.raises(ValueError, match="fused update"):
            fused_apply("rmsprop", 0.01, self.grads, (), self.params)


# ---------------------------------------------------------------------------
# checkpoints: fp32 masters always; mode mismatches refused
# ---------------------------------------------------------------------------

class TestCheckpointPrecision:
    def _trained_state(self, mode):
        cfg = _small_cfg("ppo", mode)
        cfg.model.kind = "transformer"
        cfg.model.seq_mode = "episode"
        cfg.model.num_layers = 2
        cfg.model.num_heads = 2
        cfg.model.head_dim = 8
        env = _small_env(cfg)
        agent = build_agent(cfg, env)
        ts = agent.init(jax.random.PRNGKey(0))
        ts, _ = jax.jit(agent.step)(ts)
        return agent, ts

    def test_round_trip_restores_fp32_masters_exactly(self, tmp_path):
        from sharetrade_tpu.checkpoint import CheckpointManager
        agent, ts = self._trained_state("bf16_mixed")
        mgr = CheckpointManager(str(tmp_path), precision_mode="bf16_mixed")
        mgr.save(7, ts, metadata={"episode": 0})
        meta = mgr.metadata(7)
        assert meta["precision_mode"] == "bf16_mixed"
        template = agent.init(jax.random.PRNGKey(0))
        restored, step = mgr.restore(template)
        assert step == 7
        for a, b in zip(jax.tree.leaves(ts.params),
                        jax.tree.leaves(restored.params)):
            assert b.dtype == jnp.float32      # fp32 masters, always
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the compute-dtype carry survives too (K/V bf16, hist f32)
        assert restored.carry["k"].dtype == jnp.bfloat16
        assert restored.carry["hist"].dtype == jnp.float32

    def test_mode_mismatch_refused_loudly(self, tmp_path):
        from sharetrade_tpu.checkpoint import CheckpointManager
        agent, ts = self._trained_state("bf16_mixed")
        CheckpointManager(str(tmp_path),
                          precision_mode="bf16_mixed").save(3, ts)
        wrong = CheckpointManager(str(tmp_path), precision_mode="fp32")
        template = agent.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="precision.mode"):
            wrong.restore(template)
        # the store is untouched (config mismatch, not corruption)
        assert not [p for p in os.listdir(tmp_path)
                    if p.startswith("corrupt_")]

    def test_pre_policy_checkpoints_read_as_fp32(self, tmp_path):
        """A checkpoint with NO recorded mode (every pre-PR store) is
        fp32: restorable under fp32 config, refused under bf16_mixed."""
        from sharetrade_tpu.checkpoint import CheckpointManager
        cfg = _small_cfg("qlearn")
        env = _small_env(cfg)
        agent = build_agent(cfg, env)
        ts = agent.init(jax.random.PRNGKey(0))
        CheckpointManager(str(tmp_path)).save(1, ts)   # no mode stamped
        ok = CheckpointManager(str(tmp_path), precision_mode="fp32")
        restored, _ = ok.restore(agent.init(jax.random.PRNGKey(0)))
        bad = CheckpointManager(str(tmp_path), precision_mode="bf16_mixed")
        with pytest.raises(ValueError, match="precision.mode"):
            bad.restore(agent.init(jax.random.PRNGKey(0)))


# ---------------------------------------------------------------------------
# satellites: lint check 7
# ---------------------------------------------------------------------------

class TestLintCheck7:
    def test_repo_is_clean(self):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        import lint_hot_loop
        assert lint_hot_loop.lint_precision_casts() == []

    def test_pattern_semantics(self):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        import lint_hot_loop
        pat = lint_hot_loop.PRECISION_PATTERN
        # receiver casts on params/grads: flagged
        assert pat.search('p = ts.params.astype(jnp.bfloat16)')
        assert pat.search('g = grads.astype(jnp.float32)')
        assert pat.search('w = params["w"].astype(dtype)')
        assert pat.search(
            'jax.tree.map(lambda x: x.astype(d), grads)')
        # activation casts that merely mention params: not flagged
        assert not pat.search(
            'logits = dense(params["policy"], h).astype(jnp.float32)')
        assert not pat.search('x = obs.astype(compute_dtype(params))')
        assert not pat.search('tokens = tokenize(obs).astype(dtype)')
