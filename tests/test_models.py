"""Model zoo: shapes, reference-parity properties, transform-friendliness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sharetrade_tpu.config import ModelConfig
from sharetrade_tpu.models import build_model
from sharetrade_tpu.models.mlp import ac_mlp, q_mlp

OBS_DIM = 203


def _obs(key):
    return jax.random.uniform(key, (OBS_DIM,), minval=0.0, maxval=100.0)


def _rows_finite_cases():
    """dtype x shape x what is planted, for
    ``test_rows_finite_is_the_plain_predicate``: ``[B]``, ``[B, 7]`` and a
    K/V-carry-shaped ``[B, 2, 2, 201, 128]`` at B = 5."""
    import ml_dtypes
    cases = []
    for dtype in (ml_dtypes.bfloat16, np.float32, np.int32):
        for shape in ((5,), (5, 7), (5, 2, 2, 201, 128)):
            planted = ["none", "max"]
            if dtype is not np.int32:
                spots = ["first", "last", "two_rows"]
                if len(shape) == 5:
                    spots.append("slot200")
                planted += [f"{v}_{s}" for v in ("nan", "posinf", "neginf")
                            for s in spots]
            name = np.dtype(dtype).name
            dims = "x".join(map(str, shape))
            cases += [pytest.param(dtype, shape, p, id=f"{name}-{dims}-{p}")
                      for p in planted]
    return cases


_ROWS_FINITE_CASES = _rows_finite_cases()


def _rows_finite_input(dtype, shape, planted):
    rng = np.random.default_rng(0)
    if planted == "max":
        top = (jnp.iinfo if dtype is np.int32 else jnp.finfo)(dtype).max
        x = np.full(shape, top, dtype)
        x.reshape(-1)[1::2] *= -1          # both signs: no sum may overflow
        return x
    x = rng.normal(size=shape).astype(np.float32)
    x = (x * 100).astype(dtype) if dtype is np.int32 else x.astype(dtype)
    if planted == "none":
        return x
    value, spot = planted.split("_", 1)
    value = {"nan": np.nan, "posinf": np.inf, "neginf": -np.inf}[value]
    rows = x.reshape(shape[0], -1)          # a view: writes land in x
    if spot == "first":
        rows[2, 0] = value
    elif spot == "last":
        rows[4, -1] = value
    elif spot == "two_rows":
        rows[0, rows.shape[1] // 2] = value
        rows[3, -1] = value
    else:                                   # the window's last slot
        x[1, 1, 0, 200, 5] = value
    return x


class TestQMLPParity:
    """Architecture parity with QDecisionPolicyActor.scala:38-50."""

    def test_param_shapes_match_reference_graph(self):
        model = q_mlp(parity=True)
        params = model.init(jax.random.PRNGKey(0))
        assert params["layer1"]["w"].shape == (203, 200)  # w1
        assert params["layer2"]["w"].shape == (200, 3)    # w2
        # Biases are tf.constant in the reference -> not trainable params.
        assert "b" not in params["layer1"] and "b" not in params["layer2"]
        n = sum(p.size for p in jax.tree.leaves(params))
        assert n == 203 * 200 + 200 * 3  # ~41.2k (SURVEY.md §6)

    def test_output_relu_clamps_at_zero(self):
        # Reference: q = relu(...) — Q-values can never go negative.
        model = q_mlp(parity=True)
        params = model.init(jax.random.PRNGKey(1))
        out, _ = model.apply(params, _obs(jax.random.PRNGKey(2)), ())
        assert out.logits.shape == (3,)
        assert bool(jnp.all(out.logits >= 0.0))

    def test_forward_matches_hand_computed(self):
        model = q_mlp(obs_dim=4, hidden_dim=2, num_actions=3, parity=True)
        params = {"layer1": {"w": jnp.ones((4, 2))},
                  "layer2": {"w": jnp.ones((2, 3)) * 0.5}}
        obs = jnp.array([1.0, 2.0, 3.0, 4.0])
        out, _ = model.apply(params, obs, ())
        # h = relu(10 + 0.1) = 10.1 each; q = relu(10.1*2*0.5 + 0.1) = 10.2
        np.testing.assert_allclose(np.asarray(out.logits), [10.2] * 3, rtol=1e-6)

    def test_non_parity_has_trainable_biases_and_no_output_relu(self):
        model = q_mlp(parity=False)
        params = model.init(jax.random.PRNGKey(0))
        assert "b" in params["layer1"] and "b" in params["layer2"]


class TestHeads:
    @pytest.mark.parametrize("kind", ["mlp", "lstm", "transformer"])
    def test_build_apply_shapes(self, kind):
        cfg = ModelConfig(kind=kind, hidden_dim=32, num_layers=1,
                          num_heads=2, head_dim=16)
        model = build_model(cfg, OBS_DIM)
        params = model.init(jax.random.PRNGKey(0))
        out, carry = model.apply(params, _obs(jax.random.PRNGKey(1)),
                                 model.init_carry())
        assert out.logits.shape == (3,)
        assert out.value.shape == ()
        assert jnp.isfinite(out.logits).all()

    def test_lstm_carry_evolves_and_affects_output(self):
        cfg = ModelConfig(kind="lstm", hidden_dim=16)
        model = build_model(cfg, OBS_DIM)
        params = model.init(jax.random.PRNGKey(0))
        obs = _obs(jax.random.PRNGKey(1))
        out1, carry1 = model.apply(params, obs, model.init_carry())
        out2, carry2 = model.apply(params, obs, carry1)
        assert not np.allclose(np.asarray(carry1[0]), np.asarray(carry2[0]))
        assert not np.allclose(np.asarray(out1.logits), np.asarray(out2.logits))

    def test_transformer_scale_invariance(self):
        # Price normalization: scaling the whole window (and budget) by 10x
        # must leave the policy's decision unchanged.
        cfg = ModelConfig(kind="transformer", num_layers=1, num_heads=2, head_dim=16)
        model = build_model(cfg, OBS_DIM)
        params = model.init(jax.random.PRNGKey(0))
        prices = jnp.linspace(50.0, 60.0, 201)
        obs1 = jnp.concatenate([prices, jnp.array([2400.0, 3.0])])
        obs2 = jnp.concatenate([prices * 10, jnp.array([24000.0, 3.0])])
        out1, _ = model.apply(params, obs1, ())
        out2, _ = model.apply(params, obs2, ())
        np.testing.assert_allclose(np.asarray(out1.logits),
                                   np.asarray(out2.logits), rtol=1e-4)

    def test_vmap_over_agent_batch(self):
        model = ac_mlp(OBS_DIM, 32)
        params = model.init(jax.random.PRNGKey(0))
        obs_batch = jax.random.uniform(jax.random.PRNGKey(1), (8, OBS_DIM))
        outs, _ = jax.vmap(lambda o: model.apply(params, o, ()))(obs_batch)
        assert outs.logits.shape == (8, 3)

    def test_gradients_flow(self):
        model = ac_mlp(OBS_DIM, 16)
        params = model.init(jax.random.PRNGKey(0))
        obs = _obs(jax.random.PRNGKey(1))

        def loss(p):
            out, _ = model.apply(p, obs, ())
            return jnp.sum(out.logits ** 2) + out.value ** 2

        grads = jax.grad(loss)(params)
        norms = [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(grads)]
        assert all(np.isfinite(norms)) and any(n > 0 for n in norms)

    def test_bfloat16_compute(self):
        """bf16 compute now arrives via the precision policy's compute
        copy (precision.py) — model.dtype='bfloat16' is a migration error
        (it silently put optimizer state in bf16; tests/test_precision.py
        covers the error path). Forwards compute in the dtype of the
        params they are handed; heads cast back to f32 for numerics
        downstream (TD targets etc)."""
        from sharetrade_tpu.precision import PrecisionPolicy
        cfg = ModelConfig(kind="mlp", hidden_dim=32)
        model = build_model(cfg, OBS_DIM)
        params = PrecisionPolicy(mode="bf16_mixed").cast_compute(
            model.init(jax.random.PRNGKey(0)))
        assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(params))
        out, _ = model.apply(params, _obs(jax.random.PRNGKey(1)), ())
        assert out.logits.dtype == jnp.float32


class TestEpisodeMode:
    """Episode-mode transformer (models/transformer_episode.py): the
    incremental K/V-cache rollout and the banded-replay training pass must
    compute the same function of the same tick stream."""

    WINDOW = 16                  # ticks; obs_dim = WINDOW + 2

    def _setup(self, num_layers=2, unroll=8, num_agents=3, algo="ppo",
               **model_kw):
        from sharetrade_tpu.agents import build_agent
        from sharetrade_tpu.config import FrameworkConfig
        from sharetrade_tpu.env import trading

        cfg = FrameworkConfig()
        cfg.learner.algo = algo
        cfg.model.kind = "transformer"
        cfg.model.seq_mode = "episode"
        cfg.model.num_layers = num_layers
        cfg.model.num_heads = 2
        cfg.model.head_dim = 16
        for k, v in model_kw.items():
            setattr(cfg.model, k, v)
        cfg.env.window = self.WINDOW
        cfg.parallel.num_workers = num_agents
        cfg.learner.unroll_len = unroll
        cfg.runtime.chunk_steps = unroll
        prices = 10.0 + jnp.cumsum(
            jax.random.normal(jax.random.PRNGKey(9), (64,)) * 0.1)
        env = trading.make_trading_env(
            jnp.abs(prices) + 5.0, window=cfg.env.window)
        agent = build_agent(cfg, env)
        return cfg, agent, env

    def test_rollout_replay_parity_across_chunks(self):
        """Replayed logp/value must match what the rollout recorded — for
        the FIRST chunk (prefill path) and a SECOND chunk (carry crosses
        the unroll boundary: cache + tick history + absolute positions)."""
        from sharetrade_tpu.agents.rollout import collect_rollout, replay_forward

        _, agent, env = self._setup()
        model = agent.model
        ts = agent.init(jax.random.PRNGKey(0))

        for chunk in range(2):
            init_carry = ts.carry
            ts, traj, _, replay_init = collect_rollout(
                model, env, ts, 8, agent.num_agents)
            # The replay starts from the unroll START: what it reads of
            # that carry (Model.replay_carry), never the K/V caches.
            assert set(replay_init) == {"hist", "t", "ok"}
            assert replay_init["hist"] is init_carry["hist"]
            assert replay_init["t"] is init_carry["t"]
            assert np.asarray(replay_init["ok"]).all()
            logits, values, _ = replay_forward(
                model, ts.params, traj, replay_init)
            logp = jnp.take_along_axis(
                jax.nn.log_softmax(logits), traj.action[..., None],
                axis=-1)[..., 0]
            np.testing.assert_allclose(
                np.asarray(logp), np.asarray(traj.logp), atol=2e-4,
                err_msg=f"chunk {chunk} logp mismatch")
            np.testing.assert_allclose(
                np.asarray(values), np.asarray(traj.value), atol=2e-4,
                err_msg=f"chunk {chunk} value mismatch")

    def test_precomputed_trunk_matches_incremental_stepping(self):
        """The precomputed-rollout pair (apply_rollout_trunk + head) must
        compute the same per-step outputs AND hand off the same carry as
        prefill + incremental cache stepping — an off-by-one in q_pos, the
        tick series, or the ring-cache roll would silently train every
        episode-mode run on shifted prices."""
        _, agent, env = self._setup(num_agents=2)
        model = agent.model
        params = model.init(jax.random.PRNGKey(3))
        n_agents, t_len = 2, 6
        from sharetrade_tpu.agents.base import batched_carry, batched_reset

        # Incremental: prefill at t=0 then T-1 cache steps, Hold actions.
        state = batched_reset(env, n_agents)
        carry = batched_carry(model, n_agents)
        inc_logits, inc_values, obs_seq = [], [], []
        for _ in range(t_len):
            obs = jax.vmap(env.observe)(state)
            outs, carry = model.apply_batch(params, obs, carry)
            inc_logits.append(outs.logits)
            inc_values.append(outs.value)
            obs_seq.append(obs)
            state, _ = jax.vmap(env.step)(
                state, jnp.full((n_agents,), 2, jnp.int32))  # Hold

        # Trunk: same episode start, ticks read off the future windows.
        state0 = batched_reset(env, n_agents)
        carry0 = batched_carry(model, n_agents)
        obs0 = jax.vmap(env.observe)(state0)
        ticks = jnp.stack(
            [o[:, self.WINDOW - 1] for o in obs_seq[1:]]
            + [jax.vmap(env.observe)(state)[:, self.WINDOW - 1]], axis=1)
        hn_base, carry_tr = model.apply_rollout_trunk(
            params, obs0, ticks, carry0)
        for i in range(t_len):
            outs = model.apply_rollout_head(params, hn_base[:, i], obs_seq[i])
            np.testing.assert_allclose(
                np.asarray(outs.logits), np.asarray(inc_logits[i]),
                atol=3e-4, err_msg=f"step {i} logits")
            np.testing.assert_allclose(
                np.asarray(outs.value), np.asarray(inc_values[i]),
                atol=3e-4, err_msg=f"step {i} value")

        # Carry handoff: identical ring-layout cache, history, and cursor.
        assert int(carry_tr["t"][0]) == int(carry["t"][0])
        np.testing.assert_allclose(np.asarray(carry_tr["hist"]),
                                   np.asarray(carry["hist"]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(carry_tr["k"]),
                                   np.asarray(carry["k"]), atol=3e-4)
        np.testing.assert_allclose(np.asarray(carry_tr["v"]),
                                   np.asarray(carry["v"]), atol=3e-4)

    @pytest.mark.slow
    def test_shared_trunk_replay_matches_per_agent_unroll(self):
        """apply_unroll_shared (trunk once, per-agent heads) must produce
        the same logits/values AND the same parameter gradients as the
        per-agent apply_unroll — the linearity argument (B identical trunk
        paths pulled back by per-agent cotangents == one shared path pulled
        back by their sum) checked numerically, with distinct per-agent
        loss weights so the cotangents genuinely differ."""
        from sharetrade_tpu.agents.rollout import collect_rollout

        _, agent, env = self._setup(num_agents=3)
        model = agent.model
        ts = agent.init(jax.random.PRNGKey(0))
        w_agent = jnp.asarray([0.3, 1.7, 0.9])

        for chunk in range(2):   # prefill chunk AND a carry-crossing chunk
            # The per-agent reference replays from the WHOLE unroll-start
            # carry, the shared path from the replay carry the rollout
            # hands the learners.
            whole_carry = ts.carry
            ts, traj, _, replay_init = collect_rollout(model, env, ts, 8, 3)

            l_sh, v_sh, _ = model.apply_unroll_shared(
                ts.params, traj.obs, replay_init)
            l_pa, v_pa, _ = model.apply_unroll(
                ts.params, traj.obs, whole_carry)
            np.testing.assert_allclose(np.asarray(l_sh), np.asarray(l_pa),
                                       atol=3e-4, err_msg=f"chunk {chunk}")
            np.testing.assert_allclose(np.asarray(v_sh), np.asarray(v_pa),
                                       atol=3e-4, err_msg=f"chunk {chunk}")

            def loss(params, fwd, carry):
                logits, values, _ = fwd(params, traj.obs, carry)
                lp = jax.nn.log_softmax(logits)
                return (jnp.sum(lp[..., 0] * w_agent[None, :])
                        + jnp.sum(jnp.square(values) * w_agent[None, :]))

            g_sh = jax.grad(loss)(ts.params, model.apply_unroll_shared,
                                  replay_init)
            g_pa = jax.grad(loss)(ts.params, model.apply_unroll, whole_carry)
            for p_sh, p_pa in zip(jax.tree.leaves(g_sh),
                                  jax.tree.leaves(g_pa)):
                # rtol accommodates backend reduction-order noise (TPU
                # measured ~3e-7, CPU ~5e-5 relative); a genuinely wrong
                # gradient path diverges by O(1) relative.
                np.testing.assert_allclose(
                    np.asarray(p_sh), np.asarray(p_pa),
                    rtol=1e-4, atol=5e-3,
                    err_msg=f"gradient mismatch (chunk {chunk})")

    @pytest.mark.slow
    def test_shared_trunk_replay_skips_zeroed_quarantine_rows(self):
        """A quarantined row's stored obs is all-zero; the shared replay
        must elect a live representative (not the zeroed row) and stay
        finite everywhere."""
        from sharetrade_tpu.agents.rollout import collect_rollout

        _, agent, env = self._setup(num_agents=3)
        model = agent.model
        ts = agent.init(jax.random.PRNGKey(0))
        ts, traj, _, replay_init = collect_rollout(model, env, ts, 8, 3)
        zeroed = traj._replace(
            obs=traj.obs.at[:, 0].set(0.0),
            active=traj.active.at[:, 0].set(0.0))

        l_sh, v_sh, _ = model.apply_unroll_shared(
            ts.params, zeroed.obs, replay_init)
        l_pa, v_pa, _ = model.apply_unroll(ts.params, traj.obs, replay_init)
        assert np.isfinite(np.asarray(l_sh)).all()
        assert np.isfinite(np.asarray(v_sh)).all()
        # Healthy rows replay exactly as if the zeroed row were absent.
        np.testing.assert_allclose(np.asarray(l_sh[:, 1:]),
                                   np.asarray(l_pa[:, 1:]), atol=3e-4)
        np.testing.assert_allclose(np.asarray(v_sh[:, 1:]),
                                   np.asarray(v_pa[:, 1:]), atol=3e-4)

    @pytest.mark.slow
    def test_shared_trunk_replay_skips_mid_unroll_quarantined_row(self):
        """The NORMAL fault timing: a row quarantined mid-unroll has real
        early-step obs but a zero-sanitized tail. Electing on step 0 alone
        would pick it (row 0 wins argmax) and eps-clamp its zeroed tail
        into finite garbage inside every healthy agent's trunk; the
        election must scan the WHOLE trajectory and skip it."""
        from sharetrade_tpu.agents.rollout import collect_rollout

        _, agent, env = self._setup(num_agents=3)
        model = agent.model
        ts = agent.init(jax.random.PRNGKey(0))
        ts, traj, _, replay_init = collect_rollout(model, env, ts, 8, 3)
        # Row 0 healthy through step 3, zeroed from step 4 onward.
        zeroed = traj._replace(
            obs=traj.obs.at[4:, 0].set(0.0),
            active=traj.active.at[4:, 0].set(0.0))

        l_sh, v_sh, _ = model.apply_unroll_shared(
            ts.params, zeroed.obs, replay_init)
        l_pa, v_pa, _ = model.apply_unroll(ts.params, traj.obs, replay_init)
        assert np.isfinite(np.asarray(l_sh)).all()
        assert np.isfinite(np.asarray(v_sh)).all()
        # Healthy rows replay exactly as if the poisoned row were absent —
        # fails if the zero-tailed row 0 was elected representative.
        np.testing.assert_allclose(np.asarray(l_sh[:, 1:]),
                                   np.asarray(l_pa[:, 1:]), atol=3e-4)
        np.testing.assert_allclose(np.asarray(v_sh[:, 1:]),
                                   np.asarray(v_pa[:, 1:]), atol=3e-4)

    def test_quarantined_representative_row_does_not_corrupt_trunk(self):
        """The shared-trunk rollout elects a HEALTHY representative row: a
        quarantined row's cursor freezes while the broadcast carry keeps
        advancing, so electing it (the old fixed row 0) would feed every
        healthy agent windows from a stale cursor with desynced RoPE
        positions. Poison row 0, roll two more chunks, and compare the
        healthy rows' trajectories against an unpoisoned twin."""
        from sharetrade_tpu.agents.rollout import collect_rollout

        _, agent, env = self._setup(num_agents=3)
        model = agent.model
        ts = agent.init(jax.random.PRNGKey(0))
        ts, *_ = collect_rollout(model, env, ts, 8, 3)   # chunk A: healthy
        twin = ts

        budget = np.asarray(ts.env_state.budget).copy()
        budget[0] = np.nan                               # row 0 poisoned
        ts = ts.replace(env_state=ts.env_state.replace(
            budget=jnp.asarray(budget)))

        for _ in range(2):                               # chunks B, C
            ts, traj_p, _, _ = collect_rollout(model, env, ts, 8, 3)
            twin, traj_t, _, _ = collect_rollout(model, env, twin, 8, 3)
            np.testing.assert_allclose(
                np.asarray(traj_p.obs[:, 1:]), np.asarray(traj_t.obs[:, 1:]),
                atol=1e-5, err_msg="healthy rows fed stale-cursor windows")
            np.testing.assert_array_equal(np.asarray(traj_p.action[:, 1:]),
                                          np.asarray(traj_t.action[:, 1:]))
        np.testing.assert_array_equal(np.asarray(ts.env_state.t[1:]),
                                      np.asarray(twin.env_state.t[1:]))

    @pytest.mark.parametrize("leaf", ["k", "v", "hist"])
    def test_nan_carry_row_not_elected_representative(self, leaf):
        """election_health ANDs model-carry finiteness into the election:
        a row with a finite wallet but a NaN carry (K/V cache, or tick
        history) must not be elected — its carry would broadcast into the
        shared trunk and poison every agent's windows, escalating a one-row
        fault to a full-batch corruption. The replay never reads K or V,
        and its election refuses such a row all the same: the health
        vector of its replay carry is rows_finite of the WHOLE carry."""
        from sharetrade_tpu.agents.rollout import collect_rollout

        _, agent, env = self._setup(num_agents=3)
        model = agent.model
        ts = agent.init(jax.random.PRNGKey(0))
        ts, *_ = collect_rollout(model, env, ts, 8, 3)   # chunk A: healthy
        twin = ts

        poisoned = np.asarray(ts.carry[leaf]).copy()
        poisoned[0] = np.nan                             # row 0 carry poisoned
        carry = {**ts.carry, leaf: jnp.asarray(poisoned)}
        if leaf != "hist":
            # The NaN sits in the cache ONLY. What the replay reads of
            # row 0 is finite, and wrong, so electing it would show in
            # every agent's outputs and not as a NaN.
            carry["hist"] = carry["hist"].at[0].multiply(1.5)
        ts = ts.replace(carry=carry)

        ts, traj_p, _, replay_p = collect_rollout(model, env, ts, 8, 3)
        twin, traj_t, _, replay_t = collect_rollout(model, env, twin, 8, 3)
        assert np.isfinite(np.asarray(traj_p.obs)).all(), \
            "NaN carry broadcast into the shared trunk"
        np.testing.assert_allclose(
            np.asarray(traj_p.obs[:, 1:]), np.asarray(traj_t.obs[:, 1:]),
            atol=1e-5, err_msg="healthy rows corrupted by NaN-carry rep")
        np.testing.assert_array_equal(np.asarray(traj_p.action[:, 1:]),
                                      np.asarray(traj_t.action[:, 1:]))

        # Replay-side election must skip the NaN-carry row too: every
        # row's stored obs is healthy, so an obs-only election would tie
        # at count T and elect poisoned row 0 into the ONE shared pass.
        assert "k" not in replay_p and "v" not in replay_p
        np.testing.assert_array_equal(np.asarray(replay_p["ok"]),
                                      [False, True, True])
        l_sh, v_sh, _ = model.apply_unroll_shared(
            ts.params, traj_t.obs, replay_p)
        l_tw, v_tw, _ = model.apply_unroll_shared(
            ts.params, traj_t.obs, replay_t)
        np.testing.assert_allclose(
            np.asarray(l_sh[:, 1:]), np.asarray(l_tw[:, 1:]), atol=1e-6,
            err_msg="replay elected the NaN-carry representative")
        np.testing.assert_allclose(
            np.asarray(v_sh[:, 1:]), np.asarray(v_tw[:, 1:]), atol=1e-6)

    @pytest.mark.parametrize("how", ["eager", "jit"])
    @pytest.mark.parametrize("dtype,shape,planted", _ROWS_FINITE_CASES)
    def test_rows_finite_is_the_plain_predicate(self, dtype, shape, planted,
                                                how):
        """``rows_finite`` is ``np.isfinite(x.reshape(B, -1)).all(-1)``,
        bit for bit, whatever its two stages are (PERF.md PR 35): NaN and
        either infinity anywhere in a row give False, the largest finite
        value everywhere stays True (no overflow into Inf on the way),
        integer leaves pass, and an unbatched table beside the batched
        leaf is ignored even when it holds a NaN."""
        from sharetrade_tpu.models.core import rows_finite

        x = _rows_finite_input(dtype, shape, planted)
        batch = shape[0]
        want = np.isfinite(
            x.astype(np.float32).reshape(batch, -1)).all(-1)
        bad_rows = (0 if planted in ("none", "max")
                    else 2 if planted.endswith("two_rows") else 1)
        assert want.sum() == batch - bad_rows
        fn = rows_finite if how == "eager" else jax.jit(
            rows_finite, static_argnums=1)
        leaf = jnp.asarray(x)
        np.testing.assert_array_equal(np.asarray(fn(leaf, batch)), want)
        tree = {"x": leaf, "t": jnp.arange(batch, dtype=jnp.int32),
                "table": jnp.full((batch + 1, 4), jnp.nan, jnp.float32)}
        got = fn(tree, batch)
        assert got.dtype == jnp.bool_ and got.shape == (batch,)
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_greedy_eval_trunk_matches_incremental(self):
        """Orchestrator.evaluate()'s precomputed-trunk greedy replay must
        reproduce the per-step incremental greedy rollout (same argmax
        actions, same rewards, same final portfolio)."""
        from sharetrade_tpu.agents.rollout import greedy_rollout_precomputed

        _, agent, env = self._setup()
        model = agent.model
        params = model.init(jax.random.PRNGKey(5))

        final_t, rewards_t = greedy_rollout_precomputed(model, env, params)

        state, carry = env.reset(), model.init_carry()
        rewards_i = []
        for _ in range(env.num_steps):
            obs = env.observe(state)
            out, carry = model.apply(params, obs, carry)
            action = jnp.argmax(out.logits).astype(jnp.int32)
            state, r = env.step(state, action)
            rewards_i.append(float(r))

        np.testing.assert_allclose(np.asarray(rewards_t),
                                   np.asarray(rewards_i), atol=1e-3)
        np.testing.assert_allclose(float(env.portfolio_value(final_t)),
                                   float(env.portfolio_value(state)),
                                   rtol=1e-5)

    def test_single_layer_no_history(self):
        # L=1: hist_len == 0 — the zero-width history path.
        from sharetrade_tpu.agents.rollout import collect_rollout, replay_forward

        _, agent, env = self._setup(num_layers=1)
        model = agent.model
        ts = agent.init(jax.random.PRNGKey(1))
        ts, traj, _, replay_init = collect_rollout(
            model, env, ts, 8, agent.num_agents)
        logits, values, _ = replay_forward(model, ts.params, traj, replay_init)
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(logits), traj.action[..., None], axis=-1)[..., 0]
        np.testing.assert_allclose(np.asarray(logp), np.asarray(traj.logp),
                                   atol=2e-4)

    def test_ppo_training_step_runs(self):
        _, agent, _ = self._setup()
        step = jax.jit(agent.step)
        ts = agent.init(jax.random.PRNGKey(2))
        ts, metrics = step(ts)
        assert int(ts.env_steps) == 8
        assert np.isfinite(float(metrics["loss"]))
        ts, metrics = step(ts)   # second chunk crosses the carry boundary
        assert np.isfinite(float(metrics["loss"]))

    def test_portfolio_state_reaches_the_heads(self):
        # Same prices, different budget in the observation -> different
        # logits (the head-side portfolio injection is live).
        _, agent, _ = self._setup()
        model = agent.model
        params = model.init(jax.random.PRNGKey(3))
        carry = jax.tree.map(lambda x: x[None], model.init_carry())
        obs = jnp.concatenate(
            [jnp.linspace(10.0, 12.0, self.WINDOW), jnp.array([100.0, 3.0])]
        )[None]
        out1, _ = model.apply_batch(params, obs, carry)
        obs2 = obs.at[0, self.WINDOW].set(2400.0)
        out2, _ = model.apply_batch(params, obs2, carry)
        assert not np.allclose(np.asarray(out1.logits),
                               np.asarray(out2.logits))

    @pytest.mark.slow
    def test_episode_moe_rollout_replay_parity_and_training(self):
        """Episode mode composes with MoE: the FFN routes through the
        shared dispatch (models/ffn.py). Dense-mask top-1 is per-token
        exact, so rollout (precomputed trunk + heads), banded replay, AND
        the incremental prefill must all agree; a jitted PPO chunk trains
        with finite loss and a live aux term."""
        from sharetrade_tpu.agents.rollout import (
            collect_rollout, replay_forward)

        _, agent, env = self._setup(moe_experts=4)
        model = agent.model
        ts = agent.init(jax.random.PRNGKey(0))
        assert "moe" in model.init(
            jax.random.PRNGKey(1))["blocks"][0]   # FFN is actually MoE

        for chunk in range(2):
            ts, traj, _, replay_init = collect_rollout(model, env, ts, 8, 3)
            logits, values, aux = replay_forward(
                model, ts.params, traj, replay_init)
            logp = jnp.take_along_axis(
                jax.nn.log_softmax(logits), traj.action[..., None],
                axis=-1)[..., 0]
            np.testing.assert_allclose(
                np.asarray(logp), np.asarray(traj.logp), atol=3e-4,
                err_msg=f"moe chunk {chunk} logp")
            assert float(aux) > 0.0   # balance loss is live

        ts2 = agent.init(jax.random.PRNGKey(2))
        ts2, metrics = jax.jit(agent.step)(ts2)
        assert np.isfinite(float(metrics["loss"]))

    def test_factored_rollout_head_matches_exact(self):
        """rollout_head_factored (trunk terms hoisted, tiny portfolio term
        in-scan) must equal apply_rollout_head exactly up to float
        reassociation — the linearity split is algebraic, not an
        approximation."""
        _, agent, env = self._setup(num_agents=3)
        model = agent.model
        params = model.init(jax.random.PRNGKey(7))
        t_len, bsz, d = 5, 3, model.num_actions
        key = jax.random.PRNGKey(8)
        hn_base = jax.random.normal(key, (t_len + 1, 32))  # d_model=2*16
        base_l, base_v, pf_fn = model.rollout_head_factored(params, hn_base)
        assert base_l.shape == (t_len + 1, d)
        assert base_v.shape == (t_len + 1,)
        obs = jnp.abs(jax.random.normal(
            jax.random.PRNGKey(9), (bsz, model.obs_dim))) * 30.0 + 1.0
        for i in range(t_len + 1):
            exact = model.apply_rollout_head(
                params, jnp.broadcast_to(hn_base[i], (bsz, 32)), obs)
            d_l, d_v = pf_fn(obs)
            np.testing.assert_allclose(
                np.asarray(base_l[i][None] + d_l), np.asarray(exact.logits),
                rtol=1e-5, atol=1e-5, err_msg=f"row {i} logits")
            np.testing.assert_allclose(
                np.asarray(base_v[i] + d_v), np.asarray(exact.value),
                rtol=1e-5, atol=1e-5, err_msg=f"row {i} value")

    def test_remat_blocks_matches_exact(self):
        """model.remat_blocks must be numerically a no-op — identical
        replay outputs AND parameter gradients, only the residual-memory
        profile changes (the HBM lever for the d>=1024 tier)."""
        from sharetrade_tpu.agents.rollout import collect_rollout

        _, agent, env = self._setup(num_agents=3)
        model = agent.model
        ts = agent.init(jax.random.PRNGKey(0))
        init_carry = ts.carry
        ts, traj, _, _ = collect_rollout(model, env, ts, 8, 3)

        _, agent_r, _ = self._setup(num_agents=3, remat_blocks=True)
        model_r = agent_r.model

        def loss(params, fwd):
            logits, values, _ = fwd(params, traj.obs, init_carry)
            return (jnp.sum(jax.nn.log_softmax(logits)[..., 0])
                    + jnp.sum(jnp.square(values)))

        l_e, v_e, _ = model.apply_unroll(ts.params, traj.obs, init_carry)
        l_r, v_r, _ = model_r.apply_unroll(ts.params, traj.obs, init_carry)
        np.testing.assert_allclose(np.asarray(l_r), np.asarray(l_e),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(v_r), np.asarray(v_e),
                                   atol=1e-5)
        g_e = jax.grad(loss)(ts.params, model.apply_unroll)
        g_r = jax.grad(loss)(ts.params, model_r.apply_unroll)
        for p_e, p_r in zip(jax.tree.leaves(g_e), jax.tree.leaves(g_r)):
            # rtol 5e-5, not 1e-5: remat recomputes the block forward
            # inside the backward pass, and XLA fuses/reassociates that
            # recompute differently from the saved-activation path, so
            # gradients agree only to a few float32 ulps (observed max
            # rel diff ~1.2e-5 on CPU) — a compiler-scheduling artifact,
            # not a math difference; the primal outputs above stay at
            # the tight tolerance.
            np.testing.assert_allclose(np.asarray(p_r), np.asarray(p_e),
                                       rtol=5e-5, atol=1e-5)

    def test_episode_pp_b1_pipelines_sequence_chunks(self, cpu_devices,
                                                     monkeypatch):
        """The B=1 replay pass pipelines along the SEQUENCE: banded-halo
        carries stream chunk-to-chunk through the stages, so >1 microbatch
        is in flight (round-4 weak #4: these passes ran m=1 — a full
        pipeline bubble), with parity against the unpartitioned forward."""
        from jax.sharding import Mesh
        from sharetrade_tpu.models.transformer_episode import (
            episode_transformer_policy)
        from sharetrade_tpu.parallel import pipeline as pipeline_mod
        from sharetrade_tpu.parallel.pipeline import stack_stage_params

        mesh = Mesh(np.array(cpu_devices[:2]).reshape(2), ("pp",))
        obs_dim = self.WINDOW + 2
        base = episode_transformer_policy(
            obs_dim, 3, num_layers=2, num_heads=2, head_dim=16,
            use_pallas=False)
        piped = episode_transformer_policy(
            obs_dim, 3, num_layers=2, num_heads=2, head_dim=16,
            use_pallas=False, pp_mesh=mesh)
        params = base.init(jax.random.PRNGKey(3))
        params_pp = dict(params)
        params_pp["blocks"] = stack_stage_params(params["blocks"])

        seen_m = []
        real = pipeline_mod.pipeline_apply

        def spy(stage_fn, sp, mb, *a, **k):
            seen_m.append(mb.shape[0])
            return real(stage_fn, sp, mb, *a, **k)

        monkeypatch.setattr(pipeline_mod, "pipeline_apply", spy)

        t_len = 8
        win = jnp.linspace(10.0, 12.0, self.WINDOW)
        obs_row = jnp.concatenate(
            [win, jnp.asarray([20.0, 0.0])])[None]        # (1, obs_dim)
        obs_t = jnp.broadcast_to(obs_row, (t_len, 1, obs_dim))
        carry1 = jax.tree.map(lambda x: x[None], base.init_carry())

        l_b, v_b, _ = base.apply_unroll(params, obs_t, carry1)
        l_p, v_p, _ = piped.apply_unroll(params_pp, obs_t, carry1)
        np.testing.assert_allclose(np.asarray(l_p), np.asarray(l_b),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(v_p), np.asarray(v_b),
                                   rtol=2e-4, atol=2e-4)
        assert seen_m and max(seen_m) > 1, \
            f"B=1 replay ran a full-bubble pipeline (microbatches: {seen_m})"

    @pytest.mark.slow
    def test_remat_blocks_under_pp_matches_exact(self, cpu_devices):
        """remat_blocks under pp (per-(stage, tick) checkpointing) must be
        a numeric no-op for outputs AND gradients."""
        from jax.sharding import Mesh
        from sharetrade_tpu.models.transformer_episode import (
            episode_transformer_policy)
        from sharetrade_tpu.parallel.pipeline import stack_stage_params

        mesh = Mesh(np.array(cpu_devices[:2]).reshape(2), ("pp",))
        obs_dim = self.WINDOW + 2
        kw = dict(num_layers=2, num_heads=2, head_dim=16, use_pallas=False)
        base = episode_transformer_policy(obs_dim, 3, **kw)
        piped = episode_transformer_policy(obs_dim, 3, pp_mesh=mesh, **kw)
        piped_r = episode_transformer_policy(
            obs_dim, 3, pp_mesh=mesh, remat_blocks=True, **kw)
        params = base.init(jax.random.PRNGKey(3))
        params_pp = dict(params)
        params_pp["blocks"] = stack_stage_params(params["blocks"])

        t_len = 8
        win = jnp.linspace(10.0, 12.0, self.WINDOW)
        obs_row = jnp.concatenate([win, jnp.asarray([20.0, 0.0])])[None]
        obs_t = jnp.broadcast_to(obs_row, (t_len, 1, obs_dim))
        carry1 = jax.tree.map(lambda x: x[None], base.init_carry())

        def loss(p, fwd):
            logits, values, _ = fwd(p, obs_t, carry1)
            return (jnp.sum(jax.nn.log_softmax(logits)[..., 0])
                    + jnp.sum(jnp.square(values)))

        l_p, v_p, _ = piped.apply_unroll(params_pp, obs_t, carry1)
        l_r, v_r, _ = piped_r.apply_unroll(params_pp, obs_t, carry1)
        np.testing.assert_allclose(np.asarray(l_r), np.asarray(l_p),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(v_r), np.asarray(v_p),
                                   rtol=1e-5, atol=1e-5)
        g_p = jax.grad(loss)(params_pp, piped.apply_unroll)
        g_r = jax.grad(loss)(params_pp, piped_r.apply_unroll)
        for a, b in zip(jax.tree.leaves(g_p), jax.tree.leaves(g_r)):
            # rtol accommodates recompute-order noise (the checkpointed
            # backward re-fuses differently than the stored-residual one;
            # measured ~5e-5 relative on CPU); a wrong remat diverges by
            # O(1) relative.
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-4, atol=1e-2)

        # The BATCH-microbatch path (bsz divisible by the stage count) on a
        # dp x pp mesh, with dp-sharded microbatches so the checkpointed
        # stage_fn includes the pmean(aux, b_axis) branch — the path even
        # production agent batches take.
        mesh2 = Mesh(np.array(cpu_devices[:4]).reshape(2, 2), ("dp", "pp"))
        kw2 = dict(kw, pp_mesh=mesh2, pp_batch_axis="dp")
        piped2 = episode_transformer_policy(obs_dim, 3, **kw2)
        piped2_r = episode_transformer_policy(
            obs_dim, 3, remat_blocks=True, **kw2)
        bsz = 4
        rows = jnp.stack([win * (1.0 + 0.2 * b) for b in range(bsz)])
        obs_rows = jnp.concatenate(
            [rows, jnp.full((bsz, 1), 20.0), jnp.zeros((bsz, 1))], axis=-1)
        obs_t4 = jnp.broadcast_to(obs_rows, (t_len, bsz, obs_dim))
        carry4 = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (bsz,) + x.shape),
            base.init_carry())

        def loss4(p, fwd):
            logits, values, _ = fwd(p, obs_t4, carry4)
            return (jnp.sum(jax.nn.log_softmax(logits)[..., 0])
                    + jnp.sum(jnp.square(values)))

        l_p4, v_p4, _ = piped2.apply_unroll(params_pp, obs_t4, carry4)
        l_r4, v_r4, _ = piped2_r.apply_unroll(params_pp, obs_t4, carry4)
        np.testing.assert_allclose(np.asarray(l_r4), np.asarray(l_p4),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(v_r4), np.asarray(v_p4),
                                   rtol=1e-5, atol=1e-5)
        g_p4 = jax.grad(loss4)(params_pp, piped2.apply_unroll)
        g_r4 = jax.grad(loss4)(params_pp, piped2_r.apply_unroll)
        for a, b in zip(jax.tree.leaves(g_p4), jax.tree.leaves(g_r4)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-4, atol=1e-2)

    @pytest.mark.slow
    def test_episode_pipeline_matches_unpartitioned(self, cpu_devices):
        """Episode × pp: the pipelined banded forward (positions riding the
        state, K/V + aux escaping as pipeline sides) must reproduce the
        unpartitioned model — logits/values of the replay AND the trunk's
        carry handoff — for both a multi-microbatch agent batch and the
        batch-of-1 trunk pass."""
        from jax.sharding import Mesh
        from sharetrade_tpu.agents.rollout import collect_rollout
        from sharetrade_tpu.models.transformer_episode import (
            episode_transformer_policy)
        from sharetrade_tpu.parallel.pipeline import stack_stage_params

        mesh = Mesh(np.array(cpu_devices[:2]).reshape(2), ("pp",))
        obs_dim = self.WINDOW + 2
        base = episode_transformer_policy(
            obs_dim, 3, num_layers=2, num_heads=2, head_dim=16,
            use_pallas=False)
        piped = episode_transformer_policy(
            obs_dim, 3, num_layers=2, num_heads=2, head_dim=16,
            use_pallas=False, pp_mesh=mesh)
        params = base.init(jax.random.PRNGKey(3))
        params_pp = dict(params)
        params_pp["blocks"] = stack_stage_params(params["blocks"])

        _, agent, env = self._setup(num_agents=4)
        ts = agent.init(jax.random.PRNGKey(0))
        init_carry = ts.carry
        ts, traj, _, _ = collect_rollout(base, env, ts, 6, 4)

        l_b, v_b, _ = base.apply_unroll(params, traj.obs, init_carry)
        l_p, v_p, _ = piped.apply_unroll(params_pp, traj.obs, init_carry)
        np.testing.assert_allclose(np.asarray(l_p), np.asarray(l_b),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(v_p), np.asarray(v_b),
                                   rtol=2e-4, atol=2e-4)

        # Trunk pass (B=1, single microbatch) + carry handoff via sides.
        state1 = jax.tree.map(lambda x: x[:1], ts.env_state)
        carry1 = jax.tree.map(lambda x: x[:1], ts.carry)
        obs1 = jax.vmap(env.observe)(state1)
        ticks = jnp.broadcast_to(
            jnp.linspace(11.0, 12.0, 6, dtype=jnp.float32)[None], (1, 6))
        hn_b, carry_b = base.apply_rollout_trunk(params, obs1, ticks, carry1)
        hn_p, carry_p = piped.apply_rollout_trunk(
            params_pp, obs1, ticks, carry1)
        np.testing.assert_allclose(np.asarray(hn_p), np.asarray(hn_b),
                                   rtol=2e-4, atol=2e-4)
        for key in ("k", "v", "hist"):
            np.testing.assert_allclose(
                np.asarray(carry_p[key]), np.asarray(carry_b[key]),
                rtol=2e-4, atol=2e-4, err_msg=f"carry[{key}]")
        assert int(carry_p["t"][0]) == int(carry_b["t"][0])

        # dp × pp: microbatches dp-sharded, so the K/V pipeline sides must
        # carry EACH shard's own rows (a replicated side spec would hand
        # one shard's cache to every agent). Rows are made deliberately
        # distinct — the lockstep env's identical rows would mask that.
        mesh2 = Mesh(np.array(cpu_devices[:4]).reshape(2, 2), ("dp", "pp"))
        piped2 = episode_transformer_policy(
            obs_dim, 3, num_layers=2, num_heads=2, head_dim=16,
            use_pallas=False, pp_mesh=mesh2, pp_batch_axis="dp")
        t_len, bsz = 6, 4
        base_win = jnp.linspace(10.0, 12.0, self.WINDOW)
        rows = jnp.stack([base_win * (1.0 + 0.2 * b) for b in range(bsz)])
        obs_rows = jnp.concatenate(
            [rows, jnp.full((bsz, 1), 20.0), jnp.zeros((bsz, 1))], axis=-1)
        obs_t = jnp.broadcast_to(obs_rows, (t_len, bsz, obs_dim))
        carry4 = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (bsz,) + x.shape),
            base.init_carry())
        l_b4, v_b4, _ = base.apply_unroll(params, obs_t, carry4)
        l_p4, v_p4, _ = piped2.apply_unroll(params_pp, obs_t, carry4)
        np.testing.assert_allclose(np.asarray(l_p4), np.asarray(l_b4),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg="dp-sharded pipelined replay")
        ticks4 = jnp.stack(
            [jnp.linspace(11.0, 12.0, t_len) * (1.0 + 0.2 * b)
             for b in range(bsz)])
        hn_b4, carry_b4 = base.apply_rollout_trunk(
            params, obs_rows, ticks4, carry4)
        hn_p4, carry_p4 = piped2.apply_rollout_trunk(
            params_pp, obs_rows, ticks4, carry4)
        np.testing.assert_allclose(np.asarray(hn_p4), np.asarray(hn_b4),
                                   rtol=2e-4, atol=2e-4)
        for key in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(carry_p4[key]), np.asarray(carry_b4[key]),
                rtol=2e-4, atol=2e-4,
                err_msg=f"dp-sharded K/V side carry[{key}]")

    def test_episode_mode_rejects_non_transformer_kinds(self):
        from sharetrade_tpu.config import ModelConfig as MC
        with pytest.raises(ValueError, match="transformer mode"):
            build_model(MC(kind="lstm", seq_mode="episode"), 18)
        with pytest.raises(ValueError, match="seq_mode"):
            build_model(MC(kind="mlp", seq_mode="epsiode"), 18)



    def test_a2c_and_pg_episode_replay(self):
        # replay_forward's apply_unroll dispatch serves every on-policy
        # learner, not just PPO.
        for algo in ("a2c", "pg"):
            _, agent, _ = self._setup(algo=algo)
            ts = agent.init(jax.random.PRNGKey(4))
            ts, metrics = jax.jit(agent.step)(ts)
            assert np.isfinite(float(metrics["loss"])), algo
            assert int(ts.env_steps) > 0

    def test_evaluate_and_resume_roundtrip(self, tmp_path):
        """Episode-mode carry (K/V cache + tick history + step counter)
        through the full runtime: train, checkpoint, restore bit-exact,
        greedy-evaluate (the per-step incremental path end to end)."""
        from sharetrade_tpu.config import FrameworkConfig
        from sharetrade_tpu.runtime import Orchestrator, ReplyState

        cfg = FrameworkConfig()
        cfg.learner.algo = "ppo"
        cfg.model.kind = "transformer"
        cfg.model.seq_mode = "episode"
        cfg.model.num_layers = 2
        cfg.model.num_heads = 2
        cfg.model.head_dim = 16
        cfg.env.window = self.WINDOW
        cfg.parallel.num_workers = 3
        cfg.learner.unroll_len = 8
        cfg.runtime.chunk_steps = 8
        cfg.runtime.checkpoint_dir = str(tmp_path / "ckpts")
        cfg.runtime.checkpoint_every_updates = 8

        prices = np.linspace(10.0, 20.0, self.WINDOW + 24, dtype=np.float32)
        orch = Orchestrator(cfg)
        orch.send_training_data(prices)
        orch.start_training(background=False)
        assert orch.is_everything_done().state is ReplyState.COMPLETED
        avg = orch.get_avg().value
        ev = orch.evaluate()
        assert np.isfinite(ev["eval_portfolio"])

        resumed = Orchestrator(cfg)
        resumed.send_training_data(prices, resume=True)
        carry = resumed.train_state.carry
        assert int(np.asarray(carry["t"])[0]) > 0      # cursor restored
        assert carry["k"].shape[0] == 3                # per-agent cache
        resumed.start_training(background=False)
        assert resumed.get_avg().ok
        assert resumed.get_avg().value == pytest.approx(avg, rel=1e-5)


class TestTCN:
    """Dilated causal conv tick policy (models/tcn.py)."""

    def _model(self, obs_dim=OBS_DIM, channels=16):
        return build_model(
            ModelConfig(kind="tcn", hidden_dim=channels), obs_dim)

    def test_shapes_and_finite(self):
        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        out, carry = model.apply(params, _obs(jax.random.PRNGKey(1)), ())
        assert out.logits.shape == (3,) and out.value.shape == ()
        assert np.isfinite(np.asarray(out.logits)).all()
        assert carry == ()

    def test_receptive_field_covers_window(self):
        # Perturbing the OLDEST tick must reach the summary (last) position:
        # the dilation stack is auto-sized to cover the full window.
        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        obs = _obs(jax.random.PRNGKey(2))
        base, _ = model.apply(params, obs, ())
        pert, _ = model.apply(params, obs.at[0].mul(3.0), ())
        assert not np.allclose(np.asarray(base.logits),
                               np.asarray(pert.logits))

    def test_scale_invariance(self):
        # Tokens are rel/log-ret (shared with the transformer): scaling the
        # whole window and budget by 10x leaves the decision unchanged.
        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        prices = jnp.linspace(50.0, 60.0, 201)
        obs1 = jnp.concatenate([prices, jnp.array([2400.0, 3.0])])
        obs2 = jnp.concatenate([prices * 10, jnp.array([24000.0, 3.0])])
        out1, _ = model.apply(params, obs1, ())
        out2, _ = model.apply(params, obs2, ())
        np.testing.assert_allclose(np.asarray(out1.logits),
                                   np.asarray(out2.logits), rtol=1e-3)

    def test_causal_padding_limits_receptive_field(self):
        # A deliberately SHALLOW stack (1 block, kernel 3, dilation 1) has a
        # 3-tick receptive field at the summary position. Perturbing ticks
        # OUTSIDE it must not change the output — with anti-causal (right)
        # padding the summary would instead depend on padding, not on the
        # latest ticks, and the in-field perturbation check would fail.
        from sharetrade_tpu.models.tcn import tcn_policy
        obs_dim = 34                      # window 32
        model = tcn_policy(obs_dim, channels=8, num_blocks=1)
        params = model.init(jax.random.PRNGKey(0))
        obs = jax.random.uniform(jax.random.PRNGKey(5), (obs_dim,),
                                 minval=10.0, maxval=20.0)
        base, _ = model.apply(params, obs, ())
        # Ticks 0..27 are beyond the receptive field of the last position
        # EXCEPT through the log-return of tick 28... conv taps cover ticks
        # {29, 30, 31}; tick-29's log-return reads tick 28 too. Perturb
        # strictly earlier ticks only:
        # (tick 5 affects only the rel/log-ret features of ticks 5 and 6,
        # both outside the field, so any output change would mean the conv
        # reads positions it must not)
        pert_far, _ = model.apply(params, obs.at[5].mul(2.0), ())
        np.testing.assert_allclose(np.asarray(base.logits),
                                   np.asarray(pert_far.logits), atol=1e-5)
        # An in-field tick must, by contrast, change the output:
        pert_near, _ = model.apply(params, obs.at[30].mul(2.0), ())
        assert not np.allclose(np.asarray(base.logits),
                               np.asarray(pert_near.logits))

    def test_portfolio_reaches_heads(self):
        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        obs = _obs(jax.random.PRNGKey(3))
        out1, _ = model.apply(params, obs, ())
        out2, _ = model.apply(params, obs.at[OBS_DIM - 2].set(9999.0), ())
        assert not np.allclose(np.asarray(out1.logits),
                               np.asarray(out2.logits))

    def test_gradients_flow(self):
        model = self._model(channels=8)
        params = model.init(jax.random.PRNGKey(0))
        obs = _obs(jax.random.PRNGKey(4))

        def loss(p):
            out, _ = model.apply(p, obs, ())
            return jnp.sum(out.logits ** 2) + out.value ** 2

        grads = jax.grad(loss)(params)
        norms = [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(grads)]
        assert all(np.isfinite(norms)) and any(n > 0 for n in norms)

    @pytest.mark.slow
    def test_ppo_training_step(self):
        from sharetrade_tpu.agents import build_agent
        from sharetrade_tpu.config import FrameworkConfig
        from sharetrade_tpu.env import trading

        cfg = FrameworkConfig()
        cfg.learner.algo = "ppo"
        cfg.model.kind = "tcn"
        cfg.model.hidden_dim = 16
        cfg.env.window = 32
        cfg.parallel.num_workers = 4
        cfg.learner.unroll_len = 8
        cfg.runtime.chunk_steps = 8
        env_params = trading.env_from_prices(
            jnp.linspace(10.0, 20.0, 80), window=cfg.env.window)
        agent = build_agent(cfg, env_params)
        step = jax.jit(agent.step)
        ts = agent.init(jax.random.PRNGKey(0))
        ts, metrics = step(ts)
        assert np.isfinite(float(metrics["loss"]))
        assert int(ts.env_steps) == 8

    def test_value_based_algos_reject_tcn(self):
        from sharetrade_tpu.agents import build_agent
        from sharetrade_tpu.config import FrameworkConfig
        from sharetrade_tpu.env import trading

        cfg = FrameworkConfig()
        cfg.learner.algo = "dqn"
        cfg.model.kind = "tcn"
        env_params = trading.env_from_prices(
            jnp.linspace(10.0, 20.0, 250), window=201)
        with pytest.raises(ValueError, match="mlp"):
            build_agent(cfg, env_params)
