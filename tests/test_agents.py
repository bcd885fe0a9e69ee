"""Learner tests: schedule parity, TD math, replay semantics, every algorithm
end-to-end under jit on a tiny environment."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sharetrade_tpu.agents import build_agent
from sharetrade_tpu.agents.base import epsilon_greedy, exploit_probability
from sharetrade_tpu.agents.dqn import ReplayBuffer, fill_replay_from_journal
from sharetrade_tpu.agents.qlearn import make_qlearn_agent
from sharetrade_tpu.config import FrameworkConfig, LearnerConfig
from sharetrade_tpu.data.journal import Journal
from sharetrade_tpu.env import trading
from sharetrade_tpu.models.mlp import q_mlp

WINDOW = 8


def tiny_env(n=64, budget=500.0):
    prices = jnp.linspace(10.0, 20.0, n)
    return trading.make_trading_env(prices, window=WINDOW, initial_budget=budget)


def tiny_config(algo, **learner_kw):
    cfg = FrameworkConfig()
    cfg.learner.algo = algo
    for k, v in learner_kw.items():
        setattr(cfg.learner, k, v)
    cfg.env.window = WINDOW
    cfg.model.hidden_dim = 16
    cfg.model.num_layers = 1
    cfg.model.num_heads = 2
    cfg.model.head_dim = 8
    cfg.parallel.num_workers = 4
    cfg.runtime.chunk_steps = 8
    cfg.learner.unroll_len = 8
    cfg.learner.replay_capacity = 256
    cfg.learner.replay_batch = 16
    return cfg


class TestEpsilonSchedule:
    """QDecisionPolicyActor.scala:58: exploit iff rand < min(0.9, step/1000)."""

    def test_ramp_values(self):
        cfg = LearnerConfig()
        for step, want in [(0, 0.0), (500, 0.5), (900, 0.9), (5000, 0.9)]:
            got = float(exploit_probability(jnp.int32(step), cfg))
            assert got == pytest.approx(want), step

    def test_step_zero_is_uniform_random(self):
        # At step 0 exploit prob is 0: action never comes from argmax.
        cfg = LearnerConfig()
        q = jnp.array([100.0, -100.0, -100.0])  # argmax = 0, overwhelmingly
        keys = jax.random.split(jax.random.PRNGKey(0), 300)
        acts = jax.vmap(lambda k: epsilon_greedy(k, q, jnp.int32(0), cfg))(keys)
        counts = np.bincount(np.asarray(acts), minlength=3)
        assert (counts > 50).all()  # all three actions occur ~uniformly

    def test_late_steps_mostly_greedy(self):
        cfg = LearnerConfig()
        q = jnp.array([-5.0, 10.0, -5.0])
        keys = jax.random.split(jax.random.PRNGKey(1), 300)
        acts = jax.vmap(lambda k: epsilon_greedy(k, q, jnp.int32(10_000), cfg))(keys)
        frac_greedy = float(np.mean(np.asarray(acts) == 1))
        assert 0.85 < frac_greedy < 0.99  # ~ 0.9 + 0.1/3


class TestQLearnTD:
    def _run_one_step(self, update_taken_action):
        env = tiny_env()
        cfg = LearnerConfig(update_taken_action=update_taken_action)
        model = q_mlp(obs_dim=WINDOW + 2, hidden_dim=4, parity=True)
        agent = make_qlearn_agent(model, env, cfg,
                                  num_agents=1, steps_per_chunk=1)
        ts = agent.init(jax.random.PRNGKey(42))
        ts2, metrics = jax.jit(agent.step)(ts)
        return ts, ts2, metrics, model, env, cfg

    def test_one_step_matches_independent_computation(self):
        ts, ts2, metrics, model, env, cfg = self._run_one_step(True)

        # Replicate the step with straight-line code (no scan, no masking).
        rng, k_act = jax.random.split(ts.rng)
        act_key = jax.random.split(k_act, 1)[0]
        obs = env.observe(jax.tree.map(lambda x: x[0], ts.env_state))
        q_s, _ = model.apply(ts.params, obs, ())
        action = epsilon_greedy(act_key, q_s.logits, ts.env_steps, cfg)
        env1, reward = env.step(
            jax.tree.map(lambda x: x[0], ts.env_state), action)
        next_obs = env.observe(env1)

        def loss(params):
            q, _ = model.apply(params, obs, ())
            qn, _ = model.apply(params, next_obs, ())
            target = reward + cfg.gamma * jnp.max(jax.lax.stop_gradient(qn.logits))
            return jnp.square(q.logits[action] - target)

        grads = jax.grad(loss)(ts.params)
        opt = optax.adagrad(cfg.learning_rate)
        updates, _ = opt.update(grads, opt.init(ts.params), ts.params)
        want = optax.apply_updates(ts.params, updates)

        for got_leaf, want_leaf in zip(jax.tree.leaves(ts2.params),
                                       jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(got_leaf),
                                       np.asarray(want_leaf), rtol=1e-5, atol=1e-6)
        assert int(ts2.updates) == 1 and int(ts2.env_steps) == 1

    def test_bug_parity_mode_differs(self):
        # The reference updates the NEXT state's argmax index
        # (QDecisionPolicyActor.scala:69-71); textbook updates the taken
        # action. With enough steps the two must produce different params.
        def run(taken):
            env = tiny_env()
            cfg = LearnerConfig(update_taken_action=taken)
            # parity=False: the parity head's output ReLU can kill every
            # gradient at tiny widths, making the two modes trivially equal.
            model = q_mlp(obs_dim=WINDOW + 2, hidden_dim=4, parity=False)
            agent = make_qlearn_agent(model, env, cfg,
                                      num_agents=2, steps_per_chunk=20)
            ts0 = agent.init(jax.random.PRNGKey(7))
            ts, _ = jax.jit(agent.step)(ts0)
            return ts0.params, ts.params

        p0, p_fixed = run(True)
        _, p_bug = run(False)
        trained = [float(np.abs(np.asarray(a) - np.asarray(b)).max())
                   for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p_fixed))]
        assert max(trained) > 0, "training was a no-op; test is vacuous"
        diffs = [float(np.abs(np.asarray(a) - np.asarray(b)).max())
                 for a, b in zip(jax.tree.leaves(p_fixed), jax.tree.leaves(p_bug))]
        assert max(diffs) > 0

    def test_horizon_freeze(self):
        # Chunks past episode end must not step envs or update params.
        env = tiny_env(n=WINDOW + 3)  # 3-step episode
        cfg = LearnerConfig()
        model = q_mlp(obs_dim=WINDOW + 2, hidden_dim=4)
        agent = make_qlearn_agent(model, env, cfg,
                                  num_agents=2, steps_per_chunk=10)
        ts = agent.init(jax.random.PRNGKey(0))
        ts, _ = jax.jit(agent.step)(ts)
        assert int(ts.env_steps) == 3
        assert int(ts.updates) == 3
        assert np.asarray(ts.env_state.t).tolist() == [3, 3]
        ts2, _ = jax.jit(agent.step)(ts)
        assert int(ts2.env_steps) == 3  # fully frozen
        for a, b in zip(jax.tree.leaves(ts.params), jax.tree.leaves(ts2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestGAE:
    def test_matches_hand_rolled_recursion_with_mid_rollout_termination(self):
        """Episode ends at step 3 of a 5-step unroll: padded steps carry
        frozen values, and the terminal value must not bootstrap into the
        last real step's advantage (next-step liveness gating)."""
        from sharetrade_tpu.agents.rollout import gae_advantages

        gamma, lam = 0.9, 0.8
        # (T=5, B=1); steps 0..2 real, 3..4 padding (env frozen at terminal).
        rewards = jnp.array([1.0, -0.5, 2.0, 0.0, 0.0])[:, None]
        values = jnp.array([0.3, 0.1, 0.4, 0.7, 0.7])[:, None]
        active = jnp.array([1.0, 1.0, 1.0, 0.0, 0.0])[:, None]
        bootstrap = jnp.zeros((1,))  # collect_rollout zero-masks it at the end

        got = np.asarray(gae_advantages(
            rewards, values, active, bootstrap, gamma, lam)).ravel()

        # Hand recursion with next-step liveness: live_next = active[t+1]
        # (1.0 for the final slice — its successor value is the bootstrap).
        live_next = [1.0, 1.0, 0.0, 0.0, 1.0]
        next_values = [0.1, 0.4, 0.7, 0.7, 0.0]
        adv = [0.0] * 5
        adv_next = 0.0
        for t in reversed(range(5)):
            delta = (float(rewards[t, 0])
                     + gamma * next_values[t] * live_next[t]
                     - float(values[t, 0]))
            adv[t] = delta + gamma * lam * adv_next * live_next[t]
            adv_next = adv[t]
        np.testing.assert_allclose(got, adv, rtol=1e-6)
        # The last REAL step's advantage is exactly r - V(s): no V_terminal.
        np.testing.assert_allclose(got[2], 2.0 - 0.4, rtol=1e-6)


class TestReplayForwardFold:
    """The stateless fold path (one big batched forward) must match the
    per-step scan path exactly — a reshape-order bug here would silently
    permute time/batch rows in every PPO/A2C/PG loss."""

    def _traj_and_model(self, hidden=16, t=6, b=4, obs_dim=10):
        from sharetrade_tpu.agents.rollout import StepData
        from sharetrade_tpu.models.mlp import ac_mlp
        model = ac_mlp(obs_dim, hidden)
        params = model.init(jax.random.PRNGKey(0))
        obs = jax.random.uniform(jax.random.PRNGKey(1), (t, b, obs_dim))
        z = jnp.zeros((t, b))
        traj = StepData(obs=obs, action=z.astype(jnp.int32), logp=z,
                        value=z, reward=z, active=z + 1.0)
        return model, params, traj

    def _scan_reference(self, model, params, traj):
        from sharetrade_tpu.models.core import apply_batched

        def one_step(carry, obs_t):
            outs, _ = apply_batched(model, params, obs_t, ())
            return carry, (outs.logits, outs.value)

        _, (logits, values) = jax.lax.scan(one_step, None, traj.obs)
        return logits, values

    @pytest.mark.parametrize("max_rows", [10_000, 8, 1])
    def test_fold_matches_scan(self, max_rows, monkeypatch):
        """max_rows sweeps single-fold, grouped (fold=2), and per-step."""
        from sharetrade_tpu.agents import rollout
        monkeypatch.setattr(rollout, "_MAX_FOLD_ROWS", max_rows)
        model, params, traj = self._traj_and_model()
        want_l, want_v = self._scan_reference(model, params, traj)
        for remat in (False, True):
            got_l, got_v, _aux = rollout.replay_forward(
                model, params, traj, (), remat=remat)
            np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                                       rtol=1e-5, atol=1e-6)

    def test_fold_gradients_match_scan(self, monkeypatch):
        from sharetrade_tpu.agents import rollout
        monkeypatch.setattr(rollout, "_MAX_FOLD_ROWS", 8)  # 2 groups
        model, params, traj = self._traj_and_model()

        def loss_fold(p):
            lg, v, _ = rollout.replay_forward(model, p, traj, (), remat=True)
            return jnp.sum(lg ** 2) + jnp.sum(v ** 2)

        def loss_scan(p):
            lg, v = self._scan_reference(model, p, traj)
            return jnp.sum(lg ** 2) + jnp.sum(v ** 2)

        g_fold = jax.grad(loss_fold)(params)
        g_scan = jax.grad(loss_scan)(params)
        for a, b in zip(jax.tree.leaves(g_fold), jax.tree.leaves(g_scan)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_lstm_keeps_carry_scan(self):
        """Recurrent models must stay on the carry-threading path."""
        from sharetrade_tpu.agents import rollout
        from sharetrade_tpu.agents.rollout import StepData
        from sharetrade_tpu.models.lstm import lstm_policy
        t, b, obs_dim = 3, 2, 10
        model = lstm_policy(obs_dim, 8)
        params = model.init(jax.random.PRNGKey(0))
        carry = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (b,) + x.shape), model.init_carry())
        one = jax.random.uniform(jax.random.PRNGKey(1), (b, obs_dim))
        obs = jnp.broadcast_to(one, (t, b, obs_dim))   # identical every step
        z = jnp.zeros((t, b))
        traj = StepData(obs=obs, action=z.astype(jnp.int32), logp=z,
                        value=z, reward=z, active=z + 1.0)
        logits, values, _ = rollout.replay_forward(model, params, traj, carry)
        # Same obs at every step must give DIFFERENT outputs (carry evolves).
        assert not np.allclose(np.asarray(logits[0]), np.asarray(logits[1]))


class TestReplayBuffer:
    def test_push_wraps_and_masks(self):
        rb = ReplayBuffer.create(8, 3)
        obs = jnp.arange(12.0).reshape(4, 3)
        rb = rb.push(obs, jnp.zeros(4, jnp.int32), jnp.ones(4),
                     obs + 100, jnp.array([True, True, False, True]))
        assert int(rb.size) == 3 and int(rb.pos) == 3
        # Valid rows compacted: rows 0, 1, 3 stored.
        np.testing.assert_allclose(np.asarray(rb.obs[:3, 0]), [0.0, 3.0, 9.0])
        for _ in range(3):
            rb = rb.push(obs, jnp.zeros(4, jnp.int32), jnp.ones(4),
                         obs + 100, jnp.ones(4, bool))
        assert int(rb.size) == 8  # capacity-clamped
        assert int(rb.pos) == (3 + 12) % 8

    def test_sample_in_range(self):
        rb = ReplayBuffer.create(16, 2)
        rb = rb.push(jnp.ones((4, 2)), jnp.ones(4, jnp.int32) * 2,
                     jnp.ones(4), jnp.zeros((4, 2)), jnp.ones(4, bool))
        o, a, r, n = rb.sample(jax.random.PRNGKey(0), 32)
        assert o.shape == (32, 2) and (np.asarray(a) == 2).all()

    def test_journal_fill(self, tmp_journal_path):
        with Journal(tmp_journal_path) as j:
            j.append({"type": "transitions",
                      "obs": [[1.0, 2.0]], "action": [1],
                      "reward": [0.5], "next_obs": [[3.0, 4.0]]})
            rb = fill_replay_from_journal(ReplayBuffer.create(4, 2), j)
        assert int(rb.size) == 1
        np.testing.assert_allclose(np.asarray(rb.obs[0]), [1.0, 2.0])


@pytest.mark.slow
@pytest.mark.parametrize("algo", ["qlearn", "pg", "dqn", "a2c", "ppo"])
def test_every_algorithm_trains_a_chunk(algo):
    cfg = tiny_config(algo)
    agent = build_agent(cfg, tiny_env())
    ts = agent.init(jax.random.PRNGKey(0))
    step = jax.jit(agent.step)
    ts2, metrics = step(ts)
    # Params changed, counters advanced, metrics finite.
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(ts.params), jax.tree.leaves(ts2.params)))
    assert changed, f"{algo}: params did not change"
    assert int(ts2.env_steps) > 0
    assert int(ts2.updates) > 0
    for k, v in metrics.items():
        assert np.isfinite(np.asarray(v)).all(), f"{algo}: {k} not finite"
    # Second chunk composes (scan carry shapes stable).
    ts3, _ = step(ts2)
    assert int(ts3.env_steps) >= int(ts2.env_steps)


@pytest.mark.parametrize("algo", ["pg", "a2c"])
def test_normalized_advantages_reachable_and_change_training(algo):
    """learner.normalize_advantages must actually alter the PG/A2C update
    (zero-mean unit-variance advantages over active steps) — not silently
    no-op — while the default-off path preserves the textbook estimator."""
    outs = {}
    for norm in (False, True):
        cfg = tiny_config(algo, normalize_advantages=norm, gamma=0.9)
        agent = build_agent(cfg, tiny_env())
        ts = agent.init(jax.random.PRNGKey(0))
        ts2, metrics = jax.jit(agent.step)(ts)
        assert np.isfinite(float(metrics["loss"])), (algo, norm)
        outs[norm] = jax.device_get(ts2.params)
    diffs = [float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
             for a, b in zip(jax.tree.leaves(outs[False]),
                             jax.tree.leaves(outs[True]))]
    assert max(diffs) > 0, f"{algo}: normalization changed nothing"


def test_value_based_algos_reject_recurrent_models():
    cfg = tiny_config("dqn")
    cfg.model.kind = "lstm"
    with pytest.raises(ValueError, match="requires model.kind='mlp'"):
        build_agent(cfg, tiny_env())


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["lstm", "transformer"])
def test_recurrent_and_attention_policies_with_ppo(kind):
    cfg = tiny_config("ppo")
    cfg.model.kind = kind
    agent = build_agent(cfg, tiny_env())
    ts = agent.init(jax.random.PRNGKey(0))
    ts2, metrics = jax.jit(agent.step)(ts)
    assert np.isfinite(float(metrics["loss"]))
    if kind == "lstm":
        # Carry must have evolved over the unroll.
        h0 = np.asarray(ts.carry[0])
        h1 = np.asarray(ts2.carry[0])
        assert not np.allclose(h0, h1)


# -- what the PPO minibatch loop holds of the unroll-start carry ------------

def _episode_ppo_config():
    cfg = tiny_config("ppo")
    cfg.model.kind, cfg.model.seq_mode = "transformer", "episode"
    cfg.model.num_layers = 2            # hist_len > 0: the carry has a hist
    cfg.learner.ppo_minibatches = 2     # 4 agents: minibatches of 2
    return cfg


def _carry_bytes(carry) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(carry))


def _params_after_two_chunks(agent):
    """Parameters after a prefill chunk and a carry-crossing one, seed 3."""
    ts = agent.init(jax.random.PRNGKey(3))
    step = jax.jit(agent.step)
    ts, _ = step(ts)
    ts, metrics = step(ts)
    assert np.isfinite(float(metrics["loss"]))
    return jax.device_get(ts.params)


def _assert_same_params(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(a, b)


def test_ppo_chunk_same_params_from_trimmed_or_whole_carry():
    """The loop gathers the model's ``replay_carry`` of the unroll-start
    carry; gathering the WHOLE carry (a test-only model whose hook keeps
    every leaf and adds the same health vector) trains to the same
    parameters, over a prefill chunk and a carry-crossing one: the K/V
    caches were never an input of the replay."""
    import dataclasses
    from sharetrade_tpu.models.core import rows_finite

    cfg = _episode_ppo_config()
    trimmed = build_agent(cfg, tiny_env())
    whole_model = dataclasses.replace(
        trimmed.model, replay_carry=lambda c: {
            **c, "ok": rows_finite(c, c["t"].shape[0])})
    whole = build_agent(cfg, tiny_env(), model=whole_model)
    assert whole.replay_carry_bytes > 10 * trimmed.replay_carry_bytes

    _assert_same_params(_params_after_two_chunks(trimmed),
                        _params_after_two_chunks(whole))


# -- the log-prob of the taken action: a select, not a gather ---------------

def _gathered_log_prob(log_probs, action):
    return jnp.take_along_axis(log_probs, action[..., None], axis=-1)[..., 0]


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("num_actions", [3, 9])
@pytest.mark.parametrize("batch_shape", [(5,), (7, 5)], ids=["BA", "TBA"])
@pytest.mark.parametrize("untaken_inf", [False, True],
                         ids=["finite", "untaken_inf"])
def test_taken_action_log_prob_is_the_gather_bit_for_bit(
        batch_shape, num_actions, dtype, untaken_inf):
    """Value and gradient of the select are ``take_along_axis``'s to the
    bit, also with a ``-inf`` at an action not taken (a product with the
    one-hot gives NaN there: 0 * -inf)."""
    from sharetrade_tpu.agents.rollout import taken_action_log_prob

    k_logits, k_action, k_weight = jax.random.split(jax.random.PRNGKey(11), 3)
    logits = 3.0 * jax.random.normal(k_logits, batch_shape + (num_actions,))
    action = jax.random.randint(k_action, batch_shape, 0, num_actions)
    if untaken_inf:
        logits = jnp.where(
            ((action + 1) % num_actions)[..., None] == jnp.arange(num_actions),
            -jnp.inf, logits)
    log_probs = jax.nn.log_softmax(logits).astype(dtype)
    weight = jax.random.normal(k_weight, batch_shape).astype(dtype)
    assert bool(jnp.any(jnp.isinf(log_probs))) == untaken_inf

    got = jax.jit(taken_action_log_prob)(log_probs, action)
    want = jax.jit(_gathered_log_prob)(log_probs, action)
    assert got.dtype == want.dtype == dtype and got.shape == batch_shape
    assert np.all(np.isfinite(np.asarray(got, np.float32)))
    np.testing.assert_array_equal(_bits(got), _bits(want))

    def weighted(fn):
        return jax.jit(jax.grad(
            lambda lp: jnp.sum(fn(lp, action) * weight).astype(jnp.float32)))

    g_got = weighted(taken_action_log_prob)(log_probs)
    g_want = weighted(_gathered_log_prob)(log_probs)
    assert g_got.dtype == g_want.dtype == dtype
    np.testing.assert_array_equal(_bits(g_got), _bits(g_want))


def test_ppo_chunk_same_params_from_select_or_gather(monkeypatch):
    """A PPO chunk pair from one seed trains to the same parameters, bit
    for bit, with the shared select and with ``take_along_axis`` put back
    in its place (the rollout's behaviour log-prob and the replay's)."""
    from sharetrade_tpu.agents import ppo, rollout

    def train():
        return _params_after_two_chunks(
            build_agent(_episode_ppo_config(), tiny_env()))

    selected = train()
    for module in (ppo, rollout):
        monkeypatch.setattr(module, "taken_action_log_prob",
                            _gathered_log_prob)
    _assert_same_params(selected, train())


@pytest.mark.parametrize("kind", ["transformer_episode", "lstm", "mlp"])
def test_replay_carry_bytes_gauge(kind, tmp_path):
    """The build-time gauge reads what one minibatch gathers of the
    unroll-start carry: ``hist``, ``t`` and the health bit for the episode
    transformer (its K/V caches stay out), the whole carry for an LSTM,
    nothing for a stateless policy — and the orchestrator exports it."""
    from sharetrade_tpu.agents.base import batched_carry
    from sharetrade_tpu.runtime import Orchestrator

    cfg = _episode_ppo_config()
    if kind != "transformer_episode":
        cfg.model.kind, cfg.model.seq_mode = kind, "window"
    agent = build_agent(cfg, tiny_env())
    mb_size = cfg.parallel.num_workers // cfg.learner.ppo_minibatches
    carry = batched_carry(agent.model, mb_size)
    if kind == "transformer_episode":
        want = _carry_bytes((carry["hist"], carry["t"])) + mb_size  # + ok
        assert _carry_bytes((carry["k"], carry["v"])) > 10 * want
    else:
        want = _carry_bytes(carry)
        assert (want > 0) == (kind == "lstm")
    assert agent.replay_carry_bytes == want

    cfg.runtime.checkpoint_dir = str(tmp_path / "ckpts")
    orch = Orchestrator(cfg)
    try:
        orch.send_training_data(np.linspace(10.0, 20.0, 64, dtype=np.float32))
        assert orch.metrics.latest(
            "train_replay_carry_bytes_per_minibatch") == want
    finally:
        orch.stop()


def test_batched_carry_casts_the_seed_before_broadcasting():
    """``agent.init`` runs eagerly: the precision policy's carry cast is
    applied to the one-agent seed, so no float32 batch of K/V caches ever
    exists beside its bf16 copy — leaf for leaf the same state as casting
    the batch."""
    from sharetrade_tpu.agents.base import batched_carry
    from sharetrade_tpu.precision import policy_from_config

    cfg = _episode_ppo_config()
    cfg.precision.mode = "bf16_mixed"
    precision = policy_from_config(cfg.precision)
    model = build_agent(cfg, tiny_env()).model
    got = batched_carry(model, 4, precision)
    want = precision.cast_carry(batched_carry(model, 4), model)
    assert got["k"].dtype == jnp.bfloat16 and got["hist"].dtype == jnp.float32
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
