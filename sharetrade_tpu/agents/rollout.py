"""On-policy rollout collection shared by PG / A2C / PPO.

One ``lax.scan`` gathers a ``(T, B, ...)`` trajectory block for the whole
agent batch — the TPU inversion of the reference's per-step worker↔learner
mailbox round-trips (SURVEY.md §7.2). Losses recompute the forward pass from
the stored observations (and what the model's replay reads of the unroll's
*initial* recurrent carry — ``replay_carry`` — so recurrent policies
differentiate through time correctly).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from sharetrade_tpu.agents.base import (
    TrainState, election_health, quarantine_mask)
from sharetrade_tpu.env.core import TradingEnv
from sharetrade_tpu.models.core import Model, apply_batched


class StepData(NamedTuple):
    """One time-slice of a trajectory, batched over agents."""

    obs: jax.Array      # (B, obs_dim)
    action: jax.Array   # (B,) i32
    logp: jax.Array     # (B,) log-prob of the sampled action (behavior policy)
    value: jax.Array    # (B,) critic estimate at obs
    reward: jax.Array   # (B,)
    active: jax.Array   # (B,) f32 1.0 while the episode is running


def supports_precomputed_trunk(model: Model, env: TradingEnv) -> bool:
    """THE dispatch predicate for the precomputed-rollout fast path, shared
    by training (collect_rollout) and greedy eval (Orchestrator.evaluate).
    The path hard-codes the single-asset trading layout — obs =
    [window | budget, shares] with a SCALAR wallet and a priced step — so a
    trunk-capable model alone is not enough: a one-asset portfolio env has
    num_assets == 1 but a (1,)-vector shares leaf (env/portfolio.py), which
    only the ``step_priced is not None`` check (set solely by
    make_trading_env) excludes."""
    return (model.apply_rollout_trunk is not None
            and env.num_assets == 1 and env.step_priced is not None)


def replay_carry(model: Model, carry):
    """What the model's training replay reads of the unroll-start ``carry``
    (models/core.py ``Model.replay_carry``; the whole carry where the model
    declares nothing) — the ONE carry argument :func:`replay_forward`
    takes. The rollout computes it once per chunk from the state the
    unroll starts from, so whatever a learner's update phase then holds,
    gathers per minibatch or pins across a mesh seam is this tree and not,
    for the episode transformer, a per-agent K/V cache it never reads."""
    return carry if model.replay_carry is None else model.replay_carry(carry)


def collect_rollout(model: Model, env: TradingEnv,
                    ts: TrainState, unroll_len: int, num_agents: int,
                    params=None):
    """Roll the policy forward ``unroll_len`` steps.

    Returns ``(new_ts, traj, bootstrap_value, replay_init)`` where ``traj``
    stacks :class:`StepData` along a leading time axis, ``bootstrap_value`` is
    V(s_T) for return bootstrapping, and ``replay_init`` is
    :func:`replay_carry` of the recurrent state the unroll started from
    (what :func:`replay_forward` needs to replay the forward pass in
    losses; the state itself for models that declare no ``replay_carry``).

    ``params`` overrides the weights the rollout forwards read — the
    precision policy's compute copy (precision.py cast_compute); the fp32
    masters in ``ts.params`` are never mutated here and the returned
    ``new_ts`` keeps them. None (the fp32 path) reads ``ts.params``.

    Models exposing the precomputed-rollout pair (``apply_rollout_trunk`` /
    ``apply_rollout_head``, models/core.py) take the parallel-trunk path:
    the unroll's entire trunk runs as ONE pass up front and the sequential
    env loop applies only the tiny state-dependent head per step.
    """
    # Envs outside the single-asset trading layout would be fed malformed
    # observations by the fast path; they use the generic per-step loop.
    if supports_precomputed_trunk(model, env):
        return _collect_rollout_precomputed(
            model, env, ts, unroll_len, num_agents, params=params)
    params = ts.params if params is None else params
    horizon = env.num_steps
    replay_init = replay_carry(model, ts.carry)

    def one_step(carry, _):
        env_state, model_carry, rng = carry
        rng, k_act = jax.random.split(rng)
        act_keys = jax.random.split(k_act, num_agents)

        # Horizon freeze + poisoned-row quarantine (base.quarantine_mask):
        # a non-finite agent's observation is sanitized to zeros (so no NaN
        # reaches the shared forward/loss) and its row is masked inactive —
        # frozen until the orchestrator respawns it.
        obs_raw = jax.vmap(env.observe)(env_state)
        healthy = quarantine_mask(obs_raw, env_state)
        active = ((env_state.t < horizon) & healthy).astype(jnp.float32)
        obs = jnp.where(healthy[:, None], obs_raw, 0.0)
        outs, new_model_carry = apply_batched(model, params, obs, model_carry)
        actions = jax.vmap(
            lambda k, lg: jax.random.categorical(k, lg))(act_keys, outs.logits)
        actions = actions.astype(jnp.int32)
        logp = jax.vmap(
            lambda lg, a: jax.nn.log_softmax(lg)[a])(outs.logits, actions)

        stepped, rewards = jax.vmap(env.step)(env_state, actions)
        mask = active.astype(bool)
        new_env = jax.tree.map(
            lambda new, old: jnp.where(
                mask.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
            stepped, env_state)
        # where() not *: a quarantined row's reward is NaN, and NaN*0 = NaN.
        rewards = jnp.where(mask, rewards, 0.0)

        data = StepData(obs=obs, action=actions, logp=logp,
                        value=outs.value, reward=rewards, active=active)
        return (new_env, new_model_carry, rng), data

    # Not under a named scope: this scan's body runs the model, and a scope
    # that encloses a pallas_call renames the kernel's device-trace event.
    (env_state, model_carry, rng), traj = jax.lax.scan(
        one_step, (ts.env_state, ts.carry, ts.rng), None, length=unroll_len)

    # Bootstrap value for the state the unroll stopped at.
    final_raw = jax.vmap(env.observe)(env_state)
    final_fine = quarantine_mask(final_raw, env_state)
    final_obs = jnp.where(final_fine[:, None], final_raw, 0.0)
    final_outs, _ = apply_batched(model, params, final_obs, model_carry)
    bootstrap = final_outs.value * (
        (env_state.t < horizon) & final_fine).astype(jnp.float32)

    # Count steps where ANY agent advanced (not just agent 0): with
    # per-agent healing, cursors can diverge — a respawned agent keeps
    # running after the rest finish, and its chunks must count.
    steps_taken = jnp.sum(jnp.any(traj.active > 0, axis=1)).astype(jnp.int32)
    new_ts = ts.replace(env_state=env_state, carry=model_carry, rng=rng,
                        env_steps=ts.env_steps + steps_taken)
    return new_ts, traj, bootstrap, replay_init


def _trunk_precompute(model: Model, env: TradingEnv, params, state1, carry1,
                      t_len: int, horizon: int):
    """Shared single-representative-agent precompute for trunk rollouts
    (training and greedy eval): the load-bearing alignment invariant —
    trade price at step i = the newest tick of window i+1, fed to BOTH the
    trunk's future_ticks and the priced env step — lives here once.

    ``state1``/``carry1`` are batch-of-1 pytrees. Returns
    ``(windows (T+1, W), trade_prices (T,), hn_base (T+1, d), carry_out)``.
    """
    window = model.obs_dim - 2

    def window_at(i):
        shifted = state1.replace(t=jnp.minimum(state1.t + i, horizon))
        return jax.vmap(env.observe)(shifted)[0, :window]

    windows = jax.vmap(window_at)(jnp.arange(t_len + 1))       # (T+1, W)
    obs1_raw = jax.vmap(env.observe)(state1)
    # Sanitize ONLY the wallet features: the price window comes from the
    # static series (always finite) and is all the trunk reads — zeroing
    # the whole row when agent 0's wallet is poisoned would corrupt the
    # SHARED trunk for every healthy agent.
    obs1 = jnp.concatenate(
        [obs1_raw[:, :window],
         jnp.where(jnp.isfinite(obs1_raw[:, window:]),
                   obs1_raw[:, window:], 0.0)], axis=-1)
    hn1, carry_out = model.apply_rollout_trunk(
        params, obs1, windows[None, 1:, -1], carry1)
    return windows, windows[1:, -1], hn1[0], carry_out


def _collect_rollout_precomputed(model: Model, env: TradingEnv,
                                 ts: TrainState, unroll_len: int,
                                 num_agents: int, params=None):
    """Rollout with the heavy trunk hoisted OUT of the sequential loop.

    The trading env's prices are action-independent (actions move only
    budget/shares; the cursor advances one tick per step regardless), so
    the tick that enters the observation window at each future step is
    known before any action is taken. The model's trunk — everything up to
    the portfolio-feature injection — therefore computes for the WHOLE
    unroll in one parallel banded pass (``apply_rollout_trunk``); the
    sequential ``lax.scan`` keeps only action sampling, the env transition,
    and the (B, d)-sized head (``apply_rollout_head``). This removes the
    measured 70%-of-chunk sequential cache-attention rollout
    (benchmarks/profile_flagship.py).

    Agents frozen mid-unroll (horizon reached, or quarantined by
    ``quarantine_mask``) read trunk rows computed for cursors they never
    reached; their outputs are masked inactive exactly as the incremental
    path masked its lockstep-advanced carry.
    """
    params = ts.params if params is None else params
    horizon = env.num_steps
    window = model.obs_dim - 2

    # ---- bulk precompute (everything scalar-unit-hostile hoisted out of
    # the scan: a vmapped dynamic gather costs ~75-230 us PER ITERATION on
    # TPU and a threefry split ~120 us, vs ~0.1 us for elementwise math;
    # as single ops out here they cost milliseconds total) ---------------
    #
    # Agent-invariance: every HEALTHY agent replays the SAME price series
    # in LOCKSTEP (batched_reset broadcasts one reset state, and any
    # per-agent respawn must keep healthy rows lockstep —
    # orchestrator._heal_agents), so the price windows AND the whole trunk
    # are computed for ONE representative agent and broadcast — the trunk's
    # cost and the window gather drop by a factor of B. The representative
    # must be a healthy row BY THE SAME PREDICATE the heal uses
    # (election_health: env state AND model carry finite): a quarantined
    # row's cursor freezes while the broadcast carry['t'] keeps advancing,
    # so electing it would feed every healthy agent windows from a stale
    # cursor with desynced RoPE positions — and a finite-wallet row with a
    # NaN carry would broadcast the NaN K/V cache into the shared trunk.
    # argmax picks the first healthy row. Fallback when NONE exists: row 0.
    # If every row failed on env state, all rows are also quarantine-masked
    # and the chunk is a masked no-op; if every row failed only on its
    # carry, the broadcast NaN trunk makes the chunk's loss non-finite and
    # the orchestrator's detector escalates to restore — correct when the
    # whole batch is beyond a row-level heal.
    #
    # The replay's view of the same carry is taken HERE, beside the
    # election: its health vector is rows_finite of the same array, so the
    # chunk makes one is-finite pass over the K/V caches (the two identical
    # reductions merge), outside the learners' epoch/minibatch scans —
    # tests/test_chip_compile.py holds the compiled step to it.
    with jax.named_scope("rows_finite"):
        replay_init = replay_carry(model, ts.carry)
        rep = jnp.argmax(
            election_health(ts.env_state, ts.carry)).astype(jnp.int32)
    take_rep = lambda x: jax.lax.dynamic_index_in_dim(x, rep, 0,
                                                      keepdims=True)
    state1 = jax.tree.map(take_rep, ts.env_state)
    carry1 = jax.tree.map(take_rep, ts.carry)
    windows, trade_prices, hn_base, carry1_out = _trunk_precompute(
        model, env, params, state1, carry1, unroll_len, horizon)
    new_model_carry = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (num_agents,) + x.shape[1:]),
        carry1_out)

    rng, k_noise = jax.random.split(ts.rng)
    # Gumbel-max sampling noise for the whole unroll: argmax(logits + g)
    # IS a categorical draw, with zero in-loop RNG traffic.
    gumbel = jax.random.gumbel(
        k_noise, (unroll_len, num_agents, model.num_actions), jnp.float32)

    step_priced = env.step_priced

    # Linearity-factored head (models/core.py rollout_head_factored): the
    # whole unroll's trunk→logits/value terms become ONE batched matmul
    # out here, leaving only a (3 -> A) portfolio contraction inside the
    # scan — the per-iteration d-sized head GEMMs were the measured d=256
    # bound once everything else was hoisted (BASELINE.md round 5).
    factored = model.rollout_head_factored
    if factored is not None:
        base_l, base_v, pf_fn = factored(params, hn_base)
        head_xs = (base_l[:unroll_len], base_v[:unroll_len])

        def head_outs(head_i, obs):
            base_l_i, base_v_i = head_i
            d_l, d_v = pf_fn(obs)
            return base_l_i[None] + d_l, base_v_i + d_v

        final_head = (base_l[unroll_len], base_v[unroll_len])
    else:
        head_xs = (hn_base[:unroll_len],)

        def head_outs(head_i, obs):
            (hn_i,) = head_i
            outs = model.apply_rollout_head(
                params,
                jnp.broadcast_to(hn_i, (num_agents,) + hn_i.shape), obs)
            return outs.logits, outs.value

        final_head = (hn_base[unroll_len],)

    def one_step(env_state, inputs):
        win_i, price_i, g_i, head_i = inputs
        # Assemble the observation from the precomputed (shared) window +
        # the live wallet (the only state-dependent features).
        obs_raw = jnp.concatenate(
            [jnp.broadcast_to(win_i, (num_agents, window)),
             env_state.budget[:, None], env_state.shares[:, None]],
            axis=-1)
        healthy = quarantine_mask(obs_raw, env_state)
        active = ((env_state.t < horizon) & healthy).astype(jnp.float32)
        obs = jnp.where(healthy[:, None], obs_raw, 0.0)

        logits, value = head_outs(head_i, obs)
        actions = jnp.argmax(logits + g_i, axis=-1).astype(jnp.int32)
        logp = taken_action_log_prob(jax.nn.log_softmax(logits), actions)

        # step_priced is guaranteed by supports_precomputed_trunk.
        stepped, rewards = jax.vmap(
            step_priced, in_axes=(0, 0, None))(env_state, actions, price_i)
        mask = active.astype(bool)
        new_env = jax.tree.map(
            lambda new, old: jnp.where(
                mask.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
            stepped, env_state)
        rewards = jnp.where(mask, rewards, 0.0)

        data = StepData(obs=obs, action=actions, logp=logp,
                        value=value, reward=rewards, active=active)
        return new_env, data

    with jax.named_scope("rollout_scan"):
        env_state, traj = jax.lax.scan(
            one_step, ts.env_state,
            (windows[:-1], trade_prices, gumbel, head_xs))

    final_raw = jax.vmap(env.observe)(env_state)
    final_fine = quarantine_mask(final_raw, env_state)
    final_obs = jnp.where(final_fine[:, None], final_raw, 0.0)
    _, final_value = head_outs(final_head, final_obs)
    bootstrap = final_value * (
        (env_state.t < horizon) & final_fine).astype(jnp.float32)

    steps_taken = jnp.sum(jnp.any(traj.active > 0, axis=1)).astype(jnp.int32)
    new_ts = ts.replace(env_state=env_state, carry=new_model_carry, rng=rng,
                        env_steps=ts.env_steps + steps_taken)
    return new_ts, traj, bootstrap, replay_init


def greedy_rollout_precomputed(model: Model, env: TradingEnv, params,
                               *, horizon: int | None = None):
    """Greedy (argmax) single-agent episode replay through the precomputed
    trunk — the fast ``evaluate()`` path for trunk models. Same structure
    as :func:`_collect_rollout_precomputed` (prices are action-independent,
    so the whole episode's trunk is one banded pass) minus sampling,
    batching, and quarantine. Returns ``(final_env_state, rewards (T,))``.
    """
    horizon = env.num_steps if horizon is None else horizon
    state1 = jax.tree.map(lambda x: x[None], env.reset())   # batch of 1
    carry1 = jax.tree.map(lambda x: x[None], model.init_carry())
    windows, trade_prices, hn_base, _ = _trunk_precompute(
        model, env, params, state1, carry1, horizon, horizon)
    step_priced = env.step_priced

    factored = model.rollout_head_factored
    if factored is not None:   # same hoist as _collect_rollout_precomputed
        base_l, _, pf_fn = factored(params, hn_base)
        head_xs = (base_l[:horizon],)

        def head_logits(head_i, obs):
            return head_i[0][None] + pf_fn(obs)[0]
    else:
        head_xs = (hn_base[:horizon],)

        def head_logits(head_i, obs):
            return model.apply_rollout_head(params, head_i[0][None],
                                            obs).logits

    def one(env_state, inputs):
        win_i, price_i, head_i = inputs
        obs = jnp.concatenate(
            [win_i[None], env_state.budget[:, None],
             env_state.shares[:, None]], axis=-1)
        logits = head_logits(head_i, obs)
        action = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        new_state, reward = jax.vmap(
            step_priced, in_axes=(0, 0, None))(env_state, action, price_i)
        return new_state, reward[0]

    final, rewards = jax.lax.scan(
        one, state1, (windows[:-1], trade_prices, head_xs))
    return jax.tree.map(lambda x: x[0], final), rewards


#: Max observation rows per folded forward call — bounds replay activation
#: memory (4096 seq-202 transformer rows ≈ 0.8 GB per bf16 activation
#: tensor; larger folds trade HBM headroom for no extra MXU win).
_MAX_FOLD_ROWS = 2048


def replay_forward(model: Model, params: Any, traj: StepData, replay_init,
                   *, remat: bool = False):
    """Recompute ``(logits, values, aux)`` along a stored trajectory under
    ``params``, threading the recurrent carry — the differentiable forward
    for losses. ``aux`` is the mean of the model's auxiliary loss over the
    replay (ModelOut.aux — the MoE balance term; 0 for dense models), which
    losses weight by ``LearnerConfig.aux_loss_coef``.

    ``replay_init`` is :func:`replay_carry` of the carry the unroll started
    from — ``collect_rollout``'s fourth result, or its rows for a
    minibatch of agents. For most models that is the carry itself (an LSTM
    replays from every leaf of it); a model that declares
    ``Model.replay_carry`` gets the leaves its replay reads, with row
    health (``rows_finite`` of the whole unroll-start carry) already
    folded in by the model's own hook where its representative election
    needs one: nothing here, and no learner, scans a carry for NaNs.

    Stateless models (MLP, transformer — empty carry) have no step-to-step
    data dependence, so the (T, B) trajectory folds into one big batch
    instead of a T-step scan of B-row launches: a 10-agent/32-step PPO
    replay becomes a single 320-sequence forward that actually loads the
    MXU (the scan form was the round-2 transformer-throughput bottleneck).
    The fold is BATCH-major — (T, B) transposes to (B, T) before merging —
    so a dp-sharded agent axis stays the leading factor of the merged dim
    and GSPMD keeps the shard layout (a time-major merge would force an
    all-gather of the folded observations on every minibatch).

    Folding is sliced to ``_MAX_FOLD_ROWS`` rows per call, which bounds the
    per-call transient working set (qkv/attention intermediates). Note the
    forward RESIDUALS of every slice still accumulate for the backward
    unless ``remat=True``, which checkpoints each slice so the backward
    recomputes from stored observations — the FLOPs-for-HBM trade that
    makes large agent batches fit.
    """
    if model.apply_unroll_shared is not None:
        # Shared-trunk replay: the banded pass runs ONCE for a
        # representative row and only the portfolio head runs per agent —
        # valid because every learner in this framework keeps the agent
        # batch lockstep over one shared price series (models/core.py
        # apply_unroll_shared; the factor-B update-phase redundancy).
        fwd = model.apply_unroll_shared
        if remat:
            fwd = jax.checkpoint(fwd)
        return fwd(params, traj.obs, replay_init)
    if model.apply_unroll is not None:
        # The model replays a whole trajectory natively (episode-mode
        # transformer: one banded pass over the unroll's tick sequence
        # instead of T window forwards).
        fwd = model.apply_unroll
        if remat:
            fwd = jax.checkpoint(fwd)
        return fwd(params, traj.obs, replay_init)

    stateless = not jax.tree.leaves(replay_init)
    if stateless:
        t, b = traj.obs.shape[:2]
        # Largest divisor of T whose folded rows stay under the cap.
        fold = max(f for f in range(1, t + 1)
                   if t % f == 0 and (f * b <= _MAX_FOLD_ROWS or f == 1))
        groups = t // fold

        def fwd(params, obs_g):
            # (fold, b, D) -> (b, fold, D) -> (b*fold, D): batch-major merge.
            flat = obs_g.swapaxes(0, 1).reshape(
                (b * fold,) + obs_g.shape[2:])
            outs, _ = apply_batched(model, params, flat, replay_init)
            return (outs.logits.reshape(b, fold, -1).swapaxes(0, 1),
                    outs.value.reshape(b, fold).swapaxes(0, 1),
                    jnp.mean(jnp.asarray(outs.aux)))

        if remat:
            fwd = jax.checkpoint(fwd)
        if groups == 1:
            return fwd(params, traj.obs)
        grouped = traj.obs.reshape((groups, fold) + traj.obs.shape[1:])
        _, (logits, values, aux) = jax.lax.scan(
            lambda _, obs_g: (None, fwd(params, obs_g)), None, grouped)
        return (logits.reshape((t,) + logits.shape[2:]),
                values.reshape((t,) + values.shape[2:]),
                jnp.mean(aux))

    def fwd(params, obs_t, model_carry):
        return apply_batched(model, params, obs_t, model_carry)

    if remat:
        fwd = jax.checkpoint(fwd)

    def one_step(model_carry, obs_t):
        outs, new_carry = fwd(params, obs_t, model_carry)
        return new_carry, (outs.logits, outs.value,
                           jnp.mean(jnp.asarray(outs.aux)))

    _, (logits, values, aux) = jax.lax.scan(one_step, replay_init, traj.obs)
    return logits, values, jnp.mean(aux)  # (T, B, A), (T, B), scalar


def taken_action_log_prob(log_probs: jax.Array,
                          action: jax.Array) -> jax.Array:
    """``log_probs[..., action]``, the log-prob of the action each agent
    took — THE expression the rollout's behaviour log-prob and every
    policy-gradient replay share. A select over the action axis, not
    ``take_along_axis``: gathers are scalar-unit dispatches on the TPU,
    inside a scan and outside one (half of a d256 PPO chunk, PERF.md PR 31).
    A select, not a product with the one-hot: an untaken action's ``-inf``
    stays out of the sum (0 * -inf is NaN), the result is the gather's bit
    for bit (but a taken -0.0, which no log_softmax gives, reads +0.0), and
    the derivative is a select of the cotangent — no scatter.
    ``log_probs`` (..., A), ``action`` (...) integer and in range."""
    taken = action[..., None] == jnp.arange(log_probs.shape[-1],
                                            dtype=action.dtype)
    return jnp.sum(jnp.where(taken, log_probs, 0), axis=-1)


def normalize_advantages_masked(adv: jax.Array, weight: jax.Array,
                                denom: jax.Array) -> jax.Array:
    """Zero-mean unit-variance advantages over the ACTIVE steps, re-masked —
    THE normalization every policy-gradient learner shares (PPO always, PG/
    A2C via ``learner.normalize_advantages``), so the epsilon and masking
    convention cannot drift between estimators. ``weight`` is the binary
    active mask; ``denom`` its (clamped) sum. Idempotent under the losses'
    own later ``* weight`` factors."""
    mean = jnp.sum(adv * weight) / denom
    var = jnp.sum(jnp.square(adv - mean) * weight) / denom
    return (adv - mean) * jax.lax.rsqrt(var + 1e-8) * weight


def discounted_returns(rewards: jax.Array, active: jax.Array,
                       bootstrap: jax.Array, gamma: float) -> jax.Array:
    """Returns-to-go R_t = r_t + γ R_{t+1}, seeded with the bootstrap value;
    computed as a reverse scan over the time axis. Shapes (T, B)."""

    def backward(r_next, inputs):
        reward, live = inputs
        r = reward + gamma * r_next * live
        return r, r

    _, returns = jax.lax.scan(backward, bootstrap,
                              (rewards, active), reverse=True)
    return returns


def gae_advantages(rewards, values, active, bootstrap, gamma, lam):
    """Generalized Advantage Estimation over (T, B) arrays.

    Bootstrapping is gated on the NEXT step's liveness: at an episode's last
    real step the terminal state's value must not leak into delta or flow back
    through the gamma*lam recursion — the same masking collect_rollout applies
    to its bootstrap value. (Gating on the step-start flag let the frozen
    terminal value into both terms, a net +gamma*(1-lam)*V_terminal bias on
    the final real step's advantage.)
    """
    next_values = jnp.concatenate([values[1:], bootstrap[None]], axis=0)
    # Liveness of the successor state. The final slice uses 1: its successor
    # value is `bootstrap`, which collect_rollout already zero-masks when the
    # episode has ended.
    next_active = jnp.concatenate(
        [active[1:], jnp.ones_like(bootstrap)[None]], axis=0)

    def backward(adv_next, inputs):
        reward, value, next_value, live_next = inputs
        delta = reward + gamma * next_value * live_next - value
        adv = delta + gamma * lam * adv_next * live_next
        return adv, adv

    _, advantages = jax.lax.scan(
        backward, jnp.zeros_like(bootstrap),
        (rewards, values, next_values, next_active), reverse=True)
    return advantages
