"""DQN with an on-device replay buffer — BASELINE.json config 2.

Replay lives in HBM as fixed-size circular arrays (no dynamic shapes —
position/size are carried indices), so sampling and the TD update stay inside
the jitted chunk. A target network (synced every ``target_update_every``
updates) stabilizes the bootstrap — the standard upgrade over the reference's
online Q-learning, which bootstraps from the live network
(QDecisionPolicyActor.scala:67-68).

The journal bridge gives the persistence-backed replay capability of the
reference's event-sourced layer (SURVEY.md §7.4 "Replay/persistence
bandwidth"): the runtime appends packed binary records
(data/transitions.py) and ``fill_replay_from_arrays`` /
``fill_replay_from_journal`` rebuild the device buffer on resume (the
latter reads legacy JSON events).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct

from sharetrade_tpu.agents.base import (
    Agent, TrainState, batched_carry, batched_reset, build_optimizer,
    epsilon_greedy, exploit_probability, make_update_fn, per_beta,
    portfolio_metrics, quarantine_mask,
)
from sharetrade_tpu.config import ConfigError, LearnerConfig
from sharetrade_tpu.env.core import TradingEnv
from sharetrade_tpu.models.core import Model, apply_batched
from sharetrade_tpu.ops import sum_tree
from sharetrade_tpu.precision import FP32


@struct.dataclass
class ReplayBuffer:
    obs: jax.Array       # (cap, obs_dim) f32
    action: jax.Array    # (cap,) i32
    reward: jax.Array    # (cap,) f32
    next_obs: jax.Array  # (cap, obs_dim) f32
    pos: jax.Array       # i32 next write index
    size: jax.Array      # i32 valid entries

    @classmethod
    def create(cls, capacity: int, obs_dim: int) -> "ReplayBuffer":
        return cls(
            obs=jnp.zeros((capacity, obs_dim), jnp.float32),
            action=jnp.zeros((capacity,), jnp.int32),
            reward=jnp.zeros((capacity,), jnp.float32),
            next_obs=jnp.zeros((capacity, obs_dim), jnp.float32),
            pos=jnp.int32(0),
            size=jnp.int32(0),
        )

    def push(self, obs, action, reward, next_obs, valid) -> "ReplayBuffer":
        """Insert a batch of B transitions (wrapping). ``valid`` masks agents
        whose episode already ended — their slots are written then un-counted
        by pointing them at already-valid rows (weight-neutral because the
        write happens before the pointer advances past them). XLA
        dead-code-eliminates the unused plan outputs, so this traced
        program is the pre-plan one bit-for-bit (golden-pinned)."""
        return self.push_with_plan(obs, action, reward, next_obs, valid)[0]

    def sample(self, key: jax.Array, batch: int):
        idx = jax.random.randint(key, (batch,), 0,
                                 jnp.maximum(self.size, 1))
        return (self.obs[idx], self.action[idx],
                self.reward[idx], self.next_obs[idx])

    def push_with_plan(self, obs, action, reward, next_obs, valid):
        """:meth:`push` plus its write plan ``(buffer, slot_idx,
        write_mask)`` so a priority structure (the PER sum-tree) can
        mirror exactly the slots the circular buffer touched. ``push``
        delegates here (one copy of the circular-write plan; the golden
        trajectory pins that the delegation kept the compiled uniform
        program bit-identical)."""
        batch = obs.shape[0]
        capacity = self.obs.shape[0]
        # Only advance through valid transitions: compact them to the front.
        order = jnp.argsort(~valid)  # valid rows first, stable
        obs, action = obs[order], action[order]
        reward, next_obs = reward[order], next_obs[order]
        n_valid = jnp.sum(valid).astype(jnp.int32)
        idx = (self.pos + jnp.arange(batch, dtype=jnp.int32)) % capacity
        write = jnp.arange(batch) < n_valid
        safe_idx = jnp.where(write, idx, (self.pos - 1) % capacity)
        buf = self.replace(
            obs=self.obs.at[safe_idx].set(
                jnp.where(write[:, None], obs, self.obs[safe_idx])),
            action=self.action.at[safe_idx].set(
                jnp.where(write, action, self.action[safe_idx])),
            reward=self.reward.at[safe_idx].set(
                jnp.where(write, reward, self.reward[safe_idx])),
            next_obs=self.next_obs.at[safe_idx].set(
                jnp.where(write[:, None], next_obs, self.next_obs[safe_idx])),
            pos=(self.pos + n_valid) % capacity,
            size=jnp.minimum(self.size + n_valid, capacity),
        )
        return buf, safe_idx, write


@struct.dataclass
class DQNExtras:
    target_params: object
    replay: ReplayBuffer


@struct.dataclass
class PerState:
    """Prioritized-replay state riding next to the circular arrays: the
    fixed-shape sum-tree (leaf i = stored priority of replay slot i,
    already ``per_alpha``-exponentiated) and the running max stored
    priority new transitions enter at."""

    tree: sum_tree.SumTree
    max_priority: jax.Array   # f32 scalar, stored-domain


@struct.dataclass
class DQNExtrasPER:
    """``DQNExtras`` + the PER sum-tree (``learner.replay_priority="per"``).
    A separate class — not an optional field — so the uniform default's
    pytree (and therefore its traced program and checkpoint layout) stays
    byte-identical to the pre-data-plane code."""

    target_params: object
    replay: ReplayBuffer
    per: PerState


def make_dqn_agent(model: Model, env: TradingEnv,
                   cfg: LearnerConfig, *, num_agents: int = 10,
                   steps_per_chunk: int = 200,
                   collect_transitions: bool = False,
                   precision=None) -> Agent:
    """``collect_transitions`` makes each chunk additionally return its raw
    transition batch under ``metrics["transitions"]`` so the host can journal
    them (the runtime's ``learner.journal_replay`` switch).

    ``learner.replay_priority`` selects the sampler: ``"uniform"``
    (default) is the pre-data-plane code path bit-for-bit (golden-pinned,
    tests/golden/replay_uniform_golden.json); ``"per"`` adds the
    sum-tree prioritized sampler (ops/sum_tree.py) — priority update,
    stratified sample, and TD-error write-back all inside this one traced
    step, with the importance-sampling weights folded into the TD loss."""
    if cfg.replay_priority not in ("uniform", "per"):
        raise ConfigError(
            f"unknown learner.replay_priority {cfg.replay_priority!r} "
            "(expected 'uniform' or 'per')")
    if cfg.replay_capacity <= num_agents:
        # The circular push aliases masked rows onto (pos-1): with the
        # batch spanning the whole buffer, a masked row can collide with
        # a valid write and the scatter winner is implementation-defined
        # (buffer AND sum-tree). A capacity this small is a config error,
        # not a samplable buffer.
        raise ConfigError(
            f"learner.replay_capacity ({cfg.replay_capacity}) must exceed "
            f"the agent batch ({num_agents}): a push spanning the whole "
            "circular buffer has implementation-defined slot winners")
    use_per = cfg.replay_priority == "per"
    optimizer = build_optimizer(cfg)
    precision = precision or FP32
    apply_update = make_update_fn(optimizer, cfg, precision)
    horizon = env.num_steps
    obs_dim = model.obs_dim

    def init(key: jax.Array) -> TrainState:
        k_params, k_rng = jax.random.split(key)
        params = model.init(k_params)
        replay = ReplayBuffer.create(cfg.replay_capacity, obs_dim)
        target = jax.tree.map(jnp.copy, params)
        extras = (DQNExtrasPER(
            target_params=target, replay=replay,
            per=PerState(tree=sum_tree.create(cfg.replay_capacity),
                         max_priority=jnp.float32(1.0)))
            if use_per else
            DQNExtras(target_params=target, replay=replay))
        return TrainState(
            params=params, opt_state=optimizer.init(params),
            carry=batched_carry(model, num_agents, precision),
            env_state=batched_reset(env, num_agents),
            rng=k_rng, env_steps=jnp.int32(0), updates=jnp.int32(0),
            extras=extras,
        )

    def q_batch(params, obs_batch):
        outs, _ = apply_batched(model, params, obs_batch, ())
        return outs.logits

    def q_batch_with_aux(params, obs_batch):
        """Forward that also surfaces ModelOut.aux (the MoE balance term;
        0 for dense models) so the TD loss can regularize a routed gate."""
        outs, _ = apply_batched(model, params, obs_batch, ())
        return outs.logits, jnp.mean(jnp.asarray(outs.aux))

    def one_step(ts: TrainState, _):
        rng, k_act, k_sample = jax.random.split(ts.rng, 3)
        act_keys = jax.random.split(k_act, num_agents)
        # ONE compute-dtype copy per update boundary (precision.py): the
        # online net AND the target net forwards read compute copies; the
        # update applies to the fp32 masters. Identity in fp32 mode.
        params_c = precision.cast_compute(ts.params)
        target_c = precision.cast_compute(ts.extras.target_params)

        # Horizon freeze + poisoned-row quarantine (base.quarantine_mask):
        # a non-finite agent contributes no transitions to the replay buffer
        # and no NaNs to the shared network; the orchestrator respawns it.
        obs_raw = jax.vmap(env.observe)(ts.env_state)
        healthy = quarantine_mask(obs_raw, ts.env_state)
        active = (ts.env_state.t < horizon) & healthy
        obs = jnp.where(healthy[:, None], obs_raw, 0.0)

        q_sel = q_batch(params_c, obs)
        actions = jax.vmap(lambda k, q: epsilon_greedy(k, q, ts.env_steps, cfg))(
            act_keys, q_sel)
        stepped, rewards = jax.vmap(env.step)(ts.env_state, actions)
        env_state = jax.tree.map(
            lambda new, old: jnp.where(
                active.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
            stepped, ts.env_state)
        rewards = jnp.where(active, rewards, 0.0)
        next_obs = jnp.where(healthy[:, None],
                             jax.vmap(env.observe)(env_state), 0.0)

        def td_core(params, b_obs, b_act, b_rew, b_next, weights=None):
            """ONE copy of the TD math for both samplers (a target-rule
            fix must never land in one branch only): ``weights=None`` is
            the uniform loss — literally the pre-PER ops, golden-pinned;
            PER passes its IS weights in."""
            q_s, aux = q_batch_with_aux(params, b_obs)
            q_next = jax.lax.stop_gradient(q_batch(target_c, b_next))
            target = b_rew + cfg.gamma * jnp.max(q_next, axis=-1)
            predicted = jnp.take_along_axis(
                q_s, b_act[:, None], axis=-1)[:, 0]
            td_err = predicted - target
            sq = (jnp.square(td_err) if weights is None
                  else weights * jnp.square(td_err))
            return jnp.mean(sq) + cfg.aux_loss_coef * aux, td_err

        if use_per:
            # Prioritized path: the push mirrors its write plan into the
            # sum-tree (new transitions enter at the running max stored
            # priority), the stratified sample + IS weights come from the
            # tree, and the TD errors below re-prioritize the sampled
            # leaves — all inside this traced step.
            per = ts.extras.per
            replay, push_idx, push_write = ts.extras.replay.push_with_plan(
                obs, actions, rewards, next_obs, active)
            tree = sum_tree.set_priorities(
                per.tree, push_idx,
                jnp.broadcast_to(per.max_priority, push_idx.shape),
                push_write)
            sample_idx, sample_probs = sum_tree.sample_stratified(
                tree, k_sample, cfg.replay_batch)
            beta = per_beta(ts.env_steps, cfg)
            weights = jax.lax.stop_gradient(
                sum_tree.is_weights(sample_probs, replay.size, beta))

            def td_loss(params):
                return td_core(params, replay.obs[sample_idx],
                               replay.action[sample_idx],
                               replay.reward[sample_idx],
                               replay.next_obs[sample_idx], weights)

            ready = replay.size >= cfg.replay_batch
            (loss, td_err), grads = jax.value_and_grad(
                td_loss, has_aux=True)(params_c)
        else:
            replay = ts.extras.replay.push(obs, actions, rewards, next_obs, active)

            def td_loss(params):
                b_obs, b_act, b_rew, b_next = replay.sample(k_sample, cfg.replay_batch)
                # The unused td_err aux is dead-code-eliminated: the
                # compiled uniform program is the pre-PER one bit-for-bit.
                return td_core(params, b_obs, b_act, b_rew, b_next)[0]

            # Learn only once the buffer can fill a batch.
            ready = replay.size >= cfg.replay_batch
            loss, grads = jax.value_and_grad(td_loss)(params_c)
        new_params, opt_state = apply_update(grads, ts.opt_state, ts.params)
        params = jax.tree.map(lambda new, old: jnp.where(ready, new, old),
                              new_params, ts.params)
        opt_state = jax.tree.map(lambda new, old: jnp.where(ready, new, old),
                                 opt_state, ts.opt_state)
        n_updates = ts.updates + jnp.where(ready, 1, 0)

        # Hard target sync every target_update_every updates.
        sync = ready & (n_updates % cfg.target_update_every == 0)
        target_params = jax.tree.map(
            lambda t, p: jnp.where(sync, p, t),
            ts.extras.target_params, params)

        if use_per:
            # TD-error write-back, gated on ready THROUGH THE MASK: an
            # unready sample ran on garbage strata and must not touch
            # real priorities. The mask (not a post-hoc where over old
            # and new trees) keeps the pre-write tree dead after this
            # call, so XLA scatters the levels in place instead of
            # copying them — the difference between PER riding along and
            # PER costing a tree copy per env step.
            new_p = (jnp.abs(td_err) + cfg.per_eps) ** cfg.per_alpha
            tree = sum_tree.set_priorities(
                tree, sample_idx, new_p,
                mask=jnp.broadcast_to(ready, sample_idx.shape))
            max_p = jnp.where(
                ready, jnp.maximum(per.max_priority, jnp.max(new_p)),
                per.max_priority)
            extras = DQNExtrasPER(
                target_params=target_params, replay=replay,
                per=PerState(tree=tree, max_priority=max_p))
        else:
            extras = DQNExtras(target_params=target_params, replay=replay)
        ts = ts.replace(
            params=params, opt_state=opt_state, env_state=env_state, rng=rng,
            env_steps=ts.env_steps + jnp.where(jnp.any(active), 1, 0),
            updates=n_updates,
            extras=extras,
        )
        out = (jnp.where(ready, loss, 0.0), jnp.sum(rewards))
        if collect_transitions:
            out = out + ((obs, actions, rewards, next_obs, active),)
        return ts, out

    def step(ts: TrainState):
        ts, outs = jax.lax.scan(
            one_step, ts, None, length=steps_per_chunk)
        losses, rewards = outs[0], outs[1]
        metrics = {
            "loss": jnp.mean(losses),
            "reward_sum": jnp.sum(rewards),
            "replay_size": ts.extras.replay.size,
            "exploit_prob": exploit_probability(ts.env_steps, cfg),
            "env_steps": ts.env_steps,
            "updates": ts.updates,
            **portfolio_metrics(env, ts.env_state),
        }
        if use_per:
            # PER gauges (obs/metrics.prom via the chunk metric stream);
            # only in per mode — the uniform metrics dict is part of the
            # golden-pinned pre-PR surface.
            metrics["per_max_priority"] = ts.extras.per.max_priority
            metrics["per_beta"] = per_beta(ts.env_steps, cfg)
        if collect_transitions:
            t_obs, t_act, t_rew, t_next, t_valid = outs[2]
            metrics["transitions"] = {
                "obs": t_obs, "action": t_act, "reward": t_rew,
                "next_obs": t_next, "valid": t_valid}
        return ts, metrics

    return Agent(name="dqn", init=init, step=step,
                 num_agents=num_agents, steps_per_chunk=steps_per_chunk,
                 model=model)


def reseed_per_priorities(extras, *, priority: float | None = None):
    """Rebuild the PER sum-tree after an out-of-band buffer fill (the
    resume-time journal warm start): priorities are not journaled, so the
    ``warm.size`` recovered rows re-enter at the running max stored
    priority (exactly how a fresh push would admit them) and every empty
    slot goes massless. No-op for uniform-mode extras."""
    if not isinstance(extras, DQNExtrasPER):
        return extras
    per = extras.per
    n_leaves = per.tree.num_leaves
    p = per.max_priority if priority is None else jnp.float32(priority)
    leaves = jnp.where(
        jnp.arange(n_leaves) < extras.replay.size, p, 0.0
    ).astype(jnp.float32)
    return extras.replace(per=per.replace(
        tree=sum_tree.from_leaves(leaves)))


def fill_replay_from_journal(replay: ReplayBuffer, journal) -> ReplayBuffer:
    """Replay journaled transitions into the device buffer (offline/warm-start
    path — the event-sourcing recovery pattern applied to experience).

    Only the journal tail that can actually survive in the circular buffer is
    pushed: replaying from record zero would cost time linear in the whole
    training history, and pushing batches wider than the buffer would scatter
    with duplicate indices (implementation-defined winner). Events are pushed
    oldest-first in capacity-bounded slices so "newest wins" circular
    semantics hold deterministically."""
    return fill_replay_from_events(
        replay, [e for e in journal.replay() if e.get("type") == "transitions"])


def fill_replay_from_arrays(replay: ReplayBuffer, obs, action, reward,
                            next_obs) -> ReplayBuffer:
    """Push pre-decoded transition arrays (oldest-first) into the device
    buffer in capacity-bounded slices — the fast path fed by the packed
    binary journal reader (data/transitions.py read_tail_transitions)."""
    capacity = replay.obs.shape[0]
    obs = jnp.asarray(obs, jnp.float32)
    action = jnp.asarray(action, jnp.int32)
    reward = jnp.asarray(reward, jnp.float32)
    next_obs = jnp.asarray(next_obs, jnp.float32)
    for lo in range(0, obs.shape[0], capacity):
        sl = slice(lo, lo + capacity)
        valid = jnp.ones((obs[sl].shape[0],), bool)
        replay = replay.push(obs[sl], action[sl], reward[sl],
                             next_obs[sl], valid)
    return replay


def fill_replay_from_events(replay: ReplayBuffer,
                            events: list[dict]) -> ReplayBuffer:
    capacity = replay.obs.shape[0]
    # Walk back from the tail until the kept events cover the capacity.
    kept, rows = [], 0
    for event in reversed(events):
        kept.append(event)
        rows += len(event["action"])
        if rows >= capacity:
            break
    for event in reversed(kept):
        obs = jnp.asarray(event["obs"], jnp.float32)
        action = jnp.asarray(event["action"], jnp.int32)
        reward = jnp.asarray(event["reward"], jnp.float32)
        next_obs = jnp.asarray(event["next_obs"], jnp.float32)
        for lo in range(0, obs.shape[0], capacity):
            sl = slice(lo, lo + capacity)
            valid = jnp.ones((obs[sl].shape[0],), bool)
            replay = replay.push(obs[sl], action[sl], reward[sl],
                                 next_obs[sl], valid)
    return replay
