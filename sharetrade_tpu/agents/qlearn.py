"""Online Q-learning — the reference algorithm, fused on-device.

One scan iteration here does what one fold step + four Session.run calls do
in the reference (SURVEY.md §3.3): epsilon-greedy selection
(QDecisionPolicyActor.scala:58-62), env transition
(TrainerChildActor.scala:118-146), TD(0) target
(QDecisionPolicyActor.scala:66-73), and the AdaGrad update — for the whole
agent batch at once, with no host involvement.

TD-target index: the reference writes the target at the **next** state's
argmax index (QDecisionPolicyActor.scala:69-71); its rl.py ancestor — and
textbook Q-learning — uses the *taken* action. ``cfg.update_taken_action``
selects (True = textbook, the default; False = reference-bug parity). The
elementwise square loss ``(y - q)²`` reduces to the single updated
coordinate because y equals q everywhere else — implemented directly as the
single-coordinate TD error.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sharetrade_tpu.agents.base import (
    Agent,
    TrainState,
    batched_carry,
    batched_reset,
    build_optimizer,
    epsilon_greedy,
    exploit_probability,
    make_update_fn,
    portfolio_metrics,
    quarantine_mask,
)
from sharetrade_tpu.config import LearnerConfig
from sharetrade_tpu.env.core import TradingEnv
from sharetrade_tpu.models.core import Model, apply_batched
from sharetrade_tpu.precision import FP32


def make_qlearn_agent(model: Model, env: TradingEnv,
                      cfg: LearnerConfig, *, num_agents: int = 10,
                      steps_per_chunk: int = 200, precision=None) -> Agent:
    optimizer = build_optimizer(cfg)
    precision = precision or FP32
    apply_update = make_update_fn(optimizer, cfg, precision)
    horizon = env.num_steps

    def init(key: jax.Array) -> TrainState:
        k_params, k_rng = jax.random.split(key)
        params = model.init(k_params)   # fp32 masters, always
        return TrainState(
            params=params,
            opt_state=optimizer.init(params),
            carry=batched_carry(model, num_agents, precision),
            env_state=batched_reset(env, num_agents),
            rng=k_rng,
            env_steps=jnp.int32(0),
            updates=jnp.int32(0),
        )

    def apply_batch(params, obs_batch, carry_batch):
        outs, carries = apply_batched(model, params, obs_batch, carry_batch)
        # aux = the model's auxiliary regularizer (MoE balance term; 0 for
        # dense models) — the loss adds it so a routed-FFN Q-network can't
        # train with an unregularized, collapse-prone gate.
        return outs.logits, jnp.mean(jnp.asarray(outs.aux)), carries

    def one_step(ts: TrainState, _):
        rng, k_act = jax.random.split(ts.rng)
        act_keys = jax.random.split(k_act, num_agents)
        # ONE compute-dtype weight copy per update boundary (precision.py):
        # selection forward, TD replay and backward all read it; the
        # gradients upcast inside apply_update and the update applies to
        # the fp32 masters in ts.params. Identity in fp32 mode.
        params_c = precision.cast_compute(ts.params)

        # Freeze agents whose episode is over (chunking may overrun the
        # horizon) AND quarantine poisoned rows (base.quarantine_mask): a
        # non-finite agent must not reach the shared parameters; the
        # orchestrator respawns the row.
        obs_raw = jax.vmap(env.observe)(ts.env_state)
        healthy = quarantine_mask(obs_raw, ts.env_state)
        active = (ts.env_state.t < horizon) & healthy  # (B,) bool
        obs = jnp.where(healthy[:, None], obs_raw, 0.0)

        q_sel, _aux_sel, carry_new = apply_batch(params_c, obs, ts.carry)
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

        def td_loss(params):
            # One stacked forward for Q(s) and Q(s'): tiny matmuls are
            # launch-overhead-bound on TPU, so halving the op count beats
            # two back-to-back (B, obs) contractions.
            q_both, aux, _ = apply_batch(
                params, jnp.concatenate([obs, next_obs], axis=0),
                jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                             ts.carry, carry_new))
            q_s = q_both[:num_agents]                             # (B, A)
            q_next = jax.lax.stop_gradient(q_both[num_agents:])
            target = rewards + cfg.gamma * jnp.max(q_next, axis=-1)
            idx = jnp.where(
                cfg.update_taken_action,
                actions,
                jnp.argmax(q_next, axis=-1).astype(jnp.int32),  # reference bug
            )
            predicted = jnp.take_along_axis(q_s, idx[:, None], axis=-1)[:, 0]
            per_agent = jnp.square(predicted - target) * active
            td = jnp.sum(per_agent) / jnp.maximum(jnp.sum(active), 1)
            return td + cfg.aux_loss_coef * aux

        loss, grads = jax.value_and_grad(td_loss)(params_c)
        any_active = jnp.any(active)
        new_params, opt_state = apply_update(grads, ts.opt_state, ts.params)
        params = jax.tree.map(
            lambda new, old: jnp.where(any_active, new, old),
            new_params, ts.params)
        opt_state = jax.tree.map(
            lambda new, old: jnp.where(any_active, new, old),
            opt_state, ts.opt_state)

        ts = ts.replace(
            params=params, opt_state=opt_state, carry=carry_new,
            env_state=env_state, rng=rng,
            env_steps=ts.env_steps + jnp.where(any_active, 1, 0),
            updates=ts.updates + jnp.where(any_active, 1, 0),
        )
        return ts, (loss, jnp.sum(rewards))

    def step(ts: TrainState):
        ts, (losses, rewards) = jax.lax.scan(
            one_step, ts, None, length=steps_per_chunk)
        metrics = {
            "loss": jnp.mean(losses),
            "reward_sum": jnp.sum(rewards),
            "exploit_prob": exploit_probability(ts.env_steps, cfg),
            "env_steps": ts.env_steps,
            "updates": ts.updates,
            **portfolio_metrics(env, ts.env_state),
        }
        return ts, metrics

    return Agent(name="qlearn", init=init, step=step,
                 num_agents=num_agents, steps_per_chunk=steps_per_chunk,
                 model=model)
