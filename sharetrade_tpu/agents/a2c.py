"""Advantage Actor-Critic (n-step, synchronous) — BASELINE.json config 3.

The "rollout workers → shared learner" shape of the reference (10 broadcast
workers, one parameter server; SURVEY.md §2.2) is exactly A2C's synchronous
geometry: B parallel env agents advance ``unroll_len`` steps, then one joint
update from bootstrapped n-step returns. Policy + value + entropy losses.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sharetrade_tpu.agents.base import (
    Agent, TrainState, batched_carry, batched_reset, build_optimizer,
    make_update_fn, portfolio_metrics,
)
from sharetrade_tpu.agents.rollout import (
    collect_rollout, discounted_returns, normalize_advantages_masked,
    replay_forward, taken_action_log_prob,
)
from sharetrade_tpu.config import LearnerConfig
from sharetrade_tpu.env.core import TradingEnv
from sharetrade_tpu.models.core import Model
from sharetrade_tpu.precision import FP32


def make_a2c_agent(model: Model, env: TradingEnv,
                   cfg: LearnerConfig, *, num_agents: int = 10,
                   steps_per_chunk: int | None = None,
                   precision=None) -> Agent:
    optimizer = build_optimizer(cfg)
    precision = precision or FP32
    apply_update = make_update_fn(optimizer, cfg, precision)
    unroll = steps_per_chunk or cfg.unroll_len

    def init(key: jax.Array) -> TrainState:
        k_params, k_rng = jax.random.split(key)
        params = model.init(k_params)
        return TrainState(
            params=params, opt_state=optimizer.init(params),
            carry=batched_carry(model, num_agents, precision),
            env_state=batched_reset(env, num_agents),
            rng=k_rng, env_steps=jnp.int32(0), updates=jnp.int32(0),
        )

    def step(ts: TrainState):
        # ONE compute-dtype weight copy per chunk update (precision.py);
        # the update applies to the fp32 masters. Identity in fp32 mode.
        params_c = precision.cast_compute(ts.params)
        ts, traj, bootstrap, replay_init = collect_rollout(
            model, env, ts, unroll, num_agents, params=params_c)
        returns = discounted_returns(traj.reward, traj.active,
                                     bootstrap, cfg.gamma)
        weight = traj.active
        denom = jnp.maximum(jnp.sum(weight), 1.0)

        def loss_fn(params):
            logits, values, aux = replay_forward(
                model, params, traj, replay_init, remat=cfg.remat)
            log_probs = jax.nn.log_softmax(logits)
            logp = taken_action_log_prob(log_probs, traj.action)
            adv = jax.lax.stop_gradient(returns - values) * weight
            if cfg.normalize_advantages:
                adv = normalize_advantages_masked(adv, weight, denom)
            policy_loss = -jnp.sum(logp * adv) / denom
            value_loss = jnp.sum(jnp.square(values - returns) * weight) / denom
            entropy = -jnp.sum(
                jnp.sum(jnp.exp(log_probs) * log_probs, axis=-1) * weight
            ) / denom
            total = (policy_loss + cfg.value_coef * value_loss
                     - cfg.entropy_coef * entropy + cfg.aux_loss_coef * aux)
            return total, (policy_loss, value_loss, entropy)

        (loss, (policy_loss, value_loss, entropy)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params_c)
        params, opt_state = apply_update(grads, ts.opt_state, ts.params)
        ts = ts.replace(params=params, opt_state=opt_state,
                        updates=ts.updates + 1)
        metrics = {
            "loss": loss,
            "policy_loss": policy_loss,
            "value_loss": value_loss,
            "entropy": entropy,
            "reward_sum": jnp.sum(traj.reward),
            "env_steps": ts.env_steps,
            "updates": ts.updates,
            **portfolio_metrics(env, ts.env_state),
        }
        return ts, metrics

    return Agent(name="a2c", init=init, step=step,
                 num_agents=num_agents, steps_per_chunk=unroll, model=model)
