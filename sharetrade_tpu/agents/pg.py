"""REINFORCE (vanilla policy gradient).

The reference's Python ancestor (rl.py, cited in its README) is a policy-
gradient trader — BASELINE.json config 1. Monte-Carlo returns-to-go with a
batch-mean baseline; one update per unroll.
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


def make_pg_agent(model: Model, env: TradingEnv,
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
        # ONE compute-dtype weight copy per chunk update (precision.py):
        # rollout forwards, loss replay and backward all read it; the
        # update applies to the fp32 masters. Identity in fp32 mode.
        params_c = precision.cast_compute(ts.params)
        ts, traj, bootstrap, replay_init = collect_rollout(
            model, env, ts, unroll, num_agents, params=params_c)
        returns = discounted_returns(traj.reward, traj.active,
                                     bootstrap, cfg.gamma)
        weight = traj.active
        denom = jnp.maximum(jnp.sum(weight), 1.0)
        baseline = jnp.sum(returns * weight) / denom
        adv = (returns - baseline) * weight
        if cfg.normalize_advantages:
            adv = normalize_advantages_masked(adv, weight, denom)

        def loss_fn(params):
            logits, _, aux = replay_forward(model, params, traj, replay_init,
                                            remat=cfg.remat)
            logp = taken_action_log_prob(
                jax.nn.log_softmax(logits), traj.action)
            pg_loss = -jnp.sum(logp * jax.lax.stop_gradient(adv)) / denom
            return pg_loss + cfg.aux_loss_coef * aux

        loss, grads = jax.value_and_grad(loss_fn)(params_c)
        params, opt_state = apply_update(grads, ts.opt_state, ts.params)
        ts = ts.replace(params=params, opt_state=opt_state,
                        updates=ts.updates + 1)
        metrics = {
            "loss": loss,
            "reward_sum": jnp.sum(traj.reward),
            "return_mean": baseline,
            "env_steps": ts.env_steps,
            "updates": ts.updates,
            **portfolio_metrics(env, ts.env_state),
        }
        return ts, metrics

    return Agent(name="pg", init=init, step=step,
                 num_agents=num_agents, steps_per_chunk=unroll, model=model)
