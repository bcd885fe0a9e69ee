"""PPO with GAE — BASELINE.json config 4 (LSTM policy capable).

Clipped surrogate objective over multiple epochs of minibatch updates, all
inside one jitted chunk (epochs and minibatch sweeps are ``lax.scan``s, not
Python loops — XLA sees a single static program).

Recurrence: minibatches cut across the *agent* axis, never the time axis, so
each minibatch replays full sequences from the unroll's initial carry and
LSTM gradients flow through time correctly (the standard sequence-preserving
PPO+RNN scheme). What the loop holds and gathers of that carry is the
model's ``replay_carry`` of it (agents/rollout.py): the whole carry for an
LSTM, ``hist``/``t``/health for the episode transformer — never its K/V
caches, which the replay does not read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sharetrade_tpu.agents.base import (
    Agent, TrainState, batched_carry, batched_reset, build_optimizer,
    make_update_fn, portfolio_metrics,
)
from sharetrade_tpu.agents.rollout import (
    collect_rollout, gae_advantages, normalize_advantages_masked,
    replay_carry, replay_forward, taken_action_log_prob,
)
from sharetrade_tpu.config import LearnerConfig
from sharetrade_tpu.env.core import TradingEnv
from sharetrade_tpu.models.core import Model
from sharetrade_tpu.parallel.mesh import has_shard_map_axis
from sharetrade_tpu.precision import FP32
from sharetrade_tpu.utils.logging import get_logger


def _replicated(seam_mesh):
    """The canonical replicated NamedSharding for the seam pins — resolved
    through parallel.sharding's cache (lazily: sharding.py imports
    agents.base, so a module-level import here would cycle)."""
    from sharetrade_tpu.parallel.sharding import canonical_sharding
    return canonical_sharding(seam_mesh)


def make_ppo_agent(model: Model, env: TradingEnv,
                   cfg: LearnerConfig, *, num_agents: int = 10,
                   steps_per_chunk: int | None = None, mesh=None,
                   precision=None) -> Agent:
    optimizer = build_optimizer(cfg)
    precision = precision or FP32
    apply_update = make_update_fn(optimizer, cfg, precision)
    # The rollout→update replicate seam applies ONLY on meshes with a
    # shard_map-partitioned axis (mesh.has_shard_map_axis): there, the
    # epoch scans' permuted minibatch gathers over dp-sharded rollout
    # products collide with the partitioned paths' transposed-mesh specs
    # and GSPMD bridges them with an involuntary full rematerialization
    # PER GATHER (the MULTICHIP_r01..r05 warnings; see
    # tools/shard_audit.py). Pure dp/tp meshes compile those gathers
    # cleanly already and keep their exact pre-seam programs — measured
    # byte-identical in the shard-audit manifest.
    seam_mesh = mesh if has_shard_map_axis(mesh) else None
    unroll = steps_per_chunk or cfg.unroll_len
    # Largest divisor of num_agents not exceeding the configured count keeps
    # minibatch SGD meaningful when the two don't divide evenly (e.g. 10
    # agents / 4 requested -> 2 minibatches of 5, not a silent full batch).
    requested = max(1, min(cfg.ppo_minibatches, num_agents))
    num_minibatches = max(d for d in range(1, requested + 1)
                          if num_agents % d == 0)
    if num_minibatches != requested:
        get_logger("agents.ppo").warning(
            "ppo_minibatches=%d does not divide num_agents=%d; using %d",
            cfg.ppo_minibatches, num_agents, num_minibatches)
    mb_size = num_agents // num_minibatches
    # What says the trimmed replay carry engaged, fixed at build: the bytes
    # of unroll-start carry one minibatch gathers (the orchestrator exports
    # it as a gauge). The episode transformer reads kilobytes a row where
    # its whole carry is megabytes; an LSTM reads its whole carry.
    replay_carry_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(jax.eval_shape(
            lambda: replay_carry(
                model, batched_carry(model, mb_size, precision)))))

    def init(key: jax.Array) -> TrainState:
        k_params, k_rng = jax.random.split(key)
        params = model.init(k_params)
        return TrainState(
            params=params, opt_state=optimizer.init(params),
            carry=batched_carry(model, num_agents, precision),
            env_state=batched_reset(env, num_agents),
            rng=k_rng, env_steps=jnp.int32(0), updates=jnp.int32(0),
        )

    def minibatch_loss(params, traj_mb, carry_mb, adv_mb, ret_mb):
        logits, values, aux = replay_forward(model, params, traj_mb, carry_mb,
                                             remat=cfg.remat)
        log_probs = jax.nn.log_softmax(logits)
        logp = taken_action_log_prob(log_probs, traj_mb.action)
        weight = traj_mb.active
        denom = jnp.maximum(jnp.sum(weight), 1.0)

        # Advantage normalization over the minibatch's active steps (the
        # shared masked normalizer; its re-masking is idempotent under the
        # loss terms' own * weight factors).
        adv = normalize_advantages_masked(adv_mb, weight, denom)

        ratio = jnp.exp(logp - traj_mb.logp)
        clipped = jnp.clip(ratio, 1.0 - cfg.clip_eps, 1.0 + cfg.clip_eps)
        policy_loss = -jnp.sum(
            jnp.minimum(ratio * adv, clipped * adv) * weight) / denom
        value_loss = jnp.sum(jnp.square(values - ret_mb) * weight) / denom
        entropy = -jnp.sum(
            jnp.sum(jnp.exp(log_probs) * log_probs, axis=-1) * weight) / denom
        total = (policy_loss + cfg.value_coef * value_loss
                 - cfg.entropy_coef * entropy + cfg.aux_loss_coef * aux)
        return total, (policy_loss, value_loss, entropy)

    def step(ts: TrainState):
        # Rollout forwards read ONE compute-dtype weight copy
        # (precision.py cast_compute — identity in fp32 mode); each
        # minibatch update below casts its own fresh copy of the
        # just-updated masters.
        ts, traj, bootstrap, replay_init = collect_rollout(
            model, env, ts, unroll, num_agents,
            params=precision.cast_compute(ts.params))
        advantages = gae_advantages(traj.reward, traj.value, traj.active,
                                    bootstrap, cfg.gamma, cfg.gae_lambda)
        returns = advantages + traj.value
        if seam_mesh is not None:
            # The rollout→update seam (sp/ep meshes only — see seam_mesh
            # above): marking the rollout products replicated makes the
            # epoch scans' permuted-gather data movement ONE planned
            # all-gather per chunk instead of an involuntary full
            # rematerialization per gather; the updated params/opt and the
            # carried TrainState keep their canonical specs via the jit
            # in/out shardings and the parallel layer's seam pins
            # (parallel/sharding.py constrain_train_state).
            replicated = _replicated(seam_mesh)
            traj, replay_init, advantages, returns = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(x, replicated),
                (traj, replay_init, advantages, returns))

        def epoch_body(carry, _):
            params, opt_state, rng = carry
            rng, k_perm = jax.random.split(rng)
            perm = jax.random.permutation(k_perm, num_agents)

            def mb_body(carry, mb_idx):
                params, opt_state = carry
                # The named scopes reach a profiler trace as the events'
                # scope (an operator reading runtime.profile_dir in XProf
                # sees the chunk's phases).
                with jax.named_scope("minibatch_gather"):
                    idx = jax.lax.dynamic_slice_in_dim(
                        perm, mb_idx * mb_size, mb_size)
                    traj_mb = jax.tree.map(lambda x: x[:, idx], traj)
                    carry_mb = jax.tree.map(lambda x: x[idx], replay_init)
                    adv_mb, ret_mb = advantages[:, idx], returns[:, idx]
                if seam_mesh is not None:
                    # Pin the GATHERED slices replicated as well: GSPMD
                    # otherwise re-derives a dp layout for the tiny
                    # minibatch tensors (mb_size rows can't even tile the
                    # dp axis) and the episode trunk's sp/ep attention
                    # spec then forces the involuntary remat this module
                    # exists to avoid — on carry_mb['hist'] specifically,
                    # the MULTICHIP logs' signature warning.
                    replicated = _replicated(seam_mesh)
                    traj_mb, carry_mb, adv_mb, ret_mb = jax.tree.map(
                        lambda x: jax.lax.with_sharding_constraint(
                            x, replicated),
                        (traj_mb, carry_mb, adv_mb, ret_mb))
                # Differentiate against the compute copy of the CURRENT
                # masters (re-cast per minibatch — the masters just moved);
                # the update itself applies in f32 to the masters.
                # No scope around the replay: one that encloses a
                # pallas_call enters its name stack, and XLA names the
                # kernel's trace event after the stack's last entry
                # (``%jvp__``, ``%transpose_jvp___``: the names the
                # benchmark's attention_roofline matches). Under
                # named_scope("replay") the backward kernels come out as
                # ``%jvp__`` too (described-chip compile, PR 24).
                (loss, aux), grads = jax.value_and_grad(
                    minibatch_loss, has_aux=True)(
                    precision.cast_compute(params), traj_mb, carry_mb,
                    adv_mb, ret_mb)
                with jax.named_scope("update"):
                    params, opt_state = apply_update(grads, opt_state,
                                                     params)
                return (params, opt_state), (loss, *aux)

            (params, opt_state), losses = jax.lax.scan(
                mb_body, (params, opt_state), jnp.arange(num_minibatches))
            return (params, opt_state, rng), losses

        (params, opt_state, rng), losses = jax.lax.scan(
            epoch_body, (ts.params, ts.opt_state, ts.rng), None,
            length=cfg.ppo_epochs)
        total, policy_l, value_l, entropy = (jnp.mean(x) for x in losses)

        ts = ts.replace(
            params=params, opt_state=opt_state, rng=rng,
            updates=ts.updates + cfg.ppo_epochs * num_minibatches)
        metrics = {
            "loss": total,
            "policy_loss": policy_l,
            "value_loss": value_l,
            "entropy": entropy,
            "reward_sum": jnp.sum(traj.reward),
            "env_steps": ts.env_steps,
            "updates": ts.updates,
            **portfolio_metrics(env, ts.env_state),
        }
        return ts, metrics

    return Agent(name="ppo", init=init, step=step,
                 num_agents=num_agents, steps_per_chunk=unroll, model=model,
                 replay_carry_bytes=replay_carry_bytes)
