"""Learner interface and shared RL machinery.

The reference's learner is one actor whose mailbox serializes ~230k
single-row Session.run calls (SURVEY.md §3.3). Here every learner exposes the
same two pure functions, and the whole step loop lives on-device:

- ``init(key) -> TrainState``
- ``step(TrainState) -> (TrainState, metrics)``  — advances ``steps_per_chunk``
  env steps for the WHOLE agent batch inside one jitted program (action
  selection + env transition + learning update fused; §7.2's inversion).

The orchestrator (runtime/) only ever calls these two functions, so the
algorithms (Q-learning, PG, DQN, A2C, PPO) are interchangeable — the
generalization of the reference's single hard-wired Q-policy actor that
SURVEY.md §7.1 item 3 requires.

Batching note (the explicit algorithm change demanded by SURVEY.md §7.4): the
reference's 10 workers funnel updates through one mailbox, so the network
changes between *every* worker's step. Here the B agents' per-step losses are
averaged into ONE update per env step (or per unroll). With one agent the
semantics match the reference exactly — that is the parity-test configuration.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct

from sharetrade_tpu.config import LearnerConfig
from sharetrade_tpu.env.core import TradingEnv


@struct.dataclass
class TrainState:
    """Everything a learner threads between chunks — exactly the state that
    checkpoint/resume must capture (SURVEY.md §7.1 item 7: model + optimizer
    + RNG + episode cursor)."""

    params: Any
    opt_state: Any
    carry: Any               # (B, ...) model recurrent state
    env_state: Any           # batched (B,) episode cursors (env-specific pytree)
    rng: jax.Array
    env_steps: jax.Array     # i32 global env-step counter (epsilon schedule input)
    updates: jax.Array       # i32 update counter (the reference's `iteration`)
    extras: Any = None       # algo-specific (replay buffer, target params, ...)


@dataclass(frozen=True)
class Agent:
    """A learner: pure init/step plus static shape facts for the runtime.

    ``model`` carries the policy network the learner was built around so the
    runtime evaluates exactly what was trained (rebuilding from config would
    silently evaluate a different architecture when a custom model was
    injected).

    ``replay_carry_bytes`` is set by learners whose update phase gathers
    the unroll-start carry per minibatch (PPO): the bytes one minibatch
    gathers, from the shapes of the model's ``replay_carry``
    (agents/rollout.py). The orchestrator exports it as the gauge
    ``train_replay_carry_bytes_per_minibatch``."""

    name: str
    init: Callable[[jax.Array], TrainState]
    step: Callable[[TrainState], tuple[TrainState, dict[str, jax.Array]]]
    num_agents: int
    steps_per_chunk: int
    model: Any = None
    replay_carry_bytes: int | None = None


def megachunk_step(step_fn: Callable[[TrainState],
                                     tuple[TrainState, dict[str, jax.Array]]],
                   factor: int) -> Callable[[TrainState],
                                            tuple[TrainState, dict]]:
    """Device-resident megachunk: ``factor`` consecutive chunk steps fused
    into ONE compiled program, so the host pays one dispatch per ``factor``
    chunks instead of one each — the lever against the per-dispatch host
    cost (not yet measured on an attached chip; ROADMAP S2 re-measures it).

    Per-chunk metrics stack along a leading ``(factor,)`` axis: every
    learner's metrics dict — scalars AND DQN's ``transitions`` batch — is a
    scan output, so the whole megachunk's metric stream reads back with a
    single batched ``jax.device_get`` at the boundary instead of ``factor``
    scattered scalar round-trips. The scanned body is the same traced
    function as the single-chunk program, so K fused chunks are bit-identical
    to K host-dispatched chunks (pinned by tests/test_megachunk.py parity).

    On a mesh, ``parallel/sharding.py`` composes the carry-sharding pin
    UNDER this wrapper (``step_fn`` arrives already constrained), so each
    of the K-1 inner-chunk seams — which have no jit in/out shardings of
    their own — keeps the TrainState on its canonical specs instead of
    letting GSPMD re-derive (and involuntarily reshard) the scan carry.
    """
    if factor < 1:
        raise ValueError(f"megachunk factor must be >= 1, got {factor}")

    def megastep(ts: TrainState):
        def body(carry, _):
            return step_fn(carry)

        return jax.lax.scan(body, ts, None, length=factor)

    return megastep


def build_optimizer(cfg: LearnerConfig) -> optax.GradientTransformation:
    """Reference: AdaGrad(0.01) (QDecisionPolicyActor.scala:50). optax's
    default ``initial_accumulator_value=0.1`` matches TF's AdaGrad."""
    if cfg.optimizer == "adagrad":
        return optax.adagrad(cfg.learning_rate)
    if cfg.optimizer == "adam":
        return optax.adam(cfg.learning_rate)
    if cfg.optimizer == "sgd":
        return optax.sgd(cfg.learning_rate)
    raise ValueError(f"unknown optimizer {cfg.optimizer!r}")


def make_update_fn(optimizer: optax.GradientTransformation,
                   cfg: LearnerConfig, precision):
    """THE optimizer-update seam every learner applies its gradients
    through: ``update(grads, opt_state, params) -> (params, opt_state)``.

    ``grads`` arrive in whatever dtype the loss backward produced (bf16
    under the mixed policy — differentiation runs against the compute
    copy); the seam owns the master-space upcast, so learners never touch
    a dtype. Two implementations, selected by the precision policy
    (``precision.use_fused_update``):

    - **optax pair** (fp32 default): literally ``optimizer.update`` +
      ``optax.apply_updates`` — the pre-policy code path, bit-identical,
      with the grads routed through ``precision.grads_to_master`` (an
      object identity in fp32 mode).
    - **fused** (bf16_mixed default, or ``precision.fused_update='on'``):
      ``ops/fused_update.fused_apply`` — grad-upcast + moment update +
      param update as one XLA loop fusion per leaf in its stored layout,
      optax-exact in fp32 and sharing the optax state structure either
      way. Elementwise, so on a mesh the compiler partitions it by each
      leaf's own sharding with no collective.

    Unsupported optimizers under 'on'/'auto' fall back to the optax pair
    (fused_supported) rather than failing — the policy is a performance
    lever, not a capability gate."""
    from sharetrade_tpu.ops.fused_update import fused_apply, fused_supported

    if precision is not None and precision.use_fused_update \
            and fused_supported(cfg):
        name, lr = cfg.optimizer, cfg.learning_rate
        compute_dtype = precision.compute_dtype

        def update(grads, opt_state, params):
            return fused_apply(name, lr, grads, opt_state, params,
                               compute_dtype=compute_dtype)

        return update

    def update(grads, opt_state, params):
        if precision is not None:
            grads = precision.grads_to_master(grads)
        updates, new_opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt_state

    return update


def exploit_probability(step: jax.Array, cfg: LearnerConfig) -> jax.Array:
    """P(exploit) = min(epsilon, step / ramp): fully random at step 0 ramping
    to epsilon-greedy (QDecisionPolicyActor.scala:58: ``Seq(epsilon,
    step/1000f).min``)."""
    return jnp.minimum(jnp.float32(cfg.epsilon),
                       step.astype(jnp.float32) / cfg.epsilon_ramp_steps)


def per_beta(step: jax.Array, cfg: LearnerConfig) -> jax.Array:
    """Importance-sampling exponent schedule for prioritized replay:
    anneal from ``per_beta0`` to 1 over ``per_beta_steps`` env steps (the
    Schaul et al. schedule — bias correction tightens as the policy
    stabilizes), the PER sibling of :func:`exploit_probability`."""
    frac = step.astype(jnp.float32) / max(1, cfg.per_beta_steps)
    return jnp.minimum(
        jnp.float32(1.0),
        jnp.float32(cfg.per_beta0) + (1.0 - cfg.per_beta0) * frac)


def epsilon_greedy(key: jax.Array, q_values: jax.Array, step: jax.Array,
                   cfg: LearnerConfig) -> jax.Array:
    """One agent's Buy/Sell/Hold choice (QDecisionPolicyActor.scala:58-62)."""
    k_gate, k_rand = jax.random.split(key)
    exploit = jax.random.uniform(k_gate) < exploit_probability(step, cfg)
    greedy = jnp.argmax(q_values).astype(jnp.int32)
    rand = jax.random.randint(k_rand, (), 0, q_values.shape[0], jnp.int32)
    return jnp.where(exploit, greedy, rand)


def batched_reset(env: TradingEnv, num_agents: int):
    single = env.reset()
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (num_agents,) + x.shape),
                        single)


def batched_carry(model, num_agents: int, precision=None):
    """The model's carry seed for ``num_agents`` agents. ``precision``
    (precision.py ``cast_carry``) is applied to the ONE-agent seed, before
    the broadcast: ``agent.init`` runs eagerly, and casting the batch
    afterwards held the float32 K/V caches beside their bf16 copy — three
    times the carry, and the whole run's peak device memory in both
    training cells of the benchmark (PERF.md, PR 25)."""
    carry = model.init_carry()
    if precision is not None:
        carry = precision.cast_carry(carry, model)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (num_agents,) + x.shape),
                        carry)


def healthy_mask(obs: jax.Array) -> jax.Array:
    """(B, obs_dim) observations -> (B,) bool: rows that are entirely finite.

    The quarantine predicate of the per-agent fault story (the reference's
    one-dead-child-doesn't-stop-the-other-nine supervision,
    TrainerRouterActor.scala:141-146, translated to vectorized agents): a
    poisoned agent (NaN/Inf budget, corrupted price row) is masked out of
    the shared parameter update on-device — learners AND the observation fed
    to the network (sanitized to zeros so no NaN flows through the loss) —
    and the orchestrator respawns just that row between chunks
    (Orchestrator._heal_agents)."""
    return jnp.all(jnp.isfinite(obs), axis=-1)


def agent_health(env_state) -> jax.Array:
    """(B,) bool from the env-state pytree: True where every leaf row is
    finite (the host-visible form of the quarantine predicate)."""
    leaves = jax.tree.leaves(env_state)
    b = leaves[0].shape[0]
    ok = jnp.ones((b,), bool)
    for leaf in leaves:
        ok &= jnp.all(jnp.isfinite(leaf.reshape(b, -1)), axis=-1)
    return ok


def election_health(env_state, carry) -> jax.Array:
    """(B,) bool: THE row-health predicate shared by representative
    election (agents/rollout.py) and the per-row heal
    (runtime/orchestrator.py): every env-state leaf row finite AND every
    batched model-carry leaf row finite. A row with a finite wallet but a
    non-finite carry (NaN K/V cache) must never be elected representative —
    its carry would broadcast into every agent's shared trunk, escalating a
    one-row fault to a whole-batch poisoning."""
    from sharetrade_tpu.models.core import rows_finite
    ok = agent_health(env_state)
    return ok & rows_finite(carry, ok.shape[0])


def quarantine_mask(obs_raw: jax.Array, env_state) -> jax.Array:
    """THE learner-side quarantine predicate: a row is healthy iff its
    observation AND its whole env-state row are finite. One definition so
    every learner fences the same faults — a site that checked only the
    observation would silently re-admit poison living outside it (e.g.
    ``share_value``, which reaches the loss through the reward)."""
    return healthy_mask(obs_raw) & agent_health(env_state)


def portfolio_metrics(env: TradingEnv, env_state) -> dict[str, jax.Array]:
    """The router's aggregation: mean/std over worker portfolios
    (TrainerRouterActor.scala:137-151) plus richer distribution stats.

    Two aggregation views are emitted side by side:

    - ``portfolio_mean``/``portfolio_std``: continuous stats over all
      HEALTHY agents, including in-flight ones (progressive — richer than
      the reference). Quarantined (non-finite) rows are excluded, the way a
      dead child drops out of the reference's aggregation, and counted in
      ``unhealthy_workers`` so the orchestrator can heal them.
    - ``portfolio_mean_trained``/``portfolio_std_trained``: stats over only
      the agents whose episode cursor reached the horizon — the reference's
      exact ``GetAvg`` observable, which asks the *trained* children only
      (TrainerRouterActor.scala:84-95,137-139). ``trained_workers`` carries
      the mask count so the host can answer NotComputed when it is zero
      (masked stats are 0-filled then, never NaN, to stay jit-safe).
    """
    values = jax.vmap(env.portfolio_value)(env_state)
    fine = agent_health(env_state).astype(jnp.float32)
    values = jnp.where(fine > 0, values, 0.0)
    n_fine = jnp.maximum(jnp.sum(fine), 1.0)
    mean = jnp.sum(values * fine) / n_fine
    var = jnp.sum(fine * (values - mean) ** 2) / n_fine
    done = fine * (env_state.t >= env.num_steps).astype(jnp.float32)
    n_done = jnp.sum(done)
    safe_n = jnp.maximum(n_done, 1.0)
    mean_t = jnp.sum(values * done) / safe_n
    var_t = jnp.sum(done * (values - mean_t) ** 2) / safe_n
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    return {
        "portfolio_mean": mean,
        "portfolio_std": jnp.sqrt(var),
        "portfolio_min": jnp.min(jnp.where(fine > 0, values, big)),
        "portfolio_max": jnp.max(jnp.where(fine > 0, values, -big)),
        "portfolio_mean_trained": mean_t,
        "portfolio_std_trained": jnp.sqrt(var_t),
        "trained_workers": n_done,
        "unhealthy_workers": values.shape[0] - jnp.sum(fine),
    }
