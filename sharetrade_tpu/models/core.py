"""Model interface shared by every policy network.

The reference has exactly one network — the TF graph built inline in
``QDecisionPolicyActor.scala:38-50`` — and its "interface" is the actor's
message protocol. Here the interface is three pure functions, so any model
slots under ``vmap`` (agent batches), ``lax.scan`` (time), and ``shard_map``
(devices) without special cases:

- ``init(key) -> params``              parameter pytree
- ``apply(params, obs, carry) -> (ModelOut, carry)``   one observation
- ``init_carry() -> carry``            recurrent state seed (``()`` if none)

``ModelOut.logits`` doubles as Q-values for value-based agents (a Q-head's
outputs and a policy head's logits occupy the same slot); ``ModelOut.value``
is the critic estimate for actor-critic agents (zeros for plain Q/PG heads,
keeping the pytree structure uniform across model kinds).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


class ModelOut(NamedTuple):
    logits: jax.Array  # (num_actions,) action preferences / Q-values
    value: jax.Array   # scalar critic estimate (0.0 for valueless heads)
    # Auxiliary regularizer the forward pass wants added to the training
    # loss — the MoE load-balance term (parallel/moe.py), without which a
    # capacity-dispatch gate can collapse onto one expert and silently drop
    # overflowing tokens. 0.0 for models with no such term; losses weight it
    # by LearnerConfig.aux_loss_coef.
    aux: jax.Array | float = 0.0
    # Optional small integer array a SERVING step hands the engine's
    # counters, a row a request (the routed-expert trunk: each row's picks).
    # It rides on the tick's own readback; ``Model.serve_stats`` reduces a
    # tick's real rows on the host. None for every other model and path.
    stats: jax.Array | None = None


@dataclass(frozen=True)
class Model:
    """A policy network as a bundle of pure functions (stateless module)."""

    init: Callable[[jax.Array], Any]
    apply: Callable[[Any, jax.Array, Any], tuple[ModelOut, Any]]
    init_carry: Callable[[], Any] = field(default=lambda: ())
    obs_dim: int = 0
    num_actions: int = 3
    name: str = "model"
    # Optional native batched forward (params, (B, obs_dim), carry_batch) ->
    # (ModelOut with leading B, carry_batch). Models whose hot path benefits
    # from an explicit batch dimension (the transformer folds the agent batch
    # into the flash kernel's batch*heads grid) provide this; everyone else
    # gets vmap of `apply` via `apply_batched`.
    apply_batch: Callable[[Any, jax.Array, Any], tuple[ModelOut, Any]] | None = None
    # Optional whole-unroll training forward (params, (T, B, obs_dim) obs,
    # unroll-start carry_batch, or its ``replay_carry`` where the model
    # declares one) -> (logits (T, B, A), values (T, B), aux).
    # Models that can replay a trajectory more cheaply than T per-step
    # forwards provide this (the episode-mode transformer runs ONE banded
    # pass over the unroll's tick sequence); rollout.replay_forward
    # dispatches to it.
    apply_unroll: Callable[[Any, jax.Array, Any],
                           tuple[jax.Array, jax.Array, jax.Array]] | None = None
    # Optional PRECOMPUTED-ROLLOUT pair. Models whose heavy trunk depends
    # only on action-independent inputs (the episode transformer attends
    # over price ticks alone; the agent's wallet enters at the head) provide
    # these, and rollout.collect_rollout then computes the whole unroll's
    # trunk in ONE parallel pass instead of T sequential cache-attention
    # steps — the measured 70% of the flagship chunk
    # (benchmarks/profile_flagship.py).
    #
    # apply_rollout_trunk(params, obs (B, obs_dim), future_ticks (B, T),
    #                     carry) -> (hn_base (B, T+1, d), carry after T) —
    #   row i is the trunk output for env step t0+i; row T serves the
    #   bootstrap value.
    # apply_rollout_head(params, hn_base_row (B, d), obs (B, obs_dim))
    #   -> ModelOut (batched) — the tiny state-dependent head, applied
    #   per-step inside the sequential env loop.
    apply_rollout_trunk: Callable[[Any, jax.Array, jax.Array, Any],
                                  tuple[jax.Array, Any]] | None = None
    apply_rollout_head: Callable[[Any, jax.Array, jax.Array],
                                 ModelOut] | None = None
    # Optional SHARED-TRUNK training replay: same signature and output as
    # apply_unroll (``carry`` is the REPLAY carry, below: the health vector
    # ``ok`` rides in it), but exploiting the same agent-invariance as the
    # precomputed-rollout pair — every healthy agent's stored price series
    # is identical (lockstep batch over one shared series; quarantined rows
    # are zero-sanitized and loss-masked), so the banded trunk runs ONCE
    # for a representative row and only the portfolio head runs per agent.
    # Removes the factor-B trunk redundancy of apply_unroll from the PPO/
    # PG/A2C update phase (B=128 at the flagship shape — the update was the
    # measured 70% of the post-round-3 chunk). Gradients are exact, not
    # approximate: B identical trunk paths, each pulled back by its agent's
    # head cotangent, equal one shared path pulled back by their sum.
    # Provided only by models whose learners guarantee the lockstep
    # invariant (see agents/rollout.py agent-invariance notes).
    apply_unroll_shared: Callable[[Any, jax.Array, Any],
                                  tuple[jax.Array, jax.Array, jax.Array]] | None = None
    # Optional REPLAY CARRY: replay_carry(unroll-start carry_batch) -> tree,
    # every leaf batched over agents: what the model's training replay
    # (apply_unroll / apply_unroll_shared) reads of the carry the unroll
    # started from, with any per-row summary of the rest folded in. The
    # rollout takes it ONCE per chunk (rollout.replay_carry) and it is the
    # ``carry`` the replay forwards receive, so a learner that holds the
    # unroll start across its update phase (PPO gathers it per minibatch)
    # moves these leaves and nothing else. None = the replay reads the
    # whole carry (LSTM: differentiated through time from every leaf).
    # The episode transformer's replay reads ``hist`` and ``t`` only, plus
    # ``ok`` — ``rows_finite`` of the WHOLE carry, K/V included, computed
    # here — for the shared replay's representative election; its
    # per-agent K/V cache (megabytes a row) stays out of the update phase.
    replay_carry: Callable[[Any], Any] | None = None
    # Optional LINEARITY-FACTORED rollout head. When the head is affine in
    # (trunk output, portfolio features) — logits = dense(policy,
    # hn + dense(port, feats)) with no nonlinearity between — it splits
    # exactly into a trunk term, precomputable for the WHOLE unroll in one
    # batched matmul outside the env scan, plus a tiny (3 -> A) portfolio
    # term evaluated per step. The sequential loop's per-iteration matmuls
    # drop from three d-sized GEMMs to one 3-wide contraction — the round-4
    # measured bound at d=256 was exactly those per-iteration head matmuls.
    #
    # rollout_head_factored(params, hn_base (T+1, d)) ->
    #   (base_logits (T+1, A) f32, base_values (T+1,) f32,
    #    pf_fn(obs (B, obs_dim)) -> (dlogits (B, A) f32, dvalues (B,) f32))
    # with ModelOut-equivalent totals base + pf (pinned by
    # tests/test_models.py::test_factored_rollout_head_matches_exact).
    rollout_head_factored: Callable | None = None
    # Optional SERVING pair (serve/engine.py — the continuous-batching
    # inference tier). Models with a prefill/incremental split provide
    # both; stateless or simple-carry models need neither (the engine
    # runs ``apply_batched`` over slot-gathered carries, which imposes no
    # cross-row constraint).
    #
    # apply_prefill(params, obs (B, obs_dim)) -> (ModelOut batched,
    #   carry_batch) — the episode-start forward for a COLD batch (every
    #   row a fresh session). Rows are independent: unlike
    #   ``apply_batch``'s t[0] dispatch, no lockstep assumption.
    # apply_serve_batch(params, obs (B, obs_dim), carry_batch) ->
    #   (ModelOut batched, carry_batch) — one incremental step for a WARM
    #   batch whose rows sit at HETEROGENEOUS episode steps (per-row ring
    #   slots). This is exactly the invariant a serving batch violates in
    #   ``apply_batch``: training batches step in lockstep, user sessions
    #   don't.
    apply_prefill: Callable[[Any, jax.Array],
                            tuple[ModelOut, Any]] | None = None
    apply_serve_batch: Callable[[Any, jax.Array, Any],
                                tuple[ModelOut, Any]] | None = None
    # Optional host-side reduction of a warm tick's ``ModelOut.stats``:
    # serve_stats(stats of the tick's REAL rows, a numpy array) ->
    # ({counter: increment}, {histogram: sample}). The engine adds them to
    # its registry on the consumer thread and knows none of their names.
    serve_stats: Callable[[Any], tuple[dict, dict]] | None = None
    # False for a SERVE-ONLY trunk, one with no replay or rollout pass: the
    # training loop refuses it by this when it builds its agent
    # (runtime/orchestrator.py), whatever the model is called.
    trainable: bool = True
    # Optional precision hook: cast_carry(carry, compute_dtype) -> carry,
    # casting exactly the carry leaves the model's forward produces in the
    # compute dtype (K/V caches, recurrent cells). The precision policy
    # (precision.py cast_carry) calls this when the model provides it;
    # None means "every floating leaf follows the compute dtype". The
    # episode transformer needs the hook: its ``hist`` carry holds raw
    # PRICES that its forwards always rebuild in f32 — blanket-casting it
    # would both lose tick precision and destabilize the scan carry dtype.
    cast_carry: Callable[[Any, Any], Any] | None = None


def apply_batched(model: Model, params: Any, obs_batch: jax.Array,
                  carry_batch: Any) -> tuple[ModelOut, Any]:
    """Batched forward over agents — the one call site shape every learner
    uses (SURVEY.md §7.2: workers become a batch dimension, not actors)."""
    if model.apply_batch is not None:
        return model.apply_batch(params, obs_batch, carry_batch)
    return jax.vmap(
        lambda o, c: model.apply(params, o, c))(obs_batch, carry_batch)


_EPS = 1e-6


def compute_dtype(params: Any):
    """The dtype a forward pass should COMPUTE in: the floating dtype of
    the params it was handed. Models derive their activation-cast dtype
    from this instead of a build-time closure constant, so the SAME model
    object serves both halves of the precision policy (precision.py): the
    fp32 masters (eval, fp32 mode) and the bf16 compute copy the policy
    casts at each update boundary. Trace-time only (dtypes are static
    under jit). Falls back to f32 for paramless/empty subtrees."""
    for leaf in jax.tree.leaves(params):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating):
            return leaf.dtype
    return jnp.float32


def rows_finite(tree: Any, batch: int) -> jax.Array:
    """(batch,) bool: True where every batched leaf row of ``tree`` is
    finite. THE row-finiteness predicate behind the fault-quarantine
    story — shared by the heal/election predicate
    (agents/base.election_health) and the shared-trunk replay's
    representative election (the ``ok`` leaf of the episode transformer's
    ``Model.replay_carry``, read by its apply_unroll_shared) so the two can
    never silently diverge. Leaves whose leading dim is not
    ``batch`` (unbatched scalars/tables) are ignored; integer leaves pass
    trivially (their zeros sum to zero).

    Two stages, the test between them: ``x * 0`` is NaN exactly where
    ``x`` is not finite, and summing it over the second-to-last axis alone
    (the K/V ring's window) adds whole tiles and keeps every other axis.
    The one-stage ``all(isfinite(x))`` over a whole row took the chip 6 us
    a row however short the row, a quarter of a d=256 chunk; this runs at
    the HBM rate (PERF.md §6, PR 35)."""
    ok = jnp.ones((batch,), bool)
    for leaf in jax.tree.leaves(tree):
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == batch:
            nans = leaf * 0
            if nans.ndim > 2:
                nans = jnp.sum(nans, axis=-2)
            ok &= jnp.all(jnp.isfinite(nans), axis=tuple(range(1, nans.ndim)))
    return ok


def tick_window_features(obs: jax.Array, window: int) -> jax.Array:
    """(B, obs_dim) observations -> (B, window, 3) scale-invariant per-tick
    features: price relative to the window's last price, log-return, and a
    zero channel (the window-mode transformer marks its portfolio token
    there). Shared by every tick-sequence policy (transformer window mode,
    TCN) so the tokenization cannot silently diverge between families."""
    prices = obs[:, :window].astype(jnp.float32)
    anchor = jnp.maximum(prices[:, -1:], _EPS)
    rel = prices / anchor - 1.0
    logp = jnp.log(jnp.maximum(prices, _EPS))
    log_ret = jnp.concatenate(
        [jnp.zeros_like(logp[:, :1]), logp[:, 1:] - logp[:, :-1]], axis=1)
    return jnp.stack([rel, log_ret, jnp.zeros_like(rel)], axis=-1)


def portfolio_features(budget: jax.Array, shares: jax.Array,
                       anchor: jax.Array) -> jax.Array:
    """(…,) scalars -> (…, 3) normalized portfolio features; ``anchor`` is
    the window's newest price. One definition for every policy head (window
    transformer's portfolio token, episode mode's head injection, TCN)."""
    anchor = jnp.maximum(anchor, _EPS)
    return jnp.stack([budget / (anchor * 100.0), shares / 100.0,
                      jnp.ones_like(budget)], axis=-1)


def dense_init(key: jax.Array, in_dim: int, out_dim: int, *,
               scale: float | None = None, dtype=jnp.float32) -> dict[str, jax.Array]:
    """Dense layer params. Default init is He-normal (std = sqrt(2/in)).

    ``scale`` overrides the stddev — the reference uses plain
    ``RandomNormalInitializer()`` (stddev 1.0) for both layers
    (QDecisionPolicyActor.scala:41,45); parity mode passes ``scale=1.0``.
    """
    std = jnp.sqrt(2.0 / in_dim) if scale is None else scale
    w = jax.random.normal(key, (in_dim, out_dim), dtype) * jnp.asarray(std, dtype)
    return {"w": w, "b": jnp.zeros((out_dim,), dtype)}


def dense(params: dict[str, jax.Array], x: jax.Array) -> jax.Array:
    # preferred_element_type keeps MXU accumulation in f32 even when
    # params/activations are bf16 (pallas_guide.md: "Missing preferred_element_type").
    return (
        jnp.dot(x, params["w"], preferred_element_type=jnp.float32).astype(x.dtype)
        + params["b"]
    )
