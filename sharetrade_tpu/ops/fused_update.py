"""Fused optimizer update: grad-upcast + moment update + param update (+
optional compute-dtype recast) in ONE pass over each parameter leaf.

The optax pair every learner used to call —

    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)

— materializes the intermediate ``updates`` tree (and, under bf16_mixed,
an explicitly upcast grads tree before it) between two library calls. On
TPU that is O(params) of avoidable HBM round-trips per update; at the
update cadences this framework runs (every env step for qlearn/DQN, every
minibatch for PPO) the optimizer's byte traffic sits on the hot path. This
module writes the whole update as one chain of jnp ops per leaf, on every
backend, inside the caller's jit: XLA fuses each chain into one elementwise
loop over the leaf in its stored layout, which reads the raw (possibly
bf16) gradient, the f32 master param and the f32 moments once and writes
the new master + moments once — optionally also the bf16 compute recast of
the updated param (``emit_compute``).

Why no Pallas kernel: a kernel needs the leaf in a view of its own
(``(rows, 128)`` lanes), and a 2-D leaf's device tiling is not its flat
order, so the compiler relays out every operand and result around the
call. On the v5e those copies cost 2.26 ms an update of the d=1024 tree
for 0.39 ms of kernel (PERF.md §5–6); the loop fusion needs no view.
Being elementwise, the chain is partitioned by the leaves' own shardings
on a mesh, with no collective.

``emit_compute``: the learners do not consume that third output yet;
their next boundary re-casts the masters through
``PrecisionPolicy.cast_compute`` (one O(params) read), because threading
the copy would put a second weight tree in the scan carry / TrainState
shape. It is the seam for eliminating that read, pinned by tests either
way.

Numerics contract (pinned by tests/test_precision.py): the op order
REPLICATES optax's exactly — ``scale_by_rss`` / ``scale_by_adam`` /
``sgd`` followed by ``scale_by_learning_rate`` and ``apply_updates`` — so
fp32 results are BIT-IDENTICAL to the optax pair, and bf16_mixed differs
only by the gradient's bf16 quantization (grads upcast before any
arithmetic; moments and params stay f32). The optimizer STATE is the
optax state pytree itself (``ScaleByRssState`` / ``ScaleByAdamState``
namedtuples from ``optimizer.init``), so checkpoints and the optax path
interchange freely.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from sharetrade_tpu.config import LearnerConfig

#: optax defaults replicated here (build_optimizer constructs with these).
ADAGRAD_EPS = 1e-7
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


# ---------------------------------------------------------------------------
# per-leaf math
# ---------------------------------------------------------------------------

def _adagrad_leaf(p, g, s, *, lr, compute_dtype):
    """optax ``adagrad``: scale_by_rss + scale_by_learning_rate +
    apply_updates, in optax's exact op order."""
    g = g.astype(jnp.float32)  # precision-cast-ok: THE fused grad upcast
    s_new = g * g + s
    inv = jnp.where(s_new > 0, jax.lax.rsqrt(s_new + ADAGRAD_EPS), 0.0)
    p_new = p + (inv * g) * (-lr)
    return p_new, (s_new,), p_new.astype(compute_dtype)


def _adam_leaf(p, g, mu, nu, *, lr, bias1, bias2, compute_dtype):
    """optax ``adam``: scale_by_adam (bias corrections precomputed from the
    incremented count by the caller — they are scalars shared across
    leaves) + scale_by_learning_rate + apply_updates."""
    g = g.astype(jnp.float32)  # precision-cast-ok: THE fused grad upcast
    mu_new = (1.0 - ADAM_B1) * g + ADAM_B1 * mu
    nu_new = (1.0 - ADAM_B2) * (g * g) + ADAM_B2 * nu
    mu_hat = mu_new / bias1
    nu_hat = nu_new / bias2
    u = mu_hat / (jnp.sqrt(nu_hat + 0.0) + ADAM_EPS)
    p_new = p + u * (-lr)
    return p_new, (mu_new, nu_new), p_new.astype(compute_dtype)


def _sgd_leaf(p, g, *, lr, compute_dtype):
    g = g.astype(jnp.float32)  # precision-cast-ok: THE fused grad upcast
    p_new = p + g * (-lr)
    return p_new, (), p_new.astype(compute_dtype)


# ---------------------------------------------------------------------------
# pytree-level fused apply
# ---------------------------------------------------------------------------

def fused_apply(optimizer_name: str, lr: float, grads: Any, opt_state: Any,
                params: Any, *, compute_dtype=jnp.float32,
                emit_compute: bool = False):
    """One fused pass over the parameter pytree.

    Returns ``(new_params, new_opt_state[, new_compute_params])`` — the
    third element only when ``emit_compute`` (the bf16 weight copy for the
    next forward, written by the same pass). ``opt_state`` is the optax
    state from ``build_optimizer(...).init(params)`` and the returned state
    has the identical structure, so fused and optax paths (and their
    checkpoints) interchange freely. Raw (possibly bf16) grads go in; the
    upcast happens inside the pass."""
    hyper: dict[str, Any] = {"lr": float(lr)}
    if optimizer_name == "adagrad":
        leaf_fn, n_state = _adagrad_leaf, 1
        state_of = lambda st: (st[0].sum_of_squares,)
        rebuild = lambda st, leaves: (
            st[0]._replace(sum_of_squares=leaves[0]), *st[1:])
    elif optimizer_name == "adam":
        leaf_fn, n_state = _adam_leaf, 2
        # Bias corrections are per-STEP scalars (safe_int32_increment +
        # 1 - b^t, optax's exact formulation) — computed once out here,
        # not per leaf, exactly as scale_by_adam shares them.
        count = opt_state[0].count
        count_inc = jnp.where(
            count < jnp.iinfo(jnp.int32).max, count + 1, count)
        hyper["bias1"] = 1.0 - ADAM_B1 ** count_inc.astype(jnp.float32)
        hyper["bias2"] = 1.0 - ADAM_B2 ** count_inc.astype(jnp.float32)
        state_of = lambda st: (st[0].mu, st[0].nu)
        rebuild = lambda st, leaves: (
            st[0]._replace(count=count_inc, mu=leaves[0], nu=leaves[1]),
            *st[1:])
    elif optimizer_name == "sgd":
        leaf_fn, n_state = _sgd_leaf, 0
        state_of = lambda st: ()
        rebuild = lambda st, leaves: st
    else:
        raise ValueError(
            f"fused update does not support optimizer {optimizer_name!r}; "
            "set precision.fused_update='off' for custom optimizers")

    state_trees = state_of(opt_state)
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_state = [treedef.flatten_up_to(t) for t in state_trees]

    new_p, new_state, new_pc = [], [[] for _ in range(n_state)], []
    for i, (p, g) in enumerate(zip(flat_p, flat_g)):
        out = leaf_fn(p, g, *(t[i] for t in flat_state),
                      compute_dtype=compute_dtype, **hyper)
        new_p.append(out[0])
        for j, s in enumerate(out[1]):
            new_state[j].append(s)
        new_pc.append(out[2])

    params_new = jax.tree_util.tree_unflatten(treedef, new_p)
    state_new = rebuild(
        opt_state,
        [jax.tree_util.tree_unflatten(treedef, s) for s in new_state])
    if emit_compute:
        return params_new, state_new, jax.tree_util.tree_unflatten(
            treedef, new_pc)
    return params_new, state_new


def fused_supported(cfg: LearnerConfig) -> bool:
    """Whether the learner's optimizer has a fused implementation (the
    update-path builder falls back to the optax pair otherwise)."""
    return cfg.optimizer in ("adagrad", "adam", "sgd")
