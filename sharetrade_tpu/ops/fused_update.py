"""Fused optimizer update: grad-upcast + moment update + param update (+
optional compute-dtype recast) in ONE pass over each parameter leaf.

The optax pair every learner used to call —

    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)

— materializes the intermediate ``updates`` tree (and, under bf16_mixed,
an explicitly upcast grads tree before it) between two library calls. On
TPU that is O(params) of avoidable HBM round-trips per update; at the
update cadences this framework runs (every env step for qlearn/DQN, every
minibatch for PPO) the optimizer's byte traffic sits on the hot path the
roofline telemetry measured memory-bound. This module fuses the whole
update into one pass per leaf:

- **TPU**: a Pallas kernel per leaf (`pallas_guide.md` tiling: leaves
  flatten to (rows, 128) lanes, gridded in VMEM-sized row blocks) reading
  the raw (possibly bf16) gradient, the f32 master param and the f32
  moments, and writing the new master + moments — optionally also the
  bf16 compute recast of the updated param (``emit_compute``). The
  learners do not consume that third output yet: their next boundary
  re-casts the masters through ``PrecisionPolicy.cast_compute`` (one
  O(params) read, dwarfed by activation traffic at every tier this repo
  runs), because threading the copy would put a second weight tree in
  the scan carry / TrainState shape. ``emit_compute`` is the seam for
  the TPU follow-up where that read is worth eliminating; it is
  compiled for a described v5e by tests/test_chip_compile.py and pinned
  by tests either way.
- **elsewhere** (the CPU test/dev tier): the same arithmetic as plain jnp
  ops inside the caller's jit — XLA fuses the chain into one elementwise
  pass per leaf, so the fallback is semantically identical and leaves no
  Pallas dependency on non-TPU backends.

Numerics contract (pinned by tests/test_precision.py): the op order
REPLICATES optax's exactly — ``scale_by_rss`` / ``scale_by_adam`` /
``sgd`` followed by ``scale_by_learning_rate`` and ``apply_updates`` — so
fp32 results are BIT-IDENTICAL to the optax pair, and bf16_mixed differs
only by the gradient's bf16 quantization (grads upcast before any
arithmetic; moments and params stay f32). The optimizer STATE is the
optax state pytree itself (``ScaleByRssState`` / ``ScaleByAdamState``
namedtuples from ``optimizer.init``), so checkpoints and the fallback
path interchange freely.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from sharetrade_tpu.config import LearnerConfig

#: optax defaults replicated here (build_optimizer constructs with these).
ADAGRAD_EPS = 1e-7
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8

#: Kernel identity in a device trace (``pallas_call(metadata=...)``, as
#: ops/attention.py's).
KERNEL_ID = "fused_update"

_LANE = 128
_BLOCK_ROWS = 256          # (256, 128) f32 blocks: 128 KiB per operand


# ---------------------------------------------------------------------------
# per-leaf math (shared verbatim by the XLA fallback and the Pallas kernels:
# ONE definition so the two paths cannot drift)
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
# Pallas kernels (TPU): one fused VMEM pass per row block
# ---------------------------------------------------------------------------

def _kernel(leaf_fn, n_state, emit_compute, scalar_names, static_hyper,
            *refs):
    """One (row-block) program: read p/g/state blocks, run the SHARED leaf
    math, write the new p/state (+ optional compute recast). Traced
    per-step scalars (adam's bias corrections) arrive through an SMEM
    operand — a traced value must be a kernel input, never a closure."""
    if scalar_names:
        scal_ref, *refs = refs
        hyper = {name: scal_ref[i] for i, name in enumerate(scalar_names)}
    else:
        hyper = {}
    p_ref, g_ref = refs[0], refs[1]
    state_in = refs[2:2 + n_state]
    outs = refs[2 + n_state:]
    p_new, state_new, p_c = leaf_fn(
        p_ref[:], g_ref[:], *(r[:] for r in state_in),
        **static_hyper, **hyper)
    outs[0][:] = p_new
    for ref, val in zip(outs[1:1 + n_state], state_new):
        ref[:] = val
    if emit_compute:
        outs[1 + n_state][:] = p_c


def _pallas_leaf(leaf_fn, n_state, p, g, state_leaves, *, compute_dtype,
                 emit_compute, static_hyper, scalar_hyper,
                 interpret=False):
    """Run one leaf's fused update as a Pallas program over (rows, 128)
    blocks. Leaves flatten to lanes and pad to full blocks; padded tail
    elements compute garbage that is sliced off (no cross-element data
    flow in any supported optimizer, so padding never contaminates).
    ``interpret`` runs the kernel in Pallas interpret mode — the CPU test
    path for kernel logic (tiling legality needs the TPU's compiler:
    tests/test_chip_compile.py, then ``chip_smoke.py`` on the chip)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = p.size
    rows = -(-n // _LANE)
    pad_rows = -(-rows // _BLOCK_ROWS) * _BLOCK_ROWS
    grid = pad_rows // _BLOCK_ROWS

    def prep(x):
        flat = x.reshape(-1)
        flat = jnp.pad(flat, (0, pad_rows * _LANE - n))
        return flat.reshape(pad_rows, _LANE)

    scalar_names = tuple(sorted(scalar_hyper))
    operands = []
    in_specs = []
    if scalar_names:
        operands.append(jnp.stack(
            [scalar_hyper[k].astype(jnp.float32) for k in scalar_names]))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands += [prep(p), prep(g)] + [prep(s) for s in state_leaves]
    spec = pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0))
    in_specs += [spec] * (2 + n_state)
    out_shapes = [jax.ShapeDtypeStruct((pad_rows, _LANE), jnp.float32)
                  for _ in range(1 + n_state)]
    if emit_compute:
        out_shapes.append(
            jax.ShapeDtypeStruct((pad_rows, _LANE), compute_dtype))
    kernel = functools.partial(
        _kernel, leaf_fn, n_state, emit_compute, scalar_names,
        dict(static_hyper, compute_dtype=compute_dtype))
    outs = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=tuple([spec] * len(out_shapes)),
        out_shape=tuple(out_shapes),
        interpret=interpret,
        metadata={"kernel": KERNEL_ID},
    )(*operands)

    def unprep(x):
        return x.reshape(-1)[:n].reshape(p.shape)

    p_new = unprep(outs[0])
    state_new = tuple(unprep(o) for o in outs[1:1 + n_state])
    p_c = unprep(outs[1 + n_state]) if emit_compute else None
    return p_new, state_new, p_c


def _pallas_leaf_on(mesh, spec, leaf_fn, n_state, p, g, state_leaves, *,
                    scalar_hyper, emit_compute, **kw):
    """:func:`_pallas_leaf`, per device of ``mesh`` under a shard_map when
    a ``spec`` is given: every array operand and result carries the leaf's
    own ``spec``, the traced per-step scalars ride along replicated."""
    if spec is None:
        return _pallas_leaf(leaf_fn, n_state, p, g, state_leaves,
                            scalar_hyper=scalar_hyper,
                            emit_compute=emit_compute, **kw)
    from jax.sharding import PartitionSpec as P
    names = tuple(sorted(scalar_hyper))

    def per_device(p, g, *rest):
        p_new, state_new, p_c = _pallas_leaf(
            leaf_fn, n_state, p, g, rest[:n_state],
            scalar_hyper=dict(zip(names, rest[n_state:])),
            emit_compute=emit_compute, **kw)
        return (p_new, *state_new, *((p_c,) if emit_compute else ()))

    outs = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(spec,) * (2 + n_state) + (P(),) * len(names),
        out_specs=(spec,) * (1 + n_state + int(emit_compute)),
        check_vma=False,
    )(p, g, *state_leaves, *(scalar_hyper[k] for k in names))
    return (outs[0], tuple(outs[1:1 + n_state]),
            outs[1 + n_state] if emit_compute else None)


def _use_pallas_default() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# pytree-level fused apply
# ---------------------------------------------------------------------------

def fused_apply(optimizer_name: str, lr: float, grads: Any, opt_state: Any,
                params: Any, *, compute_dtype=jnp.float32,
                emit_compute: bool = False,
                use_pallas: bool | None = None,
                interpret: bool = False,
                mesh=None, param_rules=None):
    """One fused pass over the parameter pytree.

    Returns ``(new_params, new_opt_state[, new_compute_params])`` — the
    third element only when ``emit_compute`` (the bf16 weight copy for the
    next forward, written by the same kernel pass). ``opt_state`` is the
    optax state from ``build_optimizer(...).init(params)`` and the
    returned state has the identical structure, so fused and optax paths
    (and their checkpoints) interchange freely. Raw (possibly bf16) grads
    go in; the upcast happens inside the pass.

    ``mesh`` (+ ``param_rules``): the multi-device mesh of the program this
    call is traced into. Mosaic kernels cannot be partitioned
    automatically, so each leaf's kernel then runs under a ``shard_map``
    with that leaf's own spec (``parallel/sharding.py param_shardings`` —
    the spec the TrainState is placed by): the update is elementwise, so
    every device updates exactly the shard it holds, with no collective."""
    if use_pallas is None:
        use_pallas = _use_pallas_default()
    leaf_specs = None
    if mesh is not None and mesh.size > 1 and (use_pallas or interpret):
        from sharetrade_tpu.parallel.sharding import param_shardings
        leaf_specs = [sh.spec for sh in jax.tree.leaves(
            param_shardings(params, mesh, param_rules))]
    lr = float(lr)

    static_hyper = {"lr": lr}
    scalar_hyper: dict[str, Any] = {}
    if optimizer_name == "adagrad":
        leaf_fn, n_state = _adagrad_leaf, 1
        state_of = lambda st: (st[0].sum_of_squares,)
        rebuild = lambda st, leaves: (
            st[0]._replace(sum_of_squares=leaves[0]), *st[1:])
    elif optimizer_name == "adam":
        leaf_fn, n_state = _adam_leaf, 2
        # Bias corrections are per-STEP scalars (safe_int32_increment +
        # 1 - b^t, optax's exact formulation) — computed once out here,
        # not per leaf, exactly as scale_by_adam shares them. They are
        # TRACED values, so the Pallas path feeds them through SMEM.
        count = opt_state[0].count
        count_inc = jnp.where(
            count < jnp.iinfo(jnp.int32).max, count + 1, count)
        scalar_hyper = {
            "bias1": 1.0 - ADAM_B1 ** count_inc.astype(jnp.float32),
            "bias2": 1.0 - ADAM_B2 ** count_inc.astype(jnp.float32),
        }
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
        leaves = tuple(t[i] for t in flat_state)
        # Pallas needs tiled 2-D blocks; scalars and tiny leaves stay on
        # the (identical-math) fused XLA path.
        if (use_pallas or interpret) and p.size >= _LANE:
            out = _pallas_leaf_on(
                mesh, leaf_specs[i] if leaf_specs else None,
                leaf_fn, n_state, p, g, leaves,
                compute_dtype=compute_dtype, emit_compute=emit_compute,
                static_hyper=static_hyper, scalar_hyper=scalar_hyper,
                interpret=interpret)
        else:
            out = leaf_fn(p, g, *leaves, compute_dtype=compute_dtype,
                          **static_hyper, **scalar_hyper)
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
