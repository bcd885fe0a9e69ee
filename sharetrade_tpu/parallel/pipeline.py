"""Pipeline parallelism: GPipe-style microbatch schedule over the ``pp`` axis.

No analogue exists in the reference (its model is a 2-layer MLP in one
process; SURVEY.md §2.2 lists PP as absent) — this supplies the mechanism so
deep stacks scale across chips: consecutive layer groups ("stages") live on
consecutive devices of the ``pp`` mesh axis, activations flow stage→stage via
``ppermute`` (one ICI hop per schedule tick), and M microbatches keep every
stage busy after an S-tick fill. Per-device parameter memory drops by the
pipeline factor; the bubble fraction is (S-1)/(M+S-1).

The schedule is data-oblivious (a static Python loop of M+S-1 ticks inside
one jit), so XLA sees straight-line code with S-fold smaller matmuls — no
dynamic control flow (XLA-semantics rule: no data-dependent Python control
flow under jit).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from sharetrade_tpu.config import ConfigError


def pipeline_apply(stage_fn, stage_params, microbatches, mesh: Mesh,
                   *, axis: str = "pp", mb_spec: P = P(),
                   side_template=None, side_specs=None,
                   carry_template=None):
    """Run ``microbatches`` through ``num_stages`` pipelined stages.

    - ``stage_fn(params, x) -> x``: one stage's forward (same signature for
      every stage; heterogeneous stacks encode choice inside params). With
      ``side_template``, ``stage_fn(params, x) -> (x, side)`` — ``side`` is
      a per-(stage, microbatch) pytree matching the template's
      shapes/dtypes (e.g. a block's K/V cache tail, its MoE balance loss).
      With ``carry_template`` (requires ``side_template``),
      ``stage_fn(params, x, carry) -> (x, side, carry)`` — ``carry`` is a
      STAGE-LOCAL streaming state threaded tick-to-tick within each stage
      and never communicated: microbatch m's processing at stage i sees the
      carry microbatch m-1 left there (GPipe microbatches are normally
      independent; the carry supports SEQUENTIAL microbatches — sequence
      chunks whose banded-attention halo flows chunk to chunk,
      models/transformer_episode.py). Initialized to the template's zeros
      per call; updates are masked off on fill/drain ticks so garbage
      states never pollute it.
    - ``stage_params``: pytree whose leaves have leading dim ``num_stages``
      (stage i's slice lives on pp-device i).
    - ``microbatches``: array of shape (M, ...) — M microbatches.
    - ``mb_spec``: the microbatches' PartitionSpec over OTHER mesh axes
      (e.g. ``P(None, "dp")`` when the per-microbatch batch dim is
      dp-sharded in a dp x pp mesh); must not mention ``axis`` itself —
      every pipeline stage needs the ticks it owns.

    Returns the (M, ...) outputs with the same ``mb_spec`` sharding; with
    ``side_template`` returns ``(out, sides)`` where each side leaf gains
    leading dims (num_stages, M) (each stage computes its row; a one-hot
    psum assembles the full stack) — how per-layer byproducts (K/V caches,
    aux losses) escape a schedule whose stage activations never leave
    their device. When ``mb_spec`` shards a batch axis, any side leaf
    carrying per-row data must declare that axis in ``side_specs`` (a
    side-shaped pytree of PartitionSpecs over the ASSEMBLED (S, M, ...)
    layout; default all-replicated) — a replicated spec on a sharded-batch
    side would silently return one shard's rows for everybody. Per-leaf
    template shapes are the LOCAL shard shapes in that case, and any
    scalar side (an aux loss) must be made batch-axis-uniform inside
    ``stage_fn`` (e.g. ``lax.pmean``) to honor its replicated spec.
    """
    num_stages = mesh.shape[axis]
    num_micro = microbatches.shape[0]
    if axis in jax.tree.leaves(tuple(mb_spec)):
        raise ConfigError(f"mb_spec {mb_spec} must not shard over {axis!r}")
    if carry_template is not None and side_template is None:
        raise ConfigError("carry_template requires side_template "
                         "(stage_fn returns (x, side, carry))")

    def local_fn(params_local, mb_local):
        # params_local: this stage's params (leading dim stripped by the
        # sharding: (1, ...) -> squeeze); mb_local: the (M, ...) batch in
        # this device's LOCAL view (other axes may shard trailing dims).
        params_here = jax.tree.map(lambda x: x[0], params_local)
        stage = jax.lax.axis_index(axis)
        fwd = [(i, (i + 1) % num_stages) for i in range(num_stages)]

        state = jnp.zeros(mb_local.shape[1:], mb_local.dtype)
        out = jnp.zeros(mb_local.shape, mb_local.dtype)
        sides = jax.tree.map(
            lambda t: jnp.zeros((num_micro,) + t.shape, t.dtype),
            side_template)
        carry = jax.tree.map(lambda t: jnp.zeros(t.shape, t.dtype),
                             carry_template)

        for t in range(num_micro + num_stages - 1):
            # Stage 0 ingests microbatch t on ticks 0..M-1.
            feed_idx = min(t, num_micro - 1)
            state = jnp.where(stage == 0,
                              jnp.where(t < num_micro,
                                        mb_local[feed_idx], state),
                              state)
            if side_template is None:
                state = stage_fn(params_here, state)
            else:
                # This stage processes microbatch (t - stage) at tick t;
                # record its side there (ticks outside [stage, stage+M)
                # carry fill/garbage state and are masked off).
                mb_idx = jnp.clip(t - stage, 0, num_micro - 1)
                live = (t >= stage) & (t - stage < num_micro)
                if carry_template is None:
                    state, side = stage_fn(params_here, state)
                else:
                    state, side, new_carry = stage_fn(
                        params_here, state, carry)
                    # Fill/drain ticks run on garbage states; their carry
                    # must not leak into the first real microbatch.
                    carry = jax.tree.map(
                        lambda c, nc: jnp.where(live, nc, c),
                        carry, new_carry)
                sides = jax.tree.map(
                    lambda acc, s: acc.at[mb_idx].set(
                        jnp.where(live, s, acc[mb_idx])), sides, side)
            # Last stage emits microbatch t-(S-1) on ticks S-1..M+S-2.
            emit = t - (num_stages - 1)
            if emit >= 0:
                out = jnp.where(
                    (stage == num_stages - 1),
                    out.at[emit].set(state), out)
            if t + 1 < num_micro + num_stages - 1:
                state = jax.lax.ppermute(state, axis, fwd)

        # Only the last stage holds real outputs; replicate them ring-wide.
        out = jnp.where(stage == num_stages - 1, out, jnp.zeros_like(out))
        out = jax.lax.psum(out, axis)
        if side_template is None:
            return out
        # Assemble the (S, M, ...) side stack: each stage contributes its
        # own row, zero elsewhere, and a psum over the ring fills the rest.
        onehot = (jnp.arange(num_stages) == stage)
        sides = jax.tree.map(
            lambda s: jax.lax.psum(
                jnp.where(onehot.reshape((num_stages,) + (1,) * s.ndim),
                          s[None], 0), axis), sides)
        return out, sides

    stage_spec = jax.tree.map(lambda _: P(axis), stage_params)
    if side_template is not None and side_specs is None:
        side_specs = jax.tree.map(lambda _: P(), side_template)
    out_specs = mb_spec if side_template is None else (mb_spec, side_specs)
    # check_vma=False: stage_fn may invoke a pallas_call (the flash kernel),
    # whose out_shapes don't carry varying-mesh-axes metadata; the schedule
    # is stage-local by construction so the check adds nothing here.
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(stage_spec, mb_spec), out_specs=out_specs,
        check_vma=False,
    )(stage_params, microbatches)


def stack_stage_params(per_stage_params: list) -> object:
    """Stack a list of per-stage param pytrees into the leading-dim layout
    ``pipeline_apply`` expects (leaf shapes (S, ...))."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage_params)
