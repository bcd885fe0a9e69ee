"""Sharding rules: how TrainState tensors spread over the mesh.

Replaces the reference's implicit placement (everything in one JVM heap, one
TF session owning the only parameter copy) with explicit PartitionSpecs:

- batch-leading state (env cursors, carries, replay rows) shards over ``dp``;
- parameters/optimizer state replicate by default, or shard over ``tp`` via
  path rules (the mechanism SURVEY.md §2.2 asks for even though the reference
  model is tiny);
- scalars (rng, counters) replicate.

With these in/out shardings on a jitted step, XLA turns the loss mean over
the dp-sharded batch into an ICI all-reduce — the parameter-server mailbox
(QDecisionPolicyActor.scala:54-77) become a collective (SURVEY.md §7.2).

Consistency contract (the anti-resharding tentpole): every path that places,
restores, heals, or steps a TrainState on a mesh resolves its shardings
through :func:`canonical_sharding`, and the compiled step re-pins its output
carry/env_state with ``jax.lax.with_sharding_constraint`` at the chunk seam.
Without the pin, program regions introduced by the sp/pp/ep shard_maps leave
GSPMD free to pick a transposed-mesh layout for the carry mid-program, and
the partitioner then falls back to replicate-then-repartition ("Involuntary
full rematerialization" in the SPMD log) on every chunk — the failure mode
``tools/shard_audit.py`` compiles the whole config matrix to keep out.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sharetrade_tpu.agents.base import TrainState, megachunk_step
from sharetrade_tpu.parallel.mesh import is_cpu_mesh

#: One NamedSharding OBJECT per (mesh, spec): every layer that places or
#: constrains state asks here, so "the same sharding" is identity, not an
#: equality the reader must verify across call sites.
_CANONICAL: dict[tuple[Mesh, P], NamedSharding] = {}


def canonical_sharding(mesh: Mesh, spec: P = P()) -> NamedSharding:
    """THE NamedSharding for (mesh, spec).

    Memoized so the sharding trees built by :func:`train_state_shardings`,
    the orchestrator's place/restore/heal paths, and the in-step
    ``with_sharding_constraint`` pins all hold the identical object — a
    path that constructed its own would still compare equal today, but the
    cache makes the canonical-spec contract structural instead of
    conventional."""
    got = _CANONICAL.get((mesh, spec))
    if got is None:
        if len(_CANONICAL) >= 4096:
            # Ephemeral-mesh processes (the test suite, shard-audit
            # children) would otherwise pin every mesh they ever built for
            # the process lifetime; a flush preserves identity within any
            # live working set (production owns ONE mesh) while bounding
            # retention. (A weak cache doesn't work here: the value holds
            # its mesh, so weak-keying by mesh never collects.)
            _CANONICAL.clear()
        got = _CANONICAL[(mesh, spec)] = NamedSharding(mesh, spec)
    return got


def batch_axis_sharding(mesh: Mesh, data_axis: str = "dp"):
    """P(dp, None, ...) for arrays whose leading dim is the agent batch."""
    return canonical_sharding(mesh, P(data_axis))


def param_shardings(params: Any, mesh: Mesh, rules: dict[str, P] | None = None):
    """Map each param leaf to a NamedSharding.

    ``rules`` maps a '/'-joined path *suffix* to a PartitionSpec, e.g.
    ``{"layer1/w": P(None, "tp"), "layer2/w": P("tp", None)}`` for Megatron-
    style column→row sharding of the MLP. Unmatched leaves replicate.
    """
    rules = rules or {}

    def leaf_sharding(path, leaf):
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        for suffix, spec in rules.items():
            if key.endswith(suffix):
                return canonical_sharding(mesh, spec)
        return canonical_sharding(mesh, P())

    return jax.tree_util.tree_map_with_path(leaf_sharding, params)


def mlp_tp_rules(model_axis: str = "tp") -> dict[str, P]:
    """Column-parallel first layer, row-parallel second — one all-reduce at
    the output, the classic Megatron split mapped onto ICI.

    The suffix set covers both MLP families (layer/torso heads) and the
    transformer block projections (qkv column, proj row, mlp_in column,
    mlp_out row), so one rule table serves every model kind; unmatched
    leaves (embeddings, layernorms, heads) replicate."""
    return {
        "layer1/w": P(None, model_axis),
        "layer2/w": P(model_axis, None),
        "torso1/w": P(None, model_axis),
        "torso2/w": P(model_axis, None),
        "qkv/w": P(None, model_axis),
        "proj/w": P(model_axis, None),
        "mlp_in/w": P(None, model_axis),
        "mlp_out/w": P(model_axis, None),
    }


def mesh_param_rules(mesh: Mesh, model_axis: str = "tp"):
    """THE param rules of a mesh: a tp axis shards parameters via the
    Megatron suffix rules; without rules a tp axis would silently
    replicate params, making the public surface's tensor parallelism a
    no-op."""
    return mlp_tp_rules(model_axis) if model_axis in mesh.axis_names else None


def train_state_shardings(ts: TrainState, mesh: Mesh, *,
                          data_axis: str = "dp",
                          param_rules: dict[str, P] | None = None) -> TrainState:
    """Build the TrainState-shaped pytree of NamedShardings for jit in/out."""
    replicate = canonical_sharding(mesh, P())
    batch = canonical_sharding(mesh, P(data_axis))

    p_shard = param_shardings(ts.params, mesh, param_rules)

    # Optimizer accumulators (AdaGrad sums, Adam moments) embed a params-
    # shaped subtree, so an opt leaf's path *ends with* some param's full
    # path (e.g. `.0.sum_of_squares.layer1.w` ends with `layer1/w`). Match
    # on that path suffix plus shape — never shape alone, which picks the
    # wrong spec when two differently-sharded params share a shape.
    def _path_keys(path):
        return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)

    param_items = [
        (_path_keys(path), leaf.shape, sharding)
        for (path, leaf), sharding in zip(
            jax.tree_util.tree_flatten_with_path(ts.params)[0],
            jax.tree.leaves(p_shard))
    ]

    def opt_leaf(path, leaf):
        keys = _path_keys(path)
        for pkeys, pshape, sharding in param_items:
            if (len(keys) >= len(pkeys) and keys[-len(pkeys):] == pkeys
                    and getattr(leaf, "shape", None) == pshape):
                return sharding
        return replicate

    # The agent-batch size identifies which leaves shard over dp: exactly
    # those whose leading dim is the batch (env cursors, carries).
    batch_size = int(ts.env_state.t.shape[0])

    def batched_leaf(leaf):
        shape = getattr(leaf, "shape", ())
        return batch if (len(shape) >= 1 and shape[0] == batch_size) else replicate

    def extras_leaf(path, leaf):
        # Algorithm extras mix params-shaped trees (DQN target net — shard
        # like the matching param), batch-leading arrays (shard over dp),
        # and everything else (replay rows, counters — replicate). Replay
        # buffers replicate unconditionally: their leading dim is capacity,
        # which can coincide with the batch size while the sampling indices
        # assume the whole buffer.
        keys = _path_keys(path)
        if "replay" in keys or "per" in keys:
            # "per": the PER sum-tree + max-priority scalar replicate with
            # the replay arrays they index — the tree's (2L,) leading dim
            # is a capacity, never the batch.
            return replicate
        match = opt_leaf(path, leaf)
        if match is not replicate:
            return match
        return batched_leaf(leaf)

    return TrainState(
        params=p_shard,
        opt_state=jax.tree_util.tree_map_with_path(opt_leaf, ts.opt_state),
        carry=jax.tree.map(batched_leaf, ts.carry),
        env_state=jax.tree.map(batched_leaf, ts.env_state),
        rng=replicate,
        env_steps=replicate,
        updates=replicate,
        extras=(jax.tree_util.tree_map_with_path(extras_leaf, ts.extras)
                if ts.extras is not None else None),
    )


def constrain_train_state(ts: TrainState, shardings: TrainState) -> TrainState:
    """Pin the BATCH-CARRIED TrainState leaves — ``carry`` (notably the
    episode transformer's ``hist`` buffer) and ``env_state`` — to their
    canonical shardings INSIDE a traced program
    (``jax.lax.with_sharding_constraint``). The seam this serves: between
    the shard_map regions of the sp/ring/pipeline/MoE paths and the
    surrounding dataflow, GSPMD may otherwise re-derive a transposed-mesh
    layout for the carry (e.g. ``carry['hist']`` [dp,1,sp] → [1,sp,dp]) and
    bridge it with a full replicate-then-repartition per chunk.

    Deliberately NOT the whole state: params/opt_state are loop-invariant
    inside a megachunk scan and already pinned by the outer jit's in/out
    shardings — re-constraining them mid-scan makes GSPMD materialize the
    constraint (measured +8 all-gathers on the dp4×tp2 bench_reshard
    workload) instead of leaving the tp-sharded layout untouched."""
    return ts.replace(
        carry=jax.lax.with_sharding_constraint(ts.carry, shardings.carry),
        env_state=jax.lax.with_sharding_constraint(ts.env_state,
                                                   shardings.env_state))


def _constrained(step_fn, shardings: TrainState):
    """Wrap a chunk step so its OUTPUT TrainState is re-pinned to the
    canonical specs. Composed UNDER ``megachunk_step``, this pins the
    lax.scan carry at every inner-chunk seam — the K-1 seams that have no
    jit in/out shardings of their own and where an involuntary reshard
    would otherwise be paid K times per dispatch."""

    def step(ts: TrainState):
        new_ts, metrics = step_fn(ts)
        return constrain_train_state(new_ts, shardings), metrics

    return step


def jit_parallel_step(agent, mesh: Mesh, ts: TrainState, *,
                      data_axis: str = "dp",
                      param_rules: dict[str, P] | None = None,
                      megachunk_factor: int = 1,
                      constrain: bool = True,
                      donate: bool = True,
                      cost_hook=None):
    """Build the jitted (uncalled) partitioned chunk program and its
    sharding tree: ``(shardings, jitted_fn)``.

    The ONE construction shared by :func:`make_parallel_step` (which
    executes it) and ``tools/shard_audit.py`` / ``bench.py bench_reshard``
    (which ``.lower(...).compile()`` it to inspect SPMD warnings, HLO
    collectives and memory) — so what the audit certifies is byte-for-byte
    the program the orchestrator dispatches.

    Sharding decisions:

    - in_shardings: the canonical TrainState tree (params by rule, batch-
      leading leaves over ``data_axis``, scalars replicated).
    - out_shardings: the same tree for the TrainState; ``None`` (GSPMD-
      chosen) for the metrics. Forcing the metrics to replicate — the old
      behavior — inserted an all-gather INSIDE the fused program for any
      batch-shaped metric leaf (DQN's journaled ``(K, T, B, ...)``
      transitions); leaving them unspecified keeps them shard-resident
      until the orchestrator's single batched ``device_get`` readback,
      which assembles on the host for free.
    - ``constrain`` (``parallel.shard_constraints``): re-pin the output
      state inside the program (see :func:`_constrained`); off only for
      the bench's with/without comparison.

    ``cost_hook`` (the ``obs.roofline`` seam): called once, after the jit
    wrapper is built, as ``cost_hook(fn, (ts,),
    megachunk_factor=megachunk_factor, devices=<mesh size>)`` —
    obs/roofline.py AOT-lowers the
    program there and records its XLA cost/memory analysis, so the costs
    the roofline gauges report belong to byte-for-byte the program the
    orchestrator dispatches (the same identity guarantee the shard audit
    relies on). Compile-time only: the hook must never ride a dispatch.
    """
    sh = train_state_shardings(ts, mesh, data_axis=data_axis,
                               param_rules=param_rules)
    step_fn = _constrained(agent.step, sh) if constrain else agent.step
    if megachunk_factor > 1:
        step_fn = megachunk_step(step_fn, megachunk_factor)
    # NO donation for a fused megachunk on CPU devices: donating the
    # TrainState into the lax.scan corrupts the heap on the CPU runtime
    # (use-after-free once checkpoint restores interleave with megachunk
    # dispatches — same hazard the orchestrator's CPU-fallback seam avoids).
    # ``donate=False`` extends the same carve-out to the async-pipeline
    # orchestrator on CPU meshes: a consumer-thread device_get concurrent
    # with a donating dispatch segfaults the CPU runtime the same way.
    # Accelerator meshes keep donation, where HBM double-buffering matters.
    argnums = ((0,) if donate
               and not (megachunk_factor > 1 and is_cpu_mesh(mesh))
               else ())
    fn = jax.jit(step_fn, in_shardings=(sh,), out_shardings=(sh, None),
                 donate_argnums=argnums)
    if cost_hook is not None:
        # devices: cost_analysis() describes the PER-DEVICE partition of
        # the SPMD program; the hook needs the mesh size to relate it to
        # the analytic (global-work) model.
        cost_hook(fn, (ts,), megachunk_factor=megachunk_factor,
                  devices=mesh.devices.size)
    return sh, fn


def make_parallel_step(agent, mesh: Mesh, *, data_axis: str = "dp",
                       param_rules: dict[str, P] | None = None,
                       megachunk_factor: int = 1,
                       constrain: bool = True,
                       donate: bool = True,
                       cost_hook=None):
    """jit the agent's chunk step with mesh shardings.

    Returns ``(place, step)``: ``place(ts)`` device_puts a freshly-initialized
    TrainState onto the mesh; ``step`` is the compiled chunk function with
    donated input (the TrainState is consumed each call — no HBM double-
    buffering of parameters).

    ``megachunk_factor`` K > 1 composes the device-resident megachunk
    (agents/base.py ``megachunk_step``) INSIDE the pjit boundary: the
    K-chunk ``lax.scan`` is one partitioned program, so the ICI collectives
    of consecutive inner chunks stay fused (no host round-trip re-dispatches
    them) and the host pays one dispatch per K chunks. Metrics return
    stacked ``(K, ...)`` with GSPMD-chosen (shard-resident) layouts; see
    :func:`jit_parallel_step` for the sharding contract, including the
    per-inner-chunk carry pin that keeps the scan free of involuntary
    resharding."""
    cache: dict[str, Any] = {}  # sharding pytree + jitted fn, built once

    def _ensure(ts):
        if "fn" not in cache:
            cache["sh"], cache["fn"] = jit_parallel_step(
                agent, mesh, ts, data_axis=data_axis,
                param_rules=param_rules, megachunk_factor=megachunk_factor,
                constrain=constrain, donate=donate, cost_hook=cost_hook)
        return cache

    def place(ts: TrainState) -> TrainState:
        return jax.device_put(ts, _ensure(ts)["sh"])

    def compiled(ts):
        return _ensure(ts)["fn"](ts)

    return place, compiled
