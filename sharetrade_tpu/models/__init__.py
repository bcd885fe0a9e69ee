"""Policy networks (L2 compute core).

Reference: the single inline TF graph in QDecisionPolicyActor.scala:38-50.
Here the model zoo is a registry keyed by ``ModelConfig.kind`` so learners are
model-agnostic (SURVEY.md §7.1 item 3: one policy/learner interface covering
the BASELINE.json config ladder).
"""

from __future__ import annotations

import jax.numpy as jnp

from sharetrade_tpu.config import ConfigError, ModelConfig
from sharetrade_tpu.models.core import Model, ModelOut, dense, dense_init  # noqa: F401
from sharetrade_tpu.models.lstm import lstm_policy
from sharetrade_tpu.models.mlp import ac_mlp, q_mlp
from sharetrade_tpu.models.transformer import transformer_policy

#: Master-weight dtypes a config may request. ``bfloat16`` is DELIBERATELY
#: absent: the old whole-model cast put params, gradients AND optimizer
#: accumulators in bf16 with no warning — the convergence-hostile
#: configuration the precision policy (precision.py) replaces. The
#: migration error below names the new knob.
_DTYPES = {"float32": jnp.float32}

_BF16_MIGRATION = (
    "model.dtype='bfloat16' has been removed: the whole-model cast "
    "silently put optimizer state and master weights in bf16 (a "
    "convergence-hostile configuration). Set precision.mode='bf16_mixed' "
    "instead — bf16 compute with fp32 master weights, f32 matmul "
    "accumulation, and f32 optimizer updates (see README 'Precision "
    "policy'). Model params now always initialize as fp32 masters; the "
    "precision policy casts the compute copy at each update boundary.")


def _validate_moe_dispatch(cfg: ModelConfig, ep_mesh) -> None:
    """MoE dispatch validation shared by the window and episode branches."""
    if cfg.moe_dispatch not in ("psum", "a2a"):
        raise ConfigError(
            f"unknown model.moe_dispatch {cfg.moe_dispatch!r} "
            "(expected 'psum' or 'a2a')")
    if cfg.moe_dispatch == "a2a" and cfg.moe_experts:
        if not cfg.moe_top_k:
            raise ConfigError(
                "model.moe_dispatch='a2a' is a top-k dispatch pattern; "
                "set model.moe_top_k>0 (the dense-mask top-1 scheme has "
                "no capacity buffers to all_to_all)")
        if ep_mesh is None:
            raise ConfigError(
                "model.moe_dispatch='a2a' needs a mesh with an 'ep' "
                "axis (set parallel.mesh_shape, e.g. "
                "{\"dp\": 2, \"ep\": 4})")


def build_model(cfg: ModelConfig, obs_dim: int, *, head: str = "ac",
                parity: bool = False, num_actions: int | None = None,
                mesh=None, num_assets: int = 1) -> Model:
    """Construct the policy network for ``cfg.kind``.

    ``head="q"`` selects the Q-value head (valid for MLP only — the reference
    network); ``head="ac"`` selects actor-critic heads. ``parity=True`` (with
    kind=mlp, head=q) reproduces the reference graph bit-for-bit in
    architecture: constant 0.1 biases, ReLU output, stddev-1 init.
    ``num_actions`` overrides the config (multi-asset envs widen the head).
    ``mesh`` enables the partitioned transformer paths: ``cfg.attention=
    "ring"`` rings attention over its sp axis; ``cfg.pipeline_blocks``
    pipelines the blocks over its pp axis. ``num_assets`` > 1 selects the
    window transformer's per-asset-block tokenization over the portfolio
    observation layout (episode mode stays single-asset — PARITY.md).
    """
    if cfg.dtype == "bfloat16":
        raise ConfigError(_BF16_MIGRATION)
    if cfg.dtype not in _DTYPES:
        raise ConfigError(f"unknown model.dtype {cfg.dtype!r}; "
                          f"choose from {sorted(_DTYPES)} "
                          "(low precision is precision.mode's job)")
    dtype = _DTYPES[cfg.dtype]
    actions = cfg.num_actions if num_actions is None else num_actions
    if cfg.seq_mode not in ("window", "episode"):
        raise ConfigError(f"unknown model.seq_mode {cfg.seq_mode!r}")
    if cfg.kind == "latent_moe":
        # Imported here alone: the other kinds' set-up pays nothing for it.
        if cfg.seq_mode != "episode" or head != "ac" or num_assets > 1:
            raise ConfigError(
                "model.kind='latent_moe' is a single-asset episode-mode "
                "actor-critic trunk: set model.seq_mode='episode' and an "
                "actor-critic learner.algo (ppo)")
        from sharetrade_tpu.models.latent_moe_episode import (
            latent_moe_episode_policy)
        return latent_moe_episode_policy(obs_dim, actions, cfg, dtype=dtype)
    if cfg.seq_mode == "episode" and cfg.kind != "transformer":
        raise ConfigError(
            f"model.seq_mode='episode' is a transformer mode; "
            f"model.kind={cfg.kind!r} would silently ignore it")
    if cfg.remat_blocks and not (cfg.kind == "transformer"
                                 and cfg.seq_mode == "episode"):
        raise ConfigError(
            "model.remat_blocks applies to the episode-mode transformer's "
            "banded replay only; other models would silently ignore it — "
            "use learner.remat for the window/fold replay paths")
    if cfg.kind == "mlp":
        if head == "q":
            return q_mlp(obs_dim, cfg.hidden_dim, actions,
                         parity=parity, dtype=dtype)
        return ac_mlp(obs_dim, cfg.hidden_dim, actions, dtype=dtype)
    if cfg.kind == "lstm":
        return lstm_policy(obs_dim, cfg.hidden_dim, actions, dtype=dtype)
    if cfg.kind == "tcn":
        if num_assets > 1:
            # Same loud boundary the episode transformer gets: a TCN built
            # over the portfolio layout would silently convolve asset-1
            # prices, the budget, and the share counts as one window.
            raise ConfigError(
                "model.kind='tcn' is single-asset (PARITY.md); use the "
                "window transformer, mlp, or lstm for multi-asset "
                "portfolios")
        from sharetrade_tpu.models.tcn import tcn_policy
        return tcn_policy(obs_dim, actions, channels=cfg.hidden_dim,
                          dtype=dtype)
    if cfg.kind == "transformer":
        attention_fn = None
        pp_mesh = None
        batch_axis = (  # agent batch rides dp when the mesh has it
            "dp" if mesh is not None and "dp" in mesh.axis_names else None)
        # A non-TPU mesh (the virtual-CPU test/dryrun client) can't lower the
        # Pallas kernel; the XLA reference path is numerically identical.
        from sharetrade_tpu.parallel.mesh import (
            has_shard_map_axis as _has_shard_map_axis, mesh_platform)
        use_pallas = (False if mesh is not None
                      and mesh_platform(mesh) != "tpu" else None)
        # Mosaic kernels cannot be partitioned automatically: on a
        # multi-device mesh the local kernels run under a shard_map over
        # the batch axis (ops/attention.py). A pipeline stage already is
        # one (parallel/pipeline.py), and shard_maps do not nest.
        kernel_mesh = (mesh if mesh is not None and mesh.size > 1
                       and use_pallas is None and not cfg.pipeline_blocks
                       else None)
        if cfg.seq_mode == "episode":
            if num_assets > 1:
                raise ConfigError(
                    "model.seq_mode='episode' is single-asset: its shared-"
                    "trunk design amortizes ONE tick stream across the "
                    "agent batch (see PARITY.md); use seq_mode='window' "
                    "for multi-asset portfolios")
            if cfg.attention not in ("flash", "ring"):
                raise ConfigError(
                    "model.seq_mode='episode' supports attention='flash' "
                    "(local banded) or 'ring' (the sp halo exchange — "
                    "episode mode's sequence-parallel scheme); ulysses is "
                    "window-mode only")
            episode_attention = None
            if cfg.attention == "ring":
                if mesh is None or "sp" not in mesh.axis_names:
                    raise ConfigError(
                        "model.attention='ring' needs a mesh with an 'sp' "
                        "axis (set parallel.mesh_shape, e.g. "
                        "{\"dp\": 2, \"sp\": 4})")
                if cfg.pipeline_blocks:
                    raise ConfigError(
                        "model.attention='ring' + model.pipeline_blocks is "
                        "unsupported (no sp attention inside a pipeline "
                        "stage); pick one partitioning")
                from sharetrade_tpu.parallel.episode_sp import (
                    halo_banded_attention_sharded)
                episode_attention = halo_banded_attention_sharded(
                    mesh, seq_axis="sp", batch_axis=batch_axis,
                    use_pallas=use_pallas)
            ep_pp_mesh = None
            if cfg.pipeline_blocks:
                if mesh is None or "pp" not in mesh.axis_names:
                    raise ConfigError(
                        "model.pipeline_blocks needs a mesh with a 'pp' "
                        "axis (set parallel.mesh_shape, e.g. "
                        "{\"dp\": 2, \"pp\": 4})")
                ep_pp_mesh = mesh
            ep_mesh = (mesh if cfg.moe_experts and mesh is not None
                       and "ep" in mesh.axis_names else None)
            _validate_moe_dispatch(cfg, ep_mesh)
            from sharetrade_tpu.models.transformer_episode import (
                episode_transformer_policy)
            return episode_transformer_policy(
                obs_dim, actions, num_layers=cfg.num_layers,
                num_heads=cfg.num_heads, head_dim=cfg.head_dim, dtype=dtype,
                use_pallas=use_pallas, attention_fn=episode_attention,
                pp_mesh=ep_pp_mesh, pp_batch_axis=batch_axis,
                moe_experts=cfg.moe_experts, ep_mesh=ep_mesh,
                moe_top_k=cfg.moe_top_k,
                moe_capacity_factor=cfg.moe_capacity_factor,
                moe_dispatch=cfg.moe_dispatch,
                remat_blocks=cfg.remat_blocks,
                # The carry→series seam pin applies exactly where a
                # shard_map-partitioned path (sp halo attention, ep MoE
                # dispatch) can propagate a transposed-mesh spec backward
                # onto the dp-sharded hist carry; meshes without those
                # axes compile clean already and keep their exact
                # programs (mesh.has_shard_map_axis — the same scope
                # predicate as PPO's rollout→update seam).
                seam_mesh=(mesh if _has_shard_map_axis(mesh) else None),
                kernel_mesh=kernel_mesh)
        if cfg.attention in ("ring", "ulysses"):
            if mesh is None or "sp" not in mesh.axis_names:
                raise ConfigError(
                    f"model.attention={cfg.attention!r} needs a mesh with an "
                    "'sp' axis (set parallel.mesh_shape, e.g. "
                    "{\"dp\": 2, \"sp\": 4})")
            if cfg.attention == "ring":
                from sharetrade_tpu.parallel.ring_attention import (
                    ring_attention_sharded)
                attention_fn = ring_attention_sharded(
                    mesh, seq_axis="sp", batch_axis=batch_axis)
            else:
                from sharetrade_tpu.parallel.ulysses import (
                    ulysses_attention_sharded)
                attention_fn = ulysses_attention_sharded(
                    mesh, seq_axis="sp", batch_axis=batch_axis,
                    use_pallas=use_pallas)
        elif cfg.attention != "flash":
            raise ConfigError(f"unknown model.attention {cfg.attention!r}")
        if cfg.pipeline_blocks:
            if mesh is None or "pp" not in mesh.axis_names:
                raise ConfigError(
                    "model.pipeline_blocks needs a mesh with a 'pp' axis "
                    "(set parallel.mesh_shape, e.g. {\"dp\": 2, \"pp\": 4})")
            if cfg.attention != "flash":
                raise ConfigError(
                    f"model.attention={cfg.attention!r} + "
                    "model.pipeline_blocks is unsupported (nested "
                    "shard_maps); pick one partitioning")
            pp_mesh = mesh
        # Experts shard over ep when the mesh has that axis; otherwise the
        # expert bank runs single-device (still trainable — the mechanism's
        # reachability doesn't depend on the mesh).
        ep_mesh = (mesh if cfg.moe_experts and mesh is not None
                   and "ep" in mesh.axis_names else None)
        _validate_moe_dispatch(cfg, ep_mesh)
        return transformer_policy(
            obs_dim, actions, num_layers=cfg.num_layers,
            num_heads=cfg.num_heads, head_dim=cfg.head_dim, dtype=dtype,
            use_pallas=use_pallas, attention_fn=attention_fn,
            pp_mesh=pp_mesh, pp_batch_axis=batch_axis,
            moe_experts=cfg.moe_experts, ep_mesh=ep_mesh,
            moe_top_k=cfg.moe_top_k,
            moe_capacity_factor=cfg.moe_capacity_factor,
            moe_dispatch=cfg.moe_dispatch, num_assets=num_assets,
            kernel_mesh=kernel_mesh)
    raise ConfigError(f"unknown model kind {cfg.kind!r}")
