"""Transformer tick-series policy (BASELINE.json config 5).

Treats the observation's price window as a *sequence* instead of a flat
feature vector — the long-context capability the reference lacks entirely
(SURVEY.md §5: windows iterated, never modeled as sequences). Each tick
becomes a token carrying (price, log-return, position); the (budget, shares)
portfolio scalars are appended as a final summary token whose output embedding
feeds the policy/value heads. Causal attention runs through the Pallas flash
kernel on TPU (sharetrade_tpu/ops/attention.py).

Prices are normalized by the window's last price so the policy is
scale-invariant across decades of price levels (the 1992 MSFT window differs
from 2015's by an order of magnitude).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sharetrade_tpu.config import ConfigError

from sharetrade_tpu.models.core import (
    Model, ModelOut, compute_dtype, dense, dense_init, portfolio_features,
    tick_window_features)
from sharetrade_tpu.models.ffn import ffn_apply
from sharetrade_tpu.ops.attention import flash_attention


def _layer_norm(x, scale, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def transformer_policy(obs_dim: int = 203, num_actions: int = 3, *,
                       num_layers: int = 2, num_heads: int = 4,
                       head_dim: int = 64, mlp_ratio: int = 4,
                       dtype=jnp.float32, use_pallas: bool | None = None,
                       attention_fn=None, pp_mesh=None, pp_axis: str = "pp",
                       pp_batch_axis: str | None = None,
                       moe_experts: int = 0, ep_mesh=None,
                       ep_axis: str = "ep", moe_top_k: int = 0,
                       moe_capacity_factor: float = 1.25,
                       moe_dispatch: str = "psum",
                       num_assets: int = 1, kernel_mesh=None) -> Model:
    """``attention_fn(q, k, v) -> out`` overrides the local flash kernel —
    the sequence-parallel hook (e.g. ``ring_attention_sharded`` binds a mesh
    so attention rings over the sp axis, parallel/ring_attention.py).

    ``pp_mesh`` pipelines the transformer blocks over that mesh's
    ``pp_axis`` (GPipe microbatch schedule, parallel/pipeline.py): one block
    per stage, so ``num_layers`` must equal the pp size. Blocks are then
    stored stacked (leading dim = num_layers) so stage i's slice shards onto
    pp-device i. ``pp_batch_axis`` names the mesh axis the agent batch is
    sharded over (usually "dp") so microbatches keep that sharding.

    ``kernel_mesh``: the multi-device mesh the model's programs are
    partitioned over; the local flash kernel then runs under a shard_map
    over ``pp_batch_axis`` (ops/attention.py ``_per_device`` — a bare
    Mosaic call cannot be partitioned). None inside a pipeline stage,
    which is per-device already.

    ``num_assets`` > 1 tokenizes the multi-asset portfolio observation
    (env/portfolio.py: A windows ++ budget ++ A share counts) as A
    per-asset blocks of [window tick tokens | portfolio token], each block
    tagged with a learned asset embedding; positions tile per block and the
    policy/value summary averages the A portfolio-token outputs. At A=1
    this degenerates EXACTLY to the single-asset layout (same parameters,
    same sequence), so checkpoints stay compatible."""
    if num_assets < 1:
        raise ConfigError(f"num_assets must be >= 1, got {num_assets}")
    window = (obs_dim - 1 - num_assets) // num_assets
    if num_assets * window + 1 + num_assets != obs_dim:
        raise ConfigError(
            f"obs_dim={obs_dim} does not match the {num_assets}-asset "
            f"portfolio layout (A*window + 1 + A)")
    seq_len = num_assets * (window + 1)
    d_model = num_heads * head_dim
    if attention_fn is None:
        attention_fn = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=True, use_pallas=use_pallas,
            mesh=kernel_mesh, batch_axis=pp_batch_axis)
    if pp_mesh is not None and pp_mesh.shape[pp_axis] != num_layers:
        raise ConfigError(
            f"pipeline_blocks needs num_layers == pp size "
            f"({num_layers} != {pp_mesh.shape[pp_axis]})")
    if moe_experts and pp_mesh is not None:
        raise ConfigError("pipeline_blocks + moe_experts is unsupported "
                         "(nested shard_maps); pick one partitioning")

    def init(key):
        # The asset embedding (A>1 only) draws from an extra TRAILING key:
        # split(key, n) is prefix-stable in n, so single-asset configs
        # reproduce the exact same weights per seed as before the
        # multi-asset feature existed.
        keys = jax.random.split(
            key, 4 + 6 * num_layers + (1 if num_assets > 1 else 0))
        params = {
            "embed": dense_init(keys[0], 3, d_model, dtype=dtype),
            # Within-block positions, tiled per asset block at apply time
            # (A=1: exactly the old full-sequence table).
            "pos": jax.random.normal(
                keys[1], (window + 1, d_model), dtype) * 0.02,
            "policy": dense_init(keys[2], d_model, num_actions, scale=0.01, dtype=dtype),
            "value": dense_init(keys[3], d_model, 1, dtype=dtype),
            "blocks": [],
            "final_ln": {"scale": jnp.ones((d_model,), dtype),
                         "bias": jnp.zeros((d_model,), dtype)},
        }
        if num_assets > 1:
            params["asset"] = jax.random.normal(
                keys[-1], (num_assets, d_model), dtype) * 0.02
        for i in range(num_layers):
            k = keys[4 + 6 * i: 4 + 6 * (i + 1)]
            block = {
                "ln1": {"scale": jnp.ones((d_model,), dtype),
                        "bias": jnp.zeros((d_model,), dtype)},
                "qkv": dense_init(k[0], d_model, 3 * d_model, dtype=dtype),
                "proj": dense_init(k[1], d_model, d_model,
                                   scale=0.02 / max(num_layers, 1), dtype=dtype),
                "ln2": {"scale": jnp.ones((d_model,), dtype),
                        "bias": jnp.zeros((d_model,), dtype)},
            }
            if moe_experts:
                from sharetrade_tpu.parallel.moe import init_moe_params
                block["moe"] = init_moe_params(
                    k[2], moe_experts, d_model, mlp_ratio * d_model,
                    dtype=dtype)
            else:
                block["mlp_in"] = dense_init(
                    k[2], d_model, mlp_ratio * d_model, dtype=dtype)
                block["mlp_out"] = dense_init(
                    k[3], mlp_ratio * d_model, d_model,
                    scale=0.02 / max(num_layers, 1), dtype=dtype)
            params["blocks"].append(block)
        if pp_mesh is not None:
            # Stacked layout (leading dim = stages) so stage i's slice lands
            # on pp-device i through the pipeline shard_map.
            params["blocks"] = jax.tree.map(
                lambda *leaves: jnp.stack(leaves), *params["blocks"])
        return params

    def block_apply(blk, x):
        """One pre-LN transformer block over (B, T, d) tokens.

        Returns ``(x, aux)`` — aux is the block's MoE load-balance loss
        (0.0 for dense-FFN blocks), surfaced so training can regularize the
        gate: with capacity dispatch (moe_top_k>0) an unbalanced gate
        overflows expert buffers and silently zeroes dropped tokens.
        """
        bsz, t = x.shape[0], x.shape[1]
        # Compute dtype follows the handed-in block params (masters or the
        # precision policy's bf16 copy), not the build-time closure.
        dtype = compute_dtype(blk)
        h = _layer_norm(x, blk["ln1"]["scale"], blk["ln1"]["bias"])
        qkv = dense(blk["qkv"], h).reshape(bsz, t, 3, num_heads, head_dim)
        # attention expects (batch, heads, seq, head_dim)
        q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))
        attn = attention_fn(q, k, v)
        attn = attn.transpose(0, 2, 1, 3).reshape(
            bsz, t, d_model).astype(dtype)
        x = x + dense(blk["proj"], attn)
        h = _layer_norm(x, blk["ln2"]["scale"], blk["ln2"]["bias"])
        y, aux = ffn_apply(
            blk, h, moe_experts=moe_experts, ep_mesh=ep_mesh,
            ep_axis=ep_axis, moe_top_k=moe_top_k,
            moe_capacity_factor=moe_capacity_factor,
            moe_dispatch=moe_dispatch, batch_axis=pp_batch_axis)
        return x + y, aux

    def tokenize(obs):
        """(B, obs_dim) -> (B, seq, 3): per-asset blocks of shared tick
        features plus that asset's portfolio token (budget, its shares,
        its window anchor — the flag channel is the tick features' zero
        one). A=1 reproduces the single-asset layout exactly."""
        b = obs.shape[0]
        windows = obs[:, :num_assets * window].reshape(b, num_assets, window)
        budget = obs[:, num_assets * window]
        shares = obs[:, num_assets * window + 1:]                # (B, A)
        ticks = tick_window_features(
            windows.reshape(b * num_assets, window), window
        ).reshape(b, num_assets, window, 3)
        port = portfolio_features(
            jnp.broadcast_to(budget[:, None], shares.shape), shares,
            windows[:, :, -1])                                   # (B, A, 3)
        blocks = jnp.concatenate([ticks, port[:, :, None, :]], axis=2)
        return blocks.reshape(b, seq_len, 3)

    def apply_batch(params, obs, carry):
        """Native batched forward: the whole agent batch rides one flash
        kernel call per layer with a batch*heads grid — no batch-1 programs
        (the round-1 pathology: per-agent vmapped kernel invocations)."""
        bsz = obs.shape[0]
        tokens = tokenize(obs).astype(compute_dtype(params))
        pos = jnp.tile(params["pos"], (num_assets, 1))           # (seq, d)
        x = dense(params["embed"], tokens) + pos                 # (B, seq, d)
        if num_assets > 1:
            x = x + jnp.repeat(params["asset"], window + 1, axis=0)
        aux = jnp.float32(0.0)
        if pp_mesh is None:
            for blk in params["blocks"]:
                x, blk_aux = block_apply(blk, x)
                aux = aux + blk_aux
        else:
            from sharetrade_tpu.parallel.pipeline import pipeline_apply
            from jax.sharding import PartitionSpec as P
            # GPipe microbatches over the agent batch: M = stages when the
            # batch divides evenly (bubble (S-1)/(M+S-1)), else one batch.
            stages = num_layers
            m = stages if bsz % stages == 0 else 1
            mb = x.reshape((m, bsz // m) + x.shape[1:])
            b_axis = pp_batch_axis
            if b_axis is not None and (bsz // m) % pp_mesh.shape[b_axis]:
                b_axis = None   # odd batch (e.g. eval's batch-1): replicate
            # moe + pipeline_blocks is rejected at construction, so pipelined
            # stages never carry an aux term to drop.
            mb = pipeline_apply(
                lambda blk, t: block_apply(blk, t)[0], params["blocks"], mb,
                pp_mesh, axis=pp_axis, mb_spec=P(None, b_axis))
            x = mb.reshape((bsz,) + mb.shape[2:])
        # Summary = mean over the A portfolio tokens' outputs (A=1: the
        # final token, the original readout).
        port_idx = (jnp.arange(num_assets) + 1) * (window + 1) - 1
        summary = _layer_norm(jnp.mean(x[:, port_idx], axis=1),
                              params["final_ln"]["scale"],
                              params["final_ln"]["bias"])
        logits = dense(params["policy"], summary).astype(jnp.float32)
        value = dense(params["value"], summary).astype(jnp.float32)[:, 0]
        return ModelOut(logits=logits, value=value,
                        aux=aux / max(num_layers, 1)), carry

    def apply(params, obs, carry):
        outs, carry = apply_batch(params, obs[None], carry)
        return ModelOut(logits=outs.logits[0], value=outs.value[0],
                        aux=outs.aux), carry

    return Model(init=init, apply=apply, apply_batch=apply_batch,
                 obs_dim=obs_dim, num_actions=num_actions, name="transformer")
