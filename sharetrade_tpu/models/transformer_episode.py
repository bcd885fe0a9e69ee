"""Episode-mode transformer: the tick stream IS the sequence.

The window-mode policy (models/transformer.py) re-embeds and re-attends the
full price window for every env step, so a T-step PPO replay reprocesses
T x (window+1) tokens per agent even though consecutive windows share all
but one tick. Episode mode is the TPU-first inversion: embed each tick
ONCE, run sliding-window (banded) flash attention over the episode's tick
sequence (ops/attention.py local_window), and read one output per env step
— an O(T + L*window) forward replaces T O(window) window forwards (~15-50x
fewer tokens for the BASELINE unrolls). This is also the long-context
story: the training pass handles long unrolls (the full 5,845-step MSFT
episode fits one banded pass) as ONE sequence instead of a stack of
windows; past ~512k K/V elements the kernel switches to streaming one K/V
block per grid step (ops/attention.py ``_STREAM_KV_ELEMS``), so sequence
length is bounded by HBM, not VMEM — 32k-token banded gradients compile
and run.

Architecture notes (deliberately different from window mode — this is a
redesign, not a re-tiling):

- Tokens carry step-invariant features only (log-return and its magnitude):
  keys must mean the same thing to every query that sees them, so the
  window-anchored price normalization of window mode cannot appear on the
  key side. Scale-invariance across decades of price levels is preserved —
  log-returns are dimensionless.
- Positions enter via rotary embeddings (RoPE) at ABSOLUTE tick indices:
  relative offsets inside each query's band are then position-exact
  regardless of where the band sits in the episode, and rollout/replay use
  the same indices so their numerics agree.
- The portfolio state (budget, shares) is injected on the head side: a
  learned projection added to the final-layer representation at each step's
  query position. Attention over prices does not depend on the agent's
  wallet; the decision head combines market context with it (the classic
  features+state actor-critic split). The reference folds budget/shares
  into the network input instead (QDecisionPolicyActor.scala:18, 203-dim
  x); window mode keeps that shape, episode mode redesigns it.

Rollout runs incrementally with a per-layer rolling K/V cache of exactly
``window`` entries (a Mistral-style sliding-window cache): one token's
qkv/mlp plus a 1 x window attention row per step. The training replay runs
the banded forward over [carried history | chunk ticks]. Both compute the
same function of the same tick series: the carry stores the
(L-1)*(window-1) ticks the deepest layer's receptive field reaches past
the chunk boundary, episode starts left-pad with the first price on both
paths, and RoPE uses absolute indices — so replayed logits match rollout
logits to numerical tolerance (tests/test_models.py::TestEpisodeMode).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sharetrade_tpu.config import ConfigError

from sharetrade_tpu.models.core import (
    Model, ModelOut, compute_dtype, dense, dense_init, portfolio_features,
    rows_finite)
from sharetrade_tpu.models.ffn import ffn_apply
from sharetrade_tpu.models.transformer import _layer_norm
from sharetrade_tpu.ops.attention import flash_attention

_EPS = 1e-6


def _rope(x: jax.Array, positions: jax.Array, *, base: float = 10000.0):
    """Rotary position embedding. x: (B, H, S, D) with D even; positions:
    (B, S) absolute indices (negative is fine — episode-start padding sits
    at negative ticks)."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _tick_features(series: jax.Array) -> jax.Array:
    """(B, S) prices -> (B, S, 3) step-invariant token features."""
    logp = jnp.log(jnp.maximum(series, _EPS))
    ret = jnp.concatenate(
        [jnp.zeros_like(logp[:, :1]), logp[:, 1:] - logp[:, :-1]], axis=1)
    return jnp.stack([ret, jnp.abs(ret), jnp.zeros_like(ret)], axis=-1)


def episode_transformer_policy(obs_dim: int = 203, num_actions: int = 3, *,
                               num_layers: int = 2, num_heads: int = 4,
                               head_dim: int = 64, mlp_ratio: int = 4,
                               dtype=jnp.float32,
                               use_pallas: bool | None = None,
                               attention_fn=None,
                               pp_mesh=None, pp_axis: str = "pp",
                               pp_batch_axis: str | None = None,
                               moe_experts: int = 0, ep_mesh=None,
                               ep_axis: str = "ep", moe_top_k: int = 0,
                               moe_capacity_factor: float = 1.25,
                               moe_dispatch: str = "psum",
                               remat_blocks: bool = False,
                               seam_mesh=None, kernel_mesh=None) -> Model:
    """Build the episode-mode policy (``ModelConfig.seq_mode="episode"``).

    ``attention_fn(q, k, v, window) -> out`` overrides the local banded
    flash kernel in the REPLAY pass — the sequence-parallel hook
    (``halo_banded_attention_sharded`` shards the tick sequence over an sp
    mesh axis, parallel/episode_sp.py). The rollout stays local regardless:
    the incremental path is a 1-token cache attention and the episode-start
    prefill pins the local kernel (its L*(window-1)+1 rows are too short to
    shard), so only the replay span constrains the sp size.

    ``moe_experts`` routes every block's FFN through the shared MoE
    dispatch (models/ffn.py): dense-mask top-1, capacity top-k, ep-sharded
    psum, or token-sharded all_to_all — the same variants window mode
    composes with. ``pp_mesh`` pipelines the banded blocks over its
    ``pp_axis`` (GPipe, parallel/pipeline.py; blocks stored stacked so
    stage i's slice shards onto pp-device i). Microbatches cut the agent
    batch when it divides the stage count; otherwise — the batch-of-1
    trunk/shared-replay passes — the SEQUENCE is cut into streamed chunks
    whose banded halo flows chunk-to-chunk through a stage-local pipeline
    carry (the sp halo-exchange trick, parallel/episode_sp.py, applied
    along the schedule), so those passes pipeline along time instead of
    idling (stages-1)/stages of the schedule; m=1 remains only for
    sequences shorter than two window-1 chunks. pp + MoE is rejected
    (nested shard_maps), as is pp + a non-local attention override.

    ``kernel_mesh``: the multi-device mesh the model's programs are
    partitioned over; the local banded kernel then runs under a shard_map
    over ``pp_batch_axis`` (ops/attention.py ``_per_device`` — a bare
    Mosaic call cannot be partitioned), replicated for the batch-of-one
    trunk passes. None with ``pp_mesh``: a pipeline stage is per-device
    already.
    """
    if head_dim % 2:
        raise ConfigError(f"RoPE needs an even head_dim, got {head_dim}")
    window = obs_dim - 2                    # ticks per observation window
    hist_len = (num_layers - 1) * (window - 1)

    def _pin_hist(hist):
        # The carry→series seam (round 8, the MULTICHIP involuntary-remat
        # fix): the replay/trunk passes concatenate the carry's history
        # rows into the tick series, and on an sp/ep mesh the partitioned
        # attention's sequence-sharded (transposed-mesh) spec propagates
        # BACKWARD through that concat onto the dp-sharded
        # ``ts.carry['hist']`` program input — XLA then bridges the two
        # with a full replicate-and-repartition per step ("Involuntary
        # full rematerialization", the [4,1,2]→[1,2,4] warning in
        # MULTICHIP_r01..r05). Pinning the (B, hist_len) slice replicated
        # here — bytes, not megabytes — turns that into one planned,
        # warning-free all-gather and stops the backward propagation at
        # an explicit seam; the TrainState's own carry keeps its
        # canonical dp spec via the jit in/out shardings
        # (parallel/sharding.py).
        if seam_mesh is None:
            return hist
        from sharetrade_tpu.parallel.sharding import canonical_sharding
        return jax.lax.with_sharding_constraint(
            hist, canonical_sharding(seam_mesh))
    d_model = num_heads * head_dim
    sm_scale = head_dim ** -0.5
    def local_attention(q, k, v, w):
        return flash_attention(q, k, v, causal=True, sm_scale=sm_scale,
                               local_window=w, use_pallas=use_pallas,
                               mesh=kernel_mesh, batch_axis=pp_batch_axis)

    if attention_fn is None:
        attention_fn = local_attention
    if pp_mesh is not None:
        if pp_mesh.shape[pp_axis] != num_layers:
            raise ConfigError(
                f"pipeline_blocks needs num_layers == pp size "
                f"({num_layers} != {pp_mesh.shape[pp_axis]})")
        if moe_experts:
            raise ConfigError("pipeline_blocks + moe_experts is unsupported "
                             "(nested shard_maps); pick one partitioning")
        if attention_fn is not local_attention:
            raise ConfigError("pipeline_blocks requires the local banded "
                             "attention (no sp override inside a stage)")

    def block_ffn(blk, h):
        # batch_axis keeps the dp sharding of the token batch inside the
        # MoE's shard_map (a dp x ep mesh would otherwise all_gather the
        # batch — correct but silently losing the dp split window mode
        # keeps, models/transformer.py:157).
        return ffn_apply(
            blk, h, moe_experts=moe_experts, ep_mesh=ep_mesh,
            ep_axis=ep_axis, moe_top_k=moe_top_k,
            moe_capacity_factor=moe_capacity_factor,
            moe_dispatch=moe_dispatch, batch_axis=pp_batch_axis)

    def init(key):
        keys = jax.random.split(key, 5 + 6 * num_layers)
        params = {
            "embed": dense_init(keys[0], 3, d_model, dtype=dtype),
            "port": dense_init(keys[1], 3, d_model, scale=0.02, dtype=dtype),
            "policy": dense_init(keys[2], d_model, num_actions, scale=0.01,
                                 dtype=dtype),
            "value": dense_init(keys[3], d_model, 1, dtype=dtype),
            "final_ln": {"scale": jnp.ones((d_model,), dtype),
                         "bias": jnp.zeros((d_model,), dtype)},
            "blocks": [],
        }
        for i in range(num_layers):
            k = keys[5 + 6 * i: 5 + 6 * (i + 1)]
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
            # Stacked layout (leading dim = stages) so stage i's slice
            # lands on pp-device i through the pipeline shard_map.
            params["blocks"] = jax.tree.map(
                lambda *leaves: jnp.stack(leaves), *params["blocks"])
        return params

    def blocks_of(params):
        """Per-layer block list regardless of storage layout (list, or
        stacked (S, ...) leaves under pp — indexing the stacked leaves
        outside the pipeline shard_map lets XLA gather the slice, which
        only the small incremental/one-token paths do)."""
        if pp_mesh is None:
            return params["blocks"]
        return [jax.tree.map(lambda x: x[i], params["blocks"])
                for i in range(num_layers)]

    def block_apply(blk, x, positions, *, attn, kv_offset):
        """One banded pre-LN block over (B, S, d). Returns
        ``(x, (k_tail, v_tail), aux)`` — the rotated K/V of the cached
        window (always computed; a few window-length rows) and the FFN's
        MoE balance loss."""
        bsz, s_len = x.shape[0], x.shape[1]
        # Compute dtype follows the handed-in params (fp32 masters or the
        # precision policy's bf16 copy); the build ``dtype`` = master init.
        dtype = compute_dtype(blk)
        h = _layer_norm(x, blk["ln1"]["scale"], blk["ln1"]["bias"])
        qkv = dense(blk["qkv"], h).reshape(
            bsz, s_len, 3, num_heads, head_dim)
        q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))
        q = _rope(q, positions)
        k = _rope(k, positions)
        x_attn = attn(q, k, v, window)
        lo = s_len - window - kv_offset
        kv_tail = (k[:, :, lo:lo + window], v[:, :, lo:lo + window])
        x_attn = x_attn.transpose(0, 2, 1, 3).reshape(
            bsz, s_len, d_model).astype(dtype)
        x = x + dense(blk["proj"], x_attn)
        h = _layer_norm(x, blk["ln2"]["scale"], blk["ln2"]["bias"])
        y, aux = block_ffn(blk, h)
        return x + y, kv_tail, aux

    def forward(params, series, positions, port_feats, *, want_kv=False,
                attn=None, kv_offset=0):
        """Banded forward over a (B, S) tick series.

        ``port_feats`` (B, S, 3) is zero except at query positions. Returns
        (logits (B, S, A), values (B, S), per-layer rotated (k, v) lists
        when ``want_kv``, post-final_ln hidden (B, S, d), aux scalar).
        ``kv_offset`` shifts the cached window ``offset`` ticks back from
        the series end (the precomputed-rollout trunk's last tick belongs
        to the bootstrap position, one step past where the cache should
        stop). ``attn`` overrides the attention implementation (the prefill
        pins the LOCAL kernel: its sequence is the fixed L*(window-1)+1
        rows, too short to shard).
        """
        bsz, s_len = series.shape
        dtype = compute_dtype(params)
        x = dense(params["embed"], _tick_features(series).astype(dtype))
        if pp_mesh is not None:   # overrides rejected at build: always local
            x, kv, aux = _forward_blocks_pipelined(
                params, x, positions, kv_offset)
        else:
            attn = attn or attention_fn
            blk_fn = block_apply
            if remat_blocks:
                # Block-granular rematerialization: the backward recomputes
                # each block's internals (qkv, attention, FFN activations)
                # from its input, so only the O(S·d) block boundaries are
                # stored — the FLOPs-for-HBM trade that lets the d≥1024
                # tier run long replays without learner.remat's coarser
                # whole-pass checkpoint. Functionally a no-op (pinned by
                # test_models.py::test_remat_blocks_matches_exact).
                def blk_fn(blk, x, positions, *, attn, kv_offset):
                    return jax.checkpoint(
                        lambda b, h, p: block_apply(
                            b, h, p, attn=attn, kv_offset=kv_offset)
                    )(blk, x, positions)
            kv, aux = [], jnp.float32(0.0)
            for blk in blocks_of(params):
                x, kv_tail, blk_aux = blk_fn(
                    blk, x, positions, attn=attn, kv_offset=kv_offset)
                kv.append(kv_tail)
                aux = aux + blk_aux
        hn = _layer_norm(x, params["final_ln"]["scale"],
                         params["final_ln"]["bias"])
        hn_port = hn + dense(params["port"], port_feats.astype(dtype))
        logits = dense(params["policy"], hn_port).astype(jnp.float32)
        values = dense(params["value"], hn_port).astype(jnp.float32)[..., 0]
        return logits, values, (kv if want_kv else []), hn, aux

    def _forward_blocks_pipelined(params, x, positions, kv_offset):
        """The block stack as a GPipe pipeline over ``pp_axis``.

        Positions ride the pipeline state as one extra f32 channel (every
        stage applies RoPE at the same absolute indices; a pipeline stage
        receives exactly one state array). K/V tails and the per-block aux
        escape as pipeline side outputs (pipeline_apply side_template).
        Microbatches cut the agent batch when it divides by the stage
        count; otherwise the SEQUENCE is cut into streamed chunks
        (_forward_blocks_pipelined_seq) — the batch-of-1 trunk/shared-
        replay passes pipeline along time instead of idling
        (stages-1)/stages of the schedule. m=1 (full bubble) remains only
        for sequences too short to chunk.
        """
        bsz, s_len = x.shape[0], x.shape[1]
        stages = num_layers
        if bsz % stages == 0:
            return _forward_blocks_pipelined_batch(
                params, x, positions, kv_offset, m=stages)
        plan = _seq_chunk_plan(s_len, kv_offset)
        if plan is not None:
            return _forward_blocks_pipelined_seq(
                params, x, positions, kv_offset, plan)
        return _forward_blocks_pipelined_batch(
            params, x, positions, kv_offset, m=1)

    def _forward_blocks_pipelined_batch(params, x, positions, kv_offset, m):
        """Microbatches cut the AGENT batch (independent rows)."""
        from jax.sharding import PartitionSpec as P
        from sharetrade_tpu.parallel.pipeline import pipeline_apply
        dtype = compute_dtype(params)
        bsz, s_len = x.shape[0], x.shape[1]
        mb_b = bsz // m
        state = jnp.concatenate(
            [x.astype(jnp.float32),
             positions[..., None].astype(jnp.float32)], axis=-1)
        mb = state.reshape((m, mb_b) + state.shape[1:])
        b_axis = pp_batch_axis
        if b_axis is not None and mb_b % pp_mesh.shape[b_axis]:
            b_axis = None       # odd microbatch: replicate

        def stage_fn(blk, st):
            xb = st[..., :d_model].astype(dtype)
            pos = st[..., d_model].astype(jnp.int32)
            xb, (k_t, v_t), aux = block_apply(
                blk, xb, pos, attn=local_attention, kv_offset=kv_offset)
            if b_axis is not None:
                # The K/V sides carry their own (sharded) rows; the scalar
                # aux must be made uniform across the batch axis to honor
                # its replicated side spec.
                aux = jax.lax.pmean(aux, b_axis)
            out = jnp.concatenate(
                [xb.astype(jnp.float32), st[..., d_model:]], axis=-1)
            return out, {"k": k_t, "v": v_t, "aux": aux}

        if remat_blocks:
            # Per-(stage, tick) remat: the backward recomputes the block's
            # internals from the tick's input state, so a stage stores only
            # its schedule-tick boundaries.
            stage_fn = jax.checkpoint(stage_fn)

        # Side templates use the per-device LOCAL batch shape; the K/V
        # sides declare the batch axis in their specs so each dp shard
        # contributes its own rows (a replicated spec would silently hand
        # one shard's K/V to every agent).
        b_shard = 1 if b_axis is None else pp_mesh.shape[b_axis]
        side_template = {
            "k": jnp.zeros((mb_b // b_shard, num_heads, window, head_dim),
                           dtype),
            "v": jnp.zeros((mb_b // b_shard, num_heads, window, head_dim),
                           dtype),
            "aux": jnp.float32(0.0),
        }
        side_specs = {"k": P(None, None, b_axis),
                      "v": P(None, None, b_axis), "aux": P()}
        mb_out, sides = pipeline_apply(
            stage_fn, params["blocks"], mb, pp_mesh, axis=pp_axis,
            mb_spec=P(None, b_axis), side_template=side_template,
            side_specs=side_specs)
        x = mb_out[..., :d_model].reshape(bsz, s_len, d_model).astype(dtype)
        # sides: leaves (S_stages, M, ...). Reassemble per-layer K/V over
        # the microbatched agent axis; aux sums over stages (each stage's
        # aux is identical across its microbatches' mean contributions, so
        # sum over M then divide by M keeps the per-token mean semantics).
        kv = [(sides["k"][l].reshape(bsz, num_heads, window, head_dim),
               sides["v"][l].reshape(bsz, num_heads, window, head_dim))
              for l in range(num_layers)]
        aux = jnp.sum(sides["aux"]) / m
        return x, kv, aux

    def _seq_chunk_plan(s_len, kv_offset):
        """(m, chunk_len, pad) for sequence-chunk pipelining, or None when
        the sequence is too short for >1 chunk. Constraints (all static):
        chunk_len >= window-1 (the banded halo fits in one predecessor
        chunk, and the chunk-0 exact-head pass needs window-1 local rows)
        and the cache-tail slice must start inside [halo | chunk]
        (chunk_len - 1 - kv_offset - pad >= 0). More chunks shrink the
        GPipe bubble (stages-1)/(m+stages-1); 4*stages chunks put it under
        ~20% with diminishing returns beyond."""
        halo = window - 1
        if halo < 1:
            return None   # window=1: no band to carry, nothing to pipeline
        for m in range(min(s_len // halo, 4 * num_layers), 1, -1):
            chunk_len = -(-s_len // m)
            pad = m * chunk_len - s_len
            if chunk_len >= halo and chunk_len - 1 - kv_offset - pad >= 0:
                return m, chunk_len, pad
        return None

    def _forward_blocks_pipelined_seq(params, x, positions, kv_offset,
                                      plan):
        """Microbatches cut the SEQUENCE: chunk m streams through the
        stages right behind chunk m-1, and each stage hands its banded-
        attention halo (its chunk's last window-1 roped K/V rows) to the
        next chunk through a stage-local pipeline carry
        (parallel/pipeline.py carry_template) — sequential microbatches,
        the pipeline analogue of the sp halo exchange
        (parallel/episode_sp.py), with the same chunk-0 correction: the
        first chunk's zero halo would take softmax weight, so its first
        window-1 queries (whose bands sit entirely in the local prefix)
        are answered by a small plain-causal pass. End padding rides
        behind every real row, so causality keeps it invisible; the
        cache-tail side slices around it (static offset)."""
        from jax.sharding import PartitionSpec as P
        from sharetrade_tpu.parallel.pipeline import pipeline_apply
        dtype = compute_dtype(params)
        bsz, s_len = x.shape[0], x.shape[1]
        m, chunk_len, pad = plan
        halo = window - 1
        state = jnp.concatenate(
            [x.astype(jnp.float32),
             positions[..., None].astype(jnp.float32)], axis=-1)
        if pad:
            state = jnp.pad(state, ((0, 0), (0, pad), (0, 0)))
        mb = state.reshape(bsz, m, chunk_len, d_model + 1).transpose(
            1, 0, 2, 3)
        # Chunk-index flag channel: stage_fn selects the chunk-0 head
        # correction from it (a pipeline stage sees only its state array).
        flags = jnp.broadcast_to(
            jnp.arange(m, dtype=jnp.float32).reshape(m, 1, 1, 1),
            (m, bsz, chunk_len, 1))
        mb = jnp.concatenate([mb, flags], axis=-1)
        b_axis = pp_batch_axis
        if b_axis is not None and bsz % pp_mesh.shape[b_axis]:
            b_axis = None       # odd batch (the B=1 passes): replicate
        b_shard = 1 if b_axis is None else pp_mesh.shape[b_axis]
        b_loc = bsz // b_shard
        lo = chunk_len - 1 - kv_offset - pad  # tail start in [halo|chunk]

        def stage_fn(blk, st, carry):
            xb = st[..., :d_model].astype(dtype)
            pos = st[..., d_model].astype(jnp.int32)
            first = st[0, 0, d_model + 1] == 0.0
            b, c = xb.shape[0], xb.shape[1]
            h = _layer_norm(xb, blk["ln1"]["scale"], blk["ln1"]["bias"])
            qkv = dense(blk["qkv"], h).reshape(b, c, 3, num_heads, head_dim)
            q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))
            q = _rope(q, pos)
            k = _rope(k, pos)
            kv_k = jnp.concatenate([carry["k"], k], axis=2)
            kv_v = jnp.concatenate([carry["v"], v], axis=2)
            # Left-pad queries so q row j aligns with key row j; the pad
            # rows' outputs are sliced off (episode_sp.py alignment trick).
            qp = jnp.pad(q, [(0, 0), (0, 0), (halo, 0), (0, 0)])
            out = local_attention(qp, kv_k, kv_v, window)[:, :, halo:]
            head_exact = local_attention(
                q[:, :, :halo], k[:, :, :halo], v[:, :, :halo], window)
            head = jnp.where(first, head_exact, out[:, :, :halo])
            attn_out = jnp.concatenate([head, out[:, :, halo:]], axis=2)
            attn_out = attn_out.transpose(0, 2, 1, 3).reshape(
                b, c, d_model).astype(dtype)
            xb = xb + dense(blk["proj"], attn_out)
            h2 = _layer_norm(xb, blk["ln2"]["scale"], blk["ln2"]["bias"])
            y, aux = block_ffn(blk, h2)
            if b_axis is not None:
                aux = jax.lax.pmean(aux, b_axis)
            xb = xb + y
            side = {"k": kv_k[:, :, lo:lo + window],
                    "v": kv_v[:, :, lo:lo + window], "aux": aux}
            new_carry = {"k": k[:, :, -halo:], "v": v[:, :, -halo:]}
            out_st = jnp.concatenate(
                [xb.astype(jnp.float32), st[..., d_model:]], axis=-1)
            return out_st, side, new_carry

        if remat_blocks:
            stage_fn = jax.checkpoint(stage_fn)

        side_template = {
            "k": jnp.zeros((b_loc, num_heads, window, head_dim), dtype),
            "v": jnp.zeros((b_loc, num_heads, window, head_dim), dtype),
            "aux": jnp.float32(0.0),
        }
        side_specs = {"k": P(None, None, b_axis),
                      "v": P(None, None, b_axis), "aux": P()}
        carry_template = {
            "k": jnp.zeros((b_loc, num_heads, halo, head_dim), dtype),
            "v": jnp.zeros((b_loc, num_heads, halo, head_dim), dtype),
        }
        mb_out, sides = pipeline_apply(
            stage_fn, params["blocks"], mb, pp_mesh, axis=pp_axis,
            mb_spec=P(None, b_axis), side_template=side_template,
            side_specs=side_specs, carry_template=carry_template)
        x = mb_out[..., :d_model].transpose(1, 0, 2, 3).reshape(
            bsz, m * chunk_len, d_model)[:, :s_len].astype(dtype)
        # Cache tail: only the LAST chunk's side row is the real series
        # tail (earlier chunks' slices are discarded).
        kv = [(sides["k"][l, -1], sides["v"][l, -1])
              for l in range(num_layers)]
        aux = jnp.sum(sides["aux"]) / m
        return x, kv, aux

    _port_feats = portfolio_features  # shared head-side normalization

    def _prefill(params, obs):
        """Episode-start pass: [first-price pads | first window], caching
        the last ``window`` rotated K/Vs per layer."""
        bsz = obs.shape[0]
        win = obs[:, :window]
        pads = jnp.repeat(win[:, :1], hist_len, axis=1)
        series = jnp.concatenate([pads, win], axis=1)
        positions = jnp.broadcast_to(
            jnp.arange(-hist_len, window, dtype=jnp.int32)[None, :],
            series.shape)
        port = jnp.zeros(series.shape + (3,), jnp.float32)
        port = port.at[:, -1, :].set(
            _port_feats(obs[:, window], obs[:, window + 1], win[:, -1]))
        logits, values, kv, _hn, aux = forward(
            params, series, positions, port, want_kv=True,
            attn=local_attention)
        cache_k = jnp.stack([k for k, _ in kv], axis=1)  # (B, L, H, W, Dh)
        cache_v = jnp.stack([v for _, v in kv], axis=1)
        carry = {
            "k": cache_k, "v": cache_v,
            "hist": jnp.repeat(win[:, :1], hist_len, axis=1),
            "t": jnp.ones((bsz,), jnp.int32),
        }
        return ModelOut(logits=logits[:, -1], value=values[:, -1],
                        aux=aux), carry

    def _incremental(params, obs, carry):
        """One-token step against the CIRCULAR K/V cache.

        The cache is a ring, not a shift register: tick j lives at slot
        ``j mod window`` forever (the prefill writes ticks 0..window-1 at
        slots 0..window-1, and step t writes its new tick t+window-1 over
        the evicted tick t-1 — same slot mod window). One
        ``dynamic_update_slice`` per layer per K/V replaces the old
        implementation's full-buffer shift-and-restack, cutting per-step
        cache traffic from O(B·L·H·W·D) copies (~100 MB/step at the
        flagship shape — measured 70% of the whole training chunk,
        benchmarks/profile_flagship.py) to one written row. Attention over
        the ring needs no reordering: RoPE is applied at ABSOLUTE positions
        before caching and softmax attention is permutation-invariant over
        the key axis, so slot order never matters.
        """
        bsz = obs.shape[0]
        dtype = compute_dtype(params)
        new, prev = obs[:, window - 1], obs[:, window - 2]
        ret = (jnp.log(jnp.maximum(new, _EPS))
               - jnp.log(jnp.maximum(prev, _EPS)))
        tok = jnp.stack([ret, jnp.abs(ret), jnp.zeros_like(ret)], axis=-1)
        x = dense(params["embed"], tok.astype(dtype))[:, None, :]  # (B, 1, d)
        pos = (carry["t"] + window - 1).astype(jnp.int32)[:, None]  # (B, 1)
        # Ring slot of the evicted tick (lockstep batch: t[0] speaks for
        # all — the apply_batch invariant).
        slot = jnp.mod(carry["t"][0] - 1, window).astype(jnp.int32)

        k_cache, v_cache = carry["k"], carry["v"]     # (B, L, H, W, Dh)
        aux = jnp.float32(0.0)
        for li, blk in enumerate(blocks_of(params)):
            h = _layer_norm(x, blk["ln1"]["scale"], blk["ln1"]["bias"])
            qkv = dense(blk["qkv"], h).reshape(bsz, 1, 3, num_heads, head_dim)
            q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))
            q = _rope(q, pos)
            k = _rope(k, pos)
            zero = jnp.int32(0)
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, k[:, None], (zero, jnp.int32(li), zero, slot, zero))
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, v[:, None], (zero, jnp.int32(li), zero, slot, zero))
            k_all, v_all = k_cache[:, li], v_cache[:, li]
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k_all,
                           preferred_element_type=jnp.float32) * sm_scale
            probs = jax.nn.softmax(s, axis=-1).astype(v_all.dtype)
            attn = jnp.einsum("bhqk,bhkd->bhqd", probs, v_all)
            attn = attn.transpose(0, 2, 1, 3).reshape(
                bsz, 1, d_model).astype(dtype)
            x = x + dense(blk["proj"], attn)
            h = _layer_norm(x, blk["ln2"]["scale"], blk["ln2"]["bias"])
            y, blk_aux = block_ffn(blk, h)
            x = x + y
            aux = aux + blk_aux
        hn = _layer_norm(x[:, 0], params["final_ln"]["scale"],
                         params["final_ln"]["bias"])
        hn = hn + dense(params["port"], _port_feats(
            obs[:, window], obs[:, window + 1], new).astype(dtype))
        logits = dense(params["policy"], hn).astype(jnp.float32)
        values = dense(params["value"], hn).astype(jnp.float32)[..., 0]
        hist = carry["hist"]
        if hist_len:
            # Tick t (the window's oldest) leaves the window this step.
            hist = jnp.concatenate([hist[:, 1:], obs[:, :1]], axis=1)
        carry = {"k": k_cache, "v": v_cache,
                 "hist": hist, "t": carry["t"] + 1}
        return ModelOut(logits=logits, value=values, aux=aux), carry

    def _incremental_serve(params, obs, carry):
        """One-token step for a batch at HETEROGENEOUS episode steps — the
        serving batch (serve/engine.py). Same math as :func:`_incremental`
        (layer norm → qkv → RoPE at per-row absolute positions → ring
        write → cache attention → FFN → heads), with the ONE lockstep
        dependency removed: the ring slot is computed PER ROW
        (``mod(t_i - 1, window)``) and the cache write is a select over
        the window axis, so each session writes its own slot regardless of
        where its neighbors sit in their episodes. A select, not a vmapped
        ``dynamic_update_slice``: that is a scatter, which the TPU
        compiler runs as one ``while`` loop over the batch for every layer
        and cache, eight tiny operations a row (43% of a warm tick on the
        v5e and 4,096 device events — PERF.md, PR 29); the select moves the
        same bytes in one pass over the layer's rows. Kept as a separate
        function rather than generalizing ``_incremental``: the training
        path's scalar-slot write is part of the pinned fp32 golden
        trajectory (tests/golden/). Every row must be WARM (t >= 1) —
        cold rows belong to the batched prefill."""
        bsz = obs.shape[0]
        dtype = compute_dtype(params)
        new, prev = obs[:, window - 1], obs[:, window - 2]
        ret = (jnp.log(jnp.maximum(new, _EPS))
               - jnp.log(jnp.maximum(prev, _EPS)))
        tok = jnp.stack([ret, jnp.abs(ret), jnp.zeros_like(ret)], axis=-1)
        x = dense(params["embed"], tok.astype(dtype))[:, None, :]
        pos = (carry["t"] + window - 1).astype(jnp.int32)[:, None]  # (B, 1)
        slots = jnp.mod(carry["t"] - 1, window).astype(jnp.int32)   # (B,)

        k_cache, v_cache = carry["k"], carry["v"]     # (B, L, H, W, Dh)
        at_slot = (jnp.arange(k_cache.shape[3], dtype=jnp.int32)
                   == slots[:, None])[:, None, :, None]   # (B, 1, W, 1)
        aux = jnp.float32(0.0)
        for li, blk in enumerate(blocks_of(params)):
            h = _layer_norm(x, blk["ln1"]["scale"], blk["ln1"]["bias"])
            qkv = dense(blk["qkv"], h).reshape(bsz, 1, 3, num_heads, head_dim)
            q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))
            q = _rope(q, pos)
            k = _rope(k, pos)

            k_all = jnp.where(at_slot, k, k_cache[:, li])    # (B, H, W, Dh)
            v_all = jnp.where(at_slot, v, v_cache[:, li])
            k_cache = k_cache.at[:, li].set(k_all)
            v_cache = v_cache.at[:, li].set(v_all)
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k_all,
                           preferred_element_type=jnp.float32) * sm_scale
            probs = jax.nn.softmax(s, axis=-1).astype(v_all.dtype)
            attn = jnp.einsum("bhqk,bhkd->bhqd", probs, v_all)
            attn = attn.transpose(0, 2, 1, 3).reshape(
                bsz, 1, d_model).astype(dtype)
            x = x + dense(blk["proj"], attn)
            h = _layer_norm(x, blk["ln2"]["scale"], blk["ln2"]["bias"])
            y, blk_aux = block_ffn(blk, h)
            x = x + y
            aux = aux + blk_aux
        hn = _layer_norm(x[:, 0], params["final_ln"]["scale"],
                         params["final_ln"]["bias"])
        hn = hn + dense(params["port"], _port_feats(
            obs[:, window], obs[:, window + 1], new).astype(dtype))
        logits = dense(params["policy"], hn).astype(jnp.float32)
        values = dense(params["value"], hn).astype(jnp.float32)[..., 0]
        hist = carry["hist"]
        if hist_len:
            hist = jnp.concatenate([hist[:, 1:], obs[:, :1]], axis=1)
        out_carry = {"k": k_cache, "v": v_cache,
                     "hist": hist, "t": carry["t"] + 1}
        return ModelOut(logits=logits, value=values, aux=aux), out_carry

    def apply_batch(params, obs, carry):
        """Batched rollout step.

        INVARIANT: the whole batch must sit at the same episode step —
        prefill-vs-incremental dispatches on ``carry["t"][0]`` alone. This
        holds for every env in this framework (the batch resets and steps in
        lockstep; rollout.py freezes finished agents in place rather than
        resetting them), but an env with per-agent resets or a
        heterogeneously-restored carry would silently run the wrong path for
        some agents. Eager (non-traced) calls assert the uniformity."""
        t = carry["t"]
        if not isinstance(t, jax.core.Tracer):
            import numpy as _np
            tn = _np.asarray(t)
            if tn.size and (tn.min() != tn.max()):
                raise ValueError(
                    f"episode transformer requires a lockstep batch: carry "
                    f"t spans [{tn.min()}, {tn.max()}]")
        return jax.lax.cond(
            t[0] == 0,
            lambda c: _prefill(params, obs),
            lambda c: _incremental(params, obs, c),
            carry)

    def apply(params, obs, carry):
        carry_b = jax.tree.map(lambda x: x[None], carry)
        outs, new_c = apply_batch(params, obs[None], carry_b)
        return (ModelOut(logits=outs.logits[0], value=outs.value[0],
                         aux=outs.aux),
                jax.tree.map(lambda x: x[0], new_c))

    def apply_unroll(params, obs, carry):
        """Training replay: ONE banded pass over [history | chunk ticks].

        ``obs`` is the stored (T, B, obs_dim) trajectory; ``carry`` the
        batched episode carry at unroll START, or its ``replay_carry``:
        only ``t`` and ``hist`` are read. Returns (logits (T, B, A),
        values (T, B), aux scalar).
        """
        t_len, bsz = obs.shape[0], obs.shape[1]
        first_win = obs[0, :, :window]                     # ticks t0..t0+W-1
        newer = obs[1:, :, window - 1].T                   # (B, T-1)
        t0 = carry["t"].astype(jnp.int32)                  # (B,)
        # At episode start the carry's history is the init_carry zeros the
        # prefill never saw; substitute the first-price padding the prefill
        # actually used so both paths read the same series.
        hist = _pin_hist(
            jnp.where((t0 == 0)[:, None], first_win[:, :1], carry["hist"]))
        series = jnp.concatenate([hist, first_win, newer], axis=1)
        s_len = hist_len + window + t_len - 1
        positions = (t0[:, None] - hist_len
                     + jnp.arange(s_len, dtype=jnp.int32)[None, :])
        q_pos = hist_len + window - 1 + jnp.arange(t_len)  # static indices
        anchor = obs[:, :, window - 1]                     # (T, B)
        feats = _port_feats(obs[:, :, window], obs[:, :, window + 1], anchor)
        port = jnp.zeros((bsz, s_len, 3), jnp.float32)
        port = port.at[:, q_pos, :].set(feats.swapaxes(0, 1))
        logits, values, _kv, _hn, aux = forward(
            params, series, positions, port)
        return (logits[:, q_pos].swapaxes(0, 1),
                values[:, q_pos].swapaxes(0, 1), aux)

    def _head_fold(params):
        """The (3 -> A)/(3 -> 1) folded portfolio-head matrices of the
        factored head (f32): shared by rollout_head_factored AND the
        shared replay so their op order — and thus their bf16 rounding —
        can never diverge. Differentiable (the folds stay in the graph)."""
        # precision-cast-ok (x4): deliberate f32 UPCASTS for the folded
        # head matrices — the fold must not compound bf16 rounding, and an
        # upcast of compute-copy leaves never touches the master contract.
        wp = params["port"]["w"].astype(jnp.float32)      # precision-cast-ok
        bp = params["port"]["b"].astype(jnp.float32)      # precision-cast-ok
        wl = params["policy"]["w"].astype(jnp.float32)    # precision-cast-ok
        wv = params["value"]["w"].astype(jnp.float32)     # precision-cast-ok
        return wp @ wl, bp @ wl, (wp @ wv)[:, 0], (bp @ wv)[0]

    def replay_carry(carry):
        """What the replays read of the unroll-start carry (models/core.py
        Model.replay_carry): ``hist`` and ``t``, and ``ok`` — row health of
        the WHOLE carry, K/V included, by THE predicate ``rows_finite`` —
        which the shared replay's election needs of every row. Taken once
        per chunk by the rollout; the K/V cache (L x H x window x Dh a
        row) never enters the update phase."""
        return {"hist": carry["hist"], "t": carry["t"],
                "ok": rows_finite(carry, carry["t"].shape[0])}

    def apply_unroll_shared(params, obs, carry):
        """Training replay with the trunk's factor-B agent redundancy
        removed: every healthy agent's price series is IDENTICAL (the
        lockstep-batch agent-invariance of agents/rollout.py), so the
        banded pass of ``apply_unroll`` runs ONCE for a representative row
        and only the portfolio-feature head runs per agent. Same outputs
        as ``apply_unroll``; ``carry`` is the REPLAY carry
        (``replay_carry`` above: ``hist``, ``t`` and the health vector
        ``ok``), never the K/V cache. Gradients are exact (B identical
        trunk paths each pulled back by one agent's head cotangent equal
        one shared path pulled back by their sum).

        The representative must be a live row at EVERY step of the unroll:
        a quarantined agent's stored observation is zero-sanitized (prices
        are strictly positive), and a row quarantined MID-unroll — the
        normal fault timing — has real early steps but a zeroed tail, so
        electing on step 0 alone could pick a row whose tail feeds
        eps-clamped garbage into every healthy agent's trunk. Electing the
        row with the MOST healthy steps (anchor price real) dominates both
        edge cases: a fully-healthy row wins outright (count T), and when
        every row is partially quarantined the longest-healthy row
        corrupts the fewest unmasked steps — an all-steps predicate would
        instead fall back to row 0, which could be a fully-zeroed row.
        Rows whose unroll-start carry is non-finite (``carry["ok"]``
        False: the rollout election's carry term,
        agents/base.election_health, over the same array) are excluded
        outright: a NaN carry['hist']/['t'] would poison the ONE shared
        banded pass for every agent, and a row whose K/V alone is NaN is
        no representative the rollout would have taken either. If every
        carry is poisoned, row 0 wins and the non-finite loss escalates to
        the orchestrator's restore — correct when the whole batch is
        beyond a row-level heal.
        """
        t_len = obs.shape[0]
        counts = jnp.sum(obs[:, :, window - 1] > 0, axis=0)
        rep = jnp.argmax(
            jnp.where(carry["ok"], counts, -1)).astype(jnp.int32)
        obs1 = jax.lax.dynamic_index_in_dim(obs, rep, 1, keepdims=True)
        take_rep = lambda x: jax.lax.dynamic_index_in_dim(
            x, rep, 0, keepdims=True)
        first_win = obs1[0, :, :window]                 # (1, W)
        newer = obs1[1:, :, window - 1].T               # (1, T-1)
        t0 = take_rep(carry["t"]).astype(jnp.int32)     # (1,)
        hist = _pin_hist(jnp.where((t0 == 0)[:, None], first_win[:, :1],
                                   take_rep(carry["hist"])))
        series = jnp.concatenate([hist, first_win, newer], axis=1)
        s_len = hist_len + window + t_len - 1
        positions = (t0[:, None] - hist_len
                     + jnp.arange(s_len, dtype=jnp.int32)[None, :])
        port = jnp.zeros((1, s_len, 3), jnp.float32)
        _logits, _values, _kv, hn, aux = forward(
            params, series, positions, port)
        q_pos = hist_len + window - 1 + jnp.arange(t_len)
        hn_q = hn[0, q_pos]                             # (T, d)
        # Per-agent head, in the same FACTORED form as the rollout's
        # (rollout_head_factored): base projections over the T shared
        # trunk rows + the 3-wide portfolio term per agent-step. Keeping
        # the op order identical to the rollout head makes stored logp and
        # replayed logp agree to rounding even at bf16 (split forms
        # diverge by ~bf16 eps, which would bias the PPO ratios at epoch
        # 1), and drops the replay's per-agent d-sized head GEMMs.
        base_l = dense(params["policy"], hn_q).astype(jnp.float32)  # (T, A)
        base_v = dense(params["value"], hn_q).astype(jnp.float32)[..., 0]
        w_pl, b_pl, w_pv, b_pv = _head_fold(params)
        anchor = obs[:, :, window - 1]                  # (T, B)
        feats = _port_feats(obs[:, :, window], obs[:, :, window + 1],
                            anchor).astype(jnp.float32)
        logits = base_l[:, None] + feats @ w_pl + b_pl
        values = base_v[:, None] + feats @ w_pv + b_pv
        return logits, values, aux

    def apply_rollout_trunk(params, obs, future_ticks, carry):
        """Whole-unroll trunk in ONE banded pass (the precomputed-rollout
        path, models/core.py): attention sees only price ticks, and prices
        are action-independent, so the trunk for every future step of the
        unroll is computable ahead of the env loop — the same series
        construction as ``apply_unroll``, plus one extra position for the
        bootstrap value. Replaces T sequential cache-attention steps
        (measured 70% of the flagship chunk) with one replay-shaped pass.

        ``future_ticks`` (B, T): the tick that enters the window at each of
        the next T env steps. Returns (hn_base (B, T+1, d), carry after T
        steps — ring-layout K/V refreshed so a later incremental ``apply``
        continues seamlessly).
        """
        bsz, t_len = future_ticks.shape
        t0 = carry["t"].astype(jnp.int32)
        first_win = obs[:, :window]
        # Episode start: substitute the prefill's first-price padding for
        # the init_carry zeros (same rule as apply_unroll).
        hist = _pin_hist(
            jnp.where((t0 == 0)[:, None], first_win[:, :1], carry["hist"]))
        series = jnp.concatenate(
            [hist, first_win, future_ticks.astype(jnp.float32)], axis=1)
        s_len = hist_len + window + t_len
        positions = (t0[:, None] - hist_len
                     + jnp.arange(s_len, dtype=jnp.int32)[None, :])
        port = jnp.zeros((bsz, s_len, 3), jnp.float32)
        _logits, _values, kv, hn, _aux = forward(
            params, series, positions, port, want_kv=True, kv_offset=1)
        q_pos = hist_len + window - 1 + jnp.arange(t_len + 1)
        hn_base = hn[:, q_pos]
        # Carry after T steps. The cached window (kv_offset=1) is ticks
        # [t_end-1, t_end+window-2] in series order; the ring layout stores
        # tick j at slot j mod window, so roll by (t_end-1) mod window.
        t_end = t0 + t_len
        shift = jnp.mod(t_end[0] - 1, window)   # lockstep batch invariant
        cache_k = jnp.roll(jnp.stack([k for k, _ in kv], axis=1),
                           shift, axis=3)
        cache_v = jnp.roll(jnp.stack([v for _, v in kv], axis=1),
                           shift, axis=3)
        hist_next = (series[:, t_len:t_len + hist_len] if hist_len
                     else carry["hist"])
        return hn_base, {"k": cache_k, "v": cache_v,
                         "hist": hist_next, "t": t_end}

    def apply_rollout_head(params, hn_row, obs):
        """The state-dependent remainder of the forward: inject the
        portfolio features and read the policy/value heads — a few
        (B, d)-sized ops per env step."""
        dtype = compute_dtype(params)
        hn = hn_row.astype(dtype) + dense(params["port"], _port_feats(
            obs[:, window], obs[:, window + 1],
            obs[:, window - 1]).astype(dtype))
        logits = dense(params["policy"], hn).astype(jnp.float32)
        values = dense(params["value"], hn).astype(jnp.float32)[..., 0]
        return ModelOut(logits=logits, value=values, aux=jnp.float32(0.0))

    def rollout_head_factored(params, hn_base):
        """The rollout head with its linearity exploited (models/core.py
        field doc): dense(policy, hn + dense(port, feats)) ==
        [dense(policy, hn)] + [feats @ (Wp Wl) + bp Wl]. The first term is
        one (T+1, d) x (d, A) matmul over the whole unroll's precomputed
        trunk; the second is a (3 -> A) contraction per step — removing
        the d-sized per-iteration GEMMs that bound the d=256 flagship
        scan (BASELINE.md round-5 section). Exact up to float
        reassociation; the combined matrices are folded in f32."""
        dtype = compute_dtype(params)
        base_logits = dense(params["policy"],
                            hn_base.astype(dtype)).astype(jnp.float32)
        base_values = dense(params["value"],
                            hn_base.astype(dtype)).astype(jnp.float32)[..., 0]
        w_pl, b_pl, w_pv, b_pv = _head_fold(params)

        def pf_fn(obs):
            feats = _port_feats(obs[:, window], obs[:, window + 1],
                                obs[:, window - 1]).astype(jnp.float32)
            return feats @ w_pl + b_pl, feats @ w_pv + b_pv

        return base_logits, base_values, pf_fn

    def init_carry():
        return {
            "k": jnp.zeros((num_layers, num_heads, window, head_dim), dtype),
            "v": jnp.zeros((num_layers, num_heads, window, head_dim), dtype),
            "hist": jnp.zeros((hist_len,), jnp.float32),
            "t": jnp.int32(0),
        }

    def cast_carry_fn(carry, to_dtype):
        """Precision-policy carry cast (models/core.py Model.cast_carry):
        the K/V cache follows the compute dtype — every forward writes
        rotated keys/values in that dtype, so a mismatched cache is a
        dynamic_update_slice/cond aval error, not a slowdown — while
        ``hist`` stays f32: it holds raw PRICES that prefill/trunk always
        rebuild from f32 observations (casting it would flip the scan
        carry dtype mid-episode AND quantize the tick stream)."""
        out = dict(carry)
        out["k"] = carry["k"].astype(to_dtype)  # precision-cast-ok: policy hook
        out["v"] = carry["v"].astype(to_dtype)  # precision-cast-ok: policy hook
        return out

    return Model(init=init, apply=apply, apply_batch=apply_batch,
                 apply_unroll=apply_unroll, init_carry=init_carry,
                 cast_carry=cast_carry_fn,
                 apply_prefill=_prefill,
                 apply_serve_batch=_incremental_serve,
                 apply_unroll_shared=apply_unroll_shared,
                 replay_carry=replay_carry,
                 apply_rollout_trunk=apply_rollout_trunk,
                 apply_rollout_head=apply_rollout_head,
                 rollout_head_factored=rollout_head_factored,
                 obs_dim=obs_dim, num_actions=num_actions,
                 name="transformer_episode")
