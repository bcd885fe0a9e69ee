"""Latent-attention, routed-expert, hyper-connected episode trunk
(``model.kind="latent_moe"``, ``seq_mode="episode"``): the block stack of
the DeepSeek-V3 lineage with manifold-constrained hyper-connections
(arXiv:2512.24880), served as this system's policy trunk. SERVE-ONLY:
``cli serve`` / ``ServeEngine`` run it through ``apply_prefill`` and
``apply_serve_batch``; ``Model.trainable`` is False, by which the training
loop refuses it before anything compiles (runtime/orchestrator.py).

Input and output are the system's (models/transformer_episode.py): tick
features -> ``embed``; the stack's output -> final RMSNorm -> ``+
port(wallet)`` -> ``policy`` / ``value``. Attention is causal over the last
``window`` ticks, RoPE at absolute tick indices applied before caching.

Per layer, around each of its two sub-layers F (attention, FFN), n residual
streams X (n, d) are mixed by one hyper-connection: from x~ = RMSNorm(vec X)
(no learned scale), H_pre = sigmoid(a_pre x~ Phi_pre + b_pre), H_post =
2 sigmoid(.), H_res = Sinkhorn(exp(clip(a_res mat(x~ Phi_res) + B_res))),
rows then columns, each divided by its sum + eps; X <- H_res X + H_post^T
(x) F(RMSNorm(H_pre X)).

- **Latent attention**: c_q = RMSNorm(x W_qa); [q_nope | q_r] = c_q W_qb per
  head; [c_kv | k_r] = x W_kva, c_kv <- RMSNorm(c_kv), RoPE on k_r (one key
  for all heads) and q_r; [k_nope | v] = c_kv W_kvb per head. THE CACHE
  HOLDS c_kv AND k_r: ``kv_lora_rank + qk_rope_head_dim`` numbers a tick a
  layer, in float32 whatever the precision policy (``_attend``). The
  prefill expands K and V; the warm step uses the absorbed form
  (q_nope W_kvb,k into the latent space, scores and the weighted sum over
  c_kv, then W_kvb,v).
- **Experts** (layers after the ``dense_layers`` leading SwiGLU ones):
  float32 sigmoid scores over ALL ``moe_experts``; the ``moe_top_k`` largest
  of score + bias are chosen (the bias moves the choice alone); weights =
  ``moe_routed_scale`` x chosen scores / their sum; y = sum over the chosen
  experts HELD HERE (``moe_held_first`` .. + ``moe_held_experts``: the
  share one chip of an expert-parallel deployment holds; what the absent
  experts would add is left out and nothing stands in for their chip) +
  the shared expert. NO TOKEN IS DROPPED: there is no capacity. A warm tick
  runs every held expert over every row under the routing weights (three
  plain matmuls streaming the bank once: bandwidth-bound at serving
  batches); the prefill groups its tokens by expert (sorted into blocks of
  one expert each, worst case sized, unused blocks skipped).
- **Precision** under ``bf16_mixed``: the weights are the policy's bf16
  copy (``init`` draws them representable, as a published checkpoint's
  are); the residual streams, norms, hyper-connection maps, router scores,
  attention and the cached latents are float32, and float32 activations
  meet bf16 weights as two rows (``_mm``). A routed pick is a step
  function of the hidden state: with bf16 activations or latents one pick
  in a hundred differed from the float32 reference's, each moving a logit
  by a tenth of its span (PERF.md, PR 33).

``ModelOut.stats`` of a warm step is each row's picks (B, expert layers,
top_k) int32; ``Model.serve_stats`` turns a tick's real rows into the
``serve_moe_*`` counters on the host.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from sharetrade_tpu.config import ConfigError, ModelConfig
from sharetrade_tpu.models.core import (
    Model, ModelOut, dense_init, portfolio_features)
from sharetrade_tpu.models.transformer_episode import _tick_features

_EPS = 1e-6
_INIT_STD = 0.02         # the family's initialiser
_HC_ALPHA = 0.1          # dynamic scales of the hyper-connection maps
_HC_RES_DIAG = 2.0       # B_res = 2 I
_PREFILL_ROWS = 8        # sessions one prefill pass holds at a time
_QUERY_BLOCK = 256       # queries a banded prefill attention block holds
_GROUP_BLOCK = 512       # rows of one expert a grouped block holds


def _mm(x, w):
    """x @ w, accumulated and returned in float32. Float32 weights (the
    masters): one plain product. Lower-precision weights (the precision
    policy's compute copy): the float32 activations go through the matrix
    unit as TWO rows each, their value rounded to the weights' dtype and
    what the rounding left over, stacked along the row axis so that the
    weights are streamed ONCE, and the two results are added. The weights
    (whose values the compute copy holds exactly: ``init`` draws them
    representable) set the tick's time, not the rows; and an activation
    rounded to 8 bits before every product moves the router's scores enough
    to flip a pick in a hundred, each flip a whole expert's worth of the
    layer's result (PERF.md, PR 33)."""
    if w.dtype == jnp.float32:
        return jnp.dot(x.astype(jnp.float32), w)
    lead, x = x.shape[:-1], x.astype(jnp.float32).reshape(-1, x.shape[-1])
    # reduce_precision, not a cast there and back: the TPU compiler is
    # allowed excess precision and deletes such a pair of converts, which
    # leaves ``low`` zero and the activations rounded after all.
    info = jnp.finfo(w.dtype)
    high = jax.lax.reduce_precision(x, info.nexp, info.nmant)
    both = jnp.dot(jnp.concatenate([high, x - high]).astype(w.dtype), w,
                   preferred_element_type=jnp.float32)
    return (both[:x.shape[0]] + both[x.shape[0]:]).reshape(
        lead + (w.shape[-1],))


def _attend(spec, a, b):
    """One of attention's small products, float32 in and out at ``highest``
    (the TPU's default multiplies float32 in one bfloat16 pass). Queries,
    keys, values and the CACHED LATENTS are float32 whatever the weights'
    dtype: at layer 0 the residual stream is the tick embedding alone, a
    hundredth of an attention output, so what attention rounds off is what
    the first FFN's norm scales up, and from there it reaches every later
    router. Latents cached in bfloat16 flipped a pick in three hundred
    however exact the rest (a CPU rehearsal at d 512, PERF.md, PR 33);
    float32 latents flipped none, and a float32 latent row is still seven
    times smaller than per-head keys and values in bfloat16."""
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, scale, eps):
    """In float32, returned in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """RoPE's frequencies, YaRN-blended between the base's (fast
    dimensions) and the base's over the factor (slow ones)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float32) / dim)

    def correction_dim(rotations):
        return (dim * math.log(cfg.rope_yarn_original
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.rope_yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_yarn_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (plain / cfg.rope_yarn_factor * ramp
            + plain * (1.0 - ramp)).astype(np.float32)


def _rope(x, positions, inv_freq):
    """x (B, ..., S, D), pairs (i, i + D/2); positions (B, S) absolute."""
    half = x.shape[-1] // 2
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 3) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def sinkhorn(logits, iters: int, eps: float, clamp: float):
    """(N, n, n) float32 -> ``iters`` rounds of rows then columns, each
    divided by its sum + eps, from exp of the clamped logits. Worked on as
    (n, n, N) with the sums written as adds of slices, so the whole
    iteration is elementwise over N and compiles into one fused pass
    instead of 2 x iters small reductions."""
    n = logits.shape[-1]
    m = jnp.exp(jnp.clip(logits, -clamp, clamp)).transpose(1, 2, 0)
    for _ in range(iters):
        m = m / (sum(m[:, j] for j in range(n))[:, None] + eps)
        m = m / (sum(m[i] for i in range(n))[None] + eps)
    return m.transpose(2, 0, 1)


def route(p, x, top_k: int, scale: float):
    """The router over all its experts -> (picked (N, top_k) int32, their
    weights (N, top_k) float32). Scores are float32 whatever ``x`` is, the
    product at ``highest``: the TPU's default multiplies float32 in one
    bfloat16 pass, and a pick that rounding flips moves the layer's result
    by a whole expert."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + p["bias"].astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return (chosen.astype(jnp.int32),
            scale * picked / jnp.sum(picked, -1, keepdims=True))


def swiglu(p, x):
    """W_d(silu(W_g x) * (W_u x)). Three matrices, three plain matmuls: a
    fused [W_g | W_u] is sliced apart again by the compiler, as a copy of
    the weights, every call."""
    return _mm(jax.nn.silu(_mm(x, p["w_gate"])) * _mm(x, p["w_up"]),
               p["w_down"])


def experts_dense(p, x, local, weights, held: int):
    """Every held expert over every row under the routing weights (zero
    where a row did not pick the expert): three plain matmuls that stream
    the bank once. ``local`` (N, top_k): picks as indices into the held
    bank, out of range where the pick is held elsewhere. The bank is stored
    two-dimensional, the experts side by side (``w_gate``, ``w_up`` (d, held
    x F), ``w_down`` (held x F, d)), and nothing between the matmuls
    reshapes their results: a three-dimensional bank, or a result split by
    expert, has the compiler relay out the whole bank every tick."""
    ffn = p["w_down"].shape[0] // held
    gate = jnp.sum(jnp.where(
        local[..., None] == jnp.arange(held, dtype=jnp.int32),
        weights[..., None], 0.0), axis=1)                        # (N, held)
    hid = (jax.nn.silu(_mm(x, p["w_gate"])) * _mm(x, p["w_up"])
           * jnp.repeat(gate, ffn, axis=1))
    return _mm(hid, p["w_down"])


def experts_grouped(p, x, local, weights, held: int):
    """The same result with each held expert run over its own tokens only.
    The (token, pick) pairs are sorted by expert into blocks of
    ``block`` rows of ONE expert each (an expert's last block padded with
    zero-weight rows); the block count is sized for the worst case (every
    pick held here), and blocks past the ones in use are skipped, so no
    pick is ever dropped. Gathers only: no scatter."""
    n, top_k = local.shape
    d, ffn = x.shape[1], p["w_down"].shape[0] // held
    pairs = n * top_k
    block = min(_GROUP_BLOCK, max(8, -(-pairs // (held * 8)) * 8))
    n_blocks = -(-pairs // block) + held
    rows = n_blocks * block

    here = (local >= 0) & (local < held)
    flat_e = jnp.where(here, local, held).reshape(-1)          # absent last
    _, order = jax.lax.sort(
        (flat_e, jnp.arange(pairs, dtype=jnp.int32)), num_keys=1)
    counts = jnp.sum(flat_e[:, None] == jnp.arange(held, dtype=jnp.int32),
                     axis=0, dtype=jnp.int32)                    # (held,)
    padded = -(-counts // block) * block
    ends_padded = jnp.cumsum(padded)
    starts_padded = ends_padded - padded
    starts_sorted = jnp.cumsum(counts) - counts
    used_blocks = ends_padded[-1] // block

    # Each padded row's source: its block's expert, its rank in the group.
    block_expert = jnp.minimum(jnp.searchsorted(
        ends_padded, jnp.arange(n_blocks, dtype=jnp.int32) * block,
        side="right"), held - 1).astype(jnp.int32)
    row_expert = jnp.repeat(block_expert, block)
    rank = jnp.arange(rows, dtype=jnp.int32) - starts_padded[row_expert]
    live = rank < counts[row_expert]       # false past the blocks in use
    src_pair = order[jnp.clip(starts_sorted[row_expert] + rank, 0, pairs - 1)]
    row_weight = jnp.where(live, weights.reshape(-1)[src_pair], 0.0)
    xs = x[src_pair // top_k]                         # (rows, d) float32

    def one_block(_, b):
        def run():
            e = block_expert[b]
            xb = jax.lax.dynamic_slice_in_dim(xs, b * block, block)
            wb = jax.lax.dynamic_slice_in_dim(row_weight, b * block, block)
            one = {name: jax.lax.dynamic_slice_in_dim(
                p[name], e * ffn, ffn, axis) for name, axis in
                (("w_gate", 1), ("w_up", 1), ("w_down", 0))}
            hid = (jax.nn.silu(_mm(xb, one["w_gate"])) * _mm(xb, one["w_up"])
                   * wb[:, None])
            return _mm(hid, one["w_down"])

        return None, jax.lax.cond(
            b < used_blocks, run, lambda: jnp.zeros((block, d), jnp.float32))

    _, ys = jax.lax.scan(one_block, None,
                         jnp.arange(n_blocks, dtype=jnp.int32))
    ys = jnp.concatenate([ys.reshape(rows, d), jnp.zeros((1, d))])

    # Back to the tokens: where each pair's row went (absent picks read the
    # zero row), summed over a token's picks.
    inverse = jnp.argsort(order).astype(jnp.int32)             # pair -> rank
    pair_e = jnp.minimum(flat_e, held - 1)
    dest = jnp.where(flat_e < held,
                     starts_padded[pair_e] + inverse - starts_sorted[pair_e],
                     rows).reshape(n, top_k)
    return sum(ys[dest[:, j]] for j in range(top_k))


def expert_layer(p, x, *, top_k: int, scale: float, held_first: int,
                 held: int, grouped: bool):
    """x (N, d) -> (this chip's part of the layer's result + the shared
    expert, the picks (N, top_k))."""
    chosen, weights = route(p, x, top_k, scale)
    local = chosen - held_first
    bank = experts_grouped if grouped else experts_dense
    y = bank(p, x, local, weights, held)
    for shared in p["shared"]:
        y = y + swiglu(shared, x)
    return y, chosen


def latent_moe_episode_policy(obs_dim: int, num_actions: int,
                              cfg: ModelConfig, *,
                              dtype=jnp.float32) -> Model:
    """Build the policy from ``cfg`` (``model.kind="latent_moe"``)."""
    window = obs_dim - 2
    d, n_layers, heads = cfg.hidden_dim, cfg.num_layers, cfg.num_heads
    q_rank, kv_rank = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rdim, vdim = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim)
    n_streams = cfg.hc_streams
    routed, top_k = cfg.moe_experts, cfg.moe_top_k
    held_n = cfg.moe_held_experts or routed
    held_first = cfg.moe_held_first
    n_dense = cfg.dense_layers
    n_moe = n_layers - n_dense
    if rdim % 2:
        raise ConfigError(f"RoPE needs an even qk_rope_head_dim, got {rdim}")
    if not 0 <= n_dense <= n_layers:
        raise ConfigError(
            f"model.dense_layers ({n_dense}) must lie in 0..num_layers "
            f"({n_layers})")
    if n_moe and not (0 < top_k <= routed and held_n >= 1
                      and 0 <= held_first and held_first + held_n <= routed):
        raise ConfigError(
            f"latent_moe needs 0 < moe_top_k ({top_k}) <= moe_experts "
            f"({routed}) and a held range inside them (first {held_first}, "
            f"held {held_n})")
    hist_len = (n_layers - 1) * (window - 1)
    # The ring as the arena holds it: the window axis padded to a tile's
    # rows (16 covers a bfloat16 tile too), the latents' to whole 128-lane
    # registers. With 201 ticks second-minor the TPU compiler relays out the WHOLE arena around
    # every tick's gather and scatter (2.3 GB of temporaries, described-chip
    # compile, PR 33); slots and lanes past the real ones stay zero and are
    # masked out of the scores.
    ring = -(-window // 16) * 16
    c_lanes, r_lanes = -(-kv_rank // 128) * 128, -(-rdim // 128) * 128
    inv_freq = yarn_inv_freq(cfg)
    yarn_m = (0.1 * math.log(cfg.rope_yarn_factor) + 1.0
              if cfg.rope_yarn_factor > 1 else 1.0)
    sm_scale = (nope + rdim) ** -0.5 * yarn_m * yarn_m
    eps = cfg.rms_norm_eps

    # ---- parameters (the recipe chipbench/models/xing4.py re-derives)

    def init(key):
        keys = jax.random.split(key, 4 + n_layers)

        def rounded(x):
            """Representable in bfloat16, the dtype the family's
            checkpoints are published in: the float32 masters and the
            precision policy's bf16 compute copy then hold the same
            numbers, as a served checkpoint's would."""
            return jax.lax.reduce_precision(x, 8, 7)    # survives a jit

        def normal(k, shape):
            return rounded(jax.random.normal(k, shape, dtype) * jnp.asarray(
                _INIT_STD, dtype))

        def system_dense(k, i, o, scale=None):
            p = dense_init(k, i, o, scale=scale, dtype=dtype)
            return {"w": rounded(p["w"]), "b": p["b"]}

        def hyper(k):
            n = n_streams
            bias = jnp.concatenate([
                jnp.full((n,), math.log(1.0 / (n - 1.0)) if n > 1 else 0.0,
                         dtype),
                jnp.zeros((n,), dtype),
                jnp.eye(n, dtype=dtype).reshape(-1) * _HC_RES_DIAG])
            return {"phi": normal(k, (n * d, 2 * n + n * n)),
                    "alpha": rounded(jnp.full((3,), _HC_ALPHA, dtype)),
                    "bias": rounded(bias)}

        params = {
            "embed": system_dense(keys[0], 3, d),
            "port": system_dense(keys[1], 3, d, 0.02),
            "policy": system_dense(keys[2], d, num_actions, 0.01),
            "value": system_dense(keys[3], d, 1),
            "final_norm": jnp.ones((d,), dtype), "blocks": []}
        for i in range(n_layers):
            k = jax.random.split(keys[4 + i], 11)
            blk = {
                "attn": {
                    "wq_a": normal(k[0], (d, q_rank)),
                    "q_norm": jnp.ones((q_rank,), dtype),
                    "wq_b": normal(k[1], (q_rank, heads * (nope + rdim))),
                    "wkv_a": normal(k[2], (d, kv_rank + rdim)),
                    "kv_norm": jnp.ones((kv_rank,), dtype),
                    "wkv_b": normal(k[3], (kv_rank, heads * (nope + vdim))),
                    "wo": normal(k[4], (heads * vdim, d))},
                "attn_norm": jnp.ones((d,), dtype),
                "ffn_norm": jnp.ones((d,), dtype),
                "hc_attn": hyper(k[5]), "hc_ffn": hyper(k[6])}
            def ffn_weights(kg, ku, kd, width):
                return {"w_gate": normal(kg, (d, width)),
                        "w_up": normal(ku, (d, width)),
                        "w_down": normal(kd, (width, d))}

            if i < n_dense:
                blk["mlp"] = ffn_weights(k[7], k[8], k[9], cfg.dense_ffn_dim)
            else:
                f = cfg.moe_ffn_dim
                ks = jax.random.split(k[10], 3 * cfg.moe_shared_experts + 1)
                blk["moe"] = {
                    "router": normal(ks[0], (d, routed)),
                    "bias": jnp.zeros((routed,), dtype),
                    **ffn_weights(k[7], k[8], k[9], held_n * f),
                    "shared": [
                        ffn_weights(*ks[1 + 3 * j: 4 + 3 * j], f)
                        for j in range(cfg.moe_shared_experts)]}
            params["blocks"].append(blk)
        return params

    # ---- the residual path

    def hyper_connect(p, streams, norm_scale, branch):
        """streams (N, n, d) float32 -> H_res X + H_post^T (x)
        F(RMSNorm(H_pre X)), and whatever else ``branch`` returns. The
        streams, the maps and the norms stay float32 whatever the weights'
        dtype: they cost a few elementwise passes, and every rounding of the
        residual path moves a router score toward a flipped pick."""
        n = n_streams
        with jax.named_scope("mhc"):
            flat = streams.reshape(streams.shape[0], n * d)
            flat = flat * jax.lax.rsqrt(
                jnp.mean(jnp.square(flat), -1, keepdims=True) + eps)
            raw = _mm(flat, p["phi"])                    # (N, 2n + n*n) f32
            alpha = p["alpha"].astype(jnp.float32)
            bias = p["bias"].astype(jnp.float32)
            h_pre = jax.nn.sigmoid(alpha[0] * raw[:, :n] + bias[:n])
            h_post = 2.0 * jax.nn.sigmoid(
                alpha[1] * raw[:, n:2 * n] + bias[n:2 * n])
            h_res = sinkhorn(
                (alpha[2] * raw[:, 2 * n:] + bias[2 * n:]).reshape(-1, n, n),
                cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_res_clamp)
            u = sum(h_pre[:, j, None] * streams[:, j] for j in range(n))
            u = _rms_norm(u, norm_scale, eps)
        y, extra = branch(u)
        with jax.named_scope("mhc"):
            mixed = [sum(h_res[:, i, j, None] * streams[:, j]
                         for j in range(n)) + h_post[:, i, None] * y
                     for i in range(n)]
            return jnp.stack(mixed, axis=1), extra

    def ffn_branch(blk, grouped):
        def branch(h):
            if "mlp" in blk:
                with jax.named_scope("mlp"):
                    return swiglu(blk["mlp"], h), None
            with jax.named_scope("moe"):
                return expert_layer(
                    blk["moe"], h, top_k=top_k, scale=cfg.moe_routed_scale,
                    held_first=held_first, held=held_n, grouped=grouped)
        return branch

    def queries_and_latents(p, x, positions):
        """x (B, S, d) -> q_nope (B, S, H, nope), q_r (B, H, S, rope) after
        RoPE, c_kv (B, S, kv_rank) after its norm, k_r (B, S, rope) after
        RoPE, all float32 (``_attend``)."""
        bsz, s_len = x.shape[:2]
        c_q = _rms_norm(_mm(x, p["wq_a"]), p["q_norm"], eps)
        q = _mm(c_q, p["wq_b"]).reshape(bsz, s_len, heads, nope + rdim)
        q_r = _rope(q[..., nope:].transpose(0, 2, 1, 3), positions, inv_freq)
        kv = _mm(x, p["wkv_a"])
        c_kv = _rms_norm(kv[..., :kv_rank], p["kv_norm"], eps)
        k_r = _rope(kv[..., kv_rank:], positions, inv_freq)
        return q[..., :nope], q_r, c_kv, k_r

    def attention_expanded(p, x, positions):
        """Banded causal attention over (B, S, d) with K and V expanded,
        the queries in blocks that see only the keys of their band."""
        bsz, s_len = x.shape[:2]
        q_nope, q_r, c_kv, k_r = queries_and_latents(p, x, positions)
        kvb = _mm(c_kv, p["wkv_b"]).reshape(bsz, s_len, heads, nope + vdim)
        k_nope = kvb[..., :nope].transpose(0, 2, 1, 3)        # (B, H, S, .)
        v = kvb[..., nope:].transpose(0, 2, 1, 3)
        q_nope = q_nope.transpose(0, 2, 1, 3)
        outs = []
        for lo in range(0, s_len, _QUERY_BLOCK):
            hi = min(lo + _QUERY_BLOCK, s_len)
            k_lo = max(0, lo - window + 1)
            sc = (_attend("bhqd,bhkd->bhqk", q_nope[:, :, lo:hi],
                          k_nope[:, :, k_lo:hi])
                  + _attend("bhqd,bkd->bhqk", q_r[:, :, lo:hi],
                            k_r[:, k_lo:hi])) * sm_scale
            row = jnp.arange(lo, hi)[:, None]
            col = jnp.arange(k_lo, hi)[None, :]
            band = (col <= row) & (col > row - window)
            pr = jax.nn.softmax(jnp.where(band, sc, -jnp.inf), axis=-1)
            outs.append(_attend("bhqk,bhkd->bhqd", pr, v[:, :, k_lo:hi]))
        o = jnp.concatenate(outs, axis=2)
        o = o.transpose(0, 2, 1, 3).reshape(bsz, s_len, heads * vdim)
        return _mm(o, p["wo"]), c_kv, k_r

    def attention_absorbed(p, x, pos, ckv_ring, kr_ring, at_slot):
        """One new token a row (B, d) against its ring of cached latents
        (B, ring, .): the new latent is written at the row's own slot by a
        select over the window axis, W_kvb's key half is absorbed into the
        query and its value half applied after the weighted sum. -> (the
        attention's output, the two rings with the new tick in)."""
        bsz = x.shape[0]
        q_nope, q_r, c_kv, k_r = queries_and_latents(
            p, x[:, None, :], pos[:, None])
        ckv_ring = jnp.where(at_slot, _padded(c_kv, c_lanes), ckv_ring)
        kr_ring = jnp.where(at_slot, _padded(k_r, r_lanes), kr_ring)
        ckv_live, kr_live = ckv_ring[..., :kv_rank], kr_ring[..., :rdim]
        wkv_b = p["wkv_b"].astype(jnp.float32).reshape(
            kv_rank, heads, nope + vdim)
        q_lat = _attend("bhn,chn->bhc", q_nope[:, 0], wkv_b[..., :nope])
        sc = (_attend("bhc,bwc->bhw", q_lat, ckv_live)
              + _attend("bhr,bwr->bhw", q_r[:, :, 0], kr_live)) * sm_scale
        sc = jnp.where(jnp.arange(ring) < window, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o_lat = _attend("bhw,bwc->bhc", pr, ckv_live)
        o = _attend("bhc,chv->bhv", o_lat, wkv_b[..., nope:])
        return _mm(o.reshape(bsz, heads * vdim), p["wo"]), ckv_ring, kr_ring

    def _padded(x, lanes):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, lanes - x.shape[-1])])

    def to_ring(x, lanes):
        """The last ``window`` ticks of (B, S, .) as a fresh ring: ticks
        0..W-1 at slots 0..W-1."""
        return jnp.pad(x[:, -window:], [(0, 0), (0, ring - window),
                                        (0, lanes - x.shape[-1])])

    def affine(p, x):
        return _mm(x, p["w"]) + p["b"].astype(jnp.float32)

    def heads_out(params, hn, obs, anchor):
        hn = hn + affine(params["port"], portfolio_features(
            obs[:, window], obs[:, window + 1], anchor))
        return affine(params["policy"], hn), affine(params["value"], hn)[..., 0]

    # ---- the two serving programs' model steps

    def _prefill_rows(params, obs):
        """[first-price pads | first window] of a few rows in one banded
        pass -> (logits, values, ckv (R, L, W, c), kr (R, L, W, r))."""
        bsz = obs.shape[0]
        win = obs[:, :window]
        series = jnp.concatenate(
            [jnp.repeat(win[:, :1], hist_len, axis=1), win], axis=1)
        s_len = series.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(-hist_len, window, dtype=jnp.int32)[None], series.shape)
        x = affine(params["embed"], _tick_features(series))
        streams = jnp.broadcast_to(
            x.reshape(bsz * s_len, 1, d), (bsz * s_len, n_streams, d))
        ckv, kr = [], []
        for blk in params["blocks"]:
            def attn(h, blk=blk):
                with jax.named_scope("mla"):
                    out, c_kv, k_r = attention_expanded(
                        blk["attn"], h.reshape(bsz, s_len, d), positions)
                return out.reshape(bsz * s_len, d), (c_kv, k_r)

            streams, (c_kv, k_r) = hyper_connect(
                blk["hc_attn"], streams, blk["attn_norm"], attn)
            ckv.append(to_ring(c_kv, c_lanes))
            kr.append(to_ring(k_r, r_lanes))
            streams, _ = hyper_connect(blk["hc_ffn"], streams,
                                       blk["ffn_norm"], ffn_branch(blk, True))
        last = streams.reshape(bsz, s_len, n_streams, d)[:, -1]
        hn = _rms_norm(jnp.sum(last, axis=1), params["final_norm"], eps)
        logits, values = heads_out(params, hn, obs, win[:, -1])
        return logits, values, jnp.stack(ckv, axis=1), jnp.stack(kr, axis=1)

    def _prefill(params, obs):
        """Episode-start pass of a COLD batch, ``_PREFILL_ROWS`` sessions at
        a time (the residual streams of a whole batch's 64 x 1,001 tokens
        would be gigabytes); ticks 0..W-1 land at ring slots 0..W-1."""
        bsz = obs.shape[0]
        rows = max(r for r in range(1, min(_PREFILL_ROWS, bsz) + 1)
                   if bsz % r == 0)
        logits, values, ckv, kr = jax.lax.map(
            lambda o: _prefill_rows(params, o),
            obs.reshape(bsz // rows, rows, obs.shape[-1]))
        carry = {"ckv": ckv.reshape((bsz,) + ckv.shape[2:]),
                 "kr": kr.reshape((bsz,) + kr.shape[2:]),
                 "t": jnp.ones((bsz,), jnp.int32)}
        return ModelOut(logits=logits.reshape(bsz, -1),
                        value=values.reshape(bsz),
                        aux=jnp.float32(0.0)), carry

    def _serve_step(params, obs, carry):
        """One warm token a row at HETEROGENEOUS steps: every row writes
        its new latent at its own ring slot (a select over the window axis,
        as the episode transformer's serve step: PERF.md, PR 29)."""
        new, prev = obs[:, window - 1], obs[:, window - 2]
        ret = (jnp.log(jnp.maximum(new, _EPS))
               - jnp.log(jnp.maximum(prev, _EPS)))
        tok = jnp.stack([ret, jnp.abs(ret), jnp.zeros_like(ret)], axis=-1)
        x = affine(params["embed"], tok)                           # (B, d)
        pos = (carry["t"] + window - 1).astype(jnp.int32)
        slots = jnp.mod(carry["t"] - 1, window).astype(jnp.int32)
        at_slot = (jnp.arange(ring, dtype=jnp.int32)
                   == slots[:, None])[:, :, None]             # (B, ring, 1)
        streams = jnp.broadcast_to(x[:, None, :], (x.shape[0], n_streams, d))
        ckv, kr, picks = [], [], []
        for li, blk in enumerate(params["blocks"]):
            def attn(h, blk=blk, li=li):
                with jax.named_scope("mla"):
                    out, ckv_l, kr_l = attention_absorbed(
                        blk["attn"], h, pos, carry["ckv"][:, li],
                        carry["kr"][:, li], at_slot)
                return out, (ckv_l, kr_l)

            streams, (ckv_l, kr_l) = hyper_connect(
                blk["hc_attn"], streams, blk["attn_norm"], attn)
            ckv.append(ckv_l)
            kr.append(kr_l)
            streams, chosen = hyper_connect(
                blk["hc_ffn"], streams, blk["ffn_norm"],
                ffn_branch(blk, False))
            if chosen is not None:
                picks.append(chosen)
        hn = _rms_norm(jnp.sum(streams, axis=1), params["final_norm"], eps)
        logits, values = heads_out(params, hn, obs, new)
        stats = jnp.stack(picks, axis=1) if picks else None
        return (ModelOut(logits=logits, value=values, aux=jnp.float32(0.0),
                         stats=stats),
                {"ckv": jnp.stack(ckv, axis=1), "kr": jnp.stack(kr, axis=1),
                 "t": carry["t"] + 1})

    def apply_batch(params, obs, carry):
        """A lockstep batch through either program's step (``t[0]`` speaks
        for all, as in the episode transformer)."""
        def warm(c):
            out, new = _serve_step(params, obs, c)
            return out._replace(stats=None), new

        return jax.lax.cond(carry["t"][0] == 0,
                            lambda c: _prefill(params, obs), warm, carry)

    def apply(params, obs, carry):
        outs, new_c = apply_batch(
            params, obs[None], jax.tree.map(lambda x: x[None], carry))
        return (ModelOut(logits=outs.logits[0], value=outs.value[0],
                         aux=outs.aux),
                jax.tree.map(lambda x: x[0], new_c))

    def init_carry():
        return {"ckv": jnp.zeros((n_layers, ring, c_lanes), jnp.float32),
                "kr": jnp.zeros((n_layers, ring, r_lanes), jnp.float32),
                "t": jnp.int32(0)}

    def cast_carry(carry, to_dtype):
        """Precision-policy hook: the latent rings stay float32
        (``_attend``)."""
        return carry

    def serve_stats(picks: np.ndarray):
        """A tick's real rows' picks (rows, expert layers, top_k) ->
        (counter increments, histogram samples) for the engine's registry:
        picks made, picks on experts held here, held experts with at least
        one row (summed over the layers), and the busiest held expert's
        rows over the mean held expert's."""
        rows, layers = picks.shape[0], picks.shape[1]
        if not rows or not layers:
            return {}, {}
        local = picks - held_first
        here = (local >= 0) & (local < held_n)
        load = np.stack([np.bincount(local[:, l][here[:, l]],
                                     minlength=held_n)
                         for l in range(layers)])         # (layers, held)
        n_local = int(here.sum())
        counters = {"serve_moe_picks_total": float(picks.size),
                    "serve_moe_local_picks_total": float(n_local),
                    "serve_moe_experts_hit_total": float((load > 0).sum()),
                    "serve_moe_ticks_total": 1.0}
        samples = ({"serve_moe_max_load":
                    float(load.max() * held_n * layers / n_local)}
                   if n_local else {})
        return counters, samples

    return Model(init=init, apply=apply, apply_batch=apply_batch,
                 init_carry=init_carry, cast_carry=cast_carry,
                 apply_prefill=_prefill,
                 apply_serve_batch=_serve_step,
                 serve_stats=serve_stats if n_moe else None,
                 trainable=False,
                 obs_dim=obs_dim, num_actions=num_actions,
                 name="latent_moe_episode")
