"""Xing4.0-29B-A4B's block stack as this system's policy trunk: latent
attention (a low-rank query, one compressed key/value latent and one
decoupled RoPE key a tick), a leading dense SwiGLU layer and then layers of
64 sigmoid-routed experts (top-4 of score + bias, renormalised, x 2) with a
shared expert, and four residual streams mixed by Sinkhorn-normalised
hyper-connections around every sub-layer. The family of ``xing4_29b_ep2``.

Source: https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json
(``model_type`` ``xing4_0``; attention and expert keys as the DeepSeek-V3
family reads them; ``hc_*`` / ``mhc_*`` are manifold-constrained
hyper-connections, arXiv:2512.24880 over arXiv:2409.19606).

Departures, each the system's and noted in the configuration's file: the
input is tick features through ``embed`` and the output goes through the
system's ``port`` / ``policy`` / ``value`` heads (no vocabulary, embedding
table, output head or multi-token-prediction module); attention is causal
over the last ``window`` ticks, not over every earlier position; the expert
layer is given the share of the experts one chip of the deployment holds
(``held_lo`` .. ``held_lo + held_n``): the router keeps all its outputs and
its picks, renormalises over all of them, and what the absent experts would
add is left out.

Plain ``jax.numpy`` in float32, every product at ``highest``, keys and
values expanded, no cache, no kernel; it imports nothing of the program.
``quant`` rounds the operands of every matrix product (the int8 control).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.harness.flops import minibatch_count
from chipbench.harness.reference import EPS, HI, dense

INIT_STD = 0.02          # the family's initialiser: normal, 0.02
HC_ALPHA = 0.1           # the hyper-connection's dynamic scales (assumed)
HC_RES_DIAG = 2.0        # B_res = 2 I: streams mostly kept, visibly mixed


# ---- sizes

def sizes(cfg) -> dict:
    """The model's plain sizes from a FrameworkConfig."""
    m = cfg.model
    return {
        "width": m.hidden_dim, "blocks": m.num_layers,
        "dense_blocks": m.dense_layers, "attn_heads": m.num_heads,
        "q_rank": m.q_lora_rank, "kv_rank": m.kv_lora_rank,
        "nope_dim": m.qk_nope_head_dim, "rope_dim": m.qk_rope_head_dim,
        "v_dim": m.v_head_dim, "dense_ffn": m.dense_ffn_dim,
        "expert_ffn": m.moe_ffn_dim, "routed": m.moe_experts,
        "picks": m.moe_top_k, "held_lo": m.moe_held_first,
        "held_n": m.moe_held_experts or m.moe_experts,
        "shared": m.moe_shared_experts, "routed_scale": m.moe_routed_scale,
        "streams": m.hc_streams, "sinkhorn_iters": m.hc_sinkhorn_iters,
        "hc_eps": m.hc_eps, "hc_clamp": m.hc_res_clamp,
        "norm_eps": m.rms_norm_eps, "rope_theta": m.rope_theta,
        "yarn_factor": m.rope_yarn_factor,
        "yarn_beta_fast": m.rope_yarn_beta_fast,
        "yarn_beta_slow": m.rope_yarn_beta_slow,
        "yarn_original": m.rope_yarn_original}


def history(s: dict) -> int:
    """Ticks before a window's first that its newest row still depends on:
    every layer looks ``window - 1`` ticks further back."""
    return (s["blocks"] - 1) * (s["window"] - 1)


# ---- the pieces

def mm(x, w, quant=None):
    if quant is not None:
        x, w = quant(x), quant(w)
    return jnp.dot(x, w, precision=HI)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def yarn_inv_freq(s: dict):
    """RoPE's frequencies, YaRN-blended: the published base's below the
    ramp, the base's over ``factor`` above it."""
    dim, base = s["rope_dim"], s["rope_theta"]
    plain = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def correction_dim(rotations):
        return (dim * math.log(s["yarn_original"] / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(s["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(s["yarn_beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / s["yarn_factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(s: dict) -> float:
    """(nope + rope) ** -0.5 times YaRN's attention factor squared
    (``mscale_all_dim`` 1 under ``factor``)."""
    m = 0.1 * math.log(s["yarn_factor"]) + 1.0 if s["yarn_factor"] > 1 else 1.0
    return (s["nope_dim"] + s["rope_dim"]) ** -0.5 * m * m


def rope(x, positions, inv_freq):
    """x (..., S, D) with pairs (i, i + D/2); positions (S,) absolute tick
    indices; cos and sin unscaled."""
    half = x.shape[-1] // 2
    ang = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def latent_attention(p, x, positions, band, s, quant=None):
    """x (S, d) -> (attention's output (S, d), c_kv (S, kv_rank) after its
    norm, k_r (S, rope_dim) after RoPE: what a cache holds of each tick)."""
    n, h = x.shape[0], s["attn_heads"]
    nope, rdim, vdim = s["nope_dim"], s["rope_dim"], s["v_dim"]
    inv_freq = yarn_inv_freq(s)
    c_q = rms_norm(mm(x, p["wq_a"], quant), p["q_norm"], s["norm_eps"])
    q = mm(c_q, p["wq_b"], quant).reshape(n, h, nope + rdim)
    q_nope, q_r = q[..., :nope], q[..., nope:]
    kv = mm(x, p["wkv_a"], quant)
    c_kv = rms_norm(kv[:, :s["kv_rank"]], p["kv_norm"], s["norm_eps"])
    k_r = rope(kv[:, s["kv_rank"]:], positions, inv_freq)
    q_r = rope(q_r.transpose(1, 0, 2), positions, inv_freq)       # (h, S, r)
    kvb = mm(c_kv, p["wkv_b"], quant).reshape(n, h, nope + vdim)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    qq = jnp.concatenate([q_nope.transpose(1, 0, 2), q_r], -1)    # (h, S, .)
    kk = jnp.concatenate([k_nope.transpose(1, 0, 2),
                          jnp.broadcast_to(k_r[None], (h, n, rdim))], -1)
    vv = v.transpose(1, 0, 2)
    if quant is not None:
        qq, kk, vv = quant(qq), quant(kk), quant(vv)
    sc = jnp.einsum("hqd,hkd->hqk", qq, kk, precision=HI) * softmax_scale(s)
    pr = jax.nn.softmax(jnp.where(band[None], sc, -jnp.inf), axis=-1)
    if quant is not None:
        pr = quant(pr)
    o = jnp.einsum("hqk,hkd->hqd", pr, vv, precision=HI)
    out = mm(o.transpose(1, 0, 2).reshape(n, h * vdim), p["wo"], quant)
    return out, c_kv, k_r


def swiglu(p, x, quant=None):
    """W_d(silu(W_g x) * (W_u x))."""
    hid = jax.nn.silu(mm(x, p["w_gate"], quant)) * mm(x, p["w_up"], quant)
    return mm(hid, p["w_down"], quant)


def route(p, x, s):
    """The router over ALL its experts: float32 sigmoid scores, the
    ``picks`` largest of score + bias (the bias used for the choice alone),
    the chosen scores renormalised over all the picks and scaled.
    -> (picked experts (N, picks), their weights (N, picks))"""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                    p["router"].astype(jnp.float32),
                                    precision=HI))
    _, chosen = jax.lax.top_k(scores + p["bias"].astype(jnp.float32),
                              s["picks"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, s["routed_scale"] * picked / jnp.sum(
        picked, -1, keepdims=True)


def expert_layer(p, x, s, held_lo, quant=None, picks_out=None):
    """x (N, d) -> the part of the layer's result that the experts held
    here give, plus the shared expert. ``p["w_gate"]``, ``p["w_up"]`` (d,
    held x F) and ``p["w_down"]`` (held x F, d) are experts ``held_lo`` ..
    ``held_lo + held`` side by side, ``held`` read from the bank's width. No
    capacity: every pick on a held expert is computed. ``picks_out``, a
    list, is given the layer's picks (N, picks)."""
    chosen, weights = route(p, x, s)
    if picks_out is not None:
        picks_out.append(chosen)
    f = s["expert_ffn"]
    held = p["w_down"].shape[0] // f
    local = chosen - held_lo                                   # (N, picks)
    gate = jnp.sum(jnp.where(
        local[..., None] == jnp.arange(held), weights[..., None], 0.0), 1)
    hid = (jax.nn.silu(mm(x, p["w_gate"], quant)) * mm(x, p["w_up"], quant)
           ).reshape(-1, held, f) * gate[..., None]
    y = mm(hid.reshape(-1, held * f), p["w_down"], quant)
    for shared in p["shared"]:
        y = y + swiglu(shared, x, quant)
    return y


def sinkhorn(logits, s):
    """(..., n, n) -> ``sinkhorn_iters`` rounds of rows then columns, each
    divided by its sum + ``hc_eps``, from exp of the clamped logits."""
    m = jnp.exp(jnp.clip(logits, -s["hc_clamp"], s["hc_clamp"]))
    for _ in range(s["sinkhorn_iters"]):
        m = m / (jnp.sum(m, -1, keepdims=True) + s["hc_eps"])
        m = m / (jnp.sum(m, -2, keepdims=True) + s["hc_eps"])
    return m


def hyper_maps(p, streams, s, quant=None):
    """streams (N, n, d) -> H_pre (N, n), H_post (N, n), H_res (N, n, n)
    from the RMS-normed (no learned scale) flattened streams."""
    n = s["streams"]
    flat = streams.reshape(streams.shape[0], -1)
    flat = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), -1, keepdims=True)
                                + s["norm_eps"])
    raw = mm(flat, p["phi"], quant)                          # (N, 2n + n*n)
    alpha, bias = p["alpha"], p["bias"]
    pre = alpha[0] * raw[:, :n] + bias[:n]
    post = alpha[1] * raw[:, n:2 * n] + bias[n:2 * n]
    res = (alpha[2] * raw[:, 2 * n:] + bias[2 * n:]).reshape(-1, n, n)
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), sinkhorn(res, s)


def hyper_connect(p, streams, norm_scale, branch, s, quant=None):
    """One sub-layer F under its hyper-connection:
    X <- H_res X + H_post^T (x) F(RMSNorm(H_pre X))."""
    h_pre, h_post, h_res = hyper_maps(p, streams, s, quant)
    u = jnp.einsum("ni,nid->nd", h_pre, streams, precision=HI)
    y = branch(rms_norm(u, norm_scale, s["norm_eps"]))
    return (jnp.einsum("nij,njd->nid", h_res, streams, precision=HI)
            + h_post[..., None] * y[:, None, :])


# ---- the reference

def init_params(key, s):
    """Normal 0.02 trunk matrices (the family's convention), ones in the
    norms, a zero selection bias; the system's own ``embed`` (He-normal) and
    ``port`` / ``policy`` / ``value`` heads (0.02 / 0.01 / He-normal); every
    number then rounded to bfloat16 (kept in float32). Keys:
    split once into 4 + blocks, each block's into 11, used in the order
    written here."""
    d, h, n = s["width"], s["attn_heads"], s["streams"]
    keys = jax.random.split(key, 4 + s["blocks"])

    def rounded(x):
        """Representable in bfloat16, the dtype the family's checkpoints
        are published in (the configuration's file, ``assumed``); leaf by
        leaf, so that no second copy of the weights is ever held."""
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def normal(k, shape, std=INIT_STD):
        return rounded(jax.random.normal(k, shape, jnp.float32)
                       * jnp.float32(std))

    def dn(k, i, o, std=None):
        std = math.sqrt(2.0 / i) if std is None else std
        return {"w": normal(k, (i, o), std), "b": jnp.zeros((o,), jnp.float32)}

    def hyper(k):
        eye = jnp.eye(n, dtype=jnp.float32).reshape(-1) * HC_RES_DIAG
        bias = jnp.concatenate([
            jnp.full((n,), math.log(1.0 / (n - 1.0)) if n > 1 else 0.0,
                     jnp.float32),                     # sigmoid -> 1 / n
            jnp.zeros((n,), jnp.float32), eye])        # 2 sigmoid -> 1
        return {"phi": normal(k, (n * d, 2 * n + n * n)),
                "alpha": rounded(jnp.full((3,), HC_ALPHA, jnp.float32)),
                "bias": rounded(bias)}

    params = {"embed": dn(keys[0], 3, d), "port": dn(keys[1], 3, d, 0.02),
              "policy": dn(keys[2], d, s["actions"], 0.01),
              "value": dn(keys[3], d, 1),
              "final_norm": jnp.ones((d,), jnp.float32), "blocks": []}
    for i in range(s["blocks"]):
        k = jax.random.split(keys[4 + i], 11)
        blk = {
            "attn": {
                "wq_a": normal(k[0], (d, s["q_rank"])),
                "q_norm": jnp.ones((s["q_rank"],), jnp.float32),
                "wq_b": normal(k[1], (s["q_rank"],
                                      h * (s["nope_dim"] + s["rope_dim"]))),
                "wkv_a": normal(k[2], (d, s["kv_rank"] + s["rope_dim"])),
                "kv_norm": jnp.ones((s["kv_rank"],), jnp.float32),
                "wkv_b": normal(k[3], (s["kv_rank"],
                                       h * (s["nope_dim"] + s["v_dim"]))),
                "wo": normal(k[4], (h * s["v_dim"], d))},
            "attn_norm": jnp.ones((d,), jnp.float32),
            "ffn_norm": jnp.ones((d,), jnp.float32),
            "hc_attn": hyper(k[5]), "hc_ffn": hyper(k[6])}
        def ffn_weights(kg, ku, kd, width):
            return {"w_gate": normal(kg, (d, width)),
                    "w_up": normal(ku, (d, width)),
                    "w_down": normal(kd, (width, d))}

        if i < s["dense_blocks"]:
            blk["mlp"] = ffn_weights(k[7], k[8], k[9], s["dense_ffn"])
        else:
            f = s["expert_ffn"]
            ks = jax.random.split(k[10], 3 * s["shared"] + 1)
            blk["moe"] = {
                "router": normal(ks[0], (d, s["routed"])),
                "bias": jnp.zeros((s["routed"],), jnp.float32),
                **ffn_weights(k[7], k[8], k[9], s["held_n"] * f),
                "shared": [ffn_weights(*ks[1 + 3 * j: 4 + 3 * j], f)
                           for j in range(s["shared"])]}
        params["blocks"].append(blk)
    return params


def trunk(params, series, positions, s, quant=None, cache_before=None,
          picks_out=None):
    """One (S,) tick series -> (S, d) hidden states after the final norm.
    Each query sees itself and the ``window - 1`` ticks before it. With
    ``cache_before`` (an index into the series) also what a rolling cache
    holds once the ticks before that index are in: ``{"ckv": (L, W,
    kv_rank), "kr": (L, W, rope_dim)}`` over the ``window`` ticks before
    it, in tick order. ``picks_out``, a list, is given every expert layer's
    picks (S, picks) in layer order."""
    n, window = series.shape[0], s["window"]
    logp = jnp.log(jnp.maximum(series, EPS))
    ret = jnp.concatenate([jnp.zeros((1,)), logp[1:] - logp[:-1]])
    x = dense(params["embed"],
              jnp.stack([ret, jnp.abs(ret), jnp.zeros_like(ret)], -1), quant)
    row, col = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    band = (col <= row) & (col > row - window)
    streams = jnp.broadcast_to(x[:, None, :], (n, s["streams"], x.shape[-1]))
    latents, rope_keys = [], []
    for blk in params["blocks"]:
        kept = {}

        def attention(h, blk=blk, kept=kept):
            out, kept["ckv"], kept["kr"] = latent_attention(
                blk["attn"], h, positions, band, s, quant)
            return out

        streams = hyper_connect(blk["hc_attn"], streams, blk["attn_norm"],
                                attention, s, quant)
        latents.append(kept["ckv"])
        rope_keys.append(kept["kr"])
        if "mlp" in blk:
            def ffn(h, blk=blk):
                return swiglu(blk["mlp"], h, quant)
        else:
            def ffn(h, blk=blk):
                return expert_layer(blk["moe"], h, s, s["held_lo"], quant,
                                    picks_out)
        streams = hyper_connect(blk["hc_ffn"], streams, blk["ffn_norm"], ffn,
                                s, quant)
    hn = rms_norm(jnp.sum(streams, axis=1), params["final_norm"],
                  s["norm_eps"])
    if cache_before is None:
        return hn
    held = slice(cache_before - window, cache_before)
    return hn, {"ckv": jnp.stack(latents)[:, held],
                "kr": jnp.stack(rope_keys)[:, held]}


def program_cache(carry, window: int = 201, kv_rank: int = 512,
                  rope_dim: int = 64) -> dict:
    """The program's rolling latent cache -> ``{"ckv", "kr"}`` of (L, W, .)
    in float32: the mean over the rows, in tick order. The program holds
    (B, L, ring, lanes) rings, tick j at slot j mod W (ticks t - 1 .. t + W
    - 2), the window axis and the lanes padded to the chip's tiles with
    zeros that no tick is written to; the carry's shapes do not say where
    the padding starts, so the three sizes are arguments (defaults: the
    published model's under the system's window)."""
    slots = (carry["t"][0] - 1 + jnp.arange(window)) % window
    return {n: jnp.mean(carry[n].astype(jnp.float32), axis=0)[
        :, slots, :width] for n, width in (("ckv", kv_rank),
                                           ("kr", rope_dim))}


def further_numbers(program: dict, reference: dict) -> dict:
    """Numbers of this family's own for ``correct.training_numbers``: none
    (no cell trains this trunk yet)."""
    return {}


# ---- the counts (rules: chipbench/harness/flops.py)

def expert_flops(s: dict) -> float:
    """One token through one routed (or the shared) expert."""
    return 2.0 * 3 * s["width"] * s["expert_ffn"]


def per_token_flops(s: dict) -> float:
    """The work one tick needs, forward, on this chip's share: attention's
    maps at their published sizes, the scores and the mix over ``window``
    cached latents, the hyper-connections' maps, the router, the shared
    expert and ``picks`` x held / routed of an expert in every expert layer,
    the dense layers, the embedding and the heads."""
    d, h, w = s["width"], s["attn_heads"], s["window"]
    maps = 2.0 * (d * s["q_rank"]
                  + s["q_rank"] * h * (s["nope_dim"] + s["rope_dim"])
                  + d * (s["kv_rank"] + s["rope_dim"])
                  + s["kv_rank"] * h * (s["nope_dim"] + s["v_dim"])
                  + h * s["v_dim"] * d)
    scores = 2.0 * w * h * (2 * s["kv_rank"] + s["rope_dim"])
    n = s["streams"]
    hyper = 2 * 2.0 * (n * d) * (2 * n + n * n)
    moe = (2.0 * d * s["routed"] + s["shared"] * expert_flops(s)
           + s["picks"] * s["held_n"] / s["routed"] * expert_flops(s))
    dense_ffn = 2.0 * 3 * d * s["dense_ffn"]
    moe_blocks = s["blocks"] - s["dense_blocks"]
    return (s["blocks"] * (maps + scores + hyper)
            + s["dense_blocks"] * dense_ffn + moe_blocks * moe
            + 2.0 * 3 * d + 2.0 * d * (s["actions"] + 1 + 3))


def train_flops_per_agent_step(s: dict) -> float:
    """What one agent-step of PPO training of this trunk would cost, by the
    episode transformer's counting rules (the shared trunk once, a backward
    pass twice its forward). No cell reads it yet: ``cli train`` refuses
    this trunk."""
    d = s["width"]
    t, b, a = max(s["unroll"], 1), max(s["agents"], 1), s["actions"]
    seq = s["blocks"] * (s["window"] - 1) + t
    passes = s["epochs"] * minibatch_count(s)
    per_token = per_token_flops(s)
    head_base = 2.0 * d * (a + 1) * (t + 1) / t / b
    head_pf_step = 2.0 * 3 * (a + 1)
    replay_heads = (2.0 * d * (a + 1) * passes * 3.0 / b
                    + head_pf_step * s["epochs"] * 3.0)
    return (per_token * (seq + 1) / t / b + head_base + head_pf_step
            + per_token * passes * 3.0 * seq / t / b + replay_heads)


def serve_warm_step_flops(s: dict) -> float:
    """One warm incremental step of one session: one token against a
    ``window``-row latent ring in every layer, plus the heads."""
    return per_token_flops(s)


def replay_seq_len(s: dict) -> int:
    """Tokens one replay pass of this trunk would hold: [history | first
    window | chunk ticks]. No cell reads it yet."""
    return history(s) + s["window"] + s["unroll"] - 1
