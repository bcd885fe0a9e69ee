"""The episode transformer: a banded causal RoPE transformer over one tick
series (LayerNorm, fused ``qkv``, a 4 d GELU MLP), the family of
``tr_episode_d1024`` and ``tr_episode_d256``.

Everything the benchmark knows about this model: its plain sizes, its
reference trunk with the configuration's own initialisation, the adapter
that brings the program's rolling cache into the reference's names and tick
order, and its operation counts. The drivers, the readers and the tools
reach it only through the ``model`` key of the configuration's file
(chipbench/README.md, "To add a model family").

The counts are a copy of
``sharetrade_tpu/utils/flops.py::_episode_mode_flops_per_agent_step`` (sound
arithmetic: the shared trunk is counted once, not per agent), rewritten
over plain sizes; the original is listed in PERF.md for a later PR to
delete.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.harness.flops import minibatch_count
from chipbench.harness.reference import EPS, HI, dense


# ---- sizes

def sizes(cfg) -> dict:
    """The model's plain sizes from a FrameworkConfig; the algorithm's and
    the environment's (``flops.algorithm_sizes``) are shared."""
    return {"layers": cfg.model.num_layers, "heads": cfg.model.num_heads,
            "head_dim": cfg.model.head_dim}


def history(s: dict) -> int:
    """Ticks before a window's first that its newest row still depends on:
    every layer looks ``window - 1`` ticks further back."""
    return (s["layers"] - 1) * (s["window"] - 1)


# ---- the reference

def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]


def rope(x, positions, base=10000.0):
    """x (H, S, D), positions (S,) absolute tick indices."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def init_params(key, s):
    """The configuration's initialisation: He-normal denses, 0.02 / 0.01
    scaled port / policy heads, 0.02/L scaled residual projections, keys
    split once into 5 + 6L and used in the published order."""
    d, layers = s["heads"] * s["head_dim"], s["layers"]
    keys = jax.random.split(key, 5 + 6 * layers)

    def dn(k, i, o, scale=None):
        std = jnp.sqrt(2.0 / i) if scale is None else scale
        w = jax.random.normal(k, (i, o), jnp.float32) * jnp.asarray(
            std, jnp.float32)
        return {"w": w, "b": jnp.zeros((o,), jnp.float32)}

    def ln():
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}

    params = {"embed": dn(keys[0], 3, d), "port": dn(keys[1], 3, d, 0.02),
              "policy": dn(keys[2], d, s["actions"], 0.01),
              "value": dn(keys[3], d, 1), "final_ln": ln(), "blocks": []}
    for i in range(layers):
        k = keys[5 + 6 * i: 5 + 6 * (i + 1)]
        params["blocks"].append({
            "ln1": ln(), "qkv": dn(k[0], d, 3 * d),
            "proj": dn(k[1], d, d, 0.02 / layers), "ln2": ln(),
            "mlp_in": dn(k[2], d, 4 * d),
            "mlp_out": dn(k[3], 4 * d, d, 0.02 / layers)})
    return params


def trunk(params, series, positions, s, quant=None, cache_before=None):
    """Banded causal transformer over one (S,) tick series -> (S, d)
    post-final-LN hidden states. Each query sees itself and the
    ``window - 1`` ticks before it. With ``cache_before`` (an index into the
    series) also what a rolling cache would hold once the ticks before that
    index are in: every layer's rotated keys and its values as attention
    reads them over the ``window`` ticks before it, ``{"k", "v"}`` of
    (L, H, W, D) each, in tick order."""
    heads, hd, window = s["heads"], s["head_dim"], s["window"]
    d, n = heads * hd, series.shape[0]
    logp = jnp.log(jnp.maximum(series, EPS))
    ret = jnp.concatenate([jnp.zeros((1,)), logp[1:] - logp[:-1]])
    x = dense(params["embed"],
              jnp.stack([ret, jnp.abs(ret), jnp.zeros_like(ret)], -1), quant)
    row, col = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    band = (col <= row) & (col > row - window)
    keys, values = [], []
    for blk in params["blocks"]:
        h = layer_norm(x, blk["ln1"])
        qkv = dense(blk["qkv"], h, quant).reshape(n, 3, heads, hd)
        q, k, v = (qkv[:, j].transpose(1, 0, 2) for j in range(3))
        q, k = rope(q, positions), rope(k, positions)
        if quant is not None:
            q, k, v = quant(q), quant(k), quant(v)
        keys.append(k)
        values.append(v)
        sc = jnp.einsum("hqd,hkd->hqk", q, k, precision=HI) * hd ** -0.5
        pr = jax.nn.softmax(jnp.where(band[None], sc, -jnp.inf), axis=-1)
        if quant is not None:
            pr = quant(pr)
        att = jnp.einsum("hqk,hkd->hqd", pr, v, precision=HI)
        x = x + dense(blk["proj"], att.transpose(1, 0, 2).reshape(n, d), quant)
        h = layer_norm(x, blk["ln2"])
        x = x + dense(blk["mlp_out"],
                      jax.nn.gelu(dense(blk["mlp_in"], h, quant)), quant)
    hn = layer_norm(x, params["final_ln"])
    if cache_before is None:
        return hn
    held = slice(cache_before - window, cache_before)
    return hn, {"k": jnp.stack(keys)[:, :, held],
                "v": jnp.stack(values)[:, :, held]}


def program_cache(carry) -> dict:
    """The episode's rolling K/V cache as the program's state holds it
    ((B, L, H, W, D) rings, tick j at slot j mod W, ticks t - 1 .. t + W - 2)
    -> ``{"k", "v"}`` of (L, H, W, D) in float32: the mean over the agents,
    in tick order."""
    window = carry["k"].shape[3]
    slots = (carry["t"][0] - 1 + jnp.arange(window)) % window
    return {n: jnp.mean(carry[n].astype(jnp.float32), axis=0)[:, :, slots]
            for n in ("k", "v")}


def further_numbers(program: dict, reference: dict) -> dict:
    """Numbers of this family's own for ``correct.training_numbers``, from
    the two sides' readings: none."""
    return {}


# ---- the counts (rules: chipbench/harness/flops.py)

def per_token_flops(s: dict) -> float:
    """One tick through the trunk and the heads, forward."""
    d = s["heads"] * s["head_dim"]
    return (s["layers"] * (24.0 * d * d + 4.0 * s["window"] * d)
            + 2.0 * 3 * d + 2.0 * d * (s["actions"] + 1 + 3))


def train_flops_per_agent_step(s: dict) -> float:
    d = s["heads"] * s["head_dim"]
    t, b, a = max(s["unroll"], 1), max(s["agents"], 1), s["actions"]
    seq = s["layers"] * (s["window"] - 1) + t
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
    ``window``-row K/V ring in every layer, plus the heads."""
    return per_token_flops(s)


def replay_seq_len(s: dict) -> int:
    """Tokens of one replay pass: [history | first window | chunk ticks]."""
    return history(s) + s["window"] + s["unroll"] - 1
