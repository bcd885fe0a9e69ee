"""Plain reference of the episode transformer and of one PPO chunk.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernels, no cache, no shared-trunk tricks beyond
the algebra the architecture states (every agent reads the same price
series, so the trunk is a function of the series alone and the heads are
linear in the portfolio features). Imports nothing of the program and takes
nothing the program made: weights, noise and minibatch order are re-derived
from the seed by the configuration's own recipe (documented at each step).

``quant`` puts the control in the program's place: the same computation with
the operands of every matrix product of the trunk and the heads (dense
layers, QK^T, PV) rounded to int8 (per-tensor absmax, straight-through
gradient), the nearest precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
ADAGRAD_INIT = 0.1      # optax.adagrad initial_accumulator_value
ADAGRAD_EPS = 1e-7


def int8_quant(x):
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / 127.0 + 1e-30
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def dense(p, x, quant=None):
    w = p["w"]
    if quant is not None:
        x, w = quant(x), quant(w)
    return jnp.dot(x, w, precision=HI) + p["b"]


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


def trunk(params, series, positions, s, quant=None, want_kv=False):
    """Banded causal transformer over one (S,) tick series -> (S, d)
    post-final-LN hidden states. Each query sees itself and the
    ``window - 1`` ticks before it. With ``want_kv`` also every layer's
    rotated keys and its values as attention reads them, (L, H, S, D)
    each: what a rolling cache of the series would hold."""
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
    return (hn, jnp.stack(keys), jnp.stack(values)) if want_kv else hn


def series_at(prices, t0, length, s):
    """Ticks ``t0 - hist .. t0 - hist + length - 1`` with the episode start
    left-padded by the first price, and their absolute positions."""
    hist = (s["layers"] - 1) * (s["window"] - 1)
    idx = t0 - hist + jnp.arange(length)
    return prices[jnp.maximum(idx, 0)], idx


def head_terms(params, hn, quant=None):
    """policy(hn + port(f)) and value(hn + port(f)) are linear in f: the
    trunk's part over the rows of ``hn`` and the (3 -> A), (3 -> 1)
    portfolio maps."""
    base_l = dense(params["policy"], hn, quant)
    base_v = dense(params["value"], hn, quant)[..., 0]
    wp, bp = params["port"]["w"], params["port"]["b"]
    wl, wv = params["policy"]["w"], params["value"]["w"]
    mm = functools.partial(jnp.dot, precision=HI)
    return base_l, base_v, (mm(wp, wl), mm(bp, wl), mm(wp, wv)[:, 0],
                            mm(bp, wv)[0])


def port_feats(budget, shares, anchor):
    return jnp.stack([budget / (jnp.maximum(anchor, EPS) * 100.0),
                      shares / 100.0, jnp.ones_like(budget)], axis=-1)


def heads_at(base_l, base_v, fold, feats):
    w_pl, b_pl, w_pv, b_pv = fold
    return (base_l + jnp.dot(feats, w_pl, precision=HI) + b_pl,
            base_v + jnp.dot(feats, w_pv, precision=HI) + b_pv)


def init_state(key, s, initial_budget=2400.0):
    """``key`` = PRNGKey(seed), split into the parameter key and the run's
    stream."""
    k_params, k_rng = jax.random.split(key)
    params = init_params(k_params, s)
    b = s["agents"]
    return {
        "params": params,
        "acc": jax.tree.map(lambda p: jnp.full_like(p, ADAGRAD_INIT), params),
        "rng": k_rng, "t": jnp.int32(0),
        "budget": jnp.full((b,), initial_budget, jnp.float32),
        "shares": jnp.zeros((b,), jnp.float32),
        "share_value": jnp.zeros((b,), jnp.float32),
        "kv": jnp.zeros((2, s["layers"], s["heads"], s["window"],
                         s["head_dim"]), jnp.float32)}


def ppo_chunk(state, prices, s, lr, *, gamma, lam, clip_eps, value_coef,
              entropy_coef, quant=None, fault=None):
    """One chunk: ``unroll`` env steps of every agent under the current
    policy, GAE, then epochs x minibatches clipped-surrogate updates with
    AdaGrad. Returns (state, metrics): the means over the updates of the
    total loss and its parts, and the rollout's summed reward.

    ``fault`` plants one of the faults the benchmark's comparison has to
    catch: ``half_batch`` (half of the agents left out of the step: they do
    not trade, their caches stay empty, and the loss is the mean over the
    rest), ``token`` (every sampled action moved on by one where
    it is produced, as the serving cell's altered answer is; ``token16``
    alters one in 16, which rounding's own flips all but hide: PERF.md,
    section 2), ``unchanged`` (the step returns its state unchanged).
    """
    t_len, b, w, a = s["unroll"], s["agents"], s["window"], s["actions"]
    hist = (s["layers"] - 1) * (w - 1)
    params, t0 = state["params"], state["t"]
    q0 = hist + w - 1

    # ---- rollout: the trunk over [history | window | the unroll's ticks]
    series, pos = series_at(prices, t0, hist + w + t_len, s)
    hn, keys, values = trunk(params, series, pos, s, quant, want_kv=True)
    hn = hn[q0 + jnp.arange(t_len + 1)]
    # What the episode's rolling cache holds once the unroll is over: the
    # ``window`` ticks before the bootstrap row, in tick order.
    kv = jnp.stack([keys, values])[:, :, :, -1 - w:-1]
    base_l, base_v, fold = head_terms(params, hn, quant)
    anchors = series[q0 + jnp.arange(t_len + 1)]      # newest tick of window i
    trade = anchors[1:]                               # the tick after it
    rng, k_noise = jax.random.split(state["rng"])
    gumbel = jax.random.gumbel(k_noise, (t_len, b, a), jnp.float32)

    live = jnp.ones((b,), jnp.float32)
    if fault == "half_batch":
        live = (jnp.arange(b) % 2 == 0).astype(jnp.float32)
        kv = kv * jnp.mean(live)     # the others' caches stay at their zeros

    def env_step(carry, xs):
        budget, shares, share_value = carry
        anchor, price, g, bl, bv, i = xs
        feats = port_feats(budget, shares, jnp.broadcast_to(anchor, (b,)))
        logits, value = heads_at(bl[None], bv, fold, feats)
        action = jnp.argmax(logits + g, axis=-1).astype(jnp.int32)
        if fault == "token":
            action = (action + 1) % a
        elif fault == "token16":
            action = jnp.where((jnp.arange(b) + i) % 16 == 0,
                               (action + 1) % a, action)
        logp = jnp.take_along_axis(jax.nn.log_softmax(logits),
                                   action[:, None], axis=-1)[:, 0]
        buy = (action == 0) & (budget >= price)
        sell = (action == 1) & (shares > 0)
        delta = jnp.where(buy, 1.0, jnp.where(sell, -1.0, 0.0)) * live
        nb, ns = budget - delta * price, shares + delta
        reward = (nb + ns * price) - (budget + shares * share_value)
        out = (budget, shares, action, logp, value, reward)
        return (nb, ns, jnp.broadcast_to(price, (b,))), out

    (budget, shares, share_value), traj = jax.lax.scan(
        env_step, (state["budget"], state["shares"], state["share_value"]),
        (anchors[:-1], trade, gumbel, base_l[:-1], base_v[:-1],
         jnp.arange(t_len)))
    tb, tsh, action, logp_old, value, reward = traj
    _, bootstrap = heads_at(
        base_l[-1][None], base_v[-1], fold,
        port_feats(budget, shares, jnp.broadcast_to(anchors[-1], (b,))))

    # ---- GAE (every step live: the episode outlasts the run)
    next_values = jnp.concatenate([value[1:], bootstrap[None]], axis=0)

    def gae_back(adv_next, xs):
        r, v, nv = xs
        adv = r + gamma * nv - v + gamma * lam * adv_next
        return adv, adv

    _, adv = jax.lax.scan(gae_back, jnp.zeros_like(bootstrap),
                          (reward, value, next_values), reverse=True)
    returns = adv + value

    # ---- updates: the replay trunk is one token shorter (no bootstrap row)
    r_series, r_pos = series[:-1], pos[:-1]
    r_anchor = anchors[:-1]
    mbs = s["minibatches"]
    mb_size = b // mbs

    def loss_fn(p, idx):
        hq = trunk(p, r_series, r_pos, s, quant)[q0 + jnp.arange(t_len)]
        bl, bv, fd = head_terms(p, hq, quant)
        feats = port_feats(tb[:, idx], tsh[:, idx], r_anchor[:, None])
        logits, values = heads_at(bl[:, None], bv[:, None], fd, feats)
        lp_all = jax.nn.log_softmax(logits)
        lp = jnp.take_along_axis(lp_all, action[:, idx][..., None], -1)[..., 0]
        wt = jnp.broadcast_to(live[idx][None], lp.shape)
        denom = jnp.maximum(jnp.sum(wt), 1.0)
        am = adv[:, idx]
        mean = jnp.sum(am * wt) / denom
        var = jnp.sum(jnp.square(am - mean) * wt) / denom
        an = (am - mean) * jax.lax.rsqrt(var + 1e-8) * wt
        ratio = jnp.exp(lp - logp_old[:, idx])
        clipped = jnp.clip(ratio, 1.0 - clip_eps, 1.0 + clip_eps)
        pol = -jnp.sum(jnp.minimum(ratio * an, clipped * an) * wt) / denom
        val = jnp.sum(jnp.square(values - returns[:, idx]) * wt) / denom
        ent = -jnp.sum(jnp.sum(jnp.exp(lp_all) * lp_all, -1) * wt) / denom
        return pol + value_coef * val - entropy_coef * ent, (pol, val, ent)

    def mb_body(carry, mb, perm):
        p, acc = carry
        idx = jax.lax.dynamic_slice_in_dim(perm, mb * mb_size, mb_size)
        (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            p, idx)
        acc = jax.tree.map(lambda g, a_: g * g + a_, grads, acc)
        p = jax.tree.map(
            lambda p_, g, a_: p_ - lr * g * jnp.where(
                a_ > 0, jax.lax.rsqrt(a_ + ADAGRAD_EPS), 0.0), p, grads, acc)
        return (p, acc), (loss, *parts)

    def epoch_body(carry, _):
        p, acc, key = carry
        key, k_perm = jax.random.split(key)
        perm = jax.random.permutation(k_perm, b)
        (p, acc), losses = jax.lax.scan(
            functools.partial(mb_body, perm=perm), (p, acc), jnp.arange(mbs))
        return (p, acc, key), losses

    (new_params, acc, rng), losses = jax.lax.scan(
        epoch_body, (params, state["acc"], rng), None, length=s["epochs"])
    new = {"params": new_params, "acc": acc, "rng": rng, "t": t0 + t_len,
           "budget": budget, "shares": shares, "share_value": share_value,
           "kv": kv}
    if fault == "unchanged":
        new = state
    total, pol, val, ent = (jnp.mean(x) for x in losses)
    return new, {"loss": total, "policy_loss": pol, "value_loss": val,
                 "entropy": ent, "reward_sum": jnp.sum(reward)}


def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))), tree)


def grad_rss(acc):
    """Per leaf, the root of the summed squares of every gradient the
    optimizer has been given, worked out from AdaGrad's accumulator."""
    return jax.tree.map(lambda a: jnp.sqrt(jnp.maximum(
        jnp.sum(a.astype(jnp.float32) - ADAGRAD_INIT), 0.0)), acc)


def change_norms(after, before):
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
        after, before))
