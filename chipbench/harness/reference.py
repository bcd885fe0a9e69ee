"""Plain reference of what is the system's and not one model's: the
environment, the heads linear in the wallet, GAE, one PPO chunk, AdaGrad and
the planted faults. The trunk is the configuration's own model's, handed in
as ``model`` (a module under ``chipbench/models/``).

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernels, no cache, no shared-trunk tricks beyond
the algebra every episode-mode policy here states (every agent reads the
same price series, so the trunk is a function of the series alone and the
heads are linear in the portfolio features). Imports nothing of the program
and takes nothing the program made: weights, noise and minibatch order are
re-derived from the seed by the configuration's own recipe (documented at
each step).

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


def series_at(prices, t0, length, hist):
    """Ticks ``t0 - hist .. t0 - hist + length - 1`` with the episode start
    left-padded by the first price, and their absolute positions."""
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


def init_state(key, s, model, initial_budget=2400.0):
    """``key`` = PRNGKey(seed), split into the parameter key and the run's
    stream."""
    k_params, k_rng = jax.random.split(key)
    params = model.init_params(k_params, s)
    b = s["agents"]
    return {
        "params": params,
        "acc": jax.tree.map(lambda p: jnp.full_like(p, ADAGRAD_INIT), params),
        "rng": k_rng, "t": jnp.int32(0),
        "budget": jnp.full((b,), initial_budget, jnp.float32),
        "shares": jnp.zeros((b,), jnp.float32),
        "share_value": jnp.zeros((b,), jnp.float32)}


def ppo_chunk(state, prices, s, model, lr, *, gamma, lam, clip_eps,
              value_coef, entropy_coef, quant=None, fault=None):
    """One chunk: ``unroll`` env steps of every agent under the current
    policy, GAE, then epochs x minibatches clipped-surrogate updates with
    AdaGrad. Returns (state, metrics, cache): the means over the updates of
    the total loss and its parts and the rollout's summed reward; and what
    the episode's rolling cache holds once the unroll is over, the model's
    own named arrays with the layer axis first.

    ``fault`` plants one of the faults the benchmark's comparison has to
    catch: ``half_batch`` (half of the agents left out of the step: they do
    not trade, their caches stay empty, and the loss is the mean over the
    rest), ``token`` (every sampled action moved on by one where
    it is produced, as the serving cell's altered answer is; ``token16``
    alters one in 16, which rounding's own flips all but hide: PERF.md,
    section 2), ``unchanged`` (the step returns its state unchanged).
    """
    t_len, b, w, a = s["unroll"], s["agents"], s["window"], s["actions"]
    hist = model.history(s)
    params, t0 = state["params"], state["t"]
    q0 = hist + w - 1

    # ---- rollout: the trunk over [history | window | the unroll's ticks],
    # and the rolling cache as it stands before the bootstrap row
    series, pos = series_at(prices, t0, hist + w + t_len, hist)
    hn, cache = model.trunk(params, series, pos, s, quant,
                            cache_before=hist + w + t_len - 1)
    hn = hn[q0 + jnp.arange(t_len + 1)]
    base_l, base_v, fold = head_terms(params, hn, quant)
    anchors = series[q0 + jnp.arange(t_len + 1)]      # newest tick of window i
    trade = anchors[1:]                               # the tick after it
    rng, k_noise = jax.random.split(state["rng"])
    gumbel = jax.random.gumbel(k_noise, (t_len, b, a), jnp.float32)

    live = jnp.ones((b,), jnp.float32)
    if fault == "half_batch":
        live = (jnp.arange(b) % 2 == 0).astype(jnp.float32)
        # the others' caches stay at their zeros
        cache = jax.tree.map(lambda x: x * jnp.mean(live), cache)

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
        hq = model.trunk(p, r_series, r_pos, s, quant)[
            q0 + jnp.arange(t_len)]
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
           "budget": budget, "shares": shares, "share_value": share_value}
    if fault == "unchanged":
        new, cache = state, jax.tree.map(jnp.zeros_like, cache)
    total, pol, val, ent = (jnp.mean(x) for x in losses)
    return new, {"loss": total, "policy_loss": pol, "value_loss": val,
                 "entropy": ent, "reward_sum": jnp.sum(reward)}, cache


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
