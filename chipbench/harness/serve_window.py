"""The serving driver: one ``ServeEngine`` over weights made on the device
from the seed, every session prefilled in set-up, then an open-loop window
of warm ticks at the traffic mix's fixed rate."""

from __future__ import annotations

import gc
import math

import numpy as np

from chipbench.harness import common, flops, loadgen, reference


def reference_logits(params, ticks, budget, shares, model, sizes: dict,
                     quant=None):
    """The plain reference over one session's whole history: ``model``'s
    causal pass over [first-price pads | first window | the ticks that
    followed], read at every step with that step's wallet. ``ticks`` holds
    ``window + n - 1`` prices, ``budget`` and ``shares`` ``n`` entries;
    rows past the session's served steps are padding that causality keeps
    out of every earlier row. -> (n, A)"""
    import jax.numpy as jnp
    w, n = sizes["window"], budget.shape[0]
    hist = model.history(sizes)
    series = jnp.concatenate([jnp.full((hist,), ticks[0]), ticks])
    positions = jnp.arange(-hist, w + n - 1)
    hn = model.trunk(params, series, positions, sizes, quant)
    q = hist + w - 1 + jnp.arange(n)
    base_l, base_v, fold = reference.head_terms(params, hn[q], quant)
    feats = reference.port_feats(budget, shares, series[q])
    logits, _ = reference.heads_at(base_l, base_v, fold, feats)
    return logits


def serving_numbers(sample: list[loadgen.Session], seed: int, model,
                    sizes: dict, quant=None, alter: bool = False) -> dict:
    """Over a sample of sessions: ``logit_gap``, the widest gap by which a
    served action's reference logit lies below the reference's best, and
    ``logit_err``, the widest distance between a served logit and the
    reference's. With ``quant`` the control is put in the program's place:
    the action and logits the lower precision gives at the same steps.
    ``alter`` plants the fault of an answer altered where it is produced:
    every served action moved on by one."""
    import functools

    import jax
    k_params, _ = jax.random.split(jax.random.PRNGKey(seed))
    params = model.init_params(k_params, sizes)
    w = sizes["window"]
    pad = -(-max(len(s.steps) for s in sample) // 64) * 64
    fns = {q: jax.jit(functools.partial(reference_logits, model=model,
                                        sizes=sizes, quant=q))
           for q in {None, quant}}
    gap = err = 0.0
    for sess in sample:
        n = len(sess.steps)
        ticks = sess.prices[sess.start:sess.start + w + pad - 1]
        budget = np.zeros((pad,), np.float32)
        shares = np.zeros((pad,), np.float32)
        budget[:n] = [s[0] for s in sess.steps]
        shares[:n] = [s[1] for s in sess.steps]
        ref = np.asarray(fns[None](params, ticks, budget, shares))[:n]
        if quant is None:
            served = np.stack([s[3] for s in sess.steps])
            actions = np.asarray([s[2] for s in sess.steps])
            if alter:
                actions = (actions + 1) % sizes["actions"]
        else:
            served = np.asarray(
                fns[quant](params, ticks, budget, shares))[:n]
            actions = served.argmax(-1)
        below = ref.max(-1) - ref[np.arange(n), actions]
        gap = max(gap, float(below.max()))
        err = max(err, float(np.abs(served - ref).max()))
    return {"logit_gap": gap, "logit_err": err}


def draw_sample(sessions: list[loadgen.Session], seed: int, count: int):
    """``count`` sessions drawn from the seed, the longest among them."""
    rng = np.random.default_rng([seed, 4])
    longest = max(sessions, key=lambda s: len(s.steps))
    rest = [s for s in sessions if s is not longest and s.steps]
    picks = rng.choice(len(rest), size=min(count - 1, len(rest)),
                       replace=False)
    return [longest] + [rest[i] for i in picks]


def start_engine(cfg, traffic: dict, seed: int, n_arrivals: int):
    """Set-up: weights on the device from the seed in one jitted call, the
    engine's own warm-up, and every session's first request (the cold
    prefill) in a closed loop. -> (engine, sessions, failed first requests)"""
    import jax
    from sharetrade_tpu.agents import build_agent
    from sharetrade_tpu.env import trading
    from sharetrade_tpu.precision import policy_from_config
    from sharetrade_tpu.serve.engine import ServeEngine

    load = traffic["load"]
    prices = common.make_prices(traffic["prices"])
    env_params = trading.env_from_prices(
        prices[:cfg.env.window + 2], window=cfg.env.window,
        initial_budget=cfg.env.initial_budget)
    agent = build_agent(cfg, env_params)
    params = jax.jit(lambda key: agent.init(key).params)(
        jax.random.PRNGKey(seed))
    engine = ServeEngine(agent.model, cfg.serve, params,
                         precision=policy_from_config(cfg.precision))
    del params
    engine.warmup()
    per_session = 8 + int(8 * n_arrivals / load["sessions"])
    sessions = loadgen.make_sessions(
        prices, cfg.env.window, load["sessions"], seed,
        cfg.env.initial_budget, max_steps=per_session + 64)
    cold_failed = loadgen.first_requests(engine, sessions, load["wave"])
    return engine, sessions, cold_failed


def release(engine) -> None:
    """Stop the engine and free its arena: the jitted tick programs are
    bound methods, so JAX's caches would keep the engine, and with it 7 GB,
    alive."""
    import jax
    engine.stop(drain=False)
    engine._pool = None
    jax.clear_caches()
    gc.collect()


def serve_sessions(cfg, traffic: dict, seed: int, seconds: float):
    """A short window at the cell's own load, for the calibration readings:
    the sessions with what they were served."""
    due = loadgen.arrival_times(seed, traffic["load"]["rate"], seconds)
    engine, sessions, _ = start_engine(cfg, traffic, seed, len(due))
    gen = loadgen.OpenLoop(engine, sessions, due, seed)
    gen.run()
    gen.wait_idle(60.0)
    release(engine)
    return sessions


def run(cfg, traffic: dict, limits: dict, *, model, seed: int, seconds: float,
        trace: bool, t_start: float, out_dir: str, device: dict, peaks: dict,
        readers) -> tuple[dict, dict]:
    """One run of a serving cell -> (result, compared)."""
    import threading
    import time

    from chipbench.harness import correct, trace_reduce

    sizes = flops.sizes(cfg, model)
    load = traffic["load"]
    due = loadgen.arrival_times(seed, load["rate"], seconds)
    engine, sessions, cold_failed = start_engine(cfg, traffic, seed, len(due))
    hist0 = engine.registry.histograms()
    count0 = engine.registry.counters()
    setup_s = time.time() - t_start

    gen = loadgen.OpenLoop(engine, sessions, due, seed)
    profile = trace_reduce.Profile(out_dir) if trace else None

    def traced_stretch():
        # On a thread of its own: starting and stopping the profiler takes
        # seconds, and the generator must not run late for it.
        lead = min(2.0, seconds / 4)
        time.sleep(lead)
        profile.start()
        time.sleep(min(traffic.get("trace_seconds", 2.0), seconds - 2 * lead))
        profile.stop()

    tracer = threading.Thread(target=traced_stretch) if trace else None
    if tracer:
        tracer.start()
    t0 = gen.run()
    if tracer:
        tracer.join()
    t1 = t0 + seconds
    all_answered = gen.wait_idle(60.0)
    histograms = common.histogram_delta(hist0, engine.registry.histograms())
    counters = {name: total - count0.get(name, 0.0) for name, total
                in engine.registry.counters().items()}
    peak = common.memory_peak_bytes()
    release(engine)

    in_window = sum(1 for t in gen.done_at if t <= t1)
    failed = gen.failed + cold_failed
    # A failed request counts as beyond any percentile.
    latencies = gen.latency_ms + [math.inf] * gen.failed
    values = {
        "serve_req_per_s": in_window / seconds,
        "serve_p50_ms": (common.percentile(latencies, 50)
                         if latencies else math.nan),
        "serve_p95_ms": (common.percentile(latencies, 95)
                         if latencies else math.nan),
        "setup_s": setup_s}

    sample = draw_sample(sessions, seed, load["check_sessions"])
    numbers = serving_numbers(sample, seed, model, sizes)
    ok, compared = correct.judge(numbers, limits)
    ok = ok and all_answered and failed == 0 and in_window > 0

    context = {"histograms": histograms, "values": values, "sizes": sizes,
               "peaks": peaks, "memory_peak_bytes": peak,
               "counters": counters, "max_batch": cfg.serve.max_batch,
               "late_ms": gen.late_ms}
    return common.assemble(ok, gen.attempted, failed, device, peak, values,
                           profile, context, readers), compared
