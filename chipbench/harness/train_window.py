"""The training driver: one ``Orchestrator`` built from the configuration,
its first three chunks driven from the seed for the comparison, then the
timed window on that same object."""

from __future__ import annotations

import functools
import gc
import math
import sys
import time

from chipbench.harness import common, correct, flops, reference

CHECK_STEPS = 3
_CHUNKS: dict = {}      # compiled reference chunks, by what they close over


def window_rate(rows: list[tuple[float, float]], t0: float, t1: float,
                agents: int) -> tuple[float, int]:
    """(agent-steps per second, chunks) from the ``env_steps`` metric rows
    stamped inside [t0, t1]: all the steps between the first and the last
    such row over the time between them, a stalled chunk included."""
    inside = [(ts, v) for ts, v in rows if t0 <= ts <= t1]
    if len(inside) < 2 or inside[-1][0] <= inside[0][0]:
        return float("nan"), max(len(inside) - 1, 0)
    steps = inside[-1][1] - inside[0][1]
    return steps * agents / (inside[-1][0] - inside[0][0]), len(inside) - 1


def reference_training(model, sizes: dict, learner, prices, seed: int, *,
                       steps: int = CHECK_STEPS, quant=None, fault=None,
                       initial_budget: float = 2400.0) -> dict:
    """Losses, first-step gradient norms and the parameters' change over
    ``steps`` chunks of the plain reference with ``model``'s trunk (or of
    the control, or of the reference with a fault planted)."""
    import jax
    import jax.numpy as jnp
    key = (model, tuple(sorted(sizes.items())), learner.learning_rate,
           learner.gamma, learner.gae_lambda, learner.clip_eps,
           learner.value_coef, learner.entropy_coef, quant, fault)
    if key not in _CHUNKS:
        _CHUNKS[key] = jax.jit(functools.partial(
            reference.ppo_chunk, s=sizes, model=model,
            lr=learner.learning_rate,
            gamma=learner.gamma, lam=learner.gae_lambda,
            clip_eps=learner.clip_eps, value_coef=learner.value_coef,
            entropy_coef=learner.entropy_coef, quant=quant, fault=fault))
    chunk = _CHUNKS[key]
    t_ref = time.time()
    state = jax.jit(functools.partial(
        reference.init_state, s=sizes, model=model,
        initial_budget=initial_budget))(jax.random.PRNGKey(seed))
    p0 = state["params"]
    prices = jnp.asarray(prices)
    rows, first = [], None
    for k in range(steps):
        state, metrics, cache = chunk(state, prices)
        rows.append(metrics)
        if k == 0:
            first = (jax.jit(reference.grad_rss)(state["acc"]), cache,
                     state["shares"])
    change = jax.jit(reference.change_norms)(state["params"], p0)
    rows, first = jax.device_get((rows, first))
    print(f"chipbench: reference took {time.time() - t_ref:.1f} s",
          file=sys.stderr)
    return _readings(rows, first, change)


def _readings(rows, first, change) -> dict:
    grad, cache, shares = first
    return {"losses": [float(r["loss"]) for r in rows],
            "metrics": [{k: float(v) for k, v in r.items()} for r in rows],
            "grad": correct.flat_norms(grad),
            "change": correct.flat_norms(change),
            "cache": cache, "shares": shares}


def drive_first_steps(orc, model, steps: int = CHECK_STEPS) -> dict:
    """The program's first chunks through the window's own compiled step and
    state (``orc._step_fn`` on ``orc._ts``, committed back as the dispatcher
    commits it), keeping what the comparison needs; ``model.program_cache``
    brings the carry's rolling cache into the reference's names and tick
    order."""
    import jax
    import jax.numpy as jnp
    p0 = jax.tree.map(jnp.copy, orc._ts.params)
    rows, first = [], None
    keys = ("loss", "policy_loss", "value_loss", "entropy", "reward_sum")
    for k in range(steps):
        with orc._step_lock:
            ts, metrics = orc._step_fn(orc._ts)
            orc._ts = ts
        rows.append({key: metrics[key] for key in keys})
        if k == 0:       # read now: the next step is given this state
            first = jax.device_get((
                jax.jit(reference.grad_rss)(ts.opt_state[0].sum_of_squares),
                jax.jit(model.program_cache)(ts.carry), ts.env_state.shares))
    change = jax.jit(reference.change_norms)(orc._ts.params, p0)
    rows = jax.device_get(rows)
    return _readings(rows, first, change)


def run(cfg, traffic: dict, limits: dict, *, model, seed: int, seconds: float,
        trace: bool, t_start: float, out_dir: str, device: dict, peaks: dict,
        readers) -> tuple[dict, dict]:
    """One run of a training cell -> (result, compared)."""
    from sharetrade_tpu.runtime.orchestrator import Orchestrator
    from chipbench.harness import trace_reduce

    sizes = flops.sizes(cfg, model)
    agents = cfg.parallel.num_workers
    prices = common.make_prices(traffic["prices"])
    if trace:
        cfg.obs.enabled = True     # the dispatch-gap histogram is obs-gated

    stages = {"imports_and_prices": time.time() - t_start}
    orc = Orchestrator(cfg)
    orc.send_training_data(prices)
    stages["build_and_init"] = time.time() - t_start
    program = drive_first_steps(orc, model)
    stages["compile_and_first_steps"] = time.time() - t_start
    orc.start_training(background=True)

    def rows():
        return orc.metrics.series("env_steps")

    while len(rows()) < 2:
        if not orc._thread.is_alive():
            raise RuntimeError(f"training stopped in warm-up: "
                               f"{orc.last_error!r}")
        time.sleep(0.01)
    t0 = time.time()
    setup_s = t0 - t_start
    hist0 = orc.metrics.histograms()
    print("chipbench: set-up stages (s since start):", stages,
          file=sys.stderr)

    profile = None
    if trace:
        lead = min(2.0, seconds / 4)
        time.sleep(lead)
        profile = trace_reduce.Profile(out_dir)
        profile.start()
        time.sleep(min(traffic.get("trace_seconds", 4.0), seconds - 2 * lead))
        profile.stop()
    time.sleep(max(0.0, t0 + seconds - time.time()))
    t1 = time.time()
    all_rows = rows()
    loss_rows = [v for ts, v in orc.metrics.series("loss") if t0 <= ts <= t1]
    histograms = common.histogram_delta(hist0, orc.metrics.histograms())
    restarts = orc.restarts
    orc.stop()
    horizon = orc.env.num_steps
    peak = common.memory_peak_bytes()

    rate, chunks = window_rate(all_rows, t0, t1, agents)
    failed = sum(1 for v in loss_rows if not math.isfinite(v)) + restarts
    if all_rows and all_rows[-1][1] >= horizon:
        failed += 1                      # the episode ended inside the run
    values = {"agent_steps_per_s": rate, "setup_s": setup_s}

    # Free the program's state before the reference takes the chip.
    orc._ts = None
    del orc
    gc.collect()
    ref = reference_training(model, sizes, cfg.learner, prices, seed,
                             initial_budget=cfg.env.initial_budget)
    numbers = correct.training_numbers(program, ref, model)
    ok, compared = correct.judge(numbers, limits)
    ok = ok and failed == 0 and chunks >= 1 and math.isfinite(rate)

    context = {"histograms": histograms, "values": values, "sizes": sizes,
               "peaks": peaks, "memory_peak_bytes": peak, "chunks": chunks}
    return common.assemble(ok, chunks, failed, device, peak, values,
                           profile, context, readers), compared
