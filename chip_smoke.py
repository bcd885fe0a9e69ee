"""Quickest proof that the system still starts on the chip.

Drives train -> serve through the entry points a user calls
(``sharetrade_tpu.cli.main``) in ONE process that owns the chip from its
first JAX touch to exit, checks every phase's own summary, and prints as its
LAST stdout line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

    python chip_smoke.py              # one chip: reference, train_wide, serve
    python chip_smoke.py --multichip  # four chips: dp=4 vs one chip, only

Exits non-zero (``"ok": false``) when JAX finds no TPU — JAX itself falls
back to the CPU with only a warning — or when any phase misses a condition.
Every phase runs from a fresh working directory under the output directory:
the data layer writes ``journal/`` and ``checkpoints/`` relative to cwd, and
an event-sourced replay would happily pick up whatever a stale one holds.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: benchmarks/run_all.py's name of the widest model the repo supports
#: (4 layers x d=1024, 8 heads x 128, 64 agents x unroll 512, bf16_mixed).
WIDE_CONFIG = "ppo_tr_episode_large_d1024"
WIDE_CHUNKS = 3

#: Serve-phase load: fewer slots than sessions and a warm tier, so the
#: park and install programs run beside the warm and cold ones; open loop,
#: because closed-loop re-entries race the in-flight park and never hit.
SERVE_OVERRIDES = ("serve.max_batch=8", "serve.slots=16",
                   "serve.warm_bytes=1073741824", "serve.swap_poll_s=0")
SERVE_ARGS = ("--duration", "8", "--rate", "200", "--sessions", "64")

#: --multichip: first-chunk loss / portfolio mean of the dp=4 run against
#: the one-chip run, as relative error. Same seed and the same program
#: semantics, but bf16 matmuls whose partial sums are reduced in another
#: order once the agent batch is split four ways and all-reduced (bf16 has
#: 8 mantissa bits: one rounding step is 2**-8 = 0.4%), averaged over
#: 64 agents x 512 steps. 1% is above that and far below a wrong sharding
#: (a batch shard dropped or counted twice moves the mean by >= 25%).
#: Measured on the four-chip host in PR 21: loss 0.0, portfolio 6.3e-8.
MULTICHIP_RTOL = 0.01


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class PhaseFailed(Exception):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def run_cli(argv: list[str]) -> tuple[int, list[dict]]:
    """One ``cli.main(argv)`` call in this process; its stdout is echoed
    and its JSON lines are returned (the summary is the last of them)."""
    from sharetrade_tpu import cli
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(argv)
    lines = []
    for raw in buf.getvalue().splitlines():
        print(raw, flush=True)
        try:
            obj = json.loads(raw)
        except ValueError:
            continue
        if isinstance(obj, dict):
            lines.append(obj)
    return rc, lines


@contextlib.contextmanager
def fresh_cwd(path: str):
    os.makedirs(path, exist_ok=True)
    prev = os.getcwd()
    os.chdir(path)
    try:
        yield path
    finally:
        os.chdir(prev)


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def wide_config(seed: int, chunks: int = WIDE_CHUNKS):
    """``ppo_tr_episode_large_d1024`` exactly as benchmarks/run_all.py
    defines it; changed keys, and only these: the horizon is cut to
    ``chunks`` chunks, a refused kernel fails the run at once instead of
    being retried ten times under backoff on the chip's clock, and the
    metrics stream is on because the loss is read from it."""
    from benchmarks.run_all import make_configs
    cfg = make_configs()[WIDE_CONFIG]
    cfg.seed = seed
    cfg.data.synthetic_length = (
        cfg.env.window + chunks * cfg.runtime.chunk_steps)
    cfg.runtime.max_restarts = 0
    cfg.obs.enabled = True
    return cfg


def wide_agent(cfg, mesh=None):
    from sharetrade_tpu.agents import build_agent
    from sharetrade_tpu.data.synthetic import synthetic_price_series
    from sharetrade_tpu.env import trading
    series = synthetic_price_series(length=cfg.data.synthetic_length,
                                    seed=cfg.data.synthetic_seed)
    env_params = trading.env_from_prices(
        series.prices, window=cfg.env.window,
        initial_budget=cfg.env.initial_budget,
        initial_shares=cfg.env.initial_shares)
    return build_agent(cfg, env_params, mesh=mesh)


def compile_step(cfg) -> dict:
    """Lower and compile ``agent.step`` once on the chip, the way the
    orchestrator jits it, and count the Mosaic kernels in the result."""
    import jax
    agent = wide_agent(cfg)
    ts = jax.eval_shape(agent.init, jax.random.PRNGKey(cfg.seed))
    t0 = time.perf_counter()
    compiled = jax.jit(agent.step, donate_argnums=(0,)).lower(ts).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    out = {
        "phase": "compile_step", "config": WIDE_CONFIG,
        "compile_s": round(compile_s, 3),
        "mosaic_calls": compiled.as_text().count("tpu_custom_call"),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
    }
    emit(out)
    require(out["mosaic_calls"] > 0,
            "agent.step compiled with 0 tpu_custom_call: the Pallas kernels "
            "are not on the path")
    return out


def finite(*values) -> bool:
    return all(v is not None and math.isfinite(v) for v in values)


def phase_reference(workdir: str) -> dict:
    """The paper's workload: ``cli train`` at its defaults (qlearn,
    10 workers x 41k-param MLP, 6,046 prices, 5,845 env steps)."""
    t0 = time.perf_counter()
    with fresh_cwd(workdir):
        rc, lines = run_cli(["train"])
    require(rc == 0 and lines, f"cli train exited {rc}")
    summary = lines[-1]
    out = {"phase": "reference", "elapsed_s": round(
        time.perf_counter() - t0, 3), "peak_bytes_in_use": peak_bytes(),
        **{k: summary.get(k) for k in (
            "avg_portfolio", "std_portfolio", "env_steps", "updates",
            "restarts", "device")}}
    emit(out)
    require(summary.get("restarts") == 0, "reference: restarts != 0")
    require(finite(summary.get("avg_portfolio"),
                   summary.get("std_portfolio")),
            "reference: avg/std portfolio not finite")
    require(summary.get("env_steps") == 5845,
            f"reference: env_steps {summary.get('env_steps')} != 5845")
    return out


def phase_train_wide(workdir: str, cfg, cfg_path: str) -> dict:
    t0 = time.perf_counter()
    with fresh_cwd(workdir):
        rc, lines = run_cli(["train", "--config", cfg_path, "--eval"])
    require(rc == 0 and lines, f"cli train (wide) exited {rc}")
    summary = lines[-1]
    want_steps = WIDE_CHUNKS * cfg.runtime.chunk_steps
    out = {"phase": "train_wide", "config": WIDE_CONFIG,
           "elapsed_s": round(time.perf_counter() - t0, 3),
           "peak_bytes_in_use": peak_bytes(),
           **{k: summary.get(k) for k in (
               "avg_portfolio", "std_portfolio", "env_steps", "updates",
               "agent_steps_per_sec", "restarts", "eval_portfolio",
               "device")}}
    loss = read_last_loss(workdir)
    out["loss"] = loss
    emit(out)
    require(summary.get("restarts") == 0, "train_wide: restarts != 0")
    require(summary.get("env_steps") == want_steps,
            f"train_wide: env_steps {summary.get('env_steps')} != "
            f"{want_steps}")
    require(finite(loss, summary.get("avg_portfolio")),
            f"train_wide: loss {loss!r} / avg portfolio not finite")
    require(os.path.isdir(os.path.join(workdir, "checkpoints", "tag_best")),
            "train_wide: --eval left no checkpoints/tag_best")
    return out


def read_last_loss(workdir: str) -> float | None:
    """Last sampled chunk's loss gauge from the run's metrics stream
    (``obs/metrics.jsonl``): ``cli train``'s summary does not carry it."""
    loss = None
    try:
        with open(os.path.join(workdir, "obs", "metrics.jsonl")) as fh:
            for raw in fh:
                loss = json.loads(raw).get("gauges", {}).get("loss", loss)
    except (OSError, ValueError):
        return None
    return loss


def phase_serve(workdir: str, cfg_path: str) -> dict:
    argv = ["serve", "--config", cfg_path]
    for kv in SERVE_OVERRIDES:
        argv += ["--set", kv]
    argv += list(SERVE_ARGS)
    t0 = time.perf_counter()
    with fresh_cwd(workdir):
        rc, lines = run_cli(argv)
    require(lines, f"cli serve exited {rc} and printed nothing")
    ready = next((ln for ln in lines if ln.get("event") == "serving_ready"),
                 {})
    summary = lines[-1]
    keys = ("completed", "failed", "offered", "qps", "p50_ms", "p99_ms",
            "prefills", "evictions", "warm_parks", "warm_hits",
            "warm_misses", "restarts", "engine_failed", "drained",
            "stopped_clean", "device")
    out = {"phase": "serve", "rc": rc,
           "elapsed_s": round(time.perf_counter() - t0, 3),
           "peak_bytes_in_use": peak_bytes(),
           "params_step": ready.get("params_step"),
           "model": ready.get("model"),
           **{k: summary.get(k) for k in keys}}
    emit(out)
    require(rc == 0, f"cli serve exited {rc}")
    require((ready.get("params_step") or 0) > 0,
            "serve: did not boot from train_wide's tag_best "
            f"(params_step={ready.get('params_step')!r})")
    for key in ("completed", "prefills", "warm_parks", "warm_hits"):
        require((summary.get(key) or 0) > 0, f"serve: {key} == 0")
    for key in ("failed", "restarts"):
        require(summary.get(key) == 0,
                f"serve: {key} == {summary.get(key)!r}")
    require(summary.get("engine_failed") is False, "serve: engine_failed")
    require(summary.get("drained") is True, "serve: not drained")
    require(summary.get("stopped_clean") is True, "serve: not stopped clean")
    return out


def backends_line() -> None:
    """Which wire / journal implementation this checkout runs: a clean
    export holds no built ``native/*.so``, so the defaults take their
    Python side."""
    from sharetrade_tpu.data import native as native_journal
    from sharetrade_tpu.fleet import proto
    from sharetrade_tpu.config import FrameworkConfig
    cfg = FrameworkConfig()
    proto.set_backend(cfg.fleet.proto_backend)
    emit({"phase": "backends",
          "proto_backend": proto.proto_backend,
          "journal_backend": ("native" if cfg.data.use_native_journal
                              and native_journal.native_available()
                              else "python")})


def run_one_chip(out_dir: str, seed: int) -> None:
    backends_line()
    phase_reference(os.path.join(out_dir, "reference"))
    wide_dir = os.path.join(out_dir, "wide")
    os.makedirs(wide_dir)
    cfg = wide_config(seed)
    cfg_path = os.path.join(wide_dir, "wide.json")
    cfg.save(cfg_path)
    compile_step(cfg)
    phase_train_wide(wide_dir, cfg, cfg_path)
    phase_serve(wide_dir, cfg_path)


def run_multichip(out_dir: str, seed: int) -> None:
    """dp=4 through ``cli train --mesh`` against the same config and seed
    on device 0, in this one process."""
    import jax
    import numpy as np
    from sharetrade_tpu.parallel import build_mesh
    from sharetrade_tpu.parallel.sharding import jit_parallel_step

    require(len(jax.devices()) == 4,
            f"--multichip needs 4 chips, JAX reports {len(jax.devices())}")
    cfg = wide_config(seed)
    cfg.parallel.mesh_shape = {"dp": 4}
    work = os.path.join(out_dir, "multichip")
    os.makedirs(work)
    cfg_path = os.path.join(work, "wide_dp4.json")
    cfg.save(cfg_path)

    # What the CLI run is compared with, and the placement check: the
    # first chunk on the dp=4 mesh and on one chip, same init.
    mesh = build_mesh(cfg.parallel)
    agent4 = wide_agent(cfg, mesh=mesh)
    ts4 = agent4.init(jax.random.PRNGKey(cfg.seed))
    shardings, step4 = jit_parallel_step(agent4, mesh, ts4)
    ts4 = jax.device_put(ts4, shardings)
    t0 = time.perf_counter()
    compiled4 = step4.lower(ts4).compile()
    compile4_s = time.perf_counter() - t0
    hlo4 = compiled4.as_text()
    batch = agent4.num_agents

    def batch_leaf_devices(ts):
        return sorted({len(leaf.sharding.device_set)
                       for leaf in jax.tree.leaves((ts.carry, ts.env_state))
                       if getattr(leaf, "ndim", 0) >= 1
                       and leaf.shape[0] == batch})

    spread = batch_leaf_devices(ts4)
    t0 = time.perf_counter()
    ts4, m4 = compiled4(ts4)
    jax.block_until_ready(ts4.params)
    first4_s = time.perf_counter() - t0
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in jax.devices()]
    out_spread = batch_leaf_devices(ts4)
    m4 = jax.device_get(m4)
    del ts4

    agent1 = wide_agent(cfg)
    ts1 = agent1.init(jax.random.PRNGKey(cfg.seed))
    t0 = time.perf_counter()
    compiled1 = jax.jit(agent1.step, donate_argnums=(0,)).lower(ts1).compile()
    compile1_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ts1, m1 = compiled1(ts1)
    jax.block_until_ready(ts1.params)
    first1_s = time.perf_counter() - t0
    m1 = jax.device_get(m1)
    del ts1

    def rel(key):
        a, b = float(np.mean(m4[key])), float(np.mean(m1[key]))
        return a, b, abs(a - b) / max(abs(b), 1e-12)

    loss4, loss1, loss_rel = rel("loss")
    port4, port1, port_rel = rel("portfolio_mean")
    mosaic4 = hlo4.count("tpu_custom_call")
    emit({"phase": "multichip_first_chunk", "mesh": dict(mesh.shape),
          "mosaic_calls_dp4": mosaic4,
          "all_reduces_dp4": hlo4.count(" all-reduce("),
          "devices_per_batch_leaf_in": spread,
          "devices_per_batch_leaf_out": out_spread,
          "bytes_in_use": in_use,
          "loss_dp4": loss4, "loss_one_chip": loss1, "loss_rel": loss_rel,
          "portfolio_dp4": port4, "portfolio_one_chip": port1,
          "portfolio_rel": port_rel, "rtol": MULTICHIP_RTOL,
          "compile_s_dp4": round(compile4_s, 3),
          "compile_s_one_chip": round(compile1_s, 3),
          # executed once, compile excluded, no warm-up, no repeat
          "first_chunk_s_dp4": round(first4_s, 3),
          "first_chunk_s_one_chip": round(first1_s, 3)})
    require(mosaic4 > 0, "dp=4 step compiled with 0 tpu_custom_call: the "
            "kernels were switched off, not partitioned")
    require(spread == [4] and out_spread == [4],
            f"batch-leading TrainState leaves not spread over 4 devices: "
            f"in {spread} out {out_spread}")
    require(all(b > 0 for b in in_use),
            f"a device holds nothing: bytes_in_use {in_use}")
    require(finite(loss4, loss1, port4, port1),
            "multichip: non-finite loss or portfolio")
    require(loss_rel <= MULTICHIP_RTOL and port_rel <= MULTICHIP_RTOL,
            f"dp=4 disagrees with one chip: loss_rel {loss_rel:.4g}, "
            f"portfolio_rel {port_rel:.4g} > {MULTICHIP_RTOL}")

    # The documented entry point, end to end on the mesh.
    t0 = time.perf_counter()
    with fresh_cwd(os.path.join(work, "run")):
        rc, lines = run_cli(["train", "--config", cfg_path, "--mesh"])
    require(rc == 0 and lines, f"cli train --mesh exited {rc}")
    summary = lines[-1]
    emit({"phase": "multichip_cli", "elapsed_s": round(
        time.perf_counter() - t0, 3),
        **{k: summary.get(k) for k in (
            "avg_portfolio", "std_portfolio", "env_steps", "updates",
            "agent_steps_per_sec", "restarts", "device")}})
    require(summary.get("restarts") == 0, "multichip: restarts != 0")
    require(summary.get("env_steps") == WIDE_CHUNKS * cfg.runtime.chunk_steps,
            f"multichip: env_steps {summary.get('env_steps')}")
    require(finite(summary.get("avg_portfolio")),
            "multichip: avg portfolio not finite")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--multichip", action="store_true",
                        help="run ONLY the four-chip dp=4 path and the "
                             "one-chip run it is compared with")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "chip_smoke"),
        help="output directory (emptied first; phases run from fresh "
             "working directories under it)")
    args = parser.parse_args(argv)

    from sharetrade_tpu.utils.runtime_env import (configure_compile_cache,
                                                  device_block)
    cache_dir = configure_compile_cache()
    import jax
    block = device_block()
    device = {"platform": block["platform"], "kind": block["device_kind"],
              "count": block["count"]}
    if device["platform"] != "tpu":
        emit({"ok": False, "error": "no TPU: JAX reports platform "
              f"{device['platform']!r}; this script never completes on the "
              "CPU", "device": device})
        return 1

    out_dir = os.path.abspath(args.out)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    emit({"phase": "start", "seed": args.seed, "out": out_dir,
          "compile_cache": cache_dir,     # 0 entries = every compile is cold
          "compile_cache_entries": (len(os.listdir(cache_dir))
                                    if os.path.isdir(cache_dir) else 0),
          "jax": jax.__version__, "multichip": args.multichip})
    ok, error = True, None
    t0 = time.perf_counter()
    try:
        if args.multichip:
            run_multichip(out_dir, args.seed)
        else:
            run_one_chip(out_dir, args.seed)
    except PhaseFailed as exc:
        ok, error = False, str(exc)
    except Exception as exc:    # reported below, never swallowed
        traceback.print_exc()
        ok, error = False, f"{type(exc).__name__}: {exc}"
    # Only the small artifacts travel back: the d=1024 checkpoints are
    # hundreds of MB each and have done their job once serve booted.
    for dirpath, dirnames, _ in os.walk(out_dir):
        for name in [d for d in dirnames if d == "checkpoints"]:
            shutil.rmtree(os.path.join(dirpath, name), ignore_errors=True)
            dirnames.remove(name)
    emit({"phase": "end", "ok": ok, "error": error,
          "elapsed_s": round(time.perf_counter() - t0, 3),
          "peak_bytes_in_use": peak_bytes()})
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
