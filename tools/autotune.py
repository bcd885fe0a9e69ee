#!/usr/bin/env python
"""Offline autotune: seeded successive-halving sweeps over the knob
registry, writing a per-host ``tuned_profile.json``.

ROADMAP item 5's offline tier. Three workload specs, each sweeping the
registered knobs (sharetrade_tpu/tuning.py ``KNOBS``) of one tier with a
SHORT measured window per trial and an early-stopping search:

- **train** — ``runtime.megachunk_factor`` x ``runtime.pipeline_depth``
  on the dispatch-floor workload (tiny qlearn through the REAL
  orchestrator hot loop); objective: agent-steps/s.
- **serve** — ``serve.max_batch`` x ``serve.batch_timeout_ms`` x
  ``serve.max_queue`` on the MLP serving workload (tools/serve_soak.py's
  acceptance stack); objective: closed-loop saturation QPS, with the p99
  at that load recorded per trial.
- **distrib** — ``distrib.ingest_every_updates`` x
  ``distrib.ingest_max_rows`` against a feeder thread appending
  transition rows to a synthetic actor journal while the learner trains;
  objective: geometric mean of updates/s and ingested rows/s (the
  cadence trades exactly these two against each other — the N=4
  ingest-collapse axis).

Search: **successive halving** (Jamieson & Talwalkar, the eta-fraction
keep rule): every arm runs at the smallest window; the top ``1/eta``
survive to a doubled window; repeat until one arm stands. Expensive
per-arm state (compiled orchestrators, warmed engines) is CACHED across
rungs, so an arm pays its build exactly once in BOTH search modes and
the sweep-vs-exhaustive wall-clock ratio measures the search, not
rebuild overhead. ``--exhaustive`` additionally measures EVERY arm at
the final (largest) window — the hand-sweep baseline the acceptance
compares against: chosen-arm objective within 10% of the exhaustive
best, total sweep cost < 25% of the exhaustive grid's wall-clock (CPU
readings only: no knob here has been swept on the chip, ROADMAP D2).

Output: an atomic, schema-versioned ``tuned_profile.json`` (host
fingerprint: cores/backend/device count) that ``config.py`` loads via
``tuning.profile`` — explicit config wins over the profile, the profile
wins over defaults, provenance lands in the run manifest.

Usage:
    python tools/autotune.py                       # train+serve, full
    python tools/autotune.py --quick               # seconds-scale grid
    python tools/autotune.py --spec serve --exhaustive
    python tools/autotune.py --out tuned_profile.json --seed 7
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import serve_soak  # noqa: E402  (tools/ sibling)

from sharetrade_tpu import tuning  # noqa: E402
from sharetrade_tpu.config import FrameworkConfig  # noqa: E402
from sharetrade_tpu.utils.logging import get_logger  # noqa: E402

log = get_logger("autotune")


# ---------------------------------------------------------------------------
# grids
# ---------------------------------------------------------------------------

def train_grid(quick: bool) -> list[dict]:
    ks = (1, 8) if quick else (1, 4, 8, 16)
    depths = (2,) if quick else (1, 2, 4)
    return [{"runtime.megachunk_factor": k, "runtime.pipeline_depth": d}
            for k in ks for d in depths]


def serve_grid(quick: bool) -> list[dict]:
    if quick:
        batches, timeouts, queues = (8, 32), (0.5, 2.0), (256,)
    else:
        batches, timeouts, queues = ((8, 16, 32, 64), (0.5, 2.0, 8.0),
                                     (128, 512))
    return [{"serve.max_batch": b, "serve.batch_timeout_ms": t,
             "serve.max_queue": q}
            for b in batches for t in timeouts for q in queues]


def distrib_grid(quick: bool) -> list[dict]:
    everies = (4, 16) if quick else (2, 8, 32)
    rows = (4096,) if quick else (1024, 8192)
    return [{"distrib.ingest_every_updates": e,
             "distrib.ingest_max_rows": r}
            for e in everies for r in rows]


# ---------------------------------------------------------------------------
# measurers (one class per spec; per-arm state cached across rungs)
# ---------------------------------------------------------------------------

class TrainMeasurer:
    """Dispatch-floor workload through the real orchestrator: one
    compiled orchestrator per arm (cached — an arm pays its compile once
    across rungs and across search modes); a window of weight ``w`` runs
    ``w`` episodes over a fixed chunk budget and times them."""

    CHUNKS = 32                 # per episode; divisible by every K above
    CHUNK_STEPS = 10

    def __init__(self, *, seed: int, workdir: str):
        self.seed = seed
        self.workdir = workdir
        self._orchs: dict[tuple, object] = {}

    def _orch(self, arm: dict):
        from sharetrade_tpu.data.synthetic import synthetic_price_series
        from sharetrade_tpu.runtime.orchestrator import Orchestrator
        key = tuple(sorted(arm.items()))
        orch = self._orchs.get(key)
        if orch is not None:
            return orch
        cfg = FrameworkConfig()
        cfg.seed = self.seed
        cfg.learner.algo = "qlearn"
        cfg.parallel.num_workers = 10
        cfg.env.window = 8
        cfg.model.hidden_dim = 8            # host-dominated on purpose
        cfg.runtime.chunk_steps = self.CHUNK_STEPS
        cfg.runtime.checkpoint_every_updates = 0
        cfg.runtime.keep_best_eval = False
        cfg.runtime.checkpoint_dir = os.path.join(
            self.workdir, f"ck-{len(self._orchs)}")
        for path, value in arm.items():
            tuning.set_knob(cfg, path, value)
        series = synthetic_price_series(
            length=cfg.env.window + self.CHUNKS * self.CHUNK_STEPS + 8,
            seed=self.seed)
        orch = Orchestrator(cfg)
        orch.send_training_data(series.prices)
        orch.start_training(background=False)   # episode 1: compile+warm
        self._orchs[key] = orch
        return orch

    def measure(self, arm: dict, window: float) -> dict:
        orch = self._orch(arm)
        episodes = max(1, int(round(window)))
        t0 = time.perf_counter()
        for _ in range(episodes):
            orch.start_training(background=False)   # re-arms, cached jit
        elapsed = time.perf_counter() - t0
        steps = episodes * self.CHUNKS * self.CHUNK_STEPS * 10  # workers
        return {"objective": steps / elapsed,
                "agent_steps_per_sec": round(steps / elapsed, 2),
                "elapsed_s": round(elapsed, 4)}

    def close(self) -> None:
        for orch in self._orchs.values():
            orch.stop()
        self._orchs.clear()


class ServeMeasurer:
    """Closed-loop saturation QPS per serve-knob arm on the MLP
    acceptance workload; engines cached per arm across rungs (one build +
    warmup each). p99 at saturation load rides along per trial."""

    def __init__(self, *, seed: int):
        self.seed = seed
        model, params, prices, window = serve_soak.build_workload(
            mlp=True, window=16, length=2048, seed=seed)
        self._stack = (model, params, prices, window)
        self._engines: dict[tuple, object] = {}
        self._serial = 0

    def _engine(self, arm: dict):
        from sharetrade_tpu.config import ServeConfig
        from sharetrade_tpu.serve import ServeEngine
        key = tuple(sorted(arm.items()))
        engine = self._engines.get(key)
        if engine is not None:
            return engine
        model, params, _, _ = self._stack
        mb = int(arm["serve.max_batch"])
        cfg = ServeConfig(
            max_batch=mb, slots=4 * mb,
            batch_timeout_ms=float(arm["serve.batch_timeout_ms"]),
            max_queue=int(arm["serve.max_queue"]),
            swap_poll_s=0.0, stats_interval_s=0.5)
        engine = ServeEngine(model, cfg, params)
        engine.warmup()
        self._engines[key] = engine
        return engine

    def measure(self, arm: dict, window: float) -> dict:
        from sharetrade_tpu.serve.driver import make_sessions, run_closed_loop
        engine = self._engine(arm)
        _, _, prices, win = self._stack
        self._serial += 1
        mb = int(arm["serve.max_batch"])
        sessions = make_sessions(prices, win, 8 * mb, seed=self.seed,
                                 prefix=f"at{self._serial}-")
        run = run_closed_loop(engine, sessions, concurrency=2 * mb,
                              duration_s=max(0.2, float(window)))
        return {"objective": run["qps"],
                "qps": round(run["qps"], 1),
                "p99_ms": round(run["p99_ms"], 3),
                "elapsed_s": round(run["elapsed_s"], 4)}

    def close(self) -> None:
        for engine in self._engines.values():
            engine.stop(drain=False)
        self._engines.clear()


class DistribMeasurer:
    """Learner-ingest cadence sweep against a live feeder: a thread
    appends transition rows to a synthetic actor journal at a fixed rate
    while a DQN learner trains one fixed episode and ingests at the
    arm's cadence. Objective: geometric mean of updates/s and ingested
    rows/s — the two quantities the cadence trades against each other.
    Adaptive ingest is pinned OFF so each arm measures ITS cadence, not
    the controller's. Per-arm orchestrators are CACHED across rungs like
    the other measurers (one compile per arm under either search mode);
    the env-step stamp counter continues monotone across windows so the
    learner's ingest cursor keeps advancing over one growing journal."""

    CHUNKS = 24
    CHUNK_STEPS = 10
    FEED_HZ = 40                # record batches per second
    FEED_BATCH = 64             # rows per record

    def __init__(self, *, seed: int, workdir: str):
        self.seed = seed
        self.workdir = workdir
        #: arm key -> (orchestrator, journal_path, obs_dim, rng,
        #: mutable [env_step_stamp]).
        self._arms: dict[tuple, tuple] = {}

    def _arm_state(self, arm: dict):
        import numpy as np
        from sharetrade_tpu.data.synthetic import synthetic_price_series
        from sharetrade_tpu.distrib.actor import TRANSITIONS_FILE
        from sharetrade_tpu.runtime.orchestrator import Orchestrator
        key = tuple(sorted(arm.items()))
        state = self._arms.get(key)
        if state is not None:
            return state
        root = os.path.join(self.workdir, f"arm-{len(self._arms)}")
        actor_dir = os.path.join(root, "actors")
        os.makedirs(os.path.join(actor_dir, "a0"), exist_ok=True)
        cfg = FrameworkConfig()
        cfg.seed = self.seed
        cfg.learner.algo = "dqn"
        cfg.parallel.num_workers = 10
        cfg.env.window = 8
        cfg.model.hidden_dim = 8
        cfg.learner.replay_capacity = 16384
        cfg.runtime.chunk_steps = self.CHUNK_STEPS
        cfg.runtime.checkpoint_every_updates = 0
        cfg.runtime.keep_best_eval = False
        cfg.runtime.checkpoint_dir = os.path.join(root, "ck")
        cfg.distrib.num_actors = 1          # enables ingest; no pool here
        cfg.distrib.actor_dir = actor_dir
        cfg.tuning.adaptive_ingest = False  # measure the ARM's cadence
        for path, value in arm.items():
            tuning.set_knob(cfg, path, value)
        series = synthetic_price_series(
            length=cfg.env.window + self.CHUNKS * self.CHUNK_STEPS + 8,
            seed=self.seed)
        orch = Orchestrator(cfg)
        orch.send_training_data(series.prices)
        orch.start_training(background=False)       # compile + warm
        state = (orch, os.path.join(actor_dir, "a0", TRANSITIONS_FILE),
                 cfg.env.window + 2,
                 np.random.default_rng(self.seed), [0])
        self._arms[key] = state
        return state

    def measure(self, arm: dict, window: float) -> dict:
        import numpy as np
        from sharetrade_tpu.data.journal import Journal
        from sharetrade_tpu.data.transitions import append_transitions

        orch, journal_path, obs_dim, rng, stamp = self._arm_state(arm)
        episodes = max(1, int(round(window)))
        stop = threading.Event()
        fed = [0]

        def feeder():
            # Same-process reopen of the arm's journal is legal under
            # the writer lock; stamps continue monotone across windows.
            journal = Journal(journal_path, segment_records=256)
            try:
                spacing = 1.0 / self.FEED_HZ
                while not stop.is_set():
                    stamp[0] += self.FEED_BATCH
                    obs = rng.standard_normal(
                        (self.FEED_BATCH, obs_dim)).astype(np.float32)
                    append_transitions(
                        journal, obs,
                        rng.integers(0, 3, self.FEED_BATCH,
                                     dtype=np.int32),
                        rng.standard_normal(
                            self.FEED_BATCH).astype(np.float32),
                        obs, env_steps=stamp[0])
                    journal.flush()
                    fed[0] += self.FEED_BATCH
                    stop.wait(spacing)
            finally:
                journal.close()

        thread = threading.Thread(target=feeder, daemon=True)
        rows0 = orch.metrics.counters().get(
            "distrib_rows_ingested_total", 0.0)
        thread.start()
        try:
            t0 = time.perf_counter()
            for _ in range(episodes):
                orch.start_training(background=False)
            elapsed = time.perf_counter() - t0
            rows = orch.metrics.counters().get(
                "distrib_rows_ingested_total", 0.0) - rows0
            updates = episodes * self.CHUNKS     # one update per chunk
        finally:
            stop.set()
            thread.join(5.0)
        updates_ps = updates / elapsed
        rows_ps = rows / elapsed
        return {
            "objective": math.sqrt(max(updates_ps, 1e-9)
                                   * max(rows_ps, 1e-9)),
            "updates_per_sec": round(updates_ps, 2),
            "rows_ingested_per_sec": round(rows_ps, 1),
            "rows_fed": fed[0],
            "elapsed_s": round(elapsed, 4),
        }

    def close(self) -> None:
        for orch, *_ in self._arms.values():
            orch.stop()
        self._arms.clear()


# ---------------------------------------------------------------------------
# the search
# ---------------------------------------------------------------------------

def successive_halving(arms: list[dict], measure, *, rung0_window: float,
                       eta: int = 4, max_rungs: int = 4,
                       log_fn=None) -> dict:
    """Run the halving ladder; returns ``{"best", "trials", "rungs",
    "top_window", "wall_s", "measure_s"}``. Deterministic given the arm
    order and a deterministic measure function (real measurements are
    wall-clock, so ties break by grid order — the seeded part is the
    workload underneath). ``measure_s`` sums the MEASUREMENT windows
    only (each trial's ``elapsed_s``): per-arm build/compile cost is
    identical under any search strategy (every arm builds exactly once,
    halving or exhaustive), so the sweep-cost acceptance compares what
    the strategies actually change."""
    say = log_fn or (lambda msg: log.info("%s", msg))
    t_start = time.perf_counter()
    survivors = list(arms)
    window = rung0_window
    trials: list[dict] = []
    rungs = 0
    measure_s = 0.0
    while True:
        rung_results = []
        for arm in survivors:
            res = measure(arm, window)
            trials.append({"arm": arm, "window": window, **res})
            measure_s += res.get("elapsed_s", 0.0)
            rung_results.append((res["objective"], arm))
            say(f"rung {rungs} window={window:g}: {arm} -> "
                f"objective {res['objective']:.1f}")
        rungs += 1
        if len(survivors) == 1 or rungs >= max_rungs:
            # Final ranking decides even when max_rungs truncates the
            # ladder with >1 survivor.
            best = max(rung_results, key=lambda t: t[0])[1]
            break
        keep = max(1, math.ceil(len(survivors) / eta))
        ranked = sorted(rung_results, key=lambda t: -t[0])
        survivors = [arm for _, arm in ranked[:keep]]
        window *= 2
    return {"best": best, "trials": trials, "rungs": rungs,
            "top_window": window,
            "wall_s": time.perf_counter() - t_start,
            "measure_s": measure_s}


def run_spec(spec: str, *, quick: bool, seed: int, workdir: str,
             exhaustive: bool, log_fn=None) -> dict:
    say = log_fn or (lambda msg: log.info("%s", msg))
    if spec == "train":
        grid = train_grid(quick)
        measurer = TrainMeasurer(seed=seed, workdir=workdir)
        # Episodes: an episode is tens of ms on a fast host, so the
        # rung-0 window batches several — a sub-100 ms sample ranks
        # scheduler noise, not knobs.
        rung0 = 2.0 if quick else 8.0
    elif spec == "serve":
        grid = serve_grid(quick)
        measurer = ServeMeasurer(seed=seed)
        rung0 = 0.3 if quick else 0.5       # seconds
    elif spec == "distrib":
        grid = distrib_grid(quick)
        measurer = DistribMeasurer(seed=seed, workdir=workdir)
        rung0 = 1.0                         # episodes
    else:
        raise ValueError(f"unknown spec {spec!r} "
                         "(train | serve | distrib)")
    say(f"[{spec}] sweeping {len(grid)} arms (quick={quick})")
    try:
        result = successive_halving(
            grid, measurer.measure, rung0_window=rung0,
            max_rungs=2 if quick else 4, log_fn=log_fn)
        out = {
            "spec": spec,
            "arms": len(grid),
            "best": result["best"],
            "rungs": result["rungs"],
            "sweep_wall_s": round(result["wall_s"], 3),
            "trials": result["trials"],
        }
        best_trial = max(
            (t for t in result["trials"]
             if t["arm"] == result["best"]),
            key=lambda t: t["window"])
        out["best_objective"] = best_trial["objective"]
        out["best_detail"] = {k: v for k, v in best_trial.items()
                              if k not in ("arm",)}
        if exhaustive:
            # The hand-sweep baseline: EVERY arm at the full-confidence
            # window — double the halving's top rung, best of 2 trials
            # per arm (a single short sample on a shared host ranks
            # scheduler luck).
            # sweep_cost_frac compares MEASUREMENT seconds only: per-arm
            # build/compile happens exactly once under either strategy
            # (arm state is cached across rungs and reused here), so
            # builds cancel out of the comparison; raw walls are
            # recorded alongside.
            full_window = result["top_window"] * 2
            t0 = time.perf_counter()
            rows = []
            ex_measure_s = 0.0
            for arm in grid:
                best_trial = None
                for _ in range(2):
                    res = measurer.measure(arm, full_window)
                    ex_measure_s += res.get("elapsed_s", 0.0)
                    if (best_trial is None
                            or res["objective"]
                            > best_trial["objective"]):
                        best_trial = res
                rows.append({"arm": arm, "window": full_window,
                             **best_trial})
            ex_wall = time.perf_counter() - t0
            ex_best = max(rows, key=lambda r: r["objective"])
            chosen = max(
                (r for r in rows if r["arm"] == result["best"]),
                key=lambda r: r["objective"])
            out["exhaustive"] = {
                "window": full_window,
                "trials_per_arm": 2,
                "wall_s": round(ex_wall, 3),
                "measure_s": round(ex_measure_s, 3),
                "sweep_measure_s": round(result["measure_s"], 3),
                "best": ex_best["arm"],
                "best_objective": ex_best["objective"],
                "chosen_objective_at_full_window": chosen["objective"],
                "chosen_vs_best": round(
                    chosen["objective"]
                    / max(ex_best["objective"], 1e-9), 4),
                "sweep_cost_frac": round(
                    result["measure_s"] / max(ex_measure_s, 1e-9), 4),
                "rows": rows,
            }
        return out
    finally:
        measurer.close()


def run_autotune(specs=("train", "serve"), *, quick: bool = False,
                 out_path: str = "tuned_profile.json", seed: int = 0,
                 exhaustive: bool = False, log_fn=None) -> dict:
    """Sweep every requested spec and publish the merged profile."""
    say = log_fn or (lambda msg: log.info("%s", msg))
    knobs: dict = {}
    objectives: dict = {}
    results: dict = {}
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="autotune-") as workdir:
        for spec in specs:
            res = run_spec(spec, quick=quick, seed=seed, workdir=workdir,
                           exhaustive=exhaustive, log_fn=log_fn)
            results[spec] = res
            knobs.update(res["best"])
            objectives[spec] = {
                "objective": res["best_objective"],
                **{k: v for k, v in res["best_detail"].items()
                   if k not in ("objective", "trials")},
            }
    profile = tuning.build_profile(
        knobs, objectives=objectives,
        trials=[{"spec": s,
                 "trials": [{k: v for k, v in t.items()}
                            for t in r["trials"]]}
                for s, r in results.items()],
        seed=seed,
        config_hash=None,
        notes=f"tools/autotune.py quick={quick} specs={','.join(specs)}")
    tuning.write_profile(out_path, profile)
    say(f"tuned profile written: {out_path} knobs={knobs}")
    return {
        "out": out_path,
        "knobs": knobs,
        "fingerprint": profile["fingerprint"],
        "objectives": objectives,
        "wall_s": round(time.perf_counter() - t0, 3),
        "specs": {s: {k: v for k, v in r.items() if k != "trials"}
                  for s, r in results.items()},
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--spec", default="train,serve",
                        help="comma list of train,serve,distrib")
    parser.add_argument("--quick", action="store_true",
                        help="tiny grid, seconds-scale windows (the "
                             "make-check profile)")
    parser.add_argument("--out", default="tuned_profile.json",
                        help="profile output path (atomic rename)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--exhaustive", action="store_true",
                        help="also measure the full grid at the final "
                             "window (the acceptance baseline; slow)")
    parser.add_argument("--json", action="store_true",
                        help="print one machine-readable summary line")
    args = parser.parse_args()
    specs = tuple(s.strip() for s in args.spec.split(",") if s.strip())
    say = (lambda msg: None) if args.json else (
        lambda msg: print(msg, flush=True))
    summary = run_autotune(specs, quick=args.quick, out_path=args.out,
                           seed=args.seed, exhaustive=args.exhaustive,
                           log_fn=say)
    if args.json:
        print(json.dumps(summary))
    else:
        print(json.dumps({k: v for k, v in summary.items()
                          if k != "specs"}, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
