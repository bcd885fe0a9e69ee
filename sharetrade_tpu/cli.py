"""Command-line driver — the ShareTradeHelper entry point, with flags.

Reference: ``object ShareTradeHelper extends App`` wires the system with
hard-coded constants and polls ``IsEverythingDone`` every 5 s
(ShareTradeHelper.scala:14-48). Here the same flow takes a config file +
``--set section.key=value`` overrides (the flag surface the reference lacks,
SURVEY.md §5), runs the compiled training loop, and reports the avg/std
portfolio aggregation plus throughput.

    python -m sharetrade_tpu.cli train [--config cfg.json] [--set k=v ...]
    python -m sharetrade_tpu.cli query --config cfg.json   # inspect data layer
    python -m sharetrade_tpu.cli obs --dir obs             # summarize a run dir
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import signal
import sys
import threading
import time

from sharetrade_tpu.config import FrameworkConfig
from sharetrade_tpu.data.service import PriceDataService
from sharetrade_tpu.utils.logging import configure, get_logger
from sharetrade_tpu.utils.runtime_env import (
    configure_compile_cache,
    device_block,
    device_process_refusal,
    supervising_only,
)

log = get_logger("cli")

#: Exit code of a run that was preempted (SIGTERM/SIGINT) and wrote its
#: ``tag_preempt`` emergency checkpoint path — EX_TEMPFAIL from sysexits.h:
#: "temporary failure; the user is invited to retry", which is exactly what
#: a fleet scheduler should do (relaunch with ``--resume``). Distinct from
#: 0 (completed) and 1 (failed) so supervisors can tell the three apart.
EXIT_PREEMPTED = 75


def _load_config(args) -> FrameworkConfig:
    cfg = (FrameworkConfig.from_file(args.config) if args.config
           else FrameworkConfig())
    if args.set:
        cfg = cfg.apply_overrides(args.set)
    # Tuned-profile resolution (tuning.py): file/--set values are the
    # EXPLICIT tier and win; registered knobs still at their defaults
    # take the per-host profile's values. Resolved here once so every
    # subcommand (train/serve/learner/actor) runs the same knobs the
    # manifest will report.
    from sharetrade_tpu.tuning import apply_profile
    return apply_profile(cfg)


def cmd_train(args) -> int:
    from sharetrade_tpu.runtime import Orchestrator, ReplyState
    from sharetrade_tpu.parallel import build_mesh

    cfg = _load_config(args)
    service = PriceDataService(config=cfg.data)
    orch = None

    # Preemption handling: a TERM (fleet/TPU-pod preemption notice) or INT
    # asks the orchestrator to drain at its next megachunk boundary and
    # write the tag_preempt emergency checkpoint; the poll loop below
    # enforces runtime.preempt_grace_s and exits EXIT_PREEMPTED. Installed
    # BEFORE the (slow) data/orchestrator/compile bring-up so a preemption
    # notice during startup is never lost to the default signal disposition
    # — it is replayed onto the orchestrator the moment one exists.
    # Installed here (not in the Orchestrator) because signal handlers
    # belong to the process entry point — library users wire
    # orch.request_preempt() to whatever notification their fleet uses.
    preempt_at: list[float] = []

    def _on_signal(signum, frame):
        if not preempt_at:
            log.warning("received %s; requesting preemption drain",
                        signal.Signals(signum).name)
            preempt_at.append(time.monotonic())
        else:
            # Second signal escalates: an interactive Ctrl-C on a wedged
            # drain must not have to wait out the grace+5s hard-exit
            # timer. Whatever the drain already made durable is the
            # resume point.
            log.warning("received %s during the drain; hard exit",
                        signal.Signals(signum).name)
            os._exit(EXIT_PREEMPTED)
        if orch is not None:
            orch.request_preempt()

    prev_handlers = {
        s: signal.signal(s, _on_signal)
        for s in (signal.SIGTERM, signal.SIGINT)}

    try:
        symbols = [s.strip() for s in args.symbol.split(",") if s.strip()]
        if len(symbols) > 1:
            # Multi-asset portfolio: align the symbols on common dates.
            from sharetrade_tpu.data.ingest import align_series
            series = [service.request(s, args.start, args.end).series
                      for s in symbols]
            prices = align_series(series)
            log.info("loaded %s prices for %d assets %s",
                     prices.shape, len(symbols), symbols)
        else:
            response = service.request(symbols[0], args.start, args.end)
            prices = response.series.prices
            log.info("loaded %d prices for %s", len(prices), symbols[0])

        mesh = build_mesh(cfg.parallel) if args.mesh else None
        if mesh is not None:
            # The agent batch shards over dp; round workers up to a multiple
            # so the default 10 workers still run on an 8-chip mesh.
            dp = mesh.shape.get(cfg.parallel.data_axis, 1)
            if cfg.parallel.num_workers % dp:
                adjusted = ((cfg.parallel.num_workers + dp - 1) // dp) * dp
                log.warning("num_workers=%d not divisible by dp=%d; using %d",
                            cfg.parallel.num_workers, dp, adjusted)
                cfg.parallel.num_workers = adjusted
        orch = Orchestrator(cfg, mesh=mesh)
        if preempt_at:
            # A notice arrived during bring-up: replay it — the run will
            # drain at its first boundary and exit EXIT_PREEMPTED. The
            # grace clock re-anchors HERE so the hard-exit timer below and
            # the orchestrator's drain deadline (anchored inside
            # request_preempt) agree — otherwise a long bring-up would let
            # the hard exit kill the emergency save inside its own budget.
            preempt_at[0] = time.monotonic()
            orch.request_preempt()

        t0 = time.perf_counter()
        try:
            orch.send_training_data(prices, resume=args.resume)
        except FileNotFoundError as exc:
            log.error("--resume: %s (train without --resume first)", exc)
            return 1
        orch.start_training(background=True)

        # Driver poll loop (ShareTradeHelper.scala:32-48), with a sane cadence.
        poll_s = cfg.runtime.poll_interval_s
        grace = cfg.runtime.preempt_grace_s
        while not orch.wait(timeout=poll_s):
            if preempt_at:
                if time.monotonic() - preempt_at[0] > grace + 5.0:
                    # The drain overran its budget (a wedged device call, a
                    # hung disk): hard-exit with the preemption code — the
                    # fleet's KILL follows the TERM regardless, and whatever
                    # the drain already made durable is what --resume gets.
                    # os._exit on purpose: a graceful stop() here would
                    # block on the very threads that overran the budget.
                    log.error("preemption grace (%.1fs) expired before the "
                              "drain finished; hard exit", grace)
                    os._exit(EXIT_PREEMPTED)
                continue    # draining: don't stack snapshot barriers on it
            snap = orch.snapshot()
            if snap and args.verbose:
                log.info("progress: env_steps=%s portfolio_mean=%.2f",
                         snap.get("env_steps"), snap.get("portfolio_mean", 0.0))
        for s, h in prev_handlers.items():
            signal.signal(s, h)
        elapsed = time.perf_counter() - t0

        done = orch.is_everything_done()
        if orch.preempted or (preempt_at
                              and done.state is not ReplyState.COMPLETED):
            # A signal that lands in the same poll window as normal
            # completion does NOT preempt-label a finished run: completed
            # results are served below (the fleet must not --resume a run
            # that already delivered its answer).
            log.warning("run preempted; resume with --resume "
                        "(emergency checkpoint: %s)",
                        "written" if orch.preempt_saved
                        else "not confirmed — latest cadence checkpoint "
                             "is the resume point")
            return EXIT_PREEMPTED

        avg, std = orch.get_avg(), orch.get_std()
        if done.state is not ReplyState.COMPLETED or not avg.ok:
            log.error("training did not complete: %s (last error: %r)",
                      done, orch.last_error)
            return 1
        snap = orch.snapshot()
        total_agent_steps = snap.get("env_steps", 0.0) * cfg.parallel.num_workers
        # The reference's final log line (ShareTradeHelper.scala:46), plus rate.
        log.info("The average of the portfolios: %.4f, the standard deviation: %.4f",
                 avg.value, std.value)
        result = {
            "avg_portfolio": avg.value,
            "std_portfolio": std.value,
            "env_steps": snap.get("env_steps"),
            "updates": snap.get("updates"),
            "agent_steps_per_sec": total_agent_steps / max(elapsed, 1e-9),
            "elapsed_s": elapsed,
            "restarts": orch.restarts,
            "device": device_block(),
        }
        if args.eval:
            result.update(orch.evaluate())
        if args.eval_best:
            try:
                best = orch.evaluate_best()
            except FileNotFoundError:
                log.warning("--eval-best: no retained best checkpoint "
                            "(enable runtime.keep_best_eval and run --eval)")
            else:
                result.update({f"best_{k}": v for k, v in best.items()})
        print(json.dumps(result))
        return 0
    finally:
        if orch is not None:
            orch.stop()
        service.close()


def _serve_boot_params(manager, template, tag: str):
    """Initial serving weights: the tagged best policy when one exists,
    else the latest step checkpoint, else a fresh init (loud — an
    untrained policy serves finite garbage, not answers). Returns
    ``(params, step, boot_meta)``; ``boot_meta`` seeds the swap watcher's
    already-applied stamp."""
    try:
        state, meta = manager.restore_tagged(template, tag)
        return (state.params,
                int(meta.get("updates", meta.get("step", 0)) or 0), meta)
    except FileNotFoundError:
        pass
    try:
        state, step = manager.restore(template)
        return state.params, int(step), None
    except FileNotFoundError:
        log.warning("no checkpoint under %s; serving a fresh-initialized "
                    "(UNTRAINED) policy", manager.directory)
        return template.params, 0, None


def cmd_serve(args) -> int:
    """Continuous-batching inference service (serve/engine.py): coalesce
    per-session queries into padded device batches over the session slot
    pool, hot-swap weights from the training run's ``tag_best`` checkpoint,
    and export SLO gauges through obs/. Driven here by the synthetic
    session replayer (serve/driver.py) — a network front-end would sit on
    ``ServeEngine.submit`` the same way.

    Preemption-safe from day one: SIGTERM/SIGINT drains in-flight requests,
    flushes metrics, and exits ``EXIT_PREEMPTED`` (75) — the same contract
    as ``cli train``."""
    import jax

    from sharetrade_tpu.agents import build_agent
    from sharetrade_tpu.checkpoint.manager import CheckpointManager
    from sharetrade_tpu.env import trading
    from sharetrade_tpu.obs import build_obs
    from sharetrade_tpu.precision import policy_from_config
    from sharetrade_tpu.serve import ServeEngine, WeightSwapWatcher
    from sharetrade_tpu.serve.driver import (
        make_sessions,
        run_closed_loop,
        run_open_loop,
    )
    from sharetrade_tpu.utils.metrics import MetricsRegistry

    cfg = _load_config(args)
    service = PriceDataService(config=cfg.data)
    engine = watcher = obs_bundle = controller = None
    stop_evt = threading.Event()
    preempt_at: list[float] = []

    def _on_signal(signum, frame):
        if not preempt_at:
            log.warning("received %s; draining in-flight requests",
                        signal.Signals(signum).name)
            preempt_at.append(time.monotonic())
            stop_evt.set()
        else:
            log.warning("received %s during the drain; hard exit",
                        signal.Signals(signum).name)
            os._exit(EXIT_PREEMPTED)

    prev_handlers = {
        s: signal.signal(s, _on_signal)
        for s in (signal.SIGTERM, signal.SIGINT)}
    try:
        response = service.request(args.symbol.split(",")[0].strip(),
                                   args.start, args.end)
        prices = response.series.prices
        env_params = trading.env_from_prices(
            prices, window=cfg.env.window,
            initial_budget=cfg.env.initial_budget,
            initial_shares=cfg.env.initial_shares)
        agent = build_agent(cfg, env_params)
        template = agent.init(jax.random.PRNGKey(cfg.seed))
        manager = CheckpointManager(
            cfg.runtime.checkpoint_dir, keep=cfg.runtime.keep_checkpoints,
            fsync=cfg.checkpoint.fsync, precision_mode=cfg.precision.mode)
        params, step, boot_meta = _serve_boot_params(
            manager, template, cfg.serve.swap_tag)

        registry = MetricsRegistry(
            max_points=cfg.obs.max_metric_points or None)
        obs_bundle = build_obs(cfg, registry)
        engine = ServeEngine(agent.model, cfg.serve, params,
                             params_step=step,
                             precision=policy_from_config(cfg.precision),
                             registry=registry, obs=obs_bundle,
                             obs_cfg=cfg.obs)
        engine.warmup()
        if cfg.tuning.serve_controller:
            # Online self-tuning (serve/controller.py): hold
            # tuning.target_p99_ms by adapting batch_timeout_ms/max_queue
            # below their configured ceilings — every adjustment lands as
            # gauges + flight-ring events.
            from sharetrade_tpu.serve import ServeController
            controller = ServeController(
                engine, target_p99_ms=cfg.tuning.target_p99_ms,
                interval_s=cfg.tuning.controller_interval_s,
                obs=obs_bundle).start()
        if cfg.serve.swap_poll_s > 0:
            watcher = WeightSwapWatcher(
                engine, manager, template, tag=cfg.serve.swap_tag,
                poll_s=cfg.serve.swap_poll_s, seen_meta=boot_meta,
                breaker_failures=cfg.serve.swap_breaker_failures,
                breaker_cooldown_s=cfg.serve.swap_breaker_cooldown_s,
            ).start()
        # Readiness line (machine-readable: the soak/tests wait on it).
        print(json.dumps({"event": "serving_ready", "params_step": step,
                          "model": agent.model.name,
                          "max_batch": cfg.serve.max_batch,
                          "slots": cfg.serve.slots,
                          "device": device_block()}), flush=True)

        if args.listen:
            # Fleet worker mode (fleet/frontend.py): expose submit over
            # the wire instead of driving synthetic load. The client's
            # X-Deadline-Ms header flows into submit(deadline_ms=);
            # SIGTERM drains in-flight requests and exits 75 — the same
            # contract as the synthetic-driver mode, over a socket.
            from sharetrade_tpu.fleet import EngineBackend, ServeFrontend
            from sharetrade_tpu.fleet import proto as fleet_proto
            from sharetrade_tpu.fleet.wire import WireTracer
            # Pick the HTTP parse/render implementation BEFORE the
            # front-end spins up ("native" degrades loudly to "py"
            # when the extension isn't built — proto.set_backend).
            fleet_proto.set_backend(cfg.fleet.proto_backend)
            host, _, port_s = args.listen.rpartition(":")
            # Span journaling (ISSUE 17): a worker spawned by a tracing
            # fleet carries obs.span_dir/span_proc (fleet/pool.py) and
            # journals its engine spans there even with obs.enabled
            # false; the sink-less tracer parses inbound headers so
            # those spans parent under the router's attempt span.
            frontend = ServeFrontend(
                EngineBackend(
                    engine,
                    request_timeout_s=cfg.fleet.request_timeout_s,
                    spans=obs_bundle.spans),
                registry, host=host or "127.0.0.1",
                port=int(port_s or 0),
                wire_backend=cfg.fleet.wire_backend,
                tracer=(WireTracer() if obs_bundle.spans is not None
                        else None)).start()
            # The pool tails the worker's log for this line to learn the
            # ephemeral port (fleet/pool.py LISTENING_EVENT).
            print(json.dumps({"event": "engine_listening",
                              "host": frontend.host,
                              "port": frontend.port,
                              "pid": os.getpid(),
                              "proto_backend": fleet_proto.proto_backend,
                              "params_step": step}), flush=True)
            deadline = (time.monotonic() + args.duration
                        if args.duration > 0 else None)
            while not stop_evt.is_set():
                if deadline is not None and time.monotonic() >= deadline:
                    break
                stop_evt.wait(0.2)
            frontend.drain(
                timeout_s=cfg.runtime.preempt_grace_s * 0.25)
            frontend.stop()
            stats = {"mode": "listen", "host": frontend.host,
                     "port": frontend.port}
        else:
            sessions = make_sessions(prices, cfg.env.window,
                                     args.sessions, seed=cfg.seed)
            if args.rate > 0:
                stats = run_open_loop(engine, sessions,
                                      rate_qps=args.rate,
                                      duration_s=args.duration,
                                      stop=stop_evt)
            else:
                stats = run_closed_loop(
                    engine, sessions, concurrency=cfg.serve.max_batch,
                    duration_s=args.duration, stop=stop_evt)

        # Drain + stop INSIDE the preemption grace budget (the hung-
        # thread check must run BEFORE the summary so the exit code
        # can't report a clean shutdown the threads didn't deliver).
        # The budget is subdivided: stop() waits on up to three seams
        # sequentially (dispatcher join, shutdown sentinel, consumer
        # join), so handing it the full grace each time could spend ~4x
        # grace with a hung consumer — past the point a fleet SIGKILLs
        # us, losing the summary entirely.
        grace = cfg.runtime.preempt_grace_s
        drained = engine.drain(timeout_s=grace * 0.5)
        if controller is not None:
            controller.stop()
        if watcher is not None:
            watcher.stop()
        # Per-seam timeout: the 1 s floor keeps healthy shutdowns from
        # flaking on a briefly-busy thread, but it must never push the
        # three sequential seams past the half of the grace budget left
        # after the drain — grace/6 caps the floor so a small
        # preempt_grace_s still beats the fleet's SIGKILL.
        stopped_clean = engine.stop(
            drain=False,
            timeout_s=min(max(grace / 8.0, 1.0), grace / 6.0))
        # Warm handoff (ISSUE 20): with the worker threads stopped, seal
        # every surviving carry into the spill arena so the engines this
        # one's sessions land on adopt them warm. Strictly AFTER stop()
        # (page_out_all refuses otherwise) and never allowed to sink a
        # clean shutdown — a failed page-out only costs adoptions.
        spill_pageout = None
        if stopped_clean:
            try:
                spill_pageout = engine.page_out_all()
            except Exception:   # noqa: BLE001 — degraded, not dead
                log.exception("drain page-out failed; this engine's "
                              "sessions will cold-restart elsewhere")
        engine_failed = engine.failed is not None
        obs_bundle.flush()
        counters = registry.counters()
        summary = {
            **stats,
            "params_step": engine.params_step,
            "swaps": int(counters.get("serve_swaps_total", 0)),
            "swap_rejected": int(
                counters.get("serve_swap_rejected_total", 0)),
            "swap_breaker_opens": int(
                counters.get("serve_swap_breaker_opens_total", 0)),
            "evictions": int(counters.get("serve_evictions_total", 0)),
            "prefills": int(counters.get("serve_prefills_total", 0)),
            "requests": int(counters.get("serve_requests_total", 0)),
            "shed": int(counters.get("serve_shed_total", 0)),
            "queue_rejected": int(
                counters.get("serve_queue_rejected_total", 0)),
            "deadline_expired": int(
                counters.get("serve_deadline_expired_total", 0)),
            "restarts": int(counters.get("serve_restarts_total", 0)),
            "controller_adjustments": int(
                counters.get("serve_controller_adjustments_total", 0)),
            "drained": drained,
            "stopped_clean": stopped_clean,
            "engine_failed": engine_failed,
            "device": device_block(),
        }
        # Session-tier counters (ISSUE 18): only meaningful when the
        # warm tier is on (serve.warm_bytes > 0), so gate on activity.
        warm_parks = int(counters.get("serve_warm_parks_total", 0))
        warm_hits = int(counters.get("serve_warm_hits_total", 0))
        warm_misses = int(counters.get("serve_warm_misses_total", 0))
        if warm_parks or warm_hits or warm_misses:
            summary["warm_parks"] = warm_parks
            summary["warm_hits"] = warm_hits
            summary["warm_misses"] = warm_misses
            summary["warm_demotions"] = int(
                counters.get("serve_warm_demotions_total", 0))
        # Spill-tier counters (ISSUE 20): gated the same way — only
        # meaningful with a spill arena configured.
        if spill_pageout is not None and any(spill_pageout.values()):
            summary["spill_pageout"] = spill_pageout
        spill_puts = int(counters.get("serve_spill_puts_total", 0))
        spill_hits = int(counters.get("serve_spill_hits_total", 0))
        if spill_puts or spill_hits:
            summary["spill_puts"] = spill_puts
            summary["spill_hits"] = spill_hits
            summary["adopt_warm"] = int(
                counters.get("serve_adopt_warm_total", 0))
            summary["adopt_cold"] = int(
                counters.get("serve_adopt_cold_total", 0))
            summary["spill_corrupt"] = int(
                counters.get("serve_spill_corrupt_total", 0))
        # Stage-decomposition tail (the ISSUE-11 observability surface):
        # histogram-derived per-stage p99s plus the slowest exemplars —
        # the "which stage owns the tail" answer in the run summary.
        from sharetrade_tpu.obs import serve_stage_p99s
        stage_p99 = serve_stage_p99s(registry)
        if stage_p99:
            summary["stage_p99_ms"] = stage_p99
        slowest = engine.exemplars()[:3]
        if slowest:
            summary["slowest"] = slowest
        for key, gauge in (("slo_availability_burn",
                            "serve_slo_availability_burn"),
                           ("slo_latency_burn", "serve_slo_latency_burn")):
            value = registry.latest(gauge)
            if value is not None:
                summary[key] = round(value, 4)
        if preempt_at:
            summary["preempted"] = True
            log.warning("serve run preempted; in-flight requests %s",
                        "drained" if drained else "NOT fully drained")
        if engine_failed:
            log.error("serve engine ended in the TERMINAL FAILED state "
                      "(restart storm past serve.max_restarts): %r",
                      engine.failed)
        print(json.dumps(summary))
        if preempt_at:
            return EXIT_PREEMPTED
        if not stopped_clean or engine_failed:
            # A hung dispatcher/consumer thread — or an engine that died
            # in its terminal failed state mid-run — must surface as a
            # failed run, not a quiet success.
            return 1
        return 0
    finally:
        for s, h in prev_handlers.items():
            signal.signal(s, h)
        if controller is not None:
            controller.stop()
        if watcher is not None:
            watcher.stop()
        if engine is not None:
            engine.stop(drain=False)
        if obs_bundle is not None:
            obs_bundle.close()
        service.close()


def cmd_actor(args) -> int:
    """One rollout-actor process (distrib/actor.py) — a separate failure
    domain of the disaggregated actor/learner topology: verified-restore
    weights from ``tag_best``, epsilon-greedy rollouts, transitions
    appended to this actor's OWN journal under
    ``<distrib.actor_dir>/<actor-id>/``, heartbeat stamps for the
    supervising :class:`ActorPool`. Normally spawned BY the pool
    (``cli learner``), but runnable by hand for debugging.

    Preemption contract matches ``cli train``: SIGTERM/SIGINT drains
    (journal flush + final heartbeat) and exits 75; a second signal hard-
    exits."""
    from sharetrade_tpu.distrib.actor import RolloutActor

    cfg = _load_config(args)
    if not args.actor_id:
        log.error("--actor-id is required")
        return 1
    workdir = os.path.join(cfg.distrib.actor_dir, args.actor_id)
    # The actor's data layer is scoped to ITS directory: sharing the
    # learner's journal_dir would contend for the price-event journal's
    # writer lock (and worse, interleave transition records — the exact
    # torn-record scenario the per-actor layout exists to prevent).
    cfg.data.journal_dir = workdir
    # Telemetry stays with the learner: an actor writing the shared obs
    # run dir would fight the learner's manifest/exporter; actor health
    # flows through heartbeats -> pool gauges instead.
    cfg.obs.enabled = False

    stop_evt = threading.Event()
    preempted: list[float] = []

    def _on_signal(signum, frame):
        if not preempted:
            log.warning("actor %s received %s; draining", args.actor_id,
                        signal.Signals(signum).name)
            preempted.append(time.monotonic())
            stop_evt.set()
        else:
            os._exit(EXIT_PREEMPTED)

    prev_handlers = {
        s: signal.signal(s, _on_signal)
        for s in (signal.SIGTERM, signal.SIGINT)}
    service = PriceDataService(config=cfg.data)
    try:
        response = service.request(args.symbol.split(",")[0].strip(),
                                   args.start, args.end)
        actor = RolloutActor(cfg, response.series.prices,
                             actor_id=args.actor_id, workdir=workdir)
        print(json.dumps({"event": "actor_ready",
                          "actor_id": args.actor_id,
                          "pid": os.getpid(),
                          "params_step": actor.params_step,
                          "journal": actor.journal_path}), flush=True)
        summary = actor.run(stop_evt, max_chunks=args.max_chunks)
        print(json.dumps(summary), flush=True)
        return EXIT_PREEMPTED if preempted else 0
    finally:
        for s, h in prev_handlers.items():
            signal.signal(s, h)
        service.close()


def cmd_learner(args) -> int:
    """The learner process of the disaggregated topology: hosts the
    :class:`ActorPool` supervisor (N ``cli actor`` subprocesses under the
    process-granular supervision contract) AND the training loop, which
    tails every actor's journal between megachunks
    (``Orchestrator.ingest_actor_feeds``), trains, and republishes
    ``tag_best`` for the actors to hot-swap — the closed loop.

    The learner is its own failure domain: actors dying (and being
    respawned, or failing terminally) never restarts this process — the
    property the kill-test (tools/actor_soak.py) asserts after every
    injection. SIGTERM drains BOTH tiers (pool SIGTERMs its actors, the
    orchestrator writes ``tag_preempt``) and exits 75."""
    from sharetrade_tpu.distrib.pool import ActorPool
    from sharetrade_tpu.runtime import Orchestrator, ReplyState

    cfg = _load_config(args)
    if cfg.distrib.num_actors < 1:
        log.error("cli learner needs distrib.num_actors >= 1 "
                  "(got %d); use cli train for the single-process loop",
                  cfg.distrib.num_actors)
        return 1
    refusal = device_process_refusal(
        1 + cfg.distrib.num_actors,
        f"cli learner (in-process learner + {cfg.distrib.num_actors} "
        "cli actor children)")
    if refusal:
        log.error("%s", refusal)
        return 1
    if cfg.learner.algo != "dqn" and cfg.distrib.ingest_every_updates > 0:
        log.error("actor-feed ingest requires learner.algo=dqn (replay "
                  "buffer); got %r", cfg.learner.algo)
        return 1
    if cfg.data.journal_segment_records <= 0:
        # Single-file actor journals would grow without bound (the
        # actor-side retirement only runs with rotation on) and make
        # every ingest tick re-decode the whole rollout history; the
        # saved config flows to the spawned actors, so defaulting here
        # covers the fleet.
        cfg.data.journal_segment_records = 256
        log.info("distrib: defaulting data.journal_segment_records=256 "
                 "(rotation is required for bounded actor journals and "
                 "bounded ingest reads)")
    service = PriceDataService(config=cfg.data)
    orch = None
    pool = None
    preempt_at: list[float] = []

    def _on_signal(signum, frame):
        if not preempt_at:
            log.warning("received %s; draining learner + actor pool",
                        signal.Signals(signum).name)
            preempt_at.append(time.monotonic())
        else:
            log.warning("received %s during the drain; hard exit",
                        signal.Signals(signum).name)
            # os._exit skips every finally: anything not killed NOW is an
            # orphaned actor rolling out forever with no supervisor.
            if pool is not None:
                pool.kill_all()
            os._exit(EXIT_PREEMPTED)
        if pool is not None:
            # A fleet preemption TERMs the whole process group: the
            # actors are draining alongside us, and the pool must stop
            # classifying their graceful exits as crashes (respawning
            # fresh actors into a dying run).
            pool.quiesce()
        if orch is not None:
            orch.request_preempt()

    prev_handlers = {
        s: signal.signal(s, _on_signal)
        for s in (signal.SIGTERM, signal.SIGINT)}
    try:
        response = service.request(args.symbol.split(",")[0].strip(),
                                   args.start, args.end)
        prices = response.series.prices
        orch = Orchestrator(cfg)
        if preempt_at:
            orch.request_preempt()
        pool = ActorPool(cfg, registry=orch.metrics, symbol=args.symbol,
                         start=args.start, end=args.end).start()
        if preempt_at:
            # SIGTERM landed during orchestrator bring-up, before the
            # handler had a pool to quiesce: re-apply it here or the pool
            # respawns group-TERM'd actors into the dying run.
            pool.quiesce()
        print(json.dumps({"event": "learner_ready", "pid": os.getpid(),
                          "actors": cfg.distrib.num_actors,
                          "pool_dir": pool.dir}), flush=True)
        t0 = time.perf_counter()
        try:
            orch.send_training_data(prices, resume=args.resume)
        except FileNotFoundError as exc:
            log.error("--resume: %s (train without --resume first)", exc)
            return 1
        orch.start_training(background=True)
        grace = cfg.runtime.preempt_grace_s
        while not orch.wait(timeout=cfg.runtime.poll_interval_s):
            if preempt_at and (time.monotonic() - preempt_at[0]
                               > grace + 5.0):
                log.error("preemption grace (%.1fs) expired before the "
                          "drain finished; hard exit", grace)
                pool.kill_all()     # os._exit skips the finally teardown
                os._exit(EXIT_PREEMPTED)
        elapsed = time.perf_counter() - t0

        done = orch.is_everything_done()
        pool.stop(grace_s=grace)
        counters = orch.metrics.counters()
        snap = orch.snapshot()
        summary = {
            "env_steps": snap.get("env_steps"),
            "updates": snap.get("updates"),
            "elapsed_s": elapsed,
            "learner_restarts": orch.restarts,
            "actor_restarts": pool.restarts_total,
            "rows_ingested": int(
                counters.get("distrib_rows_ingested_total", 0)),
            **{f"actors_{k}": v for k, v in pool.counts().items()},
        }
        if orch.preempted or (preempt_at
                              and done.state is not ReplyState.COMPLETED):
            summary["preempted"] = True
            print(json.dumps(summary))
            return EXIT_PREEMPTED
        if done.state is not ReplyState.COMPLETED:
            log.error("learner did not complete: %s (last error: %r)",
                      done, orch.last_error)
            print(json.dumps(summary))
            return 1
        avg, std = orch.get_avg(), orch.get_std()
        if avg.ok:
            summary["avg_portfolio"] = avg.value
            summary["std_portfolio"] = std.value
        print(json.dumps(summary))
        return 0
    finally:
        for s, h in prev_handlers.items():
            signal.signal(s, h)
        if pool is not None:
            pool.stop(grace_s=10.0)
        if orch is not None:
            orch.stop()
        service.close()


def cmd_fleet(args) -> int:
    """``cli fleet``. Without ``--learner`` this process only supervises
    (EnginePool, router, front-end): it must stay off JAX, or it takes the
    chip its one engine child needs."""
    with (contextlib.nullcontext() if args.learner else supervising_only()):
        return _cmd_fleet(args)


def _cmd_fleet(args) -> int:
    """The whole serving fleet in one command (fleet/): N supervised
    ``cli serve --listen`` engine workers (EnginePool), the telemetry-
    driven router behind one public front-end port, and — with
    ``--learner`` — a live in-process learner closing the
    train→serve→train flywheel: served sessions journal transitions
    under ``distrib.actor_dir`` (fleet/flywheel.py), the learner tails
    them between megachunks (``distrib.ingest_without_pool``),
    republishes ``tag_best``, and every engine's swap watcher hot-swaps
    it in.

    Machine-readable ``fleet_ready`` line once the router port is bound
    and every engine reported listening; SIGTERM drains the front-end,
    the engines (their own drain → 75 contract) and the learner, then
    exits 75."""
    from sharetrade_tpu.fleet import EnginePool, FleetRouter, ServeFrontend
    from sharetrade_tpu.utils.metrics import MetricsRegistry
    from sharetrade_tpu.obs import build_obs

    cfg = _load_config(args)
    if args.engines:
        cfg.fleet.num_engines = args.engines
    if getattr(args, "autoscale", False):
        cfg.fleet.autoscale = True
    engines = max(cfg.fleet.num_engines,
                  cfg.fleet.max_engines if cfg.fleet.autoscale else 0)
    refusal = device_process_refusal(
        engines + bool(args.learner),
        f"cli fleet ({engines} cli serve --listen engine(s)"
        + (" + the in-process learner)" if args.learner else ")"))
    if refusal:
        log.error("%s", refusal)
        return 1
    if args.learner:
        # The flywheel's learner half: ingest session journals with no
        # ActorPool in this process, and evaluate often enough that
        # tag_best republishes while the fleet is live.
        cfg.distrib.ingest_without_pool = True
        if cfg.learner.algo != "dqn":
            log.error("--learner requires learner.algo=dqn (replay "
                      "ingest); got %r", cfg.learner.algo)
            return 1
        if cfg.data.journal_segment_records <= 0:
            cfg.data.journal_segment_records = 256
    service = orch = None
    pool = router = frontend = obs_bundle = autoscaler = None
    stop_evt = threading.Event()
    preempt_at: list[float] = []

    def _on_signal(signum, frame):
        if not preempt_at:
            log.warning("received %s; draining the fleet",
                        signal.Signals(signum).name)
            preempt_at.append(time.monotonic())
            stop_evt.set()
            if pool is not None:
                pool.quiesce()
            if orch is not None:
                orch.request_preempt()
        else:
            log.warning("received %s during the drain; hard exit",
                        signal.Signals(signum).name)
            if pool is not None:
                pool.kill_all()     # os._exit skips every finally
            os._exit(EXIT_PREEMPTED)

    prev_handlers = {
        s: signal.signal(s, _on_signal)
        for s in (signal.SIGTERM, signal.SIGINT)}
    try:
        registry = MetricsRegistry(
            max_points=cfg.obs.max_metric_points or None)
        if cfg.obs.enabled and cfg.obs.trace and not cfg.obs.span_dir:
            # Fleet-wide distributed tracing (ISSUE 17): one shared
            # spans dir; this process journals as "fleet", each worker
            # as "engine-<id>" (fleet/pool.py injects the same dir).
            cfg.obs.span_dir = os.path.join(cfg.obs.dir, "spans")
            cfg.obs.span_proc = cfg.obs.span_proc or "fleet"
        obs_bundle = build_obs(cfg, registry)
        # Pick the HTTP parse/render implementation for the router's
        # own front-end and FleetClient relay legs before anything
        # touches the wire; workers pick theirs from the same config.
        from sharetrade_tpu.fleet import proto as fleet_proto
        fleet_proto.set_backend(cfg.fleet.proto_backend)
        pool = EnginePool(cfg, registry=registry, symbol=args.symbol,
                          start=args.start, end=args.end).start()
        if preempt_at:
            pool.quiesce()
        router = FleetRouter(pool, cfg.fleet, registry,
                             workdir=cfg.fleet.dir, obs_cfg=cfg.obs,
                             obs=obs_bundle).start()
        from sharetrade_tpu.fleet.wire import WireTracer
        frontend = ServeFrontend(
            router, registry, host=cfg.fleet.host, port=cfg.fleet.port,
            wire_backend=cfg.fleet.wire_backend,
            tracer=(WireTracer(obs_bundle.spans, mint=True)
                    if obs_bundle.spans is not None else None)).start()
        if cfg.fleet.autoscale:
            # Membership control loop (ISSUE 18): reads the router's
            # telemetry history ring, drives EnginePool.scale within
            # [min_engines, max_engines].
            from sharetrade_tpu.fleet.autoscale import EngineAutoscaler
            autoscaler = EngineAutoscaler(
                pool, cfg.fleet, workdir=cfg.fleet.dir,
                registry=registry, obs=obs_bundle).start()

        if args.learner:
            from sharetrade_tpu.config import FrameworkConfig
            from sharetrade_tpu.runtime import Orchestrator
            service = PriceDataService(config=cfg.data)
            response = service.request(args.symbol.split(",")[0].strip(),
                                       args.start, args.end)
            # The orchestrator owns its OWN obs bundle; scope it to a
            # subdir so two exporters never fight over one run dir's
            # manifest/metrics files (learner telemetry lands in
            # <obs.dir>/learner, fleet telemetry in <obs.dir>).
            learner_cfg = FrameworkConfig.from_dict(cfg.to_dict())
            learner_cfg.distrib.ingest_without_pool = True
            if learner_cfg.obs.enabled:
                learner_cfg.obs.dir = os.path.join(cfg.obs.dir,
                                                   "learner")
            orch = Orchestrator(learner_cfg)
            if preempt_at:
                orch.request_preempt()
            orch.send_training_data(response.series.prices,
                                    resume=args.resume)
            orch.start_training(background=True)

        # Readiness: every engine reported its port (or hit its
        # bring-up budget — surface what came up either way).
        deadline = time.monotonic() + cfg.fleet.startup_timeout_s + 10.0
        while (time.monotonic() < deadline and not stop_evt.is_set()
               and len(pool.endpoints()) < cfg.fleet.num_engines):
            stop_evt.wait(0.25)
        router.poll_once()
        print(json.dumps({"event": "fleet_ready",
                          "host": frontend.host, "port": frontend.port,
                          "engines": len(pool.endpoints()),
                          "target_engines": cfg.fleet.num_engines,
                          "dir": cfg.fleet.dir,
                          "wire_backend": cfg.fleet.wire_backend,
                          "proto_backend": fleet_proto.proto_backend,
                          "learner": bool(args.learner),
                          "pid": os.getpid()}), flush=True)

        run_deadline = (time.monotonic() + args.duration
                        if args.duration > 0 else None)
        while not stop_evt.is_set():
            if (run_deadline is not None
                    and time.monotonic() >= run_deadline):
                break
            stop_evt.wait(0.25)

        grace = cfg.fleet.drain_grace_s
        if autoscaler is not None:
            autoscaler.stop()   # membership frozen before the drain
        frontend.drain(timeout_s=grace * 0.5)
        frontend.stop()
        router.stop()
        pool.stop(grace_s=grace)
        if orch is not None:
            orch.stop()
        obs_bundle.flush()
        counters = registry.counters()
        summary = {
            "requests": int(counters.get("fleet_requests_total", 0)),
            "completed": int(counters.get("fleet_completed_total", 0)),
            "refused": int(counters.get("fleet_refused_total", 0)),
            "migrations": int(
                counters.get("fleet_migrations_total", 0)),
            "engine_restarts": pool.restarts_total,
            **{f"engines_{k}": v for k, v in pool.counts().items()},
        }
        if autoscaler is not None:
            summary["scale_events"] = pool.scale_events
            summary["autoscale_up"] = int(
                counters.get("fleet_autoscale_up_total", 0))
            summary["autoscale_down"] = int(
                counters.get("fleet_autoscale_down_total", 0))
        if orch is not None:
            snap = orch.snapshot() or {}
            summary["learner_updates"] = snap.get("updates")
            summary["rows_ingested"] = int(orch.metrics.counters().get(
                "distrib_rows_ingested_total", 0))
        if preempt_at:
            summary["preempted"] = True
        print(json.dumps(summary))
        return EXIT_PREEMPTED if preempt_at else 0
    finally:
        for s, h in prev_handlers.items():
            signal.signal(s, h)
        if autoscaler is not None:
            autoscaler.stop()
        if frontend is not None:
            frontend.stop()
        if router is not None:
            router.stop()
        if pool is not None:
            pool.stop(grace_s=10.0)
        if orch is not None:
            orch.stop()
        if obs_bundle is not None:
            obs_bundle.close()
        if service is not None:
            service.close()


def cmd_obs(args) -> int:
    """Summarize a telemetry run dir (obs.enabled=true output): manifest
    identity, span aggregates from the Chrome trace, metrics tail, and the
    flight-recorder verdict when a bundle was dumped.

    ``--trace <id>`` (or ``--trace list``) switches to the ISSUE-17
    cross-process collector: stitch the span journals under
    ``<dir>/spans`` into one trace (``--out`` renders it for Perfetto).
    ``--history N`` reads the fleet router's per-poll gauge ring
    (``fleet_history.jsonl`` under ``--dir``, the fleet WORKDIR for this
    flag) and prints the last-N-windows summary."""
    import os

    from sharetrade_tpu.obs import summarize_run_dir

    if args.trace:
        from sharetrade_tpu.obs import collect
        spans_dir = os.path.join(args.dir, "spans")
        if not os.path.isdir(spans_dir):
            log.error("no span journals under %s (run `cli fleet` with "
                      "obs.enabled=true)", spans_dir)
            return 1
        if args.trace == "list":
            ids = collect.trace_ids(collect.read_span_dir(spans_dir))
            print(json.dumps({"spans_dir": spans_dir, "traces": ids},
                             indent=2))
            return 0
        stitched = collect.collect_trace(spans_dir, args.trace,
                                         out=args.out)
        if not stitched["spans"]:
            log.error("trace %s not found under %s (try --trace list)",
                      args.trace, spans_dir)
            return 1
        view = {"trace_id": stitched["trace_id"],
                "procs": stitched["procs"],
                "errors": stitched["errors"],
                "spans": [{k: s.get(k) for k in
                           ("name", "proc", "span", "parent", "ts_us",
                            "dur_us", "note") if k in s}
                          for s in stitched["spans"]]}
        if "perfetto" in stitched:
            view["perfetto"] = stitched["perfetto"]
        print(json.dumps(view, indent=2))
        return 0 if not stitched["errors"] else 1
    if args.history is not None:
        from sharetrade_tpu.obs.tsdb import (FLEET_HISTORY_FILE,
                                             read_history,
                                             summarize_history)
        path = os.path.join(args.dir, FLEET_HISTORY_FILE)
        rows = read_history(path, last_n=max(0, args.history))
        if not rows:
            log.error("no telemetry history at %s (the fleet router "
                      "writes it next to fleet_status.json)", path)
            return 1
        print(json.dumps({"path": path,
                          **summarize_history(rows)}, indent=2))
        return 0
    if not os.path.isdir(args.dir):
        log.error("no run dir at %s (train with --set obs.enabled=true "
                  "--set obs.dir=%s first)", args.dir, args.dir)
        return 1
    summary = summarize_run_dir(args.dir)
    if len(summary) <= 1:   # only {"run_dir": ...}: nothing telemetric inside
        log.error("%s contains no telemetry artifacts "
                  "(manifest.json/trace.jsonl/metrics.jsonl)", args.dir)
        return 1
    print(json.dumps(summary, indent=2))
    return 0


def cmd_query(args) -> int:
    cfg = _load_config(args)
    service = PriceDataService(config=cfg.data)
    response = service.request(args.symbol, args.start, args.end)
    series = response.series
    print(json.dumps({
        "symbol": response.symbol,
        "rows": len(series),
        "first": str(series.dates[0]) if len(series) else None,
        "last": str(series.dates[-1]) if len(series) else None,
    }))
    service.close()
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="sharetrade_tpu")
    parser.add_argument("--verbose", action="store_true")
    sub = parser.add_subparsers(dest="command", required=True)

    for name, fn in [("train", cmd_train), ("query", cmd_query),
                     ("serve", cmd_serve), ("actor", cmd_actor),
                     ("learner", cmd_learner), ("fleet", cmd_fleet)]:
        p = sub.add_parser(name)
        p.add_argument("--config", default=None, help="JSON config file")
        p.add_argument("--set", action="append", default=[],
                       metavar="SECTION.KEY=VALUE", help="config override")
        p.add_argument("--symbol", default="MSFT")
        # The reference asks for 1992-01-01..2015-01-01 (ShareTradeHelper.scala:23)
        p.add_argument("--start", default=None)
        p.add_argument("--end", default=None)
        p.add_argument("--verbose", action="store_true")
        if name == "train":
            p.add_argument("--mesh", action="store_true",
                           help="shard over all visible devices")
            p.add_argument("--resume", action="store_true",
                           help="restore the latest checkpoint and continue")
            p.add_argument("--eval", action="store_true",
                           help="greedy-policy evaluation after training")
            p.add_argument("--eval-best", action="store_true",
                           help="also evaluate the retained best-eval "
                                "checkpoint (runtime.keep_best_eval)")
        if name == "actor":
            p.add_argument("--actor-id", default=None,
                           help="this actor's id (its per-actor dir under "
                                "distrib.actor_dir)")
            p.add_argument("--max-chunks", type=int, default=0,
                           help="stop after this many rollout chunks "
                                "(0 = until SIGTERM)")
        if name == "learner":
            p.add_argument("--resume", action="store_true",
                           help="restore the latest checkpoint and "
                                "continue")
        if name == "serve":
            p.add_argument("--duration", type=float, default=10.0,
                           help="seconds to serve the synthetic load "
                                "(SIGTERM drains and exits 75 earlier; "
                                "with --listen, 0 = until SIGTERM)")
            p.add_argument("--sessions", type=int, default=512,
                           help="synthetic user sessions to replay")
            p.add_argument("--rate", type=float, default=0.0,
                           help="open-loop offered QPS; 0 = closed loop "
                                "at serve.max_batch concurrency")
            p.add_argument("--listen", default=None, metavar="HOST:PORT",
                           help="fleet worker mode: expose submit over "
                                "the wire (fleet/frontend.py) instead "
                                "of driving synthetic load; port 0 = "
                                "ephemeral, reported in the "
                                "engine_listening line")
        if name == "fleet":
            p.add_argument("--engines", type=int, default=0,
                           help="engine workers (0 = fleet.num_engines)")
            p.add_argument("--duration", type=float, default=0.0,
                           help="seconds to run (0 = until SIGTERM)")
            p.add_argument("--learner", action="store_true",
                           help="run the flywheel's live learner in-"
                                "process (ingest session journals, "
                                "republish tag_best)")
            p.add_argument("--resume", action="store_true",
                           help="learner resumes the latest checkpoint")
            p.add_argument("--autoscale", action="store_true",
                           help="drive EnginePool.scale from the "
                                "telemetry history ring (fleet/"
                                "autoscale.py; implies fleet.autoscale)")
        p.set_defaults(fn=fn)

    p = sub.add_parser("obs", help="summarize a telemetry run dir")
    p.add_argument("--dir", default="obs",
                   help="run dir written by a train run with obs.enabled "
                        "(for --history: the fleet workdir holding "
                        "fleet_history.jsonl)")
    p.add_argument("--trace", default=None, metavar="TRACE_ID",
                   help="stitch one cross-process trace from the span "
                        "journals under <dir>/spans ('list' enumerates "
                        "trace ids)")
    p.add_argument("--out", default=None,
                   help="with --trace: write the stitched trace as "
                        "Perfetto/Chrome trace-event JSON here")
    p.add_argument("--history", type=int, default=None, metavar="N",
                   help="summarize the newest N fleet telemetry-history "
                        "rows (0 = all retained)")
    p.set_defaults(fn=cmd_obs)

    args = parser.parse_args(argv)
    configure()
    configure_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
