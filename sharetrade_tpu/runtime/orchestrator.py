"""The training orchestrator — L4/L5 of the reference, actor-free.

What the reference spreads across ShareTradeHelper (driver poll loop),
TrainerRouterActor (broadcast + lifecycle + aggregation + supervision) and
BackoffSupervisor wrappers (SURVEY.md §3.1, §3.5), this one host-side object
owns:

- the lifecycle FSM (awaiting-data → ready → training → trained/completed),
  with StartTraining stashing (TrainerRouterActor.scala:75-76);
- the chunked device loop: the agent's jitted ``step`` advances
  ``chunk_steps`` env steps per host visit; between chunks the host snapshots
  metrics, so ``get_avg``/``get_std`` answer **without stopping the device**
  (the reference interrupts trained workers with ask(GetPortfolio);
  SURVEY.md §7.4 "Queryability"). With ``runtime.megachunk_factor`` K > 1
  the host visit itself amortizes: K chunks fuse into one device-resident
  lax.scan (agents/base.py ``megachunk_step``), per-chunk metrics stack into
  a (K, ...) buffer read back with ONE batched ``jax.device_get`` at
  megachunk boundaries, and the loop falls back to K=1 dispatches near the
  episode threshold so the exact-completion gate keeps its semantics;
- supervision: a failing chunk triggers exponential-backoff restart from the
  latest checkpoint (initial 3 s, cap 60 s, jitter 0.2 — the reference's
  Backoff.onFailure envelope, TrainerRouterActor.scala:46-52) up to
  ``max_restarts``, then FAILED (the Escalate arm of its decider);
- checkpoint cadence: every ``checkpoint_every_updates`` updates — the
  reference's intended-but-stubbed every-500 (QDecisionPolicyActor.scala:74);
- a typed error policy — the reference's OneForOneStrategy decider maps
  exception classes to Resume/Restart/Stop/Escalate
  (TrainerRouterActor.scala:53-58); ``error_policy`` maps exception types to
  the same four verbs (resume = keep state and continue; restart =
  backoff + restore from checkpoint; stop = mark FAILED; escalate = re-raise);
- test seams: ``step_override`` replaces the compiled step (the overridable
  ``train()`` seam, TrainerRouterActorSpec.scala:144-153) and
  ``fault_hook`` injects failures mid-run (the PoisonPill chaos seam,
  :97-115).
"""

from __future__ import annotations

import random
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from sharetrade_tpu.agents import build_agent
from sharetrade_tpu.agents.base import Agent, TrainState, megachunk_step
from sharetrade_tpu.checkpoint import CheckpointManager
from sharetrade_tpu.config import ConfigError, FrameworkConfig
from sharetrade_tpu.env import trading
from sharetrade_tpu.env.portfolio import make_portfolio_env
from sharetrade_tpu.obs import build_obs
from sharetrade_tpu.obs.trace import span as trace_span
from sharetrade_tpu.parallel import build_mesh, make_parallel_step
from sharetrade_tpu.runtime.lifecycle import Lifecycle, Phase, QueryReply, ReplyState
from sharetrade_tpu.runtime.pipeline import AsyncPipeline, Boundary
from sharetrade_tpu.utils.logging import EventLog, get_logger
from sharetrade_tpu.utils.metrics import MetricsRegistry
from sharetrade_tpu.utils.profiling import StepTimer, Tracer

log = get_logger("runtime.orchestrator")

#: Shared no-op context for un-sampled / obs-disabled span sites.


#: Supervision verbs (the Akka directive vocabulary).
RESUME, RESTART, STOP, ESCALATE = "resume", "restart", "stop", "escalate"

#: Default decider, mirroring TrainerRouterActor.scala:53-58
#: (ArithmeticException→Resume, NullPointer→Restart, IllegalArgument→Stop,
#: anything else→Escalate... except here unknown errors Restart, because on
#: TPU transient device errors are the common case and restart-from-
#: checkpoint is the designed recovery path). The Stop verb is scoped to
#: ConfigError, not all ValueError: a bad config can never heal by
#: restarting, but a transient in-loop ValueError (a JAX tracing/shape
#: error from a restored-then-retraced step) deserves the restart path
#: instead of permanently failing the run.
DEFAULT_ERROR_POLICY: dict[type, str] = {
    ArithmeticError: RESUME,
    AttributeError: RESTART,
    ConfigError: STOP,
    KeyboardInterrupt: ESCALATE,
}


def _metric_rows(host: dict, k: int) -> list[dict[str, float]]:
    """Split one batched megachunk readback into its K per-chunk rows.

    ``host`` holds host-side arrays: scalars for a single chunk (k == 1),
    ``(K,)``-stacked values for a fused megachunk — the scan-stacked metric
    buffer of agents/base.py ``megachunk_step``."""
    if k == 1:
        return [{key: float(v) for key, v in host.items()}]
    return [{key: float(v[i]) for key, v in host.items()} for i in range(k)]


def _start_readback(*trees) -> None:
    """Kick off non-blocking device→host DMA for every array leaf
    (``copy_to_host_async`` — the async-checkpoint D2H trick applied to the
    metric/transition buffers). By the time the pipeline consumer calls its
    blocking ``device_get``, the bytes are usually already on the host; on
    backends without the method the consumer's device_get simply blocks on
    the CONSUMER thread — still off the dispatch critical path."""
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            if hasattr(leaf, "copy_to_host_async"):
                try:
                    leaf.copy_to_host_async()
                except Exception:   # fallback documented above
                    return


class Orchestrator:
    def __init__(self, cfg: FrameworkConfig, *,
                 mesh=None,
                 checkpoints: CheckpointManager | None = None,
                 event_log: EventLog | None = None,
                 step_override: Callable[[TrainState], tuple[TrainState, dict]] | None = None,
                 fault_hook: Callable[[int, dict], None] | None = None,
                 error_policy: dict[type, str] | None = None):
        # Tuned-profile resolution (tuning.py): registered knobs still at
        # their defaults take the per-host profile's values; explicit
        # config wins; a fingerprint-mismatched profile raises loudly
        # (ProfileError is ConfigError = STOP territory). Idempotent, so
        # a cfg the CLI already resolved passes through unchanged.
        from sharetrade_tpu.tuning import apply_profile
        cfg = apply_profile(cfg)
        self.cfg = cfg
        self.mesh = mesh
        if cfg.runtime.megachunk_factor < 1:
            # A bad factor can never heal by restarting — same class of
            # error as any other impossible composition, so it fails at
            # construction (the supervision decider's STOP verb territory).
            raise ConfigError(
                "runtime.megachunk_factor must be >= 1, got "
                f"{cfg.runtime.megachunk_factor}")
        if cfg.runtime.pipeline_depth < 1:
            # Same class as a bad megachunk factor: an impossible
            # composition that restarting can never heal — STOP territory.
            raise ConfigError(
                "runtime.pipeline_depth must be >= 1, got "
                f"{cfg.runtime.pipeline_depth}")
        if (cfg.runtime.megachunk_factor > 1
                and cfg.runtime.metrics_every_chunks
                % cfg.runtime.megachunk_factor != 0):
            # Not an error — sampling quantizes UP to the next megachunk
            # boundary (rows are delivered late-but-complete from the
            # stacked buffer) — but worth a line in the log so a surprised
            # operator finds the interaction documented in config.py.
            log.info(
                "metrics_every_chunks=%d is not a multiple of "
                "megachunk_factor=%d; metric samples land on megachunk "
                "boundaries (rounded up)",
                cfg.runtime.metrics_every_chunks,
                cfg.runtime.megachunk_factor)
        self.lifecycle = Lifecycle()
        # Precision policy (precision.py): validated at construction (a bad
        # mode is STOP territory). The agents own the training-side casts;
        # the orchestrator applies the same policy to the eval forwards and
        # stamps the mode into checkpoint metadata (restore refuses a
        # mode-mismatched store with a loud error instead of letting flax
        # silently deserialize the wrong dtypes).
        from sharetrade_tpu.precision import policy_from_config
        self._precision = policy_from_config(cfg.precision)
        self.metrics = MetricsRegistry(
            max_points=cfg.obs.max_metric_points)
        # Telemetry (obs/): inert facade when cfg.obs.enabled is False —
        # zero files, span() hands back a shared null context. All of the
        # hot-loop instrumentation below rides the metrics_every_chunks
        # sampling cadence and reads only host values that the batched
        # megachunk readback already materialized (no new device syncs).
        self.obs = build_obs(cfg, self.metrics, mesh=mesh)
        # Training-side mergeable histograms (obs/hist.py; ISSUE 11): the
        # per-boundary chunk wall time and the inter-dispatch gap as
        # fixed-bucket distributions, exported through metrics.prom next
        # to the serve tier's stage histograms — the fleet-mergeable form
        # of what bench_async_pipeline measures from trace spans. Obs-
        # gated: the default obs-off hot loop stays structurally
        # instrumentation-free (one None check per dispatch).
        # The three stage histograms say where the host's time per chunk
        # goes (inside the dispatch call, blocked on the pipeline's
        # back-pressure, in the consumer's host_process block): one
        # observation per materialization boundary, each divided by the
        # chunks the boundary covers as train_chunk_seconds is, so the
        # ratio of their sums to its sum is a share of the chunk at any
        # metrics_every_chunks.
        self._h_chunk_seconds = self._h_dispatch_gap = None
        self._h_dispatch_call = self._h_pipeline_stall = None
        self._h_host_process = None
        if cfg.obs.enabled:
            from sharetrade_tpu.obs.hist import SECONDS_BOUNDS, Histogram
            from sharetrade_tpu.obs.trace import attach_gc_pauses
            self._h_chunk_seconds = self.metrics.attach_histogram(
                "train_chunk_seconds", Histogram(bounds=SECONDS_BOUNDS))
            self._h_dispatch_gap = self.metrics.attach_histogram(
                "train_dispatch_gap_ms", Histogram())
            self._h_dispatch_call = self.metrics.attach_histogram(
                "train_dispatch_call_ms", Histogram())
            self._h_pipeline_stall = self.metrics.attach_histogram(
                "train_pipeline_stall_ms", Histogram())
            self._h_host_process = self.metrics.attach_histogram(
                "train_host_process_ms", Histogram())
            # The process's garbage-collection pauses (obs/trace.py).
            attach_gc_pauses(self.metrics, self.obs.tracer)
        self.checkpoints = checkpoints or CheckpointManager(
            cfg.runtime.checkpoint_dir, keep=cfg.runtime.keep_checkpoints,
            fsync=cfg.checkpoint.fsync,
            precision_mode=cfg.precision.mode)
        if getattr(self.checkpoints, "precision_mode", None) is None:
            # Injected managers join the run's precision contract the same
            # way they join its metrics/tracer below.
            self.checkpoints.precision_mode = cfg.precision.mode
        if getattr(self.checkpoints, "metrics", None) is None:
            # Restore walk-back counters (ckpt_restore_fallbacks_total,
            # ckpt_quarantined_total) land in the run's registry and flow
            # out through the obs MetricsExporter like every other counter.
            self.checkpoints.metrics = self.metrics
        self.events = event_log or EventLog(None)
        if self.obs.enabled:
            # Structured run events double into the flight ring (the tap),
            # lifecycle transitions mark the trace timeline, and checkpoint
            # save/restore phases span it from whichever thread writes.
            self.events.mirror = self._obs_event_tap
            self.lifecycle.on_transition = self._obs_phase_tap
            if getattr(self.checkpoints, "tracer", None) is None:
                self.checkpoints.tracer = self.obs.tracer
        self.tracer = Tracer(cfg.runtime.profile_dir)
        self._step_override = step_override
        self._fault_hook = fault_hook
        self._error_policy = (DEFAULT_ERROR_POLICY if error_policy is None
                              else error_policy)

        self.agent: Agent | None = None
        self.env = None  # TradingEnv once data arrives
        self._ts: TrainState | None = None
        self._step_fn = None
        self._mega_fn = None   # K-chunk fused program (megachunk_factor > 1)
        self._eval_fn = None   # cached jitted greedy-eval program
        self._snapshot: dict[str, float] = {}
        self._snapshot_lock = threading.Lock()
        # Guards the donated step dispatch vs concurrent _ts readers
        # (evaluate()'s snapshot): held only across the non-blocking
        # dispatch + reassignment, never across device execution.
        self._step_lock = threading.Lock()
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        # Preemption (SIGTERM/SIGINT via cli train, or any caller's
        # request_preempt): the dispatcher honors it at the next megachunk
        # boundary — drain, emergency tag_preempt checkpoint, journal flush,
        # flight dump — inside runtime.preempt_grace_s. ``preempted`` is the
        # caller-visible outcome flag (the CLI maps it to a distinct exit
        # code).
        self._preempt = threading.Event()
        self._preempt_deadline: float | None = None
        self.preempted = False
        #: Whether the preemption drain actually published tag_preempt —
        #: the CLI's "emergency checkpoint: written" claim keys off this,
        #: not off preemption having been attempted.
        self.preempt_saved = False
        self.restarts = 0
        self.agent_heals = 0   # per-agent row respawns (partial_recovery)
        self._best_eval: float | None = None  # lazily seeded from tag_best
        self._best_eval_lock = threading.Lock()
        self.episode = 0
        self.last_error: BaseException | None = None
        # Async readback pipeline (runtime.async_pipeline): live only while
        # a supervised run is in flight; _committed_idx is the consumer's
        # per-row progress cursor (== the synchronous loop's chunk_idx),
        # read by the dispatcher for fault attribution and drain math.
        self._pl: AsyncPipeline | None = None
        self._committed_idx = 0
        self._timer: StepTimer | None = None
        self._last_ckpt_updates = 0
        #: Stats of the most recent run's pipeline (max queue depth seen,
        #: dispatcher stalls) — kept after shutdown for tests/benchmarks.
        self.pipeline_stats: dict[str, int] = {}
        self._transitions_journal = None
        self._journal_high_water = 0  # env_steps already journaled
        self._journal_rows_since_compact = 0
        # Actor/learner disaggregation (distrib/): the learner tails every
        # actor's transitions journal between megachunks and splices the
        # new rows into its device replay buffer — per-actor cursors are
        # the last-ingested env-step stamps (monotone per journal, so a
        # restarted actor resumes cleanly past them). DQN-only: the other
        # algos have no replay buffer to feed.
        self._actor_cursors: dict[str, int] = {}
        self._last_ingest_updates = 0
        # num_actors gates too: with no pool (plain ``cli train``) the
        # cadence must not force pipeline-drain boundaries every
        # ingest_every_updates just to glob an empty actors dir.
        # ingest_without_pool bypasses that gate for the fleet flywheel:
        # SERVED SESSIONS write the journals there (fleet/flywheel.py),
        # so there is data to tail with no ActorPool in this process.
        self._ingest_enabled = ((cfg.distrib.num_actors > 0
                                 or cfg.distrib.ingest_without_pool)
                                and cfg.distrib.ingest_every_updates > 0
                                and cfg.learner.algo == "dqn")
        # Adaptive ingest cadence (tuning.adaptive_ingest — the online
        # half of ROADMAP item 5 on the learner side): the LIVE cadence
        # the boundary checks read. The configured value is the BASE;
        # the controller backs off (doubling, up to 8x base) after
        # consecutive all-dry ticks — a caught-up learner must not keep
        # paying a pipeline-drain boundary + header-peek scan of every
        # actor journal each `base` updates for nothing — and snaps back
        # to base the moment rows arrive; a tick that reads a FULL
        # per-actor window (backlog: the actors are outrunning the
        # learner, the N=4 ingest-collapse signature) tightens below
        # base (halving, down to base/4) so the backlog streams in
        # sooner. Every move is bounded, visible (gauge + counter +
        # flight event) and inert without a pool.
        self._ingest_every = max(1, cfg.distrib.ingest_every_updates)
        self._ingest_base = self._ingest_every
        self._adaptive_ingest = (self._ingest_enabled
                                 and cfg.tuning.adaptive_ingest)
        self._ingest_dry_streak = 0
        if self._ingest_enabled:
            self.metrics.record("ingest_every_updates_current",
                                float(self._ingest_every))
        if cfg.learner.algo == "dqn" and cfg.learner.journal_replay:
            import os
            from sharetrade_tpu.data.service import _open_journal
            path = os.path.join(cfg.data.journal_dir, "transitions.journal")
            self._transitions_journal = None
            if cfg.data.journal_segment_records > 0:
                # Bounded journal: segment rotation + retirement
                # (data.journal_segment_records). Rotation lives in the
                # Python backend — the C++ async writer appends to one
                # file, so it is bypassed here; group-commit watermarks
                # still apply per segment.
                from sharetrade_tpu.data.journal import Journal
                self._transitions_journal = Journal(
                    path,
                    fsync_every_records=cfg.data.journal_fsync_every_records,
                    fsync_interval_s=cfg.data.journal_fsync_interval_s,
                    segment_records=cfg.data.journal_segment_records)
            elif cfg.data.async_transition_writer and cfg.data.use_native_journal:
                # Hot-path appends drain through the C++ background thread;
                # the step loop never blocks on journal IO.
                from sharetrade_tpu.data.native import (
                    AsyncNativeJournal, async_writer_available)
                if async_writer_available():
                    self._transitions_journal = AsyncNativeJournal(path)
            if self._transitions_journal is None:
                # Group-commit knobs (data.journal_fsync_*): consumer-side
                # appends batch in memory and hit the disk (write + fsync)
                # on a count/interval watermark instead of one flush per
                # chunk — the Python-backend half of taking journaling off
                # the dispatch critical path (the C++ async writer above
                # already batches in its background thread).
                self._transitions_journal = _open_journal(
                    path, prefer_native=cfg.data.use_native_journal,
                    fsync_every_records=cfg.data.journal_fsync_every_records,
                    fsync_interval_s=cfg.data.journal_fsync_interval_s)

    # ------------------------------------------------------------------
    # telemetry taps (obs/): wired only when cfg.obs.enabled
    # ------------------------------------------------------------------

    def _obs_event_tap(self, kind: str, payload: dict) -> None:
        self.obs.record("event", event=kind, **payload)

    def _obs_phase_tap(self, old: Phase, new: Phase) -> None:
        self.obs.record("lifecycle", frm=old.value, to=new.value)
        self.obs.tracer.instant(f"phase:{new.value}")

    # ------------------------------------------------------------------
    # protocol: SendTrainingData (TrainerRouterActor.scala:77-81)
    # ------------------------------------------------------------------

    def send_training_data(self, prices: np.ndarray | Any, *,
                           resume: bool = False) -> None:
        """Build the env + agent from a price series — 1-D for the
        single-asset env, (A, T) for the multi-asset portfolio env. With
        ``resume=True`` the latest checkpoint (params, optimizer, RNG, env
        cursors) is restored instead of a fresh init — the user-facing
        continuation of the crash-recovery path (SURVEY.md §7.1 item 7)."""
        prices = np.asarray(prices)
        if prices.ndim == 2 and prices.shape[0] > 1:
            self.env = make_portfolio_env(
                prices, window=self.cfg.env.window,
                initial_budget=self.cfg.env.initial_budget,
                initial_shares=self.cfg.env.initial_shares)
        else:
            self.env = trading.make_trading_env(
                prices.reshape(-1), window=self.cfg.env.window,
                initial_budget=self.cfg.env.initial_budget,
                initial_shares=self.cfg.env.initial_shares)
        self.agent = build_agent(self.cfg, self.env, mesh=self.mesh)
        if not self.agent.model.trainable:
            # No replay, rollout trunk or sharding rule exists for such a
            # trunk: it would fail deep inside the first chunk otherwise.
            raise ConfigError(
                f"model.kind={self.cfg.model.kind!r} "
                f"({self.agent.model.name}) is serve-only: training it is "
                "not implemented (the model has no replay pass); run it "
                "through `cli serve`")
        if self.agent.replay_carry_bytes is not None:
            self.metrics.record("train_replay_carry_bytes_per_minibatch",
                                self.agent.replay_carry_bytes)
        self._build_step()
        self._eval_fn = None   # env/model changed: retrace on next evaluate
        template = self.agent.init(jax.random.PRNGKey(self.cfg.seed))
        self._capture_roofline_fallback(template)
        if resume:
            state, step, saved_meta = self._restore_for_resume(template)
            horizon = self.env.num_steps
            max_cursor = int(np.max(np.asarray(state.env_state.t)))
            if max_cursor > horizon:
                # A shorter series would freeze every agent past the new
                # horizon and the completion arithmetic could never fire.
                raise ValueError(
                    f"checkpoint env cursor ({max_cursor}) exceeds the new "
                    f"series horizon ({horizon}); resume needs the same or a "
                    f"longer price series")
            self._ts = self._place(self._warm_start_replay(state))
            # Recover the episode index from the checkpoint metadata; the
            # env_steps//horizon heuristic is the fallback for pre-metadata
            # checkpoints (it overcounts once per-agent heals inflate the
            # step count, which is why the index is persisted). Clamp to
            # episodes-1 either way: the FINAL checkpoint of a completed run
            # is written after the episode counter increments past the last
            # episode, and resuming it unclamped would set a completion
            # threshold ((episode+1) x horizon) that frozen agents can never
            # reach — an infinite chunk spin.
            saved_episode = saved_meta.get("episode")
            raw = (int(saved_episode) if saved_episode is not None
                   else int(state.env_steps) // horizon)
            self.episode = max(0, min(raw, self.cfg.runtime.episodes - 1))
            from sharetrade_tpu.agents.base import agent_health
            ok = np.asarray(jax.device_get(agent_health(state.env_state)))
            t = np.asarray(state.env_state.t)
            # HEALTHY cursors only: a run completed via the stranded-rows-
            # excluded gate (partial_recovery off) carries a quarantined
            # row frozen BELOW the horizon; counting it would skip the
            # re-arm and reintroduce the spin for exactly that resume.
            # ALL rows stranded counts as done too — no live cursor can
            # advance, and the re-arm's fresh state is the only recovery
            # (restoring the same poisoned checkpoint can't be).
            done_cursors = (not bool(ok.any())
                            or int(np.min(t[ok])) >= horizon)
            if (done_cursors and int(state.env_steps)
                    < (self.episode + 1) * horizon):
                # Resumed the final checkpoint of a COMPLETED episode while
                # the config asks for more passes (runtime.episodes raised):
                # every live cursor is frozen at the horizon, so without a
                # re-arm the run would spin chunks forever waiting for a
                # completion threshold frozen agents can never advance
                # toward. Re-arm the next episode in place — fresh env
                # cursors/carry (which also respawns any stranded row),
                # learned params/opt/env_steps kept (the Initialise→Train
                # cycle, TrainerChildActor.scala:57-59). (If heals inflated
                # env_steps past the threshold instead, the normal
                # completion gate re-arms on the first chunk.)
                log.info("resumed a %s with episodes=%d; re-arming "
                         "episode %d",
                         "completed episode" if ok.any()
                         else "checkpoint with every row stranded "
                              "(mid-episode progress discarded)",
                         self.cfg.runtime.episodes, self.episode)
                self._reset_episode()
            log.info("resumed from checkpoint step=%d "
                     "(env cursor %d, %d updates, episode %d)", step,
                     int(state.env_state.t[0]), int(state.updates),
                     self.episode)
            self.events.emit("resumed", step=step)
        else:
            if self._transitions_journal is not None:
                # A fresh run must not inherit another run's experience: the
                # journal is truncated, not appended to (warm starts would
                # otherwise seed the buffer with off-distribution data). The
                # high-water mark resets with it — the new run's env_steps
                # restart at zero and must journal from the first chunk.
                self._transitions_journal.compact([])
                self._journal_high_water = 0
            # Fresh state counts episodes from zero; a stale episode index
            # from a previous run would push the completion threshold to
            # (episode+1) x horizon — unreachable for frozen envs.
            self.episode = 0
            self._ts = self._place(template)
        self.lifecycle.to(Phase.READY)
        self.events.emit("training_data_received",
                         episode_steps=self.env.num_steps)
        # Honor a stashed StartTraining (reference stash/unstashAll, :75-76).
        # The stash is consumed: later send_training_data calls (a fresh
        # retrain on the same orchestrator) must not silently auto-start.
        if self.lifecycle.start_requested:
            self.lifecycle.start_requested = False
            self.start_training(
                background=getattr(self, "_stashed_background", True))

    def _build_step(self) -> None:
        factor = self.cfg.runtime.megachunk_factor
        self._mega_fn = None
        # Roofline capture (obs.roofline): seed the analytic FLOP model for
        # the cross-check, and hand the compile-time capture hook to the
        # program constructors. All of this runs at BUILD time — the
        # capture itself is one extra AOT lowering per program, and the
        # run-time gauge math rides the pipeline consumer (_host_process).
        roofline = (self.obs.roofline if self._step_override is None
                    else None)
        if roofline is not None:
            roofline.steps_per_chunk = self.cfg.runtime.chunk_steps
            roofline.precision_mode = self.cfg.precision.mode
            try:
                from sharetrade_tpu.utils.flops import (
                    train_flops_per_agent_step)
                roofline.analytic_flops_per_chunk = (
                    train_flops_per_agent_step(self.cfg, self.env.obs_dim)
                    * self.cfg.parallel.num_workers
                    * self.cfg.runtime.chunk_steps)
            except Exception:   # no analytic model: capture still runs
                log.exception("analytic FLOP model unavailable; roofline "
                              "cross-check disabled")
        cost_hook = roofline.capture if roofline is not None else None
        # Async-pipeline donation carve-out, CPU runtime only: the pipeline
        # consumer's device_get runs CONCURRENTLY with the dispatcher's
        # donating dispatch, and on the CPU runtime that combination
        # corrupts the heap (segfaults in unrelated threads once restores
        # interleave — the exact hazard the CPU megachunk carve-out below
        # already documents; reproduced by the supervision tests with the
        # pipeline on). Accelerator backends keep donation: concurrent D2H
        # against a donating dispatch is the designed overlap there (same
        # pattern as CheckpointManager.save_async).
        async_on = (self.cfg.runtime.async_pipeline
                    and self._step_override is None)
        if self._step_override is not None:
            # Host-side test seam: an arbitrary Python callable cannot be
            # traced into a lax.scan, so megachunks are unavailable and the
            # loop runs its K=1 path regardless of megachunk_factor.
            self._place = lambda ts: ts
            self._step_fn = self._step_override
        elif self.mesh is not None:
            from sharetrade_tpu.parallel.sharding import mesh_param_rules
            rules = mesh_param_rules(self.mesh,
                                     self.cfg.parallel.model_axis)
            # Both programs (and _place, _reset_episode, _heal_agents and
            # the checkpoint-restore path through it) resolve their specs
            # from the same canonical train_state_shardings tree, so a
            # restored or warm-started state lands on exactly the layout
            # the compiled step's in_shardings expect — no involuntary
            # reshard on the first chunk after a recovery.
            constrain = self.cfg.parallel.shard_constraints
            from sharetrade_tpu.parallel.mesh import is_cpu_mesh
            donate = not (async_on and is_cpu_mesh(self.mesh))
            self._place, self._step_fn = make_parallel_step(
                self.agent, self.mesh, data_axis=self.cfg.parallel.data_axis,
                param_rules=rules, constrain=constrain, donate=donate,
                cost_hook=cost_hook)
            if factor > 1:
                # The K-chunk scan composes INSIDE the pjit boundary (one
                # partitioned program), so ICI collectives stay fused across
                # inner chunks; the single-chunk program above remains the
                # exact path near episode thresholds.
                _, self._mega_fn = make_parallel_step(
                    self.agent, self.mesh,
                    data_axis=self.cfg.parallel.data_axis,
                    param_rules=rules, megachunk_factor=factor,
                    constrain=constrain, donate=donate,
                    cost_hook=cost_hook)
        else:
            self._place = lambda ts: ts
            # Donated input, matching the mesh path: the previous chunk's
            # TrainState is dead the moment the next step executes, halving
            # the state's HBM footprint (matters at the d>=1024 tier:
            # params+opt+replay double-buffered otherwise). Failure paths
            # are covered — _ensure_live_state restores when a raise leaves
            # donated-dead buffers behind, and save_async snapshots to host
            # before the next chunk can free them. Known trade (same as the
            # mesh path has always made): a RESUME-verb error raised from
            # INSIDE the step can no longer resume-in-place — the input was
            # donated — so it recovers via checkpoint restore, losing at
            # most checkpoint_every_updates updates instead of none (the
            # bound holds from chunk 0: _run_supervised writes a baseline
            # checkpoint before the first chunk). Under the async pipeline
            # on the CPU backend donation is carved out (see above) — the
            # cost is one extra live TrainState, on the host-memory
            # fallback path only.
            donate = ((0,) if not (async_on
                                   and jax.default_backend() == "cpu")
                      else ())
            self._step_fn = jax.jit(self.agent.step, donate_argnums=donate)
            if factor > 1:
                # NO donation on the CPU-fallback megachunk: donating the
                # TrainState into the fused lax.scan corrupts the heap on
                # the CPU runtime (use-after-free that surfaces as segfaults
                # in unrelated threads once checkpoint restores interleave
                # with megachunk dispatches — reproduced by the supervision
                # tests). The cost is one extra live TrainState per K chunks
                # on the fallback path only; the mesh/pjit path above keeps
                # donation, where HBM double-buffering actually matters.
                self._mega_fn = jax.jit(
                    megachunk_step(self.agent.step, factor))

    def _capture_roofline_fallback(self, template: TrainState) -> None:
        """Compile-time roofline capture for the MESHLESS build paths —
        the mesh path captures through ``jit_parallel_step``'s
        ``cost_hook`` (parallel/sharding.py), but the CPU-fallback
        programs are plain ``jax.jit`` wrappers built in
        :meth:`_build_step`, so their costs are recorded here, against
        the same template the first dispatch will see. Build-time only;
        a capture failure is swallowed inside RooflineCapture."""
        roofline = self.obs.roofline
        if (roofline is None or self.mesh is not None
                or self._step_override is not None):
            return
        roofline.capture(self._step_fn, (template,), megachunk_factor=1)
        if self._mega_fn is not None:
            roofline.capture(
                self._mega_fn, (template,),
                megachunk_factor=self.cfg.runtime.megachunk_factor)

    # ------------------------------------------------------------------
    # protocol: StartTraining (TrainerRouterActor.scala:86-88)
    # ------------------------------------------------------------------

    def start_training(self, *, background: bool = True) -> None:
        if self.lifecycle.phase is Phase.AWAITING_DATA:
            self.lifecycle.start_requested = True  # stashed until data
            self._stashed_background = background
            log.info("StartTraining stashed until training data arrives")
            return
        if self.lifecycle.phase not in (Phase.READY, Phase.COMPLETED,
                                        Phase.TRAINED, Phase.FAILED):
            log.info("already training; ignoring StartTraining")
            return
        if self.lifecycle.phase is not Phase.READY:
            self.initialise()
        self.lifecycle.to(Phase.TRAINING)
        self._stop.clear()
        if background:
            self._thread = threading.Thread(
                target=self._run_supervised, name="trainer", daemon=True)
            self._thread.start()
        else:
            self._run_supervised()

    # protocol: Initialise (TrainerChildActor.scala:57-59) — re-arm for a
    # fresh episode keeping learned parameters.
    def initialise(self) -> None:
        if self.agent is None or self._ts is None:
            return
        self._reset_episode()
        self.lifecycle.to(Phase.READY)

    # ------------------------------------------------------------------
    # the supervised device loop (BackoffSupervisor + Terminated respawn)
    # ------------------------------------------------------------------

    def _run_supervised(self) -> None:
        """The dispatcher: issues (mega)chunks and makes state-mutating
        decisions. With ``runtime.async_pipeline`` on, EVERY blocking host
        sync of the steady state — the batched ``device_get`` readback and
        the whole host_process block (metric rows, flight recorder,
        journaling, fault hooks, snapshot) — runs on the pipeline's
        consumer thread (:meth:`_host_process`), so the inter-megachunk
        dispatch gap no longer includes host time; the dispatcher drains
        the pipeline (a strict barrier) before the exact-completion K=1
        fallback, episode completion, heal/NaN supervision and
        checkpoint/eval cadence actions (:meth:`_boundary_actions`), and a
        consumer fault propagates here before the next megachunk commits
        state. With the knob off (or under ``step_override``) the same two
        methods run inline — the pre-pipeline synchronous path, byte-
        identical behavior."""
        rt = self.cfg.runtime
        horizon = self.env.num_steps
        chunk_idx = 0
        self._last_ckpt_updates = 0  # reference guards iteration != 0 (:74)
        # Sampled metrics (config.RuntimeConfig.metrics_every_chunks): a
        # per-chunk float(np.asarray(v)) is a device round-trip that
        # serializes the dispatch pipeline (per-dispatch host cost, not yet
        # measured on an attached chip). Between samples, chunks
        # dispatch back-to-back; every decision below (fault detection,
        # snapshot, eval/ckpt cadence, completion) runs on sampled chunks,
        # with completion made exact by a host-side env_steps upper bound
        # (each chunk advances the cumulative counter by AT MOST
        # chunk_steps) that forces per-chunk sampling near the episode
        # threshold. A fault_hook (the reference's mock seam) implies
        # per-chunk sampling so injected faults surface on the chunk that
        # raised them.
        metrics_every = (1 if self._fault_hook is not None
                         else max(1, rt.metrics_every_chunks))
        # Device-resident megachunks (config.RuntimeConfig.megachunk_factor):
        # K consecutive chunks fused into ONE compiled lax.scan, so the host
        # pays one dispatch per K chunks instead of K — the lever against
        # the per-dispatch host cost (not yet measured on an attached chip).
        # Per-chunk metrics
        # come back as a stacked (K, ...) buffer read with ONE batched
        # device_get; near the episode threshold the loop falls back to the
        # K=1 exact path below. _build_step leaves _mega_fn None for the
        # host-side step_override seam.
        mega = rt.megachunk_factor if self._mega_fn is not None else 1
        timer = StepTimer(rt.chunk_steps, self.cfg.parallel.num_workers,
                          max_history=self.cfg.obs.max_timer_history or None)
        self._timer = timer   # the consumer's tick handle (_host_process)
        obs = self.obs
        self.tracer.start()
        # ONE batched readback seeds both the baseline-checkpoint label and
        # the env-step completion bound (formerly two scalar device_gets —
        # tools/lint_hot_loop.py keeps stray per-scalar syncs out).
        updates0, env_steps0 = (
            int(v) for v in jax.device_get(  # hot-loop-sync-ok: once, before the first chunk
                (self._ts.updates, self._ts.env_steps)))
        # Baseline checkpoint before the first chunk (async; skipped when
        # one already exists or checkpointing is off): with donated step
        # inputs, a failure INSIDE a step can never resume in place — it
        # restores from the latest checkpoint — and without this save the
        # pre-first-cadence window would restore-to-nothing and silently
        # reinitialize, discarding warm-start/resume state. This makes the
        # "lose at most checkpoint_every_updates updates" bound true from
        # chunk 0.
        # "Exists" is not enough — steps() lists damaged dirs so the
        # walk-back can quarantine them; the baseline must be saved unless
        # an INTACT checkpoint could actually serve a restore (one hash of
        # the newest checkpoint, once per run start).
        has_intact = getattr(self.checkpoints, "any_intact",
                             lambda: self.checkpoints.latest_step()
                             is not None)
        if rt.checkpoint_every_updates > 0 and not has_intact():
            self.checkpoints.save_async(
                updates0, self._ts,
                metadata={"episode": self.episode, "env_steps": env_steps0})
        timer.tick()
        last_env_steps: int | None = env_steps0
        chunks_since = 0   # chunks since the last materialization decision
        chunks_ahead = 0   # chunks dispatched past the last boundary row SEEN
        # Inter-dispatch gap histogram (obs-gated): end of one dispatch
        # call to the start of the next — the dispatch-floor signal
        # bench_async_pipeline derives from trace spans, kept here as a
        # mergeable distribution. Reset to None across recoveries so a
        # backoff sleep never counts as a "gap". ONE helper pair shared
        # by the sync and prefetch dispatch sites: both paths must stamp
        # identically for train_dispatch_gap_ms to mean one distribution.
        # The same two stamps give train_dispatch_call_ms: the host time
        # inside the dispatch calls since the last boundary, observed
        # there (_observe_boundary) beside the time blocked in pl.put.
        last_dispatch_end: float | None = None
        dispatch_ms = 0.0

        def _dispatch_begin() -> float | None:
            if self._h_dispatch_gap is None:
                return None
            now = time.perf_counter()
            if last_dispatch_end is not None:
                self._h_dispatch_gap.observe((now - last_dispatch_end) * 1e3)
            return now

        def _dispatch_end(t_begin: float | None) -> None:
            nonlocal last_dispatch_end, dispatch_ms
            if t_begin is not None:
                last_dispatch_end = time.perf_counter()
                dispatch_ms += (last_dispatch_end - t_begin) * 1e3

        def _observe_boundary(chunks: int, stall_ms: float) -> None:
            nonlocal dispatch_ms
            if self._h_dispatch_call is not None:
                self._h_dispatch_call.observe(dispatch_ms / chunks)
                self._h_pipeline_stall.observe(stall_ms / chunks)
                dispatch_ms = 0.0
        self._committed_idx = 0
        # Double-buffered dispatch (runtime.double_buffer_dispatch; sync
        # path only — the async pipeline subsumes it): the (metrics, K,
        # agent_heals-at-dispatch) of a megachunk already issued while its
        # predecessor's rows are read back and processed. The heals mark
        # lets the health check recognize a STALE unhealthy_workers report:
        # rows computed before a boundary heal still carry the quarantined
        # row, and re-healing it would find no bad rows and spuriously
        # escalate to a full restart.
        pending: tuple[dict, int, int] | None = None
        # Async readback pipeline (runtime.async_pipeline, default on): the
        # dispatcher below never blocks on a readback — each materialization
        # boundary's device buffers go to the consumer thread, which runs
        # _host_process strictly in chunk order. Forced off under the
        # step_override test seam (lockstep semantics) alongside megachunks.
        pl: AsyncPipeline | None = None
        if rt.async_pipeline and self._step_override is None:
            self.pipeline_stats = {}
            pl = AsyncPipeline(
                rt.pipeline_depth, self._host_process,
                attn_check=self._row_needs_attention, span=obs.span)
        self._pl = pl
        # Chunk position of the boundary row _boundary_actions is acting on
        # in the attention path — a supervision raise from there (NaN loss,
        # heal escalation) is attributed to ITS boundary, not to however
        # far ahead the dispatcher has dispatched (sync-path parity).
        acting_chunk: int | None = None
        try:
          while not self._stop.is_set():
            try:
                acting_chunk = None
                if self._preempt.is_set():
                    # Megachunk-boundary preemption point: every committed
                    # state lands here between dispatches, so the emergency
                    # checkpoint below captures a coherent boundary state.
                    self._preempt_shutdown(pl)
                    return
                if pl is not None and (pl.error is not None
                                       or pl.attention.is_set()):
                    # A consumer fault, or a boundary row that needs a
                    # dispatcher-side action (heal / cadence / completion):
                    # drain so every queued readback lands in order, then
                    # act on the newest boundary row — the drain barrier
                    # that keeps supervision and completion exact.
                    pl.drain()
                    pl.attention.clear()
                    if pl.error is not None:
                        # True-chunk attribution: the consumer's committed
                        # cursor stopped AT the failing chunk, exactly where
                        # the synchronous loop's chunk_idx would be.
                        chunk_idx = self._committed_idx
                        raise pl.error
                    if pl.last_row is not None:
                        last_env_steps = int(pl.last_row["env_steps"])
                        chunks_ahead = chunk_idx - self._committed_idx
                    # Act on EVERY flagged row, in chunk order — cadence
                    # crossings on consecutive boundaries each get their
                    # action (eval/checkpoint), exactly like the
                    # synchronous path's per-boundary decision block.
                    for row, mark, end_idx in pl.take_attention():
                        acting_chunk = end_idx
                        ret = self._boundary_actions(row, mark, horizon)
                        if ret == "completed":
                            return
                        if ret == "rearmed":
                            break   # later rows predate the re-arm
                    continue
                if last_env_steps is None:  # after any recovery path
                    last_env_steps = int(
                        jax.device_get(self._ts.env_steps))  # hot-loop-sync-ok: once per recovery, not per chunk
                    chunks_since = 0
                    chunks_ahead = 0
                threshold = horizon * (self.episode + 1)
                if pending is not None:
                    metrics, k, heals_mark = pending
                    pending = None
                else:
                    heals_mark = self.agent_heals
                    # Fuse K chunks ONLY when even the env-step UPPER BOUND
                    # after K more chunks stays strictly below the episode
                    # threshold (each chunk advances the counter by at most
                    # chunk_steps): no inner chunk can hit the completion
                    # gate, so near episode ends the loop degrades to K=1
                    # dispatches and the gate keeps its exact semantics.
                    can_fuse = (mega > 1
                                and (last_env_steps + (chunks_ahead + mega)
                                     * rt.chunk_steps) < threshold)
                    if (pl is not None and mega > 1 and not can_fuse
                            and chunks_ahead > 0):
                        # Drain barrier BEFORE the K=1 exact fallback: the
                        # fusion guard ran on an upper bound that staled
                        # while boundaries were in flight; refresh from the
                        # drained consumer row — often fusion is still
                        # legal, and the completion math is exact again.
                        # Only a refresh that actually MOVED the bound
                        # re-enters the loop: un-materialized fast-path
                        # chunks have no row to reclaim, and looping on
                        # them would spin forever — they fall through to
                        # the K=1 exact path below.
                        if (pl.drain() and pl.error is None
                                and pl.last_row is not None):
                            refreshed = (int(pl.last_row["env_steps"]),
                                         chunk_idx - self._committed_idx)
                            if refreshed != (last_env_steps, chunks_ahead):
                                last_env_steps, chunks_ahead = refreshed
                                continue    # re-enter: attention first
                    k = mega if can_fuse else 1
                    # trace.jsonl events ride the SAMPLING cadence, not the
                    # chunk cadence: only the dispatch whose readback will
                    # materialize this sample is written, so between samples
                    # the fast path opens the profiler annotation alone (the
                    # <2% overhead budget, bench_obs_overhead). The predicate
                    # mirrors the sample decision below — chunk-count cadence, the
                    # near-threshold exact path, or a transitions journal
                    # (journaled runs materialize every chunk).
                    sampling = obs.enabled and (
                        chunks_since + k >= metrics_every
                        or self._transitions_journal is not None
                        or (last_env_steps + (chunks_ahead + k)
                            * rt.chunk_steps) >= threshold)
                    t_begin = _dispatch_begin()
                    with (obs.span if sampling else trace_span)(
                            "train/dispatch", chunk=chunk_idx, k=k):
                        # The step lock fences evaluate()'s state snapshot
                        # from this donating dispatch; dispatch is
                        # non-blocking so the lock is held microseconds,
                        # not the chunk.
                        with self._step_lock:
                            ts, metrics = (self._mega_fn if k > 1
                                           else self._step_fn)(self._ts)
                            # Commit the new state BEFORE any hook can
                            # raise: the mesh/accelerator paths donate their
                            # input (old state already dead), and the non-
                            # donating CPU megachunk paths must still never
                            # re-dispatch a superseded state after a hook
                            # fault. Do NOT assume donation on every path —
                            # the CPU fused-scan carve-outs (_build_step,
                            # sharding.py) exist to avoid a use-after-free.
                            self._ts = ts
                    _dispatch_end(t_begin)
                transitions = metrics.pop("transitions", None)
                chunks_since += k
                chunks_ahead += k
                est_env_steps = min(
                    last_env_steps + chunks_ahead * rt.chunk_steps, threshold)
                if (chunks_since < metrics_every and transitions is None
                        and est_env_steps < threshold):
                    chunk_idx += k
                    continue        # fast path: no host materialization
                if pl is not None:
                    # Hand the boundary to the consumer: start the D2H copy
                    # without blocking, enqueue (backpressure when the
                    # bounded queue is full — in-flight HBM stays bounded),
                    # and keep dispatching. Readback + the entire
                    # host_process block happen on the consumer thread.
                    _start_readback(metrics, transitions)
                    boundary = Boundary(chunk_idx, k, metrics, transitions,
                                        heals_mark, chunks_since)
                    stall_ms = 0.0
                    if not pl.try_put(boundary):
                        t_stall = time.perf_counter()
                        with obs.span("train/pipeline_stall",
                                      chunk=chunk_idx, depth=pl.depth):
                            ok = pl.put(boundary, stop=self._stop)
                        stall_ms = (time.perf_counter() - t_stall) * 1e3
                        self.metrics.inc("pipeline_stalls_total")
                        if not ok:
                            continue   # fault/stop while blocked: top of
                                       # loop takes over
                    _observe_boundary(chunks_since, stall_ms)
                    self.metrics.record("pipeline_queue_depth", pl.qsize())
                    chunk_idx += k
                    chunks_since = 0
                    if (est_env_steps >= threshold
                            or self._fault_hook is not None):
                        # Drain barrier for the exact completion gate: the
                        # upper bound says this boundary MAY finish the
                        # episode; wait for its true row (the consumer
                        # flags attention when it actually completes).
                        # A fault_hook keeps the SAME barrier on every
                        # boundary — the chaos seam's contract is dispatch-
                        # synchronous state (hooks mutate self._ts in the
                        # supervision tests), so the hook still runs on the
                        # consumer (fault propagation is exercised) but the
                        # dispatcher never runs ahead of it.
                        if (pl.drain() and pl.error is None
                                and pl.last_row is not None):
                            last_env_steps = int(pl.last_row["env_steps"])
                            chunks_ahead = chunk_idx - self._committed_idx
                    continue
                if (rt.double_buffer_dispatch and k > 1
                        and transitions is None and self._fault_hook is None
                        and (last_env_steps + (chunks_ahead + k)
                             * rt.chunk_steps) < threshold):
                    # Cruise-regime double buffering (sync path): issue
                    # megachunk k+1 BEFORE blocking on this one's readback,
                    # so the D2H metric transfer below overlaps device
                    # compute (the async-checkpoint D2H overlap applied to
                    # the metrics path). Guarded exactly like the fused
                    # dispatch (no inner chunk of the in-flight program can
                    # complete the episode), and off when transitions are
                    # journaled (durability) or a fault_hook is installed
                    # (the chaos seam needs dispatch-synchronous state).
                    # Consequence, documented in config.py: fault detection
                    # and the checkpoint/eval cadence act on a state one
                    # in-flight megachunk ahead of the rows being read.
                    # The span names the chunks the prefetch advances
                    # (chunk_idx + k onward), so the trace keeps one
                    # train/dispatch entry per dispatch (this block only
                    # runs at materialization boundaries, so it is already
                    # on the sampled path).
                    t_begin = _dispatch_begin()
                    with obs.span("train/dispatch", chunk=chunk_idx + k,
                                  k=k, prefetch=True):
                        with self._step_lock:
                            ts, ahead = self._mega_fn(self._ts)
                            self._ts = ts
                    _dispatch_end(t_begin)
                    pending = (ahead, k, self.agent_heals)
                # Synchronous path: readback + host processing inline (the
                # pre-pipeline behavior, byte-identical). No queue, so the
                # dispatcher is never stalled by back-pressure: 0.
                _observe_boundary(chunks_since, 0.0)
                metrics = self._host_process(Boundary(
                    chunk_idx, k, metrics, transitions, heals_mark,
                    chunks_since))
                chunk_idx = self._committed_idx
                last_env_steps = int(metrics["env_steps"])
                chunks_since = 0
                chunks_ahead = 0
                ret = self._boundary_actions(metrics, heals_mark, horizon)
                if ret == "completed":
                    return
            except Exception as exc:  # supervision decider
                last_env_steps = None   # resync after any recovery path
                pending = None          # in-flight megachunk is now stale
                last_dispatch_end = None  # recovery/backoff is not a "gap"
                dispatch_ms = 0.0
                pipeline_fault = pl is not None and exc is pl.error
                if pl is not None:
                    # Quiesce and replace the pipeline: boundaries still
                    # queued were computed from state the restore below
                    # rewinds — they are stale, and the fresh run segment
                    # re-materializes those chunks.
                    pl.shutdown()
                    self._record_pipeline_stats(pl)
                    pl = AsyncPipeline(
                        rt.pipeline_depth, self._host_process,
                        attn_check=self._row_needs_attention, span=obs.span)
                    self._pl = pl
                # Attribution: a consumer fault belongs to the chunk the
                # consumer committed last; a supervision raise from the
                # attention path belongs to the boundary row it was acting
                # on (the dispatcher may be several megachunks ahead of
                # both); any other dispatcher-local fault keeps its own
                # position (the consumer can only be behind it).
                if pipeline_fault:
                    chunk_idx = self._committed_idx
                elif acting_chunk is not None:
                    chunk_idx = acting_chunk
                else:
                    chunk_idx = max(chunk_idx, self._committed_idx)
                self.last_error = exc
                verb = self._decide(exc)
                self.events.emit("worker_failed", error=repr(exc), verb=verb,
                                 restarts=self.restarts + 1)
                # Forensic bundle BEFORE any recovery mutates state: the
                # ring holds the last-capacity chunk rows (its newest
                # chunk_metrics entry is the failing chunk — rows are
                # recorded before the hooks that raise on them),
                # lifecycle transitions, run events and WARNING+ logs.
                obs.dump_flight(reason="supervision", error=repr(exc),
                                verb=verb, restarts=self.restarts,
                                episode=self.episode, next_chunk=chunk_idx)
                if verb == RESUME:
                    log.warning("resuming after %r (policy: resume)", exc)
                    self._ensure_live_state()
                    timer.rebase()   # exclude the failed chunk's time
                    continue
                if verb == STOP:
                    self.lifecycle.force(Phase.FAILED)
                    self.tracer.stop()
                    obs.flush()
                    log.error("stopping after %r (policy: stop)", exc)
                    return
                if verb == ESCALATE:
                    self.lifecycle.force(Phase.FAILED)
                    self.tracer.stop()
                    obs.flush()
                    raise
                self.restarts += 1
                self.metrics.inc("restarts_total")
                if self.restarts > rt.max_restarts:
                    self.lifecycle.force(Phase.FAILED)
                    self.tracer.stop()
                    obs.flush()
                    log.error("restart budget exhausted: %r", exc)
                    return
                delay = min(rt.backoff_initial_s * 2 ** (self.restarts - 1),
                            rt.backoff_max_s)
                delay *= 1.0 + random.uniform(-rt.backoff_jitter,
                                              rt.backoff_jitter)
                log.warning("chunk failed (%r); restart %d/%d in %.2fs",
                            exc, self.restarts, rt.max_restarts, delay)
                with obs.span("supervision_recovery",
                              restart=self.restarts):
                    if self._wait_backoff(delay):
                        return
                    self._restore_or_reinit()
                # Exclude the failed chunk + backoff + restore from the
                # next throughput sample.
                timer.rebase()
        finally:
            self._pl = None
            if pl is not None:
                pl.shutdown()
                self._record_pipeline_stats(pl)

    def _record_pipeline_stats(self, pl: AsyncPipeline) -> None:
        self.pipeline_stats = {
            "max_depth_seen": max(
                self.pipeline_stats.get("max_depth_seen", 0),
                pl.max_depth_seen),
            "boundaries": (self.pipeline_stats.get("boundaries", 0)
                           + pl.processed),
        }

    # ------------------------------------------------------------------
    # preemption (SIGTERM/SIGINT): drain, emergency checkpoint, exit
    # ------------------------------------------------------------------

    def request_preempt(self) -> None:
        """Ask the run to preempt: the training thread drains and writes the
        ``tag_preempt`` emergency checkpoint at its next megachunk boundary
        (:meth:`_preempt_shutdown`), then returns. Installed as the
        SIGTERM/SIGINT action by ``cli train``; safe to call from
        signal-handler context (it only sets an Event). The grace deadline
        anchors HERE — at notice time, not at the boundary the dispatcher
        eventually reaches — so a long in-flight megachunk eats into the
        budget instead of extending it past the fleet's follow-up KILL."""
        if not self._preempt.is_set():
            self._preempt_deadline = (time.monotonic()
                                      + self.cfg.runtime.preempt_grace_s)
        self._preempt.set()

    def _wait_backoff(self, delay: float) -> bool:
        """Backoff sleep that wakes EARLY on preemption — the restart
        backoff must not eat the ``runtime.preempt_grace_s`` budget (the
        loop top then runs the preemption drain against the restored
        state). Returns True when stop was requested."""
        deadline = time.monotonic() + delay
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0 or self._preempt.is_set():
                return False
            if self._stop.wait(min(remaining, 0.1)):
                return True

    def _preempt_shutdown(self, pl: AsyncPipeline | None) -> None:
        """The preemption drain, run on the training thread at a megachunk
        boundary, inside ``runtime.preempt_grace_s``: queued readbacks drain
        in order (their metric rows and journal appends commit), in-flight
        async checkpoint writes land, an emergency ``tag_preempt``
        checkpoint with full resume metadata (updates / env_steps / episode)
        is written, the journal group-commit batch hits the disk, and the
        flight recorder dumps with reason ``"preemption"``. Never raises — a
        failure here degrades durability but must not convert a preemption
        into a supervision restart that burns the remaining grace."""
        obs = self.obs
        grace = self.cfg.runtime.preempt_grace_s
        # Anchored at request_preempt time: boundary latency (a long
        # in-flight megachunk) already consumed part of the budget.
        deadline = self._preempt_deadline or (time.monotonic() + grace)
        log.warning("preemption requested; draining for an emergency "
                    "checkpoint (%.1fs of the %.1fs grace left)",
                    max(0.0, deadline - time.monotonic()), grace)
        saved = False
        with obs.span("preemption_drain", grace_s=grace):
            try:
                if pl is not None:
                    pl.drain(timeout_s=max(0.5,
                                           deadline - time.monotonic()))
                self._ensure_live_state()
                updates, env_steps = (int(v) for v in jax.device_get(
                    (self._ts.updates, self._ts.env_steps)))
                self.checkpoints.wait_pending(
                    timeout=max(0.5, deadline - time.monotonic()))
                self.checkpoints.save_tagged(
                    "preempt", self._ts,
                    metadata={"updates": updates, "env_steps": env_steps,
                              "episode": self.episode, "preempted": True})
                saved = True
                # Durability-critical work strictly BEFORE any telemetry
                # write: a failing obs volume must not skip the journal
                # batch flush or the event-log record.
                flush = getattr(self._transitions_journal, "flush", None)
                if flush is not None:
                    flush()
                self.events.emit("preempted", updates=updates,
                                 env_steps=env_steps, episode=self.episode)
                log.warning("emergency checkpoint tag_preempt written "
                            "(updates=%d, env_steps=%d, episode=%d)",
                            updates, env_steps, self.episode)
            except Exception:
                log.exception("preemption drain failed; exiting with "
                              "whatever was already durable")
        try:
            # Telemetry is inside its own no-raise envelope too: an IO
            # error here must not convert the preemption into a
            # supervision restart that burns the remaining grace.
            if saved:
                obs.tracer.instant("emergency_checkpoint",
                                   updates=updates, env_steps=env_steps)
            obs.dump_flight(reason="preemption", episode=self.episode,
                            restarts=self.restarts)
            self.tracer.stop()
            obs.flush()
        except Exception:
            log.exception("preemption telemetry flush failed")
        self.preempt_saved = saved
        self.preempted = True

    def _host_process(self, b: Boundary) -> dict[str, float]:
        """The consumer half: ONE batched readback for the whole megachunk
        (the stacked (K, ...) metric rows and, for DQN journaling, the
        stacked transition batch cross together), then the per-row host
        work — flight-ring records, journal appends, fault hooks, metric
        stream, snapshot — strictly in chunk order. Runs on the pipeline's
        consumer thread under ``runtime.async_pipeline`` (every blocking
        call here is off the dispatch critical path), inline on the
        dispatcher otherwise. ``self._committed_idx`` advances per row and
        is the fault-attribution cursor either way."""
        obs = self.obs
        self._committed_idx = b.base
        with obs.span("train/readback", chunk=b.base, k=b.k):
            host, host_tr = jax.device_get((b.metrics, b.transitions))  # hot-loop-sync-ok: consumer-side batched megachunk readback, off the dispatch path
        t_host = time.perf_counter()
        with obs.span("train/host_process", chunk=b.base, k=b.k):
            rows = _metric_rows(host, b.k)
            for i, row in enumerate(rows):
                if obs.enabled:
                    # Into the flight ring BEFORE the fault hook / health
                    # checks that can raise on this row: at dump time the
                    # ring's newest chunk_metrics entry IS the failing
                    # chunk.
                    obs.record("chunk_metrics", chunk=b.base + i, **row)
                if host_tr is not None:
                    self._journal_transitions(
                        jax.tree.map(lambda a: a[i], host_tr)
                        if b.k > 1 else host_tr,
                        int(row["env_steps"]))
                if self._fault_hook is not None:
                    # Per inner chunk with its TRUE chunk index: a fault
                    # landing mid-megachunk surfaces at the boundary but is
                    # attributed (and, on raise, retried) at the chunk that
                    # raised it.
                    self._fault_hook(b.base + i, row)
                self._committed_idx = b.base + i + 1
                if i + 1 < b.k:
                    # Inner (non-boundary) rows keep the per-chunk metric
                    # stream complete — delivered late, at the boundary;
                    # snapshot/supervision/cadence read the boundary row,
                    # which subsumes them (quarantine and counters are
                    # monotone within a megachunk).
                    self.metrics.record_many(row)
            metrics = rows[-1]
            metrics.update(self._timer.tick(b.chunks_covered))
            if (self._h_chunk_seconds is not None
                    and metrics.get("chunk_seconds")):
                # Consumer-thread histogram of the sampled per-chunk wall
                # time (obs/hist.py): the mergeable distribution behind
                # the chunk_seconds gauge — host floats only, no sync.
                self._h_chunk_seconds.observe(metrics["chunk_seconds"])
            if obs.roofline is not None:
                # Live roofline gauges (mfu / achieved_tflops / hbm_gbps):
                # static compiled costs divided by the sampled per-chunk
                # wall time — consumer-thread math on already-host values,
                # never a device sync, never the dispatcher.
                obs.roofline.on_boundary(
                    k=b.k, chunk_seconds=metrics.get("chunk_seconds"))
            with self._snapshot_lock:
                self._snapshot = metrics
            self.metrics.record_many(metrics)
        if self._h_host_process is not None:
            self._h_host_process.observe(
                (time.perf_counter() - t_host) * 1e3 / b.chunks_covered)
        return metrics

    def _row_needs_attention(self, row: dict[str, float]) -> bool:
        """Consumer-side hint: does this boundary row need a DISPATCHER
        action (heal, NaN supervision, eval/checkpoint cadence, episode
        completion)? Over-triggering is harmless — the dispatcher drains
        and re-evaluates the exact conditions in _boundary_actions — so the
        reads here tolerate benign races with dispatcher-owned state."""
        rt = self.cfg.runtime
        unhealthy = row.get("unhealthy_workers", 0)
        if rt.partial_recovery and unhealthy > 0:
            return True
        if rt.partial_recovery and not np.isfinite(row.get("loss", 0.0)):  # hot-loop-sync-ok: consumer thread, host floats
            return True
        if (not rt.partial_recovery
                and unhealthy >= self.cfg.parallel.num_workers):
            return True
        updates = int(row.get("updates", 0))
        last = self._last_ckpt_updates
        for every in (rt.eval_every_updates, rt.checkpoint_every_updates):
            if every > 0 and updates // every > last // every:
                return True
        if self._ingest_enabled:
            # Live cadence (adaptive ingest): benign race with the
            # dispatcher's adjustments — over-triggering just drains and
            # re-evaluates, like every other attention hint here.
            every = self._ingest_every
            if updates // every > self._last_ingest_updates // every:
                return True
        return (int(row.get("env_steps", 0))
                >= self.env.num_steps * (self.episode + 1))

    def _boundary_actions(self, metrics: dict[str, float], heals_mark: int,
                          horizon: int) -> str | None:
        """Dispatcher-side decisions on a boundary row: per-agent healing,
        NaN supervision (raises feed the decider), eval/checkpoint cadence,
        and the episode-completion gate. Runs inline on the synchronous
        path; under the async pipeline it runs only after a drain barrier,
        so the row is the newest and the live state corresponds to it.
        Returns "completed" (terminal — caller returns), "rearmed" (episode
        re-armed), or None."""
        rt = self.cfg.runtime
        timer = self._timer
        obs = self.obs
        workers = self.cfg.parallel.num_workers
        if (rt.partial_recovery
                and metrics.get("unhealthy_workers", 0) > 0
                # Stale report from a pre-heal in-flight megachunk (double
                # buffering / pipeline depth): the row was already respawned
                # at the previous boundary; the next fresh megachunk
                # re-reports if the fault actually persists.
                and heals_mark == self.agent_heals):
            # Quarantined rows detected: respawn just those agents
            # (the reference's one-dead-child heal). Raising falls
            # through to the supervision decider -> full restore.
            # A recurring fault must not heal->re-poison->heal
            # forever: past the heal budget it escalates to the
            # restart path, whose max_restarts bounds availability.
            if (self.agent_heals >= rt.max_agent_heals
                    or not self._heal_agents()):
                raise RuntimeError(
                    f"{int(metrics['unhealthy_workers'])} agent(s) "
                    "non-finite and beyond row respawn "
                    f"(heals used: {self.agent_heals}/"
                    f"{rt.max_agent_heals})")
        if (rt.partial_recovery
                and not np.isfinite(metrics.get("loss", 0.0))):
            # Poison reached the shared loss (and so the params on
            # the next update): beyond any row respawn — full
            # checkpoint restore via the supervision path.
            raise RuntimeError("non-finite training loss "
                               "(shared state poisoned)")

        updates = int(metrics.get("updates", 0))
        if (self._ingest_enabled
                and updates // self._ingest_every
                > self._last_ingest_updates // self._ingest_every):
            # Actor-feed ingest (distrib/): contained like the periodic
            # eval below — a torn actor journal or a transient read error
            # is an ingest miss, not a training fault; the next cadence
            # tick retries from the same cursors.
            try:
                self.ingest_actor_feeds()
            except Exception:
                log.exception("actor-feed ingest failed; "
                              "training continues")
            self._last_ingest_updates = updates
        if (rt.eval_every_updates > 0
                and updates // rt.eval_every_updates
                > self._last_ckpt_updates // rt.eval_every_updates):
            # Periodic greedy eval between chunks: feeds the
            # event-log learning curve and (keep_best_eval) the
            # retained-best checkpoint during long unattended runs.
            # Contained: an eval/retention failure (e.g. disk full
            # in save_tagged) is an observability loss, not a
            # training fault — it must not consume a restart or
            # roll the healthy run back to a checkpoint.
            try:
                self.evaluate()
            except Exception:
                log.exception("periodic evaluation failed; "
                              "training continues")
        if (rt.checkpoint_every_updates > 0
                and updates // rt.checkpoint_every_updates
                > self._last_ckpt_updates // rt.checkpoint_every_updates):
            # Async: device->host DMA overlaps the next chunk.
            # The episode index rides the metadata: env_steps alone
            # can't recover it once per-agent heals inflate the step
            # count past horizon-per-episode.
            self.checkpoints.save_async(
                updates, self._ts,
                # env_steps rides along for the crash-soak/journal
                # consistency checks and the resume-source comparison
                # (tag_preempt vs latest step checkpoint).
                metadata={"episode": self.episode,
                          "env_steps": int(metrics.get("env_steps", 0))})
            self.metrics.inc("checkpoints_total")
            self.events.emit("checkpoint", updates=updates)
        self._last_ckpt_updates = updates

        # env_steps is cumulative across episodes (the epsilon ramp
        # input), so episode N completes at (N+1) x horizon. With
        # per-agent healing, a respawned row restarts its episode
        # mid-run and may still be training when the step count
        # crosses the threshold — completion additionally waits for
        # every worker's cursor to reach the horizon (the reference
        # completes only when all 10 children report Trained,
        # including replacements, TrainerRouterActor.scala:114,125).
        done_steps = (int(metrics.get("env_steps", 0))
                      >= horizon * (self.episode + 1))
        # With partial_recovery off, a quarantined row can never be
        # respawned: it would strand the all-trained gate forever
        # (the learners' on-device quarantine is unconditional), so
        # stranded rows count as excluded — the run completes
        # without them, like a dead child nobody respawns.
        stranded = (0.0 if rt.partial_recovery
                    else metrics.get("unhealthy_workers", 0.0))
        all_trained = (metrics.get("trained_workers", float(workers))
                       + stranded >= workers)
        if done_steps and all_trained:
            self.episode += 1
            self.metrics.inc("episodes_completed_total")
            if self.episode < rt.episodes:
                # Re-arm for another pass over the history, keeping
                # learned parameters (the Initialise→Train cycle,
                # TrainerChildActor.scala:57-59).
                self.events.emit("episode_completed",
                                 episode=self.episode)
                self._reset_episode()
                return "rearmed"
            self.checkpoints.wait_pending(timeout=60)
            self.checkpoints.save(
                updates, self._ts,
                metadata={"episode": self.episode,
                          "env_steps": int(metrics.get("env_steps", 0))})
            # Completion is a durability point: group-commit batches (and
            # the C++ async writer's queue) drain to disk before the run
            # reports COMPLETED, so a reader of the journal file sees every
            # journaled chunk the moment the lifecycle says done.
            flush = getattr(self._transitions_journal, "flush", None)
            if flush is not None:
                flush()
            self.lifecycle.to(Phase.TRAINED)
            self.lifecycle.to(Phase.COMPLETED)
            self.tracer.stop()
            self.events.emit("training_completed",
                             env_steps=int(metrics["env_steps"]),
                             episodes=self.episode,
                             **timer.summary())
            obs.flush()   # trace + final metrics drain durable now
            log.info("training completed at %d env steps", horizon)
            return "completed"
        if (not rt.partial_recovery
                and metrics.get("unhealthy_workers", 0) >= workers):
            # Every row non-finite with healing disabled AND the run
            # not complete: the unconditional on-device quarantine
            # freezes every cursor, so no further progress is
            # possible — route through the supervision path instead
            # of spinning chunks forever. (Checked AFTER the
            # completion gate: a run whose last chunk both finishes
            # the episode and poisons every row still completes via
            # the stranded-rows-excluded path above.)
            raise RuntimeError(
                "all agent rows non-finite (partial_recovery off); "
                "no further progress is possible")
        return None

    def _reset_episode(self) -> None:
        """Fresh env cursors/carry/RNG for the next episode; parameters,
        optimizer state, update counter, AND the cumulative env-step count
        carry over (env_steps drives the epsilon exploration ramp — resetting
        it would replay ~1000 fully-random steps into a learned policy)."""
        fresh = self.agent.init(
            jax.random.PRNGKey(self.cfg.seed + self.episode))
        self._ts = self._place(fresh.replace(
            params=self._ts.params, opt_state=self._ts.opt_state,
            updates=self._ts.updates, env_steps=self._ts.env_steps,
            # DQN keeps its replay buffer and target net across episodes.
            extras=self._ts.extras))

    def _ensure_live_state(self) -> None:
        """A failure inside the donated-input step can leave self._ts holding
        deleted buffers; resume-in-place is then impossible and we fall back
        to restore."""
        leaves = jax.tree.leaves(self._ts)
        if any(getattr(l, "is_deleted", lambda: False)() for l in leaves):
            log.warning("state was donated into the failed step; restoring")
            self._restore_or_reinit()

    def _decide(self, exc: BaseException) -> str:
        for etype, verb in self._error_policy.items():
            if isinstance(exc, etype):
                return verb
        return RESTART

    def _heal_agents(self) -> bool:
        """Respawn poisoned agent ROWS in place — the reference's per-worker
        heal (one dead child replaced while the other nine keep training,
        TrainerRouterActor.scala:141-146) translated to vectorized agents.

        The learners' on-device quarantine (base.healthy_mask) guarantees a
        non-finite row never reached the shared parameters, so recovery is
        local: splice a fresh env cursor + model carry into the bad rows
        (params/optimizer/RNG/step counters untouched) and let the respawned
        agents retrain their episode — the reference's re-fired
        StartTraining (:116-120). Survivors lose nothing; completion waits
        for the respawned rows (the all_trained gate).

        Trunk-rollout models (the episode-mode transformer) share one
        representative agent's price windows and carry across the batch
        (agents/rollout.py agent-invariance), so their respawned rows CANNOT
        restart at cursor 0 — a healthy-but-desynced row could be elected
        representative and corrupt every agent's windows. Instead they
        rejoin AT the survivors' cursor: a fresh wallet spliced in at the
        representative's env cursor, with the representative's carry (the
        trunk/K-V cache is action-independent, so every lockstep row's carry
        is identical — the respawned row's "recomputed" carry already exists
        on a healthy neighbor). The respawned agent trades the remainder of
        the episode; survivors lose nothing; lockstep is preserved. This is
        the round-3 exemption removed — previously one poisoned flagship row
        rolled the WHOLE run back to the last checkpoint.

        Returns False — caller falls back to checkpoint restore — when the
        damage exceeds a row respawn: shared params/opt non-finite (the
        quarantine was breached), EVERY row bad (device-level corruption),
        or no bad rows found (the fault is elsewhere)."""
        if self._step_override is not None or self.agent is None:
            return False
        from sharetrade_tpu.agents.base import election_health
        ts = self._ts
        # THE shared row-health predicate (also used to elect the shared-
        # trunk representative in agents/rollout.py): env state AND model
        # carry finite, per row.
        ok = np.asarray(jax.device_get(election_health(ts.env_state,
                                                       ts.carry)))
        bad = ~ok
        if not bad.any() or bad.all():
            return False
        shared = jax.device_get((ts.params, ts.opt_state))
        if not all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree.leaves(shared)):
            return False
        fresh = self.agent.init(jax.random.PRNGKey(
            self.cfg.seed + 7919 * (self.agent_heals + 1)))

        def splice(cur, new):
            m = bad.reshape((-1,) + (1,) * (np.asarray(cur).ndim - 1))
            return jnp.where(m, new, cur)

        fresh_env, fresh_carry = fresh.env_state, fresh.carry
        if getattr(self.agent.model, "apply_rollout_trunk", None) is not None:
            # Lockstep rejoin (see docstring): fresh wallet at the
            # representative healthy row's cursor, carry copied from it.
            rep = int(np.flatnonzero(ok)[0])
            fresh_env = fresh_env.replace(
                t=jnp.broadcast_to(ts.env_state.t[rep],
                                   fresh_env.t.shape))
            fresh_carry = jax.tree.map(
                lambda c: jnp.broadcast_to(c[rep:rep + 1],
                                           c.shape).astype(c.dtype),
                ts.carry)
        self._ts = self._place(ts.replace(
            env_state=jax.tree.map(splice, ts.env_state, fresh_env),
            carry=jax.tree.map(splice, ts.carry, fresh_carry)))
        self.agent_heals += 1
        self.metrics.inc("heals_total")
        idx = [int(i) for i in np.flatnonzero(bad)]
        log.warning("respawned poisoned agent row(s) %s in place "
                    "(heal %d; params untouched)", idx, self.agent_heals)
        self.events.emit("agents_healed", agents=idx,
                         heals=self.agent_heals)
        return True

    def _restore_or_reinit(self) -> None:
        """Restore the latest INTACT checkpoint — the manager verifies each
        candidate (checksums, deserializability, finite shared leaves),
        quarantines damaged ones and walks back — else restart the episode
        from scratch: respawn-and-retrain (TrainerRouterActor.scala:116-120,
        141-146). "All corrupt" raises CheckpointCorruptError, a
        FileNotFoundError subclass, so it lands on the same reinit arm as
        "none saved yet" — a run never strands on damaged newest bytes."""
        template = self.agent.init(jax.random.PRNGKey(self.cfg.seed))
        self.checkpoints.wait_pending(timeout=60)  # pick up in-flight saves
        try:
            state, step = self.checkpoints.restore(template)
            self._surface_restore_fallback()
            self._ts = self._place(self._warm_start_replay(state))
            self.events.emit("restored", step=step)
        except FileNotFoundError:
            self._ts = self._place(self._warm_start_replay(template))
            self.events.emit("reinitialized")

    def _surface_restore_fallback(self) -> None:
        """A restore that had to walk back past quarantined checkpoints is
        a supervision-visible fact, not just a manager log line: the event
        log records which steps were skipped and why (the counters —
        ckpt_restore_fallbacks_total / ckpt_quarantined_total — already
        flowed through the manager's metrics hook)."""
        report = getattr(self.checkpoints, "last_restore_report", None) or {}
        skipped = report.get("skipped")
        if skipped:
            self.events.emit(
                "restore_fallback", step=report.get("step"),
                skipped=[[int(s), reason] for s, reason in skipped])

    def _restore_for_resume(self, template: TrainState
                            ) -> tuple[TrainState, int, dict]:
        """``--resume`` source selection: prefer the ``tag_preempt``
        emergency checkpoint when it is at least as new (by update count)
        as the newest VERIFIED step checkpoint — it was written AFTER the
        last cadence save, at the exact megachunk boundary the preempted
        run stopped on. Falls back to the verified step-checkpoint
        walk-back when the tag is absent, older, or quarantined; and
        symmetrically, when the step walk-back lands BELOW the tag's
        update count (the unverified ``latest_step`` number was inflated
        by a checkpoint verification rejected), the intact emergency
        checkpoint is re-preferred. Returns ``(state, step_label,
        metadata)``."""
        pmeta = self.checkpoints.tagged_metadata("preempt")
        tag_hint = int(pmeta.get("updates", -1)) if pmeta else -1
        latest = self.checkpoints.latest_step()

        def tag_candidate() -> tuple[TrainState, int, dict] | None:
            """Verified tag_preempt restore; None when absent or every
            copy (primary + .old) was quarantined by verification."""
            try:
                state, meta = self.checkpoints.restore_tagged(
                    template, "preempt")
            except FileNotFoundError:
                return None
            return state, int(meta.get("updates", 0)), meta

        def accept(t: tuple[TrainState, int, dict]
                   ) -> tuple[TrainState, int, dict]:
            log.info("resuming from preemption checkpoint (updates=%d)",
                     t[1])
            self.events.emit("resumed_from_preempt", updates=t[1])
            return t

        tag = None
        if pmeta is not None and (latest is None or tag_hint >= latest):
            tag = tag_candidate()
            # Compare the ACTUALLY-restored metadata, not the hint: a
            # corrupt primary makes restore_tagged serve the .old crash-
            # window copy, which can be older than a step checkpoint.
            if tag is not None and (latest is None or tag[1] >= latest):
                return accept(tag)
        try:
            state, step = self.checkpoints.restore(template)
        except FileNotFoundError:
            # Steps gone or ALL corrupt: an intact emergency checkpoint —
            # even one OLDER than the (now-quarantined) step numbers that
            # suppressed the preference above — beats stranding the run.
            if tag is None and pmeta is not None:
                tag = tag_candidate()
            if tag is not None:
                return accept(tag)
            raise
        self._surface_restore_fallback()
        # The VERIFIED metadata rides the restore report — re-reading
        # meta.json here would be redundant IO plus a window for an
        # unverified copy to diverge from what restore just checksummed.
        report = getattr(self.checkpoints, "last_restore_report", None) or {}
        meta = report.get("meta") or self.checkpoints.metadata(step)
        if pmeta is not None and tag is None and tag_hint > step:
            # The step side's number was inflated by a checkpoint that the
            # walk-back quarantined; the emergency checkpoint may now be
            # the freshest intact state after all.
            tag = tag_candidate()
        if tag is not None and tag[1] > step:
            return accept(tag)
        if tag is not None:
            log.warning(
                "preemption checkpoint restored at updates=%d is older "
                "than step checkpoint %d; using the step checkpoint",
                tag[1], step)
        return state, step, meta

    # ------------------------------------------------------------------
    # journal-backed replay (learner.journal_replay; SURVEY.md §7.4)
    # ------------------------------------------------------------------

    def _journal_transitions(self, transitions, env_steps: int) -> None:
        """Host-side append of one chunk's transition batch to the durable
        event log. Arrays arrive as (T, B, ...) from the scanned chunk;
        frozen (episode-complete) agent rows are filtered by the validity
        mask before writing. Chunks replayed after a restore (RNG restored,
        identical data) are skipped via the env-step high-water mark so a
        heal never double-journals."""
        if transitions is None or self._transitions_journal is None:
            return
        if env_steps <= self._journal_high_water:
            return
        self._journal_high_water = env_steps
        from sharetrade_tpu.data.transitions import append_transitions
        valid = np.asarray(transitions["valid"]).reshape(-1)
        if not valid.any():
            return
        flat = {k: np.asarray(v).reshape((-1,) + np.asarray(v).shape[2:])
                for k, v in transitions.items() if k != "valid"}
        # Packed binary records (data/transitions.py): ~5x smaller than the
        # JSON encoding and decoded on recovery by one C++/numpy pass.
        append_transitions(
            self._transitions_journal, flat["obs"][valid],
            flat["action"][valid], flat["reward"][valid],
            flat["next_obs"][valid], env_steps=env_steps)
        # Bound the journal: once a buffer's worth of NEW rows accumulated,
        # drop records older than the recoverable tail (2x capacity keeps a
        # full buffer recoverable at any resume cutoff inside the last
        # capacity rows). Record boundaries/stamps survive compaction, so
        # cutoff filtering stays exact. With segment rotation on
        # (data.journal_segment_records) compaction is segment-granular:
        # whole sealed segments older than the horizon are deleted —
        # never a rewrite of live data, never a segment newer than the
        # horizon — and the journal_segments / journal_compacted_bytes
        # telemetry tracks the bound.
        capacity = self.cfg.learner.replay_capacity
        self._journal_rows_since_compact += int(valid.sum())
        segmented = self.cfg.data.journal_segment_records > 0
        if self._journal_rows_since_compact >= capacity:
            if segmented:
                from sharetrade_tpu.data.transitions import (
                    retire_transition_segments)
                retired, freed = retire_transition_segments(
                    self._transitions_journal, 2 * capacity)
                if freed:
                    self.metrics.inc("journal_compacted_bytes_total", freed)
                if retired:
                    self.metrics.inc("journal_segments_retired_total",
                                     retired)
            else:
                from sharetrade_tpu.data.transitions import (
                    compact_transitions)
                compact_transitions(self._transitions_journal, 2 * capacity)
            self._journal_rows_since_compact = 0
        if segmented:
            from sharetrade_tpu.data.journal import segment_paths
            self.metrics.record(
                "journal_segments",
                len(segment_paths(self._transitions_journal.path)) + 1)

    def ingest_actor_feeds(self) -> int:
        """Feed-driven ingest — the learner half of actor/learner
        disaggregation (distrib/): tail every actor's transitions journal
        under ``distrib.actor_dir`` for rows STAMPED past the per-actor
        cursor, splice them into the live device replay buffer
        (oldest-first circular pushes, exactly the ``_warm_start_replay``
        fill path), and reseed PER priorities at the stored max (the
        priorities were never journaled — same contract as a resume).

        Membership is ELASTIC by construction: the journal set is
        re-discovered from the filesystem every call, so an actor that
        joined mid-run starts being ingested at its first committed
        record and a dead actor simply stops producing — the learner
        never needs to know the pool's membership, only its data. Runs on
        the dispatcher thread at a drained boundary (``_boundary_actions``
        cadence ``distrib.ingest_every_updates``), so no dispatch is in
        flight; the step lock fences ``evaluate()`` racers exactly like
        every other state mutation. Returns the rows ingested."""
        if not self._ingest_enabled or self._ts is None:
            return 0
        import glob
        import os
        from sharetrade_tpu.agents.dqn import (
            fill_replay_from_arrays, reseed_per_priorities)
        from sharetrade_tpu.data.transitions import read_new_transitions
        from sharetrade_tpu.distrib.actor import TRANSITIONS_FILE
        root = self.cfg.distrib.actor_dir
        max_rows = (self.cfg.distrib.ingest_max_rows
                    or self.cfg.learner.replay_capacity)
        total = 0
        backlog = False
        per_actor: dict[str, int] = {}
        for path in sorted(glob.glob(
                os.path.join(root, "*", TRANSITIONS_FILE))):
            actor_id = os.path.basename(os.path.dirname(path))
            cursor = self._actor_cursors.get(actor_id, 0)
            try:
                out = read_new_transitions(path, cursor, max_rows)
            except OSError:
                log.exception("actor feed %s unreadable; skipping this "
                              "ingest tick", path)
                continue
            if out is None:
                continue
            obs, action, reward, next_obs, high_water = out
            rows = int(obs.shape[0])
            if rows >= max_rows:
                # A FULL window means the reader truncated: this actor's
                # journal holds more committed rows than one tick may
                # splice — the backlog signal the adaptive cadence
                # tightens on (the rest streams across later ticks, the
                # read_new_transitions oldest-first contract).
                backlog = True
            if rows:
                if obs.shape[1] != self.env.obs_dim:
                    log.error(
                        "actor feed %s obs_dim %d != learner obs_dim %d; "
                        "refusing the rows (actor running a different "
                        "env config?)", path, obs.shape[1],
                        self.env.obs_dim)
                    self._actor_cursors[actor_id] = max(cursor, high_water)
                    continue
                with self._step_lock:
                    extras = self._ts.extras
                    extras = extras.replace(
                        replay=fill_replay_from_arrays(
                            extras.replay, obs, action, reward, next_obs))
                    self._ts = self._ts.replace(extras=extras)
                total += rows
                per_actor[actor_id] = rows
                self.metrics.inc(
                    f"actor_rows_ingested_total_{actor_id}", rows)
            # The cursor advances to the scanned high-water even when no
            # rows were kept (all filtered): stamps are monotone, so
            # nothing committed is ever skipped by advancing.
            self._actor_cursors[actor_id] = max(cursor, high_water)
        if total:
            with self._step_lock:
                # ONE tree rebuild per ingest tick, not per journal
                # (no-op for uniform extras).
                self._ts = self._ts.replace(
                    extras=reseed_per_priorities(self._ts.extras))
            self.metrics.inc("distrib_rows_ingested_total", total)
            self.metrics.record("distrib_actor_feeds", len(per_actor))
            self.events.emit("actor_feed_ingest", rows=total,
                             actors=sorted(per_actor))
            log.info("ingested %d actor transition rows (%s)", total,
                     ", ".join(f"{k}:{v}"
                               for k, v in sorted(per_actor.items())))
        self._adapt_ingest_cadence(total, backlog)
        return total

    #: Adaptive-cadence bounds, as factors of the configured base
    #: cadence: backoff doubles up to base*8 (dry feeds), tightening
    #: halves down to max(1, base/4) (backlog). Class attributes so the
    #: fake-clock tests and the bench name the same contract.
    INGEST_BACKOFF_MAX_FACTOR = 8
    INGEST_TIGHTEN_DIV = 4
    #: Consecutive all-dry ticks before the first backoff step: one dry
    #: tick is a scheduling phase artifact, three is a caught-up learner.
    INGEST_DRY_TICKS = 3

    def _adapt_ingest_cadence(self, rows: int, backlog: bool) -> None:
        """One bounded AIMD step of the live ingest cadence (see the
        ``_ingest_every`` construction comment for the policy). Runs on
        the dispatcher thread right after an ingest tick — the only
        writer of ``_ingest_every``."""
        if not self._adaptive_ingest:
            return
        base = self._ingest_base
        every = self._ingest_every
        new = every
        reason = None
        if rows == 0:
            self._ingest_dry_streak += 1
            if (self._ingest_dry_streak >= self.INGEST_DRY_TICKS
                    and every < base * self.INGEST_BACKOFF_MAX_FACTOR):
                new = min(base * self.INGEST_BACKOFF_MAX_FACTOR, every * 2)
                reason = "feeds_dry"
        else:
            self._ingest_dry_streak = 0
            if backlog:
                floor = max(1, base // self.INGEST_TIGHTEN_DIV)
                if every > floor:
                    new = max(floor, every // 2)
                    reason = "backlog"
            elif every > base:
                # Data is flowing again after a dry backoff: snap back
                # to the configured cadence in one step (a gradual walk
                # down would under-ingest for several boundaries).
                new = base
                reason = "recovered"
        if new == every:
            return
        self._ingest_every = new
        self.metrics.inc("ingest_adjustments_total")
        self.metrics.record("ingest_every_updates_current", float(new))
        self.obs.record("ingest_cadence_adjust", reason=reason,
                        every=new, base=base, rows=rows,
                        backlog=backlog)
        log.info("adaptive ingest cadence: every %d -> %d updates (%s)",
                 every, new, reason)

    def _warm_start_replay(self, state: TrainState) -> TrainState:
        """Rebuild the DQN replay buffer from the transitions journal. The
        journal sees every chunk as it happens while checkpoints lag by the
        save cadence, so after a crash the journal is the fresher (and
        durable) source of truth — the event-sourcing recovery pattern the
        reference applies to price data (SharePriceGetter.scala:55-62),
        applied to experience."""
        if self._transitions_journal is None:
            return state
        from sharetrade_tpu.agents.dqn import (
            ReplayBuffer, fill_replay_from_arrays, fill_replay_from_events)
        from sharetrade_tpu.data.transitions import read_tail_transitions
        capacity = self.cfg.learner.replay_capacity
        cutoff = int(state.env_steps)
        # Legacy JSON "transitions" events (older logs — a pre-rotation
        # journal may carry them INTO its first sealed segment, so the
        # scan covers every segment); binary records are skipped by
        # replay() and decoded below. This stays bounded: segment
        # retirement caps the whole journal near the 2x-capacity horizon,
        # and the binary fast path below walks only the tail segments
        # newest-first (the bounded-recovery fix).
        events = [e for e in self._transitions_journal.replay()
                  if e.get("type") == "transitions"]
        # Packed binary tail (the fast path): one C++/numpy pass returns the
        # capacity-bounded arrays plus the journal's env-step high water.
        # Fill only up to the restored state's env-step count: the chunks
        # between checkpoint and crash re-run with restored RNG and push
        # identical transitions themselves — filling them here too would
        # double-count them in the live buffer. cutoff=0 (fresh init) keeps
        # nothing but still recovers the high-water mark. journal= makes
        # the reader quiesce group-commit/async-writer buffers first, so
        # every append that returned is visible to the tail walk.
        tail = read_tail_transitions(self._transitions_journal.path,
                                     capacity if cutoff > 0 else 1,
                                     cutoff_env_steps=cutoff,
                                     journal=self._transitions_journal)
        # Recover the journaling high-water mark so chunks replayed between
        # the restored checkpoint and the crash point aren't re-journaled.
        self._journal_high_water = max(
            [self._journal_high_water]
            + [e.get("env_steps", 0) for e in events]
            + ([tail[4]] if tail is not None else []))
        fresh = ReplayBuffer.create(capacity, self.env.obs_dim)
        warm = fill_replay_from_events(
            fresh, [e for e in events if e.get("env_steps", 0) <= cutoff])
        if tail is not None and cutoff > 0:
            warm = fill_replay_from_arrays(warm, *tail[:4])
        if int(warm.size) == 0:
            return state            # nothing journaled yet: keep as restored
        log.info("warm-started replay buffer with %d journaled transitions",
                 int(warm.size))
        self.events.emit("replay_warm_started", size=int(warm.size))
        from sharetrade_tpu.agents.dqn import reseed_per_priorities
        # PER mode: priorities are not journaled — the recovered rows
        # re-enter the sum-tree at the checkpointed max priority (no-op
        # for uniform extras).
        return state.replace(extras=reseed_per_priorities(
            state.extras.replace(replay=warm)))

    # ------------------------------------------------------------------
    # queries (IsEverythingDone / GetAvg / GetStd; ShareTradeHelper.scala:35-39)
    # ------------------------------------------------------------------

    def is_everything_done(self) -> QueryReply:
        phase = self.lifecycle.phase
        if phase is Phase.AWAITING_DATA:
            return QueryReply(ReplyState.NO_TRAINING_DATA)
        if phase in (Phase.READY, Phase.TRAINING):
            return QueryReply(ReplyState.TRAINING_NOT_COMPLETED)
        if phase is Phase.FAILED:
            return QueryReply(ReplyState.NOT_COMPUTED)
        return QueryReply(ReplyState.COMPLETED)

    def _drain_pipeline(self) -> None:
        """Barrier for external readers: wait until every boundary enqueued
        so far has been consumed, so ``get_avg``/``get_std``/``snapshot``
        answer from the newest processed chunk — the async pipeline must
        not make queries staler than the synchronous path's sampling
        cadence already allows. No-op when no pipeline is live, from the
        consumer thread itself, or after a consumer fault (the supervision
        path owns recovery)."""
        pl = self._pl
        if pl is not None:
            pl.drain(timeout_s=30.0)

    def _stat(self, key: str, *, trained_only: bool = False) -> QueryReply:
        self._drain_pipeline()
        phase = self.lifecycle.phase
        if phase is Phase.AWAITING_DATA:
            return QueryReply(ReplyState.NO_TRAINING_DATA)
        if phase is Phase.FAILED:
            # A dead run must not serve its stale pre-failure snapshot as a
            # RESULT — the reference's protocol has no reply arm for "here is
            # a number from a run that died" (TrainerRouterActor.scala:15-34),
            # and is_everything_done() already answers NOT_COMPUTED here.
            return QueryReply(ReplyState.NOT_COMPUTED)
        with self._snapshot_lock:
            snap = dict(self._snapshot)
        if trained_only:
            # Reference GetAvg semantics: average only the workers that
            # FINISHED training (it asks the trained list, nobody else —
            # TrainerRouterActor.scala:84-95,137-139). NotComputed until at
            # least one agent's episode cursor reached the horizon.
            if snap.get("trained_workers", 0.0) < 1.0:
                return QueryReply(ReplyState.NOT_COMPUTED)
            key = f"{key}_trained"
        value = snap.get(key)
        if value is None:
            return QueryReply(ReplyState.NOT_COMPUTED)
        # Mid-run replies use the latest chunk snapshot — progressive stats
        # over all agents by default; ``trained_only`` reproduces the
        # reference's completed-workers-at-time-t observable.
        return QueryReply(ReplyState.RESULT, value)

    def get_avg(self, *, trained_only: bool | None = None) -> QueryReply:
        if trained_only is None:
            trained_only = self.cfg.runtime.query_trained_only
        return self._stat("portfolio_mean", trained_only=trained_only)

    def get_std(self, *, trained_only: bool | None = None) -> QueryReply:
        if trained_only is None:
            trained_only = self.cfg.runtime.query_trained_only
        return self._stat("portfolio_std", trained_only=trained_only)

    def snapshot(self) -> dict[str, float]:
        self._drain_pipeline()
        with self._snapshot_lock:
            return dict(self._snapshot)

    def evaluate(self) -> dict[str, float]:
        """Greedy-policy evaluation: replay the episode with argmax actions,
        no exploration, no updates — the measurement the reference never
        separates from training (its portfolio avg mixes ~10% random actions
        even at full epsilon, QDecisionPolicyActor.scala:58-62). Runs one
        scan on the current params; training state is untouched.

        With ``runtime.keep_best_eval`` the evaluated state is retained as
        the ``best`` tagged checkpoint whenever it improves on the best
        eval seen (across resumes — the tag's own metadata seeds the bar):
        on-policy training can find the strategy and then collapse, and
        without retention the collapsed policy is what a user ships."""
        if self.agent is None or self._ts is None:
            raise RuntimeError("no training data / state")
        # Snapshot the state under the step lock (_snapshot_ts): both step
        # paths donate their input, so an external evaluate() racing the
        # training thread's next dispatch could otherwise read donated-dead
        # buffers ("Array has been deleted").
        ts = self._snapshot_ts()
        result = self._evaluate_params(ts.params)
        # The greedy-eval curve lands in the event log so learning progress
        # is auditable after the run (the reference's only observable is the
        # final avg, ShareTradeHelper.scala:46; this is the per-policy
        # learning signal it never records).
        self.events.emit("evaluation", updates=int(ts.updates), **result)
        if self.cfg.runtime.keep_best_eval:
            # Locked check-then-act: the training thread's periodic eval
            # (runtime.eval_every_updates) and a caller thread's explicit
            # evaluate() can race here, and an unguarded compare would let
            # a worse policy overwrite a better tag_best.
            with self._best_eval_lock:
                if self._best_eval is None:
                    prior = self.checkpoints.tagged_metadata("best")
                    self._best_eval = (float(prior["eval_portfolio"])
                                       if prior else float("-inf"))
                if result["eval_portfolio"] > self._best_eval:
                    self._best_eval = result["eval_portfolio"]
                    self.checkpoints.save_tagged(
                        "best", ts,
                        metadata={"eval_portfolio": result["eval_portfolio"],
                                  "updates": int(ts.updates)})
                    self.events.emit(
                        "best_eval_retained",
                        eval_portfolio=result["eval_portfolio"],
                        updates=int(ts.updates))
        return result

    def evaluate_best(self) -> dict[str, float]:
        """Greedy evaluation of the RETAINED best policy (the ``best``
        tagged checkpoint written by :meth:`evaluate` under
        ``runtime.keep_best_eval``) — what a user should ship when the live
        policy has collapsed past its discovery peak. Training state is
        untouched; raises FileNotFoundError when nothing was retained."""
        if self.agent is None or self._ts is None:
            raise RuntimeError("no training data / state")
        template = self.agent.init(jax.random.PRNGKey(self.cfg.seed))
        state, meta = self.checkpoints.restore_tagged(template, "best")
        result = self._evaluate_params(self._place(state).params)
        result["eval_updates"] = float(meta.get("updates", -1))
        return result

    def _evaluate_params(self, params) -> dict[str, float]:
        env = self.env
        horizon = env.num_steps
        # Evaluate in the precision the policy TRAINS in (the compute copy
        # of the fp32 masters — identity in fp32 mode): the shipped
        # numbers should describe the network as it actually runs, and a
        # master-dtype eval would retrace the cached program besides.
        params = self._precision.cast_compute(params)

        # The jitted eval program is cached on the orchestrator (jit caches
        # by function identity — a fresh lambda per call would retrace the
        # full-episode program on every evaluate(), tens of seconds at
        # larger models); send_training_data invalidates it. Both branches
        # are params -> (final_env_state, rewards) so params never freeze
        # into the cached closure.
        if self._eval_fn is None:
            # Evaluate the exact network that was trained (the agent carries
            # its model) — rebuilding from config here would silently
            # evaluate a different architecture whenever a custom model was
            # injected. Resolved only on a cache miss.
            model = self.agent.model
            if model is None:
                from sharetrade_tpu.models import build_model
                from sharetrade_tpu.agents import _HEADS  # registry heads
                model = build_model(self.cfg.model, self.env.obs_dim,
                                    head=_HEADS[self.cfg.learner.algo],
                                    num_actions=self.env.num_actions,
                                    num_assets=self.env.num_assets)
            from sharetrade_tpu.agents.rollout import (
                supports_precomputed_trunk)
            if supports_precomputed_trunk(model, env):
                # Precomputed-trunk greedy replay: the whole episode's
                # trunk is one banded pass (prices are action-independent),
                # vs horizon sequential one-token cache-attention steps —
                # the same inversion the training rollout uses
                # (agents/rollout.py).
                from sharetrade_tpu.agents.rollout import (
                    greedy_rollout_precomputed)
                self._eval_fn = jax.jit(
                    lambda p: greedy_rollout_precomputed(model, env, p))
            else:
                precision = self._precision

                def greedy_scan(p):
                    def body(carry, _):
                        state, model_carry = carry
                        obs = env.observe(state)
                        out, model_carry = model.apply(p, obs, model_carry)
                        action = jnp.argmax(out.logits).astype(jnp.int32)
                        new_state, reward = env.step(state, action)
                        return (new_state, model_carry), reward

                    # The carry seed follows the compute dtype (identity in
                    # fp32): a recurrent model fed bf16 weights writes a
                    # bf16 carry, and an f32 seed would flip the scan
                    # carry's dtype on the first iteration.
                    carry0 = precision.cast_carry(model.init_carry(), model)
                    (final, _), rewards = jax.lax.scan(
                        body, (env.reset(), carry0), None,
                        length=horizon)
                    return final, rewards

                self._eval_fn = jax.jit(greedy_scan)

        final, rewards = self._eval_fn(params)
        return {
            "eval_portfolio": float(env.portfolio_value(final)),
            "eval_reward_sum": float(jnp.sum(rewards)),
        }

    # ------------------------------------------------------------------

    def wait(self, timeout: float | None = None) -> bool:
        """Join the training thread (the driver's poll loop, minus polling)."""
        if self._thread is None:
            return True
        self._thread.join(timeout)
        return not self._thread.is_alive()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
        # Queued save_async writes must land before teardown: a stop right
        # after a cadence save would otherwise silently drop it (the writer
        # is a daemon thread — process exit kills it mid-write, and the
        # atomic protocol would roll that checkpoint back to nothing).
        self.checkpoints.wait_pending(timeout=60)
        if self._transitions_journal is not None:
            self._transitions_journal.close()
            self._transitions_journal = None
        # Telemetry teardown LAST: the final exporter drain and trace flush
        # see everything the run wrote, including its shutdown events.
        self.obs.close()

    def _snapshot_ts(self) -> TrainState:
        """Copy the live TrainState under the step lock. Both step paths
        DONATE their input, so any reader racing the training thread's
        next dispatch could observe freed buffers; while the lock is held
        no donating dispatch can be enqueued, and the copies own their
        buffers afterwards. Raises when the state is mid-recovery (a
        failed donated step left dead buffers behind) — the caller should
        retry after the supervision path restores."""
        with self._step_lock:
            if any(getattr(l, "is_deleted", lambda: False)()
                   for l in jax.tree.leaves(self._ts)):
                raise RuntimeError(
                    "training state is recovering from a failed step; "
                    "retry shortly")
            return jax.tree.map(
                lambda x: jnp.copy(x) if hasattr(x, "devices") else x,
                self._ts)

    @property
    def train_state(self) -> TrainState | None:
        """A SNAPSHOT of the live training state (safe against the donated
        step consuming the original buffers mid-read); None before data."""
        if self._ts is None:
            return None
        if self._thread is None or not self._thread.is_alive():
            return self._ts          # no concurrent dispatch: zero-copy
        return self._snapshot_ts()


def run_end_to_end(cfg: FrameworkConfig, prices, *, use_mesh: bool = False,
                   background: bool = False) -> Orchestrator:
    """The ShareTradeHelper main flow: data → orchestrator → train →
    aggregate (ShareTradeHelper.scala:14-48), in one call."""
    mesh = build_mesh(cfg.parallel) if use_mesh else None
    orch = Orchestrator(cfg, mesh=mesh)
    orch.start_training(background=True)   # stashed: data not sent yet
    orch.send_training_data(prices)        # unstashes and launches
    if not background:
        orch.wait()
    return orch
