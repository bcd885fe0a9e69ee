"""Configuration system.

The reference hard-codes every ML hyperparameter as Scala constants and keeps
infrastructure config in HOCON (`application.conf`); there are no CLI flags
(SURVEY.md §5 "Config / flag system"; reference QDecisionPolicyActor.scala:17-22,
ShareTradeHelper.scala:20-21, TrainerRouterActor.scala:36). This module replaces
both with one typed, file-loadable, CLI-overridable config tree.

Design: plain nested dataclasses; ``from_file`` reads JSON; ``apply_overrides``
accepts ``section.key=value`` strings (the CLI flag surface). No external deps.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field, fields
from typing import Any


class ConfigError(ValueError):
    """An invalid configuration: unknown keys/kinds, impossible
    compositions (e.g. pipeline_blocks + moe_experts), malformed
    overrides. The supervision decider maps THIS type — not every
    ValueError — to STOP (the reference's IllegalArgumentException→Stop,
    TrainerRouterActor.scala:53-58): a bad config can never heal by
    restarting, but a transient in-loop ValueError (JAX retrace/shape
    wobble after a checkpoint restore) deserves the restart path."""


@dataclass
class DataConfig:
    """L1 market-data layer (reference: SharePriceGetter.scala)."""

    csv_path: str | None = None        # price CSV ("price, date" rows); None -> synthetic
    # HTTP market-data endpoint serving the same CSV rows; "{symbol}" is
    # substituted (the reference FAKES this call, SharePriceGetter.scala:83
    # — here it's real). Takes precedence over csv_path.
    http_url: str | None = None
    synthetic_length: int = 6046       # matches the MSFT fixture's line count
    synthetic_seed: int = 1992
    journal_dir: str = "journal"       # event journal root (reference: LevelDB dir)
    use_native_journal: bool = True    # prefer the C++ journal if built
    # Drain hot-path journal appends (the per-chunk transition records of
    # learner.journal_replay) through the C++ background-thread writer so the
    # training loop never blocks on file IO. Durability window = the writer's
    # bounded queue; falls back to synchronous appends when the native
    # library isn't built.
    async_transition_writer: bool = True
    # Group-commit watermarks for the PYTHON transitions-journal backend
    # (data/journal.py): appends batch in memory and hit the disk — one
    # write + one fsync — when the batch reaches this many records, or on
    # the first append after this many seconds since the last commit
    # (watermarks are evaluated AT APPEND TIME; there is no background
    # timer, so a batch below both watermarks persists only at the next
    # append, a read, completion, or close. 0 disables that watermark;
    # both 0/1 = the legacy flush-per-append behavior).
    # Durability window = the unflushed batch; the CRC-framed torn-tail
    # recovery contract is unchanged (a crash between watermark commits
    # loses at most the batch, never the prefix). The C++ async writer
    # (async_transition_writer) batches in its own background thread and
    # ignores these knobs.
    journal_fsync_every_records: int = 64
    journal_fsync_interval_s: float = 0.5
    # Bounded journal: rotate the transitions journal into sealed segment
    # files once the ACTIVE segment holds this many records (checked at
    # watermark commit time — a sealed segment is fsynced before its
    # rename publishes it, so torn tails only ever live in the newest
    # segment; the CRC-framed recovery contract is per-segment). Retired
    # by compaction: segments wholly older than the replay-capacity
    # horizon (2x learner.replay_capacity rows of newer data) are deleted,
    # so multi-day journaled runs hold a bounded segment set instead of
    # rewriting one ever-growing file, and resume reads only the tail
    # segments. 0 (default) = single-file journal, the pre-segment
    # behavior (in-place compact_transitions rewrites). Rotation uses the
    # Python journal backend — the C++ async writer appends to one file
    # and is bypassed when this is set.
    journal_segment_records: int = 0
    # Streaming ingest (PriceDataService.tail): path of an append-only
    # "price, date" feed (a growing file or FIFO; "{symbol}" substituted)
    # that tail(symbol) consumes incrementally — the learner trains from a
    # stream it doesn't own, the seam actor/learner disaggregation cuts
    # at. None = tail() requires an explicitly attached feed.
    feed_path: str | None = None
    # Auto-compact the price-event journal once its REDUNDANCY — events
    # beyond the one snapshot per symbol a compaction would leave — exceeds
    # this count (events replayed at recovery included, so a bloated
    # journal shrinks on the first fetch after a restart; a service caching
    # more symbols than the threshold never thrashes) — the reference's
    # config-driven per-actor ``compaction-intervals``
    # (application.conf:7-14). 0 disables; explicit
    # ``PriceDataService.compact()`` always remains available.
    price_compact_every_events: int = 64


@dataclass
class EnvConfig:
    """L3 trading environment (reference: TrainerChildActor.scala:82-146)."""

    window: int = 201                  # price history per observation
    initial_budget: float = 2400.0     # reference ShareTradeHelper.scala:20
    initial_shares: int = 0            # reference ShareTradeHelper.scala:21


@dataclass
class ModelConfig:
    """Policy network (reference: QDecisionPolicyActor.scala:38-50)."""

    kind: str = "mlp"          # mlp | lstm | transformer | tcn | latent_moe
    hidden_dim: int = 200              # reference h1Dim (tcn: conv channels)
    num_actions: int = 3               # Buy / Sell / Hold
    # transformer-only:
    num_layers: int = 2
    num_heads: int = 4
    head_dim: int = 64
    seq_block: int = 128               # pallas attention block size
    dtype: str = "float32"             # compute dtype ("bfloat16" on TPU for speed)
    # "window" re-attends the full price window per env step (the reference's
    # 203-float observation kept as a sequence); "episode" embeds each tick
    # once and runs sliding-window flash attention over the episode's tick
    # stream with an incremental K/V-cache rollout — one O(T+window) replay
    # pass instead of T O(window) window forwards (transformer only;
    # models/transformer_episode.py).
    seq_mode: str = "window"
    # Attention partitioning: "flash" = local Pallas kernel per device;
    # "ring" = sequence-parallel attention over the mesh's sp axis — full
    # K/V rotation in window mode (parallel/ring_attention.py), a single
    # neighbor halo exchange in episode mode (parallel/episode_sp.py, the
    # band crosses at most one shard boundary); "ulysses" = all_to_all
    # head<->sequence re-partition running the full-sequence local kernel
    # per head group (window mode only; sp must divide num_heads). ring/
    # ulysses need a mesh with sp>1 — the long-context scale-out paths.
    attention: str = "flash"
    # Pipeline the transformer blocks over the mesh's pp axis (one block per
    # stage; requires num_layers == pp size and a mesh with pp>1).
    pipeline_blocks: bool = False
    # Mixture-of-experts FFN: >0 replaces each transformer block's dense MLP
    # with a routed expert bank (sharded over the mesh's ep axis when one
    # exists, single-device otherwise). The gate trains through the task
    # loss via its routing weight.
    moe_experts: int = 0
    # moe_top_k=0 keeps the exact dense-mask top-1 scheme (every expert runs
    # every token — O(E·N), no drops). >0 switches to capacity-bucketed
    # top-k dispatch (GShard-style): each expert evaluates only its routed
    # buffer, picks past ``moe_capacity_factor`` headroom are dropped.
    moe_top_k: int = 0
    moe_capacity_factor: float = 1.25
    # How top-k expert traffic moves over the ep mesh axis: "psum" routes
    # replicated tokens and psums the partial outputs (every device sees the
    # global batch); "a2a" shards the tokens over ep and moves only the
    # dispatched capacity buffers through two all_to_alls — the GShard
    # pattern whose communication volume is independent of E and never
    # materializes the global batch on one device. Requires moe_top_k>0 and
    # a mesh with an ep axis.
    moe_dispatch: str = "psum"
    # Episode-mode block-granular rematerialization: the replay backward
    # recomputes each transformer block's internals from its input instead
    # of storing them — O(L·S·d) residuals drop to the block boundaries,
    # the HBM lever for the d>=1024 tier's long replays. Finer than
    # learner.remat (which checkpoints the whole replay pass); composes
    # with it, and with pipeline_blocks (each stage then stores only its
    # schedule-tick boundary states).
    remat_blocks: bool = False
    # latent_moe-only (models/latent_moe_episode.py; SERVE-ONLY, episode
    # mode): a latent-attention, routed-expert trunk on several residual
    # streams. Width = hidden_dim, depth = num_layers (the first
    # ``dense_layers`` with a dense SwiGLU of ``dense_ffn_dim``, the rest
    # with ``moe_experts`` sigmoid-routed experts of ``moe_ffn_dim``,
    # ``moe_top_k`` a token, plus ``moe_shared_experts`` shared ones),
    # ``num_heads`` heads. ``moe_held_experts`` (0 = all) from
    # ``moe_held_first`` on are the experts THIS chip holds of an
    # expert-parallel deployment: the router keeps all its outputs, and
    # picks on experts held elsewhere add nothing here.
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    dense_layers: int = 1
    dense_ffn_dim: int = 9216
    moe_ffn_dim: int = 1024
    moe_held_experts: int = 0
    moe_held_first: int = 0
    moe_shared_experts: int = 1
    moe_routed_scale: float = 2.0
    # Hyper-connections: residual streams, Sinkhorn rounds (rows, then
    # columns), the eps added to each sum, the clamp on H_res's logits.
    hc_streams: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    rms_norm_eps: float = 1e-6
    # RoPE of the decoupled key, YaRN-blended frequencies.
    rope_theta: float = 10000.0
    rope_yarn_factor: float = 64.0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_original: int = 4096


@dataclass
class LearnerConfig:
    """Q-learning hyperparameters (reference: QDecisionPolicyActor.scala:17-22)."""

    algo: str = "qlearn"               # qlearn | pg | dqn | a2c | ppo
    epsilon: float = 0.9
    epsilon_ramp_steps: int = 1000     # exploit prob = min(epsilon, step/ramp)
    gamma: float = 0.001
    learning_rate: float = 0.01
    optimizer: str = "adagrad"
    # Fidelity switch: the reference updates the Q-value at the *next* state's
    # argmax index (a bug; its rl.py ancestor uses the taken action). True =
    # correct semantics (update taken action); False = bug-parity mode for tests.
    update_taken_action: bool = True
    # DQN/replay:
    replay_capacity: int = 65536
    replay_batch: int = 256
    target_update_every: int = 500
    # Journal every chunk's transitions to a durable event log and rebuild
    # the replay buffer from it on resume (the reference's event-sourced
    # persistence generalized to experience data, SURVEY.md §7.4).
    journal_replay: bool = False
    # Replay sampling discipline (DQN): "uniform" (default) keeps the
    # pre-PER sampler — BIT-IDENTICAL to the pre-data-plane code, pinned
    # by the golden trajectory in tests/golden/replay_uniform_golden.json
    # (the same exactness contract as precision.mode="fp32"). "per" turns
    # on prioritized replay (Schaul et al., arxiv 1511.05952): a
    # fixed-shape sum-tree (ops/sum_tree.py) lives in the DQN extras next
    # to the circular replay arrays, so priority update -> stratified
    # sample -> TD-error write-back all run INSIDE the jitted (mega)chunk
    # — no host round-trip, no new host syncs (lint_hot_loop check 9).
    # New transitions enter at the running max priority; sampled
    # transitions re-prioritize to (|td_error| + per_eps)^per_alpha; the
    # TD loss folds in importance-sampling weights (N*P(i))^-beta with
    # beta annealed from per_beta0 to 1 over per_beta_steps env steps.
    replay_priority: str = "uniform"   # "uniform" | "per"
    per_alpha: float = 0.6
    per_beta0: float = 0.4
    per_beta_steps: int = 100_000
    per_eps: float = 1e-3
    # Weight on the model's auxiliary loss (ModelOut.aux — the MoE balance
    # regularizer); inert (aux = 0) for dense models.
    aux_loss_coef: float = 0.01
    # Normalize advantages to zero mean / unit variance over the unroll's
    # active steps before the policy-gradient term (PG and A2C; PPO always
    # normalizes per minibatch, its standard form). Off by default — raw
    # advantages are the textbook PG/A2C estimators and the parity-test
    # numerics — but strongly recommended for training stability: the raw
    # advantage scale tracks the portfolio's reward scale, which wanders
    # over decades of prices.
    normalize_advantages: bool = False
    # PPO/A2C:
    entropy_coef: float = 0.01
    value_coef: float = 0.5
    clip_eps: float = 0.2
    gae_lambda: float = 0.95
    ppo_epochs: int = 4
    ppo_minibatches: int = 4
    unroll_len: int = 128
    # Rematerialize the loss replay forward (jax.checkpoint): trades ~1 extra
    # forward per backward for O(T) instead of O(T x activations) residual
    # memory — required for large agent batches on big models.
    remat: bool = False


@dataclass
class ParallelConfig:
    """Device-mesh layout (replaces the Akka Router/mailbox fan-out, SURVEY §2.2)."""

    num_workers: int = 10              # reference noOfChildren (TrainerRouterActor.scala:36)
    data_axis: str = "dp"
    model_axis: str = "tp"
    seq_axis: str = "sp"
    pipeline_axis: str = "pp"
    expert_axis: str = "ep"
    mesh_shape: dict[str, int] = field(default_factory=dict)  # {} -> all devices on dp
    # Re-pin the step's output TrainState to its canonical shardings inside
    # the compiled program (jax.lax.with_sharding_constraint at the chunk /
    # inner-megachunk seams). Keeps GSPMD from re-deriving a transposed-mesh
    # layout for the carry around the sp/pp/ep shard_map regions — the
    # "Involuntary full rematerialization" replicate-and-repartition the
    # shard audit (tools/shard_audit.py) gates on. Off exists ONLY as the
    # with/without comparison's other arm (ROADMAP D4: no cell on either
    # side); leave it on in production.
    shard_constraints: bool = True


@dataclass
class PrecisionConfig:
    """Numeric precision policy (precision.py) — the ROADMAP item-4
    low-precision lever, done the convergence-safe way.

    ``mode`` selects the compute tier:

    - ``"fp32"`` (default): everything float32 — BIT-IDENTICAL to the
      pre-policy behavior (the policy helpers are structural identities,
      pinned by tests/test_precision.py's golden trajectory).
    - ``"bf16_mixed"``: fp32 MASTER weights live in the TrainState (and in
      every checkpoint); each update boundary inside the jitted (mega)chunk
      casts one bf16 compute copy, every model forward/backward runs bf16
      with f32 matmul accumulation (``preferred_element_type`` — the
      ops/attention.py convention, now framework-wide), gradients upcast to
      f32, and the optimizer update applies in f32. Halves the
      activation/weight HBM traffic of the hot loop (the roofline
      telemetry's measured memory-bound axis) without the silently-bf16
      optimizer state the old whole-model ``model.dtype`` cast produced.

    The old ``model.dtype="bfloat16"`` knob is DEPRECATED with a loud
    migration error (models/__init__.py): it cast params, grads, and
    optimizer accumulators wholesale — the convergence-hostile
    configuration this policy exists to replace.

    fp8 forward-compatibility: the compute tier is a single dtype seam
    (``PrecisionPolicy.compute_dtype``) and every matmul already pins f32
    accumulation, so an ``"fp8_mixed"`` mode slots in here when a backend
    supports it — no new mechanism needed."""

    mode: str = "fp32"                 # "fp32" | "bf16_mixed"
    # Fused optimizer update (ops/fused_update.py): grad-upcast + moment
    # update + param update in ONE pass per parameter leaf (one XLA loop
    # fusion over the leaf as stored, on every backend) instead of the
    # O(params) intermediate buffers optax's update/apply_updates pair
    # materializes. "auto" = on for bf16_mixed, off for fp32 (keeping the
    # default mode's update path literally the pre-policy optax calls);
    # "on"/"off" force it. fp32-exact vs optax is pinned by
    # tests/test_precision.py regardless of mode.
    fused_update: str = "auto"         # "auto" | "on" | "off"


@dataclass
class CheckpointConfig:
    """Durability contract of the checkpoint store (checkpoint/manager.py)."""

    # fsync payload files and their directories around the atomic rename, so
    # a checkpoint that LOOKS complete after a power loss IS complete (the
    # same durability contract the framed journal honors). ``os.replace``
    # alone only orders the rename against other renames — without the
    # fsyncs, a crash can surface a fully-named checkpoint directory whose
    # data blocks never reached the disk. Default on; the cost is paid on
    # the async writer thread, not the training loop (not measured on the
    # chip: the cells save nothing, ROADMAP C5). Off exists for throwaway
    # runs on ephemeral storage.
    fsync: bool = True


@dataclass
class RuntimeConfig:
    """Orchestration / fault tolerance (reference: TrainerRouterActor.scala:46-58)."""

    chunk_steps: int = 200             # device steps per host visit (progress cadence;
                                       # reference logs every 200 fold steps)
    episodes: int = 1                  # replays of the price history (reference: 1;
                                       # Initialise re-arms for more, TrainerChildActor.scala:57-59)
    checkpoint_every_updates: int = 500  # reference cadence (stubbed there, real here)
    checkpoint_dir: str = "checkpoints"
    keep_checkpoints: int = 3
    backoff_initial_s: float = 3.0     # reference Backoff.onFailure 3s
    backoff_max_s: float = 60.0        # reference max 1 min
    backoff_jitter: float = 0.2        # reference randomFactor
    max_restarts: int = 10
    poll_interval_s: float = 0.05
    profile_dir: str | None = None     # jax.profiler trace output
    # GetAvg/GetStd reply semantics: False = progressive stats over ALL
    # agents (richer than the reference); True = the reference's exact
    # observable — average only workers whose episode finished, NotComputed
    # until at least one has (TrainerRouterActor.scala:84-95,137-139).
    query_trained_only: bool = False
    # Per-agent fault recovery (the reference heals ONE dead child while the
    # other nine keep training, TrainerRouterActor.scala:141-146): learners
    # quarantine non-finite agent rows on-device so poison never reaches the
    # shared parameters, and the orchestrator respawns just those rows
    # (fresh env cursor + carry) between chunks — survivors lose nothing.
    # Whole-state checkpoint restore remains the fallback for faults the
    # row-respawn can't fix (poisoned params, device errors, episode-mode
    # transformers whose K/V carry requires a lockstep batch).
    partial_recovery: bool = True
    # Row-respawn budget: past this many heals the fault is treated as
    # systemic and escalates to the full restart path (whose max_restarts
    # budget then bounds availability) — a recurring per-row fault must not
    # heal->re-poison->heal forever.
    max_agent_heals: int = 10
    # Metrics/fault sampling cadence: materialize chunk metrics on the host
    # every this many chunks (1 = every chunk). Each materialization is a
    # device round-trip that serializes the dispatch pipeline (per-dispatch
    # host cost, not yet measured on an attached chip); between samples,
    # chunks dispatch back-to-back. Consequences, all
    # bounded by this knob: fault DETECTION latency (non-finite rows /
    # loss) is at most metrics_every_chunks chunks — the on-device
    # quarantine still fences poison from the shared params every chunk,
    # so only healing is delayed, not containment; GetAvg/GetStd snapshots
    # can be up to this many chunks stale; eval/checkpoint cadences
    # quantize to sampled chunks. Completion is NEVER missed: the loop
    # tracks a host-side upper bound on env_steps and samples every chunk
    # once it nears the episode threshold. Chunks that emit replay
    # transitions (DQN journaling) and runs with a fault_hook installed
    # sample every chunk regardless (durability / test-seam semantics).
    metrics_every_chunks: int = 10
    # Device-resident megachunks: fuse this many consecutive chunks into ONE
    # jitted program (a lax.scan over the agent step), so the host pays one
    # dispatch per K chunks instead of K — the lever against the
    # per-dispatch host cost (not yet measured on an attached chip).
    # Per-chunk metrics stack
    # into a (K, ...) device buffer read back with a single batched
    # device_get at megachunk boundaries, so sampled metric streams stay
    # per-chunk (bit-identical to K=1 — the parity contract,
    # tests/test_megachunk.py). Supervision semantics are preserved at
    # megachunk granularity: fault_hook fires per inner chunk with its true
    # chunk index after readback; health checks / eval / checkpoint cadence
    # evaluate on the boundary; near the episode threshold the loop falls
    # back to K=1 dispatches so the exact-completion gate never overshoots.
    # 1 (default) = today's per-chunk host loop. Must be >= 1 (validated at
    # orchestrator construction, alongside metrics_every_chunks: sampling
    # finer than a megachunk is delivered late-but-complete via the stacked
    # rows, and a cadence that is not a multiple of K rounds up to the next
    # megachunk boundary).
    megachunk_factor: int = 1
    # Double-buffered dispatch: issue megachunk k+1 BEFORE blocking on
    # megachunk k's metric readback, so the host-side D2H transfer overlaps
    # device compute (the async-checkpoint overlap pattern applied to the
    # metrics path). Only engages in the cruise regime — when the env-step
    # upper bound after one more megachunk stays strictly below the episode
    # threshold and no replay transitions are being journaled — so the
    # completion gate and journal durability never race an in-flight
    # program. Fault detection and checkpoint step labels may lag by one
    # in-flight megachunk. Inert at megachunk_factor=1 on the single-chunk
    # exact path near episode ends.
    double_buffer_dispatch: bool = False
    # Async readback & host-offload pipeline (the host-side half of the
    # dispatch-floor work): the orchestrator's dispatch loop issues
    # megachunks back-to-back and hands each materialization boundary's
    # device buffers (stacked (K, ...) metrics + DQN transitions) to a
    # background consumer thread via a bounded queue. Readback starts with
    # a non-blocking copy_to_host_async (device_get on the consumer thread
    # as fallback) and the consumer performs the ENTIRE host-processing
    # block — metric rows, flight recorder, journaling, fault hooks,
    # snapshot updates — strictly in chunk order, so the inter-megachunk
    # dispatch gap no longer includes host time (on the chip:
    # ``train.host_busy_share`` and ``train.pipeline_stall_share``,
    # PERF.md §5). Semantics preserved exactly: backpressure
    # when the queue is full (HBM held by in-flight buffers stays bounded),
    # a drain barrier before the exact-completion K=1 fallback,
    # get_avg/get_std snapshots and checkpoint/eval cadence decisions, and
    # supervision parity — a consumer-raised fault is attributed to its
    # true chunk index and propagates to the dispatcher before the next
    # megachunk commits state (restart/backoff/heal behavior unchanged,
    # tests/test_async_pipeline.py). Forced off under the step_override
    # test seam (lockstep semantics); turn off to recover the pre-pipeline
    # synchronous loop byte-for-byte.
    async_pipeline: bool = True
    # Bounded queue depth of the async pipeline: how many materialization
    # boundaries may be in flight between dispatcher and consumer before
    # dispatch blocks (the pipeline_stall span/counter). Each in-flight
    # boundary pins one megachunk's metric buffers (+ transition batch when
    # journaling) in device memory, so the knob is also the HBM bound for
    # readback buffers. Must be >= 1 (validated at construction).
    pipeline_depth: int = 2
    # Periodic greedy evaluation DURING training: every this many updates
    # the orchestrator runs evaluate() between chunks (one argmax episode
    # replay; the jitted program is cached), feeding the event-log learning
    # curve and the best-eval retention below without the caller having to
    # evaluate manually. 0 (default) = only explicit evaluate() calls.
    eval_every_updates: int = 0
    # Preemption grace budget (seconds): when the CLI's SIGTERM/SIGINT
    # handler requests preemption, the orchestrator drains the async
    # pipeline at the next megachunk boundary, writes the ``tag_preempt``
    # emergency checkpoint with full resume metadata, flushes the journal
    # batch and dumps the flight recorder — all inside this budget; the CLI
    # hard-exits with the preemption code once it expires (a fleet
    # scheduler's kill follows the TERM after its own grace, so an
    # over-budget drain must not block the inevitable). A later ``--resume``
    # prefers ``tag_preempt`` when it is newer than the latest step
    # checkpoint.
    preempt_grace_s: float = 30.0
    # Retain the best-greedy-eval policy as a tagged checkpoint
    # (<checkpoint_dir>/tag_best) every time evaluate() improves on the
    # best seen: on-policy training can discover a strategy and then
    # collapse (entropy -> all-Hold), so without this the final checkpoint
    # a user ships can be the collapsed one. Evaluate the retained policy
    # with Orchestrator.evaluate_best() / ``cli train --eval-best``.
    keep_best_eval: bool = True


@dataclass
class ServeConfig:
    """Continuous-batching inference tier (serve/engine.py) — ROADMAP
    item 2's low-latency policy-inference service, decoupled from
    training.

    The engine coalesces per-user ``(window, portfolio)`` queries into
    padded device batches under a deadline and keeps a fixed-capacity
    device-resident SESSION SLOT POOL — a ``(slots, ...)`` arena of
    per-session recurrent carries (the episode transformer's incremental
    K/V cache repurposed as a per-session serving cache) with LRU
    admission/eviction and batched re-prefill for cold sessions — so
    steady-state serving is ONE jitted batched program per tick instead
    of a dispatch per request (the TF-Agents batched-simulation thesis,
    arxiv 1709.02878, applied to inference)."""

    # Padded device batch per serving tick: the ONE compiled program's
    # batch dimension. Larger amortizes dispatch over more requests;
    # latency under light load is bounded by batch_timeout_ms, not this.
    max_batch: int = 64
    # Deadline to coalesce a partial batch (milliseconds): the dispatcher
    # sends whatever arrived once the FIRST request of a batch has waited
    # this long (work-conserving — a full batch never waits). 0 = dispatch
    # immediately with whatever is queued.
    batch_timeout_ms: float = 2.0
    # Session slot-pool capacity: how many sessions keep their device-
    # resident carry (K/V cache) between requests. Must be >= max_batch
    # (a batch's sessions all need live slots). An evicted session that
    # returns is COLD: it re-enters through the batched prefill and its
    # episode restarts from its request's window (README "Serving tier"
    # slot-pool contract).
    slots: int = 256
    # Hot weight swap: poll the training run's tagged checkpoint at this
    # cadence and swap serving params atomically between batches when it
    # advances; restores go through the PR-5 verified path (checksums +
    # finite check + precision-mode check) and a corrupt candidate is
    # refused without interrupting serving. 0 disables the watcher.
    swap_poll_s: float = 5.0
    swap_tag: str = "best"
    # SLO gauge publication cadence (serve_qps / serve_p50_ms /
    # serve_p99_ms / serve_batch_occupancy / serve_queue_depth through
    # MetricsRegistry -> metrics.prom).
    stats_interval_s: float = 1.0
    # --- Overload & failure semantics (README "Serving tier") ---------
    # Admission control: the ingress queue holds at most this many
    # requests. A submit past the bound is never silently absorbed into
    # host memory: under shed_policy="reject" the NEW request is refused
    # (its handle completes immediately with a ServeRejected error);
    # under "oldest" the OLDEST queued request is shed instead and the
    # new one admitted (brownout: bounded queueing delay, finite p99,
    # at the cost of failing stale work first). Must be >= 1: an
    # unbounded ingress queue turns a request flood into unbounded host
    # memory growth
    # (tools/lint_hot_loop.py check 10 guards the code side).
    max_queue: int = 1024
    shed_policy: str = "reject"          # "reject" | "oldest"
    # Default per-request deadline (milliseconds), overridable per
    # submit(..., deadline_ms=). 0 = no deadline. An expired request is
    # completed with a ServeDeadlineExceeded error BEFORE batch
    # collection, so dead work never occupies a padded device row; the
    # batch-coalescing deadline is anchored to the earliest surviving
    # request's deadline so admission never expires a request it could
    # have served.
    default_deadline_ms: float = 0.0
    # Dispatch supervision: after a dispatch/consumer fault fails its
    # batch, retry the ENGINE — rebuild the jitted programs and a fresh
    # slot arena (every session re-enters cold through the batched
    # prefill, which is bitwise-equivalent to a fresh session suffix)
    # under seeded exponential backoff. 0 = PR-8 behavior: fail the
    # batch, keep the arena, never rebuild (a per-request fault like a
    # malformed observation then costs one batch, not every warm
    # session's carry). More than max_restarts CONSECUTIVE faults
    # (the streak resets on a completed batch) trip the engine into a
    # terminal failed state that fails all queued work loudly instead
    # of wedging.
    max_restarts: int = 0
    restart_backoff_s: float = 0.05      # initial; doubles per attempt
    restart_backoff_max_s: float = 2.0   # backoff ceiling
    # --- Warm session tier (ISSUE 18: tiered session paging) ---------
    # Host-RAM byte budget for PARKED session carries (the warm tier of
    # the hot/warm/cold hierarchy). An evicted session's device carry is
    # gathered on the dispatch thread (async device op), read back on
    # the CONSUMER thread (page-out never blocks dispatch), and held in
    # a bounded LRU keyed by session id; when the session returns, the
    # parked carry is reinstalled through the batched scatter path and
    # the session continues BITWISE-identically to one that was never
    # evicted. Past the budget (or warm_max_sessions) the stalest parked
    # carry demotes to COLD — the session journal / re-prefill path, the
    # pre-existing contract. 0 (default) disables the tier entirely:
    # every eviction is a cold restart, the PR-8 bitwise fresh-session
    # contract unchanged.
    warm_bytes: int = 0
    # Session-count bound on the warm tier (belt to the byte budget's
    # suspenders; both are enforced — lint check 17 requires the tier
    # to be bounded in code).
    warm_max_sessions: int = 4096
    # --- Disk spill tier (ISSUE 20: sessions survive their engine) ---
    # Directory of the crash-consistent parked-carry arena
    # (serve/spill.py): carries demoted past the warm-RAM budget — and
    # every live/parked carry at drain — are sealed to per-session
    # records here (CRC + step stamp + atomic rename), so a carry
    # survives its writer's SIGKILL and a DIFFERENT engine sharing the
    # directory can adopt it warm. fleet/pool.py points every worker of
    # a fleet at <pool.dir>/spill; a standalone engine may set it
    # directly. Empty (default) disables the tier: past warm_bytes a
    # session demotes straight to cold, the ISSUE-18 contract unchanged.
    spill_dir: str = ""
    # Byte budget for THIS engine's view of the arena (puts past the
    # budget are refused and the session stays cold — bounded like
    # warm_bytes; the tier is never an unbounded disk leak). 0 with a
    # spill_dir set means "adopt-only": the engine reads records peers
    # wrote but never spills its own.
    spill_bytes: int = 0
    # Hot-swap circuit breaker: this many CONSECUTIVE verified-restore
    # failures (distinct corrupt/mismatched candidates) stop the watcher
    # from polling the wedged tag for swap_breaker_cooldown_s (exported
    # as the serve_swap_breaker_open gauge); after the cooldown one
    # probe poll runs — success closes the breaker, failure re-opens
    # it. 0 disables the breaker (every fresh candidate is verified).
    swap_breaker_failures: int = 3
    swap_breaker_cooldown_s: float = 30.0


@dataclass
class DistribConfig:
    """Disaggregated actor/learner topology (distrib/) — the reference's
    ten-worker/one-learner actor system (TrainerRouterActor.scala:36) run
    as separate OS-process FAILURE DOMAINS: an :class:`ActorPool`
    supervisor (distrib/pool.py) spawns ``num_actors`` rollout-actor
    subprocesses (``cli actor``), each of which restores weights from the
    training run's ``tag_best`` through the verified-restore path
    (serve/swap.py semantics: checksums + finite + precision-mode check,
    refusal-not-fatal), rolls out episodes, and appends transitions to its
    OWN journal/feed (one writer per journal — the data plane's
    concurrent-writer lock makes sharing one impossible by construction),
    while the learner process tails all actor feeds between megachunks
    (runtime/orchestrator.py ``ingest_actor_feeds``), splices the rows
    into its device replay buffer (PER priorities reseeded the
    ``_warm_start_replay`` way), trains, and republishes ``tag_best`` —
    closing the loop without the learner ever restarting when an actor
    dies (MSRL's per-fragment restart property, arxiv 2210.00882;
    Podracer's Sebulba split, arxiv 2104.06272)."""

    # Rollout-actor subprocesses the pool supervises. 0 (default) =
    # disaggregation off: nothing spawns, the learner ingests nothing,
    # single-process behavior is untouched.
    num_actors: int = 0
    # Root directory for per-actor state: ``<actor_dir>/<actor_id>/``
    # holds each actor's transitions journal + heartbeat file; the pool's
    # ``status.json`` (membership/counters, atomically rewritten) and the
    # ``scale`` control file live at the root.
    actor_dir: str = "actors"
    # Supervision contract at PROCESS granularity (the PR-5/PR-10
    # contract): a crashed actor respawns under seeded exponential
    # backoff; more than this many CONSECUTIVE crashes (the streak resets
    # once a respawned actor proves healthy by advancing its heartbeat)
    # marks the actor TERMINALLY FAILED and the pool degrades gracefully
    # onto the survivors (gauges actors_alive / actors_failed, counter
    # actor_restarts_total).
    max_actor_restarts: int = 5
    actor_backoff_initial_s: float = 0.5
    actor_backoff_max_s: float = 10.0
    actor_backoff_jitter: float = 0.2   # seeded from the run's seed
    # Actor heartbeat cadence (each actor rewrites its heartbeat stamp at
    # least this often while rolling out) and the pool-side staleness
    # bound: an actor whose heartbeat is older than ``heartbeat_timeout_s``
    # is presumed wedged and killed (counts as a crash -> restart path).
    # timeout 0 = observe-only (ages are still exported).
    heartbeat_interval_s: float = 1.0
    heartbeat_timeout_s: float = 0.0
    # Pool supervise/reap cadence (seconds between membership scans).
    supervise_interval_s: float = 0.25
    # Learner-side feed ingest cadence: every this many updates the
    # orchestrator tails every actor journal for rows newer than its
    # per-actor cursor and splices them into the live replay buffer
    # (requires learner.algo="dqn"; PER priorities reseed at the stored
    # max). 0 disables ingest (the pool can still run for rollout-only
    # workloads).
    ingest_every_updates: int = 8
    # Per-ingest row bound per actor journal (0 = learner.replay_capacity).
    ingest_max_rows: int = 0
    # Actor-side weight refresh: poll ``tag_best`` at this cadence and
    # hot-swap the rollout policy through the verified-restore watcher
    # (serve/swap.py). 0 = boot weights only.
    weight_poll_s: float = 2.0
    # Device steps per actor rollout chunk (0 = runtime.chunk_steps).
    actor_chunk_steps: int = 0
    # Run the learner-side feed ingest WITHOUT an ActorPool: the fleet
    # flywheel's learner (``cli fleet --learner``) tails journals that
    # SERVED SESSIONS write under ``actor_dir`` (fleet/flywheel.py) —
    # same format, same per-writer cursors, no subprocesses to
    # supervise. Off by default so plain ``cli train`` runs never pay a
    # pipeline-drain boundary just to glob an empty actors dir (the
    # num_actors > 0 gate this flag bypasses).
    ingest_without_pool: bool = False


@dataclass
class FleetConfig:
    """Horizontal serving fleet (fleet/) — ROADMAP item 2's scale-out
    tier: ``num_engines`` whole serve-engine WORKER PROCESSES
    (``cli serve --listen``, each one PR-10 overload-safe engine behind
    its own stdlib HTTP front-end) supervised by an :class:`~sharetrade_
    tpu.fleet.pool.EnginePool` (the distrib/ladder.py supervision
    contract at engine granularity), behind ONE telemetry-driven router
    (fleet/router.py) that balances on the signals every engine already
    exports — ``serve_overload``, queue depth, windowed p99 from
    bucket-wise-merged histograms — with session affinity and
    cold-restart-through-prefill as the migration story when an engine
    drains, dies, or deploys."""

    # Engine worker processes behind the router. The router degrades
    # gracefully onto survivors as engines fail; ALL engines terminally
    # failed = the router answers 503 loudly instead of wedging.
    num_engines: int = 2
    # Router bind address. Port 0 = ephemeral (the chosen port is printed
    # in the machine-readable ``fleet_ready`` line). Engines always bind
    # ephemeral ports on host; the pool discovers them from each worker's
    # ``engine_listening`` ready line.
    host: str = "127.0.0.1"
    port: int = 0
    # Fleet state root: per-engine logs + worker config, the atomically
    # rewritten ``fleet_status.json`` (what ``cli obs`` summarizes), and
    # the journals served sessions write when the flywheel is on.
    dir: str = "fleet"
    # Pin each engine worker to a dedicated CPU slice of this many cores
    # (``sched_setaffinity``, inherited by the worker's XLA threads) — the
    # one-host stand-in for one-engine-per-machine, and what makes the
    # scale-out bench honest (without it every engine contends for every
    # core and N engines measure scheduler noise). 0 = no pinning.
    engine_cpus: int = 0
    # Router telemetry cadence: scrape every engine's /healthz +
    # /metrics, merge the ``serve_request_ms`` bucket expositions
    # bucket-wise (EXACT — obs/hist.py), publish fleet p50/p99 + SLO
    # burn, refresh routing scores, rewrite fleet_status.json.
    telemetry_poll_s: float = 0.5
    # Session-affinity table bound (LRU): a session sticks to the engine
    # holding its slot-pool carry; past the bound the stalest mapping is
    # forgotten (that session re-routes — and re-prefills — like any
    # migrated one).
    affinity_max_sessions: int = 65536
    # Engine-process supervision ladder (shared with distrib actors —
    # distrib/ladder.py): consecutive-crash streak past
    # ``max_engine_restarts`` = terminal FAILED, degrade onto survivors.
    max_engine_restarts: int = 5
    engine_backoff_initial_s: float = 0.5
    engine_backoff_max_s: float = 10.0
    engine_backoff_jitter: float = 0.2
    supervise_interval_s: float = 0.25
    # Bring-up budget: a worker that has not printed its
    # ``engine_listening`` line within this window is presumed wedged
    # during startup and killed (counts as a crash → ladder).
    startup_timeout_s: float = 120.0
    # Health heartbeat: a LISTENING engine whose /healthz has not
    # answered for this long is presumed wedged and killed (crash →
    # ladder). 0 = observe-only (ages still exported).
    health_timeout_s: float = 10.0
    # Per-scrape HTTP timeout for healthz/metrics polls.
    scrape_timeout_s: float = 2.0
    # Front-end wait bound for requests WITHOUT a deadline (a deadline'd
    # request waits its own deadline plus slack). Bounds a handler
    # thread's life, never the engine's queueing semantics.
    request_timeout_s: float = 30.0
    # Drain budget on SIGTERM: in-flight requests finish, engines drain
    # (their own SIGTERM → 75 contract), stragglers are killed past it.
    drain_grace_s: float = 15.0
    # --- Fleet autoscaler (ISSUE 18: fleet/autoscale.py) -------------
    # Close the telemetry loop into fleet MEMBERSHIP: a controller
    # thread reads the router's per-poll gauge history ring
    # (obs/tsdb.py, the PR-17 ``fleet_history.jsonl``) and drives
    # ``EnginePool.scale()`` from sustained ``fleet_slo_availability_
    # burn`` / ``fleet_overload`` / per-engine queue depth — the PR-14
    # serve-controller discipline verbatim: dead band between the up/
    # down thresholds, a LONGER quiet window before scaling down than
    # up (hysteresis), at most ONE engine per decision (bounded steps),
    # one decision per cooldown, and the CONFIG as the ceiling (the
    # autoscaler may never exceed max_engines nor drop below
    # min_engines). Off by default — membership changes are an operator
    # decision until explicitly delegated.
    autoscale: bool = False
    # Membership bounds the autoscaler must respect. max_engines 0 =
    # num_engines (no headroom: the autoscaler can only shed).
    min_engines: int = 1
    max_engines: int = 0
    # Decision cadence (seconds between history reads) and cooldown
    # (minimum seconds between two APPLIED scalings — the rate limit).
    autoscale_interval_s: float = 1.0
    autoscale_cooldown_s: float = 5.0
    # Scale-up triggers, each averaged over the last autoscale_window
    # history rows: availability burn >= burn_high (1.0 = spending the
    # full error budget), or per-engine queue depth >= queue_high, or
    # overload on at least half the window's rows. Scale-down requires
    # a 2x-longer window with burn < burn_low AND queue < queue_low AND
    # zero overload throughout — the dead band is everything between.
    autoscale_window: int = 5
    autoscale_burn_high: float = 1.0
    autoscale_burn_low: float = 0.25
    autoscale_queue_high: float = 8.0
    autoscale_queue_low: float = 1.0
    # Wire data path for every front-end in the fleet (the router's
    # public port and each engine worker's listener). "evloop" (default)
    # = the sans-IO selector event loop (fleet/evloop.py): one thread,
    # no thread per connection or in-flight request — the path that
    # scales past the thread-per-request GIL convoy. "threaded" = the
    # stdlib ThreadingHTTPServer path, retained as the differential-
    # testing oracle (identical wire contract, byte-identical replies).
    wire_backend: str = "evloop"
    # HTTP parse/render implementation behind fleet/proto.py — the
    # third rung of the wire ladder (ROADMAP item 2). "native"
    # (default) = the C extension native/stwire.so (built by `make -C
    # native`), which frames bytes with the GIL RELEASED; when the
    # extension is missing or fails to load this degrades to the
    # Python parser with one loud log line (a mode, not an error).
    # "py" = the pure-Python state machines, retained as the
    # differential oracle. Identical event semantics either way —
    # tests/test_fleet_wire.py replays seeded corpora through both.
    proto_backend: str = "native"


@dataclass
class TuningConfig:
    """Self-tuning runtime (tuning.py, serve/controller.py,
    tools/autotune.py) — the layer that closes the telemetry loop into
    the performance knobs (ROADMAP item 5).

    Two tiers:

    - **Offline profile** (``profile``): path of a ``tuned_profile.json``
      written by ``tools/autotune.py`` (``make autotune``). Registered
      knobs (tuning.py ``KNOBS``) still at their dataclass defaults take
      the profile's per-host values; anything the operator set explicitly
      wins over the profile, the profile wins over defaults, and the
      resolution is stamped into the run manifest. A profile whose host
      fingerprint (cores/backend/device count) mismatches this host is
      refused LOUDLY unless ``allow_fingerprint_mismatch``.
    - **Online serve controller** (``serve_controller``): a feedback loop
      (serve/controller.py) on the engine's own windowed latency
      histogram and overload gauges that adapts ``serve.batch_timeout_ms``
      and ``serve.max_queue`` — bounded, hysteresis-guarded, rate-limited
      steps, never ABOVE the configured values (config is the safety
      ceiling) — to hold ``target_p99_ms`` under the measured arrival
      rate. Plus the learner-side ``adaptive_ingest``: the orchestrator
      backs off ``distrib.ingest_every_updates`` while the actor feeds
      are dry and tightens it (down to the configured cadence and below,
      bounded) when a tick reads a full backlog window.
    """

    # Path of the per-host tuned_profile.json; None = no profile.
    profile: str | None = None
    # Apply a fingerprint-mismatched profile anyway (logged, not silent).
    allow_fingerprint_mismatch: bool = False
    # Online serve controller: off by default — an SLO target is an
    # operator decision, not a guessable constant.
    serve_controller: bool = False
    # The controller's latency objective (end-to-end request p99, ms).
    target_p99_ms: float = 50.0
    # Controller tick cadence (seconds): at most ONE knob adjustment per
    # interval (the rate limit), objectives windowed per interval.
    controller_interval_s: float = 1.0
    # Adaptive learner-ingest cadence (distrib runs only; inert without
    # a pool): on by default — it only ever moves within bounds derived
    # from the configured cadence, and a dry-feed backoff is pure waste
    # reduction.
    adaptive_ingest: bool = True


@dataclass
class ObsConfig:
    """Telemetry (obs/): span trace, metrics export, crash flight recorder.

    Everything is OFF by default: a run with ``enabled=False`` creates no
    directories, opens no files, and adds no measurable hot-loop cost
    (pinned by tests/test_obs.py; what tracing costs on the chip when it
    is on: PERF.md §5). All
    instrumentation rides the existing ``runtime.metrics_every_chunks``
    sampling cadence and reads only host-side values from the batched
    megachunk readback — enabling obs adds NO new device syncs
    (tools/lint_hot_loop.py stays the guard)."""

    enabled: bool = False
    # Run directory: manifest.json, trace.jsonl, metrics.jsonl,
    # metrics.prom, and (on failure) flight_recorder.json land here.
    dir: str = "obs"
    # Host span trace (dispatch / readback / host_process / checkpoint /
    # recovery phases) in Chrome trace-event format — open the file at
    # https://ui.perfetto.dev or chrome://tracing.
    trace: bool = True
    # Background MetricsRegistry drain: append-only metrics.jsonl history
    # plus an atomically-rewritten Prometheus textfile snapshot.
    metrics_export: bool = True
    export_interval_s: float = 2.0
    # Bounded ring of recent chunk metrics / lifecycle transitions /
    # WARNING+ log lines, dumped as flight_recorder.json when supervision
    # trips, the NaN-loss guard fires, or the run escalates.
    flight_recorder: bool = True
    flight_capacity: int = 256
    # Roofline telemetry (obs/roofline.py): capture XLA cost_analysis /
    # memory_analysis for every compiled (mega)chunk program at COMPILE
    # time (one extra AOT lowering per program, never a per-step cost),
    # cross-check the XLA FLOP count against the analytic utils/flops.py
    # model (>25% discrepancy warns through the flight recorder), and
    # publish live mfu / achieved_tflops / hbm_gbps /
    # arithmetic_intensity gauges from the pipeline consumer thread —
    # plus a schema-versioned roofline.json artifact in the run dir
    # (summarized by ``cli obs``). Off by default like the rest of obs/:
    # disabled means no artifact, no gauges, no capture compile.
    roofline: bool = False
    # Per-REQUEST serve tracing (serve/engine.py): with obs enabled and
    # the span trace on, every submitted request's lifecycle — submitted
    # -> collected -> dispatched -> device-complete -> callback-complete,
    # plus the shed / expired / failed terminal edges — is emitted as
    # nested async spans keyed by request/batch/session ids, so Perfetto
    # renders request flows THROUGH batches. Sub-knob of obs.enabled +
    # obs.trace (volume control: a busy engine emits several events per
    # request); off everywhere by the obs.enabled=false default.
    request_trace: bool = True
    # Slowest-request exemplars: the serve engine keeps the K slowest
    # completed requests of each stats window — with their full stage
    # breakdown — in a bounded ring, written to serve_exemplars.json in
    # the run dir (obs enabled), surfaced by ``cli obs`` / ``cli serve``,
    # and recorded into the flight ring on overload/SLO-burn/failure
    # events. Bounds the ring; 0 disables exemplar tracking.
    exemplar_k: int = 8
    # --- SLO burn-rate monitoring (serve/engine.py _publish_stats) ----
    # Availability objective: the fraction of terminal requests that must
    # SUCCEED (sheds, rejections, deadline expiries, batch/engine
    # failures all count against it). The engine publishes
    # serve_slo_availability_burn = (observed bad fraction over the
    # rolling window) / (1 - objective): burn 1.0 = exactly spending the
    # error budget, >1 = burning it faster. 0 (default) disables.
    slo_availability: float = 0.0
    # Latency objective: target p99 in ms — at most 1% of completed
    # requests per window may exceed it. serve_slo_latency_burn =
    # (observed slow fraction) / 0.01. 0 (default) disables.
    slo_target_p99_ms: float = 0.0
    # Rolling window the burn rates are computed over (seconds).
    slo_window_s: float = 60.0
    # Burn level that records a flight-recorder event (with the current
    # exemplars) and a trace instant when first crossed; re-arms after
    # burn falls below half the threshold (hysteresis, not spam).
    slo_burn_threshold: float = 2.0
    # Soak-run growth caps (active regardless of ``enabled`` — they bound
    # the IN-MEMORY primitives, not the exported files). Short runs never
    # reach them, so default behavior is unchanged; 0 = unbounded (the
    # pre-cap behavior, growing without limit on long runs).
    max_metric_points: int = 65536     # per-series ring in MetricsRegistry
    max_timer_history: int = 65536     # StepTimer per-sample history ring
    # --- Cross-process wire tracing (fleet/; obs/collect.py) ----------
    # Per-process span journal directory. "" (default) = no span journal
    # and no trace headers anywhere — the obs.enabled=false zero-artifact
    # contract extends to the wire. ``cli fleet`` sets it to
    # <obs.dir>/spans when obs is enabled with the span trace on, and
    # the EnginePool injects the SAME path into every worker via --set
    # (workers run with obs.enabled=false so telemetry stays with the
    # fleet process — the span journal is the one deliberate exception,
    # keyed per (proc,pid) so writers never contend).
    span_dir: str = ""
    # This process's label in span journals and stitched traces
    # ("client", "fleet", "engine-0", ...; "" = pid-derived fallback).
    span_proc: str = ""
    # Span-journal bounds: framed batches per segment before rotation,
    # and sealed segments retained per process (oldest pruned).
    span_journal_records: int = 4096
    span_journal_segments: int = 8
    # Fleet telemetry history ring (obs/tsdb.py): router poll rows
    # retained in <obs.dir>/fleet_history.jsonl for ``cli obs
    # --history`` — the last-N-windows substrate the fleet autoscaler
    # (ROADMAP item 3) will read.
    history_rows: int = 2048


@dataclass
class FrameworkConfig:
    data: DataConfig = field(default_factory=DataConfig)
    env: EnvConfig = field(default_factory=EnvConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    learner: LearnerConfig = field(default_factory=LearnerConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    runtime: RuntimeConfig = field(default_factory=RuntimeConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    precision: PrecisionConfig = field(default_factory=PrecisionConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    distrib: DistribConfig = field(default_factory=DistribConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    tuning: TuningConfig = field(default_factory=TuningConfig)
    seed: int = 0

    # ---- serialization ----

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "FrameworkConfig":
        return _dataclass_from_dict(cls, d)

    @classmethod
    def from_file(cls, path: str) -> "FrameworkConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)

    # ---- CLI overrides ----

    def apply_overrides(self, overrides: list[str]) -> "FrameworkConfig":
        """Apply ``section.key=value`` strings, returning a new config.

        Values are parsed as JSON when possible, else kept as strings, so
        ``learner.gamma=0.99``, ``model.kind=lstm`` and
        ``parallel.mesh_shape={"dp":4,"tp":2}`` all work.

        The overridden dotted paths are remembered on the returned
        instance (``_explicit_overrides``, instance attribute — not a
        field, so it never serializes): the tuned-profile resolution
        (tuning.py) consults it so a knob EXPLICITLY ``--set`` back to
        its default value still beats the profile — value-equality alone
        cannot see that decision.
        """
        cfg = FrameworkConfig.from_dict(self.to_dict())
        explicit = set(getattr(self, "_explicit_overrides", ()))
        for item in overrides:
            if "=" not in item:
                raise ConfigError(f"override must look like section.key=value, got {item!r}")
            dotted, raw = item.split("=", 1)
            try:
                value = json.loads(raw)
            except json.JSONDecodeError:
                value = raw
            target: Any = cfg
            *path, leaf = dotted.split(".")
            for part in path:
                if not hasattr(target, part):
                    raise KeyError(f"unknown config section {part!r} in {dotted!r}")
                target = getattr(target, part)
            if not hasattr(target, leaf):
                raise KeyError(f"unknown config key {leaf!r} in {dotted!r}")
            setattr(target, leaf, value)
            explicit.add(dotted)
        cfg._explicit_overrides = frozenset(explicit)
        return cfg


def _dataclass_from_dict(cls: type, d: dict[str, Any]) -> Any:
    known = {f.name for f in fields(cls)}
    unknown = set(d) - known
    if unknown:
        # Typos in a config file must fail loudly, matching the CLI-override path.
        raise KeyError(f"unknown config key(s) {sorted(unknown)} for {cls.__name__}")
    kwargs: dict[str, Any] = {}
    for f in fields(cls):
        if f.name not in d:
            continue
        v = d[f.name]
        # Field annotations are strings under `from __future__ import
        # annotations`, so nested sections resolve through _NESTED by name.
        if isinstance(v, dict) and f.name in _NESTED:
            kwargs[f.name] = _dataclass_from_dict(_NESTED[f.name], v)
        else:
            kwargs[f.name] = v
    return cls(**kwargs)


_NESTED = {
    "data": DataConfig,
    "env": EnvConfig,
    "model": ModelConfig,
    "learner": LearnerConfig,
    "parallel": ParallelConfig,
    "runtime": RuntimeConfig,
    "checkpoint": CheckpointConfig,
    "precision": PrecisionConfig,
    "serve": ServeConfig,
    "distrib": DistribConfig,
    "fleet": FleetConfig,
    "obs": ObsConfig,
    "tuning": TuningConfig,
}
