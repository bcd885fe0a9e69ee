"""Analytic model-FLOPs accounting and MFU for the benchmark harness.

Round 1 reported only agent-steps/s against a derived CPU ceiling, which
flatters without informing (a 3,440x multiplier on a 41k-param MLP is ~10
MFLOP/s of useful math). These helpers put model FLOPs/step and MFU — the
fraction of the chip's peak matmul throughput the workload achieves — next to
every throughput number so chip utilization is visible in our own tables.

Counting rules (standard MFU conventions, stated explicitly):
- A dense layer in->out over N rows costs 2*N*in*out FLOPs.
- Causal attention is counted at its *useful* cost, ~half the full score
  matrix: 2*seq^2*d per attention matmul pair member (the Pallas kernel skips
  fully-masked blocks, so this reflects work actually scheduled).
- A backward pass costs 2x the forward it differentiates.
- Env-step arithmetic, optimizer updates, layernorms, and softmaxes are
  ignored (orders of magnitude below the matmuls).

Peak numbers are per-chip dense bf16 matmul peaks. f32 inputs at JAX's
default matmul precision also run single-pass bf16 on the MXU, so one peak
serves both dtypes; "highest"-precision runs (parity tests) are not what we
benchmark.
"""

from __future__ import annotations

import jax

from sharetrade_tpu.config import FrameworkConfig, LearnerConfig, ModelConfig

# device_kind substrings -> dense bf16 peak FLOP/s per chip (Google Cloud
# TPU documentation, per-generation system architecture pages). A device
# that is not in the table is an error, not a default: a utilisation
# figure relative to some other chip's peak is not a measurement.
_PEAK_BY_KIND = (
    ("v6 lite", 918e12),   # Trillium
    ("v5p", 459e12),
    ("v5 lite", 197e12),   # v5e
    ("v4", 275e12),
)

# device_kind substrings -> HBM bandwidth bytes/s per chip — the other
# roofline axis (obs/roofline.py): achieved HBM GB/s and the ridge point
# peak_flops / peak_bw that splits compute-bound from memory-bound.
_HBM_BW_BY_KIND = (
    ("v6 lite", 1640e9),   # Trillium
    ("v5p", 2765e9),
    ("v5 lite", 819e9),    # v5e
    ("v4", 1228e9),
)


class UnknownDeviceKind(LookupError):
    """The device (the CPU included) has no entry in the peak tables, so
    no utilisation or roofline figure can be stated for it."""


def _peak_for(device, table, what: str) -> float:
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "")
    for sub, peak in table:
        if sub in kind.lower():
            return peak
    raise UnknownDeviceKind(
        f"no published {what} for device_kind {kind!r}; utilisation is "
        "not measured on this device")


def chip_peak_flops(device=None) -> float:
    """Dense bf16 peak FLOP/s of ``device`` (default: the first attached
    device); raises :class:`UnknownDeviceKind` for a kind not in the
    table."""
    return _peak_for(device, _PEAK_BY_KIND, "bf16 peak FLOP/s")


def chip_peak_hbm_bw(device=None) -> float:
    """Peak HBM bytes/s of ``device``; raises :class:`UnknownDeviceKind`
    for a kind not in the table."""
    return _peak_for(device, _HBM_BW_BY_KIND, "HBM bandwidth")


def forward_flops_per_obs(model: ModelConfig, obs_dim: int,
                          algo: str = "qlearn") -> float:
    """Matmul FLOPs for ONE observation's policy forward pass.

    The MLP family has two distinct architectures (models/mlp.py): value-based
    algos (qlearn/dqn) use ``q_mlp`` — obs->h->acts, no value head — while
    pg/a2c/ppo use ``ac_mlp`` — obs->h, h->h torso, policy AND value heads.
    """
    acts = model.num_actions
    if model.kind == "mlp":
        h = model.hidden_dim
        if algo in ("qlearn", "dqn"):
            return 2.0 * h * (obs_dim + acts)           # q_mlp: two denses
        return 2.0 * h * (obs_dim + h + acts + 1)       # ac_mlp: torso2 + heads
    if model.kind == "lstm":
        # lstm_policy (models/lstm.py): obs->h input dense, fused [x;h]->4h
        # gate matmul (16*h^2), then policy + value heads.
        h = model.hidden_dim
        return 2.0 * h * obs_dim + 16.0 * h * h + 2.0 * h * (acts + 1)
    if model.kind == "tcn":
        # models/tcn.py: per block a K-tap dilated conv (2*W*K*C^2) plus a
        # 1x1 mix (2*W*C^2); block count auto-sized to cover the window
        # (kernel width and sizing imported so the accounting can't drift
        # from the model).
        from sharetrade_tpu.models.tcn import KERNEL, default_num_blocks
        w = obs_dim - 2
        c = model.hidden_dim
        per_block = 2.0 * w * KERNEL * c * c + 2.0 * w * c * c
        return (default_num_blocks(w) * per_block
                + 2.0 * w * 3 * c + 2.0 * c * (acts + 1 + 3))
    if model.kind == "transformer":
        seq = obs_dim - 1                               # window + summary token
        d = model.num_heads * model.head_dim
        ffn = 16.0 * seq * d * d                        # MLP in/out at ratio 4
        if model.moe_experts:
            # Dense-mask MoE evaluates every expert on every token (E x the
            # dense FFN); top-k capacity dispatch evaluates ~k experts per
            # token (drops make this a slight overcount; the dispatch/combine
            # one-hot matmuls are routing overhead, not model FLOPs).
            ffn *= (model.moe_top_k if model.moe_top_k else model.moe_experts)
        per_layer = (
            6.0 * seq * d * d        # qkv projection
            + 2.0 * seq * seq * d    # causal QK^T + PV (useful half of 4*s^2*d)
            + 2.0 * seq * d * d      # output projection
            + ffn
        )
        return model.num_layers * per_layer + 2.0 * seq * 3 * d  # + embed
    raise ValueError(f"unknown model kind {model.kind!r}")


def forward_equivalents_per_agent_step(cfg: LearnerConfig,
                                       num_agents: int) -> float:
    """How many single-observation forward passes one agent-step of TRAINING
    costs under each algorithm (backward = 2x the differentiated forward)."""
    if cfg.algo == "qlearn":
        # select fwd + stacked TD fwd over (s, s') + backward of that stack
        # (stop_gradient zeroes the s' cotangents but the matmul grads still
        # run full-size).
        return 1.0 + 2.0 + 2.0 * 2.0
    if cfg.algo in ("pg", "a2c"):
        # rollout fwd + replay fwd + backward
        return 1.0 + 1.0 + 2.0
    if cfg.algo == "ppo":
        # rollout fwd + ppo_epochs x (replay fwd + backward); minibatching
        # repartitions the same totals.
        return 1.0 + cfg.ppo_epochs * 3.0
    if cfg.algo == "dqn":
        # select fwd; per env-step the learner trains on replay_batch
        # observations (online fwd + target fwd + backward), amortized over
        # the agent batch.
        per_replay = (cfg.replay_batch / max(num_agents, 1))
        return 1.0 + per_replay * (1.0 + 1.0 + 2.0)
    raise ValueError(f"unknown algo {cfg.algo!r}")


def _episode_mode_flops_per_agent_step(cfg: FrameworkConfig,
                                       obs_dim: int) -> float:
    """Episode-mode transformer (models/transformer_episode.py), counting
    FLOPs actually EXECUTED. Both halves of the chunk exploit the same
    agent-invariance (every lockstep agent replays one shared price series),
    so the banded trunk runs for ONE representative row and amortizes over
    the B agents in BOTH places:

        rollout trunk:  (S+1)/T tokens / B agents (agents/rollout.py
                        precomputed path)
        rollout head:   FACTORED (round 5, rollout_head_factored): the
                        d-sized policy/value projections run ONCE over the
                        representative's T+1 trunk rows (shared /B), and
                        the per-agent-step residue is the 3-wide portfolio
                        contraction
        replay trunk:   epochs x minibatches x 3 (fwd+bwd) x S/T tokens / B
                        (apply_unroll_shared: one trunk per minibatch PASS,
                        not per agent — each pass re-runs it because the
                        params just changed)
        replay heads:   ALSO factored (round 5): d-sized base projections
                        once per pass over the shared trunk rows, 3-wide
                        portfolio term per agent-step, x3 for fwd+bwd

    MFU computed from this is hardware utilization of the executed matmuls;
    the pre-round-4 convention counted the per-agent replay trunks the
    shared path no longer runs, which would overstate MFU by ~B/minibatches.
    """
    model, learner = cfg.model, cfg.learner
    w = obs_dim - 2
    d = model.num_heads * model.head_dim
    per_token = (model.num_layers * (24.0 * d * d + 4.0 * w * d)
                 + 2.0 * 3 * d        # tick embed
                 + 2.0 * d * (model.num_actions + 1 + 3))  # heads + port
    t = max(learner.unroll_len, 1)
    b = max(cfg.parallel.num_workers, 1)
    s = model.num_layers * (w - 1) + t
    if learner.algo == "ppo":
        epochs = learner.ppo_epochs
        # Mirror ppo.py's divisor fallback: the actual minibatch count is
        # the largest divisor of the agent count not exceeding the request.
        requested = max(1, min(learner.ppo_minibatches, b))
        mb_count = max(d for d in range(1, requested + 1) if b % d == 0)
        passes = epochs * mb_count
    else:
        epochs, passes = 1, 1
    # Factored heads: shared base projections over the trunk rows plus the
    # per-step 3-wide portfolio term (policy+value: A+1 outputs).
    head_base = 2.0 * d * (model.num_actions + 1) * (t + 1) / t / b
    head_pf_step = 2.0 * 3 * (model.num_actions + 1)
    replay_heads = (2.0 * d * (model.num_actions + 1) * passes * 3.0 / b
                    + head_pf_step * epochs * 3.0)
    return (per_token * (s + 1) / t / b           # rollout trunk (shared)
            + head_base + head_pf_step             # factored rollout head
            + per_token * passes * 3.0 * s / t / b  # replay trunks (shared)
            + replay_heads)                        # factored replay heads


def train_flops_per_agent_step(cfg: FrameworkConfig, obs_dim: int) -> float:
    if (cfg.model.kind == "transformer" and cfg.model.seq_mode == "episode"
            and cfg.learner.algo in ("pg", "a2c", "ppo")):
        return _episode_mode_flops_per_agent_step(cfg, obs_dim)
    return (forward_flops_per_obs(cfg.model, obs_dim, cfg.learner.algo)
            * forward_equivalents_per_agent_step(
                cfg.learner, cfg.parallel.num_workers))


def mfu(agent_steps_per_sec: float, cfg: FrameworkConfig, obs_dim: int,
        device=None) -> float:
    """Model FLOPs utilization in [0, 1]."""
    achieved = agent_steps_per_sec * train_flops_per_agent_step(cfg, obs_dim)
    return achieved / chip_peak_flops(device)
