"""Operations and bytes the algorithm needs, from shapes alone.

The yardstick: kept with the benchmark so that no later PR can move it.
``episode_train_flops_per_agent_step`` is a copy of
``sharetrade_tpu/utils/flops.py::_episode_mode_flops_per_agent_step`` (sound
arithmetic: the shared trunk is counted once, not per agent), rewritten over
plain sizes; the original is listed in PERF.md for a later PR to delete.

Counting rules: a dense layer in->out over N rows costs 2*N*in*out; banded
causal attention at its useful cost (each query sees ``window`` keys); a
backward pass costs twice its forward; elementwise work is ignored.
"""

from __future__ import annotations


def model_sizes(cfg) -> dict:
    """The plain sizes every count here needs, from a FrameworkConfig."""
    return {
        "layers": cfg.model.num_layers, "heads": cfg.model.num_heads,
        "head_dim": cfg.model.head_dim, "window": cfg.env.window,
        "actions": cfg.model.num_actions, "unroll": cfg.learner.unroll_len,
        "agents": cfg.parallel.num_workers,
        "epochs": cfg.learner.ppo_epochs,
        "minibatches": cfg.learner.ppo_minibatches,
    }


def per_token_flops(s: dict) -> float:
    """One tick through the trunk and the heads, forward."""
    d = s["heads"] * s["head_dim"]
    return (s["layers"] * (24.0 * d * d + 4.0 * s["window"] * d)
            + 2.0 * 3 * d + 2.0 * d * (s["actions"] + 1 + 3))


def minibatch_count(s: dict) -> int:
    requested = max(1, min(s["minibatches"], s["agents"]))
    return max(k for k in range(1, requested + 1) if s["agents"] % k == 0)


def episode_train_flops_per_agent_step(s: dict) -> float:
    d = s["heads"] * s["head_dim"]
    t, b, a = max(s["unroll"], 1), max(s["agents"], 1), s["actions"]
    seq = s["layers"] * (s["window"] - 1) + t
    passes = s["epochs"] * minibatch_count(s)
    per_token = per_token_flops(s)
    head_base = 2.0 * d * (a + 1) * (t + 1) / t / b
    head_pf_step = 2.0 * 3 * (a + 1)
    replay_heads = (2.0 * d * (a + 1) * passes * 3.0 / b
                    + head_pf_step * s["epochs"] * 3.0)
    return (per_token * (seq + 1) / t / b + head_base + head_pf_step
            + per_token * passes * 3.0 * seq / t / b + replay_heads)


def serve_warm_step_flops(s: dict) -> float:
    """One warm incremental step of one session: one token against a
    ``window``-row K/V ring in every layer, plus the heads."""
    return per_token_flops(s)


def replay_seq_len(s: dict) -> int:
    """Tokens of one replay pass: [history | first window | chunk ticks]."""
    return (s["layers"] - 1) * (s["window"] - 1) + s["window"] + s["unroll"] - 1


def banded_attention_cost(s: dict, seq: int, *, backward: bool,
                          itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) one banded flash-attention call needs for a
    batch-of-one ``seq``-token pass over all heads: QK^T and PV over the
    ``window`` keys each query sees (forward 4*S*W*d; backward recomputes
    the scores and forms dQ, dK, dV: 2.5x the forward's matmuls), and Q, K,
    V, O (and their gradients) each crossing HBM once."""
    d = s["heads"] * s["head_dim"]
    band = min(s["window"], seq)
    fwd = 4.0 * seq * band * d
    tensors = 4.0 * seq * d * itemsize
    if backward:
        return 2.5 * fwd, 2.0 * tensors + 3.0 * seq * d * itemsize
    return fwd, tensors
