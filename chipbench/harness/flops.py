"""The counting rules every model family's counts keep to, and the sizes
that are the algorithm's and the environment's, not a model's.

The yardstick: kept with the benchmark so that no later PR can move it. A
family's own counts (the operations of one training agent-step and of one
warm serving step) are in its module under ``chipbench/models/``; a
kernel's cost function is with that kernel's reader.

Counting rules: a dense layer in->out over N rows costs 2*N*in*out; banded
causal attention at its useful cost (each query sees ``window`` keys); a
backward pass costs twice its forward; elementwise work is ignored.
"""

from __future__ import annotations


def algorithm_sizes(cfg) -> dict:
    """What every family shares, from a FrameworkConfig: the environment's
    window and actions, the learner's unroll, agents, epochs, minibatches."""
    return {
        "window": cfg.env.window, "actions": cfg.model.num_actions,
        "unroll": cfg.learner.unroll_len, "agents": cfg.parallel.num_workers,
        "epochs": cfg.learner.ppo_epochs,
        "minibatches": cfg.learner.ppo_minibatches,
    }


def sizes(cfg, model) -> dict:
    """The plain sizes a run is given: the shared ones and the model's."""
    return {**algorithm_sizes(cfg), **model.sizes(cfg)}


def minibatch_count(s: dict) -> int:
    requested = max(1, min(s["minibatches"], s["agents"]))
    return max(k for k in range(1, requested + 1) if s["agents"] % k == 0)
