"""Learner zoo (L2): the generalization of the reference's single Q-policy
actor into an algorithm registry (SURVEY.md §7.1 item 3; BASELINE.json
config ladder: qlearn → pg → dqn → a2c → ppo).
"""

from __future__ import annotations

from sharetrade_tpu.agents.a2c import make_a2c_agent
from sharetrade_tpu.agents.base import (  # noqa: F401
    Agent,
    TrainState,
    build_optimizer,
    epsilon_greedy,
    exploit_probability,
    portfolio_metrics,
)
from sharetrade_tpu.agents.dqn import make_dqn_agent
from sharetrade_tpu.agents.pg import make_pg_agent
from sharetrade_tpu.agents.ppo import make_ppo_agent
from sharetrade_tpu.agents.qlearn import make_qlearn_agent
from sharetrade_tpu.config import FrameworkConfig
from sharetrade_tpu.env import trading
from sharetrade_tpu.env.core import TradingEnv
from sharetrade_tpu.models import build_model
from sharetrade_tpu.models.core import Model
from sharetrade_tpu.precision import policy_from_config

_FACTORIES = {
    "qlearn": make_qlearn_agent,
    "pg": make_pg_agent,
    "dqn": make_dqn_agent,
    "a2c": make_a2c_agent,
    "ppo": make_ppo_agent,
}

# Value-based algorithms drive a Q-head; the rest are actor-critic.
_HEADS = {"qlearn": "q", "dqn": "q", "pg": "ac", "a2c": "ac", "ppo": "ac"}


def build_agent(cfg: FrameworkConfig, env: TradingEnv | trading.EnvParams,
                model: Model | None = None, mesh=None) -> Agent:
    """Wire model + env + learner from a framework config.

    Accepts either the generic :class:`TradingEnv` bundle or a bare
    single-asset ``EnvParams`` (wrapped automatically — the common
    test/bench construction path). ``mesh`` flows to ``build_model`` for the
    partitioned transformer paths (ring attention over sp, pipelined blocks
    over pp).
    """
    if isinstance(env, trading.EnvParams):
        params = env
        env = trading.make_trading_env(
            params.prices, window=params.window,
            initial_budget=float(params.initial_budget),
            initial_shares=int(params.initial_shares))
    algo = cfg.learner.algo
    if algo not in _FACTORIES:
        raise ValueError(f"unknown learner.algo {algo!r}; "
                         f"choose from {sorted(_FACTORIES)}")
    if _HEADS[algo] == "q" and cfg.model.kind != "mlp":
        # Value-based learners drive a stateless Q-head; recurrent/attention
        # policies go through the actor-critic algorithms (a2c/ppo/pg).
        raise ValueError(
            f"learner.algo={algo!r} requires model.kind='mlp' "
            f"(got {cfg.model.kind!r}); use a2c/ppo for {cfg.model.kind} policies")
    # Multi-asset model-family boundaries (TCN, episode transformer —
    # PARITY.md) are enforced by build_model, the single authority every
    # construction path funnels through.
    if model is None:
        model = build_model(cfg.model, env.obs_dim, head=_HEADS[algo],
                            num_actions=env.num_actions, mesh=mesh,
                            num_assets=env.num_assets)
    kwargs = {}
    # Precision policy (precision.py): fp32 = structural identity with the
    # pre-policy code; bf16_mixed = fp32 masters + bf16 compute copies at
    # each update boundary + fused f32 updates. Validated here (ConfigError
    # on unknown modes — construction-time STOP, like every impossible
    # composition).
    kwargs["precision"] = policy_from_config(cfg.precision)
    if algo == "dqn" and cfg.learner.journal_replay:
        kwargs["collect_transitions"] = True
    if algo == "ppo":
        # PPO's minibatch phase gathers PERMUTED agent rows out of the
        # dp-sharded rollout products; with the mesh in hand it marks that
        # layout change explicitly (one planned all-gather at the
        # rollout→update seam) instead of leaving GSPMD an involuntary
        # full rematerialization per gather (agents/ppo.py).
        kwargs["mesh"] = mesh
    return _FACTORIES[algo](
        model, env, cfg.learner,
        num_agents=cfg.parallel.num_workers,
        steps_per_chunk=cfg.runtime.chunk_steps, **kwargs)
