"""The repo's named training configurations, and nothing else.

``make_configs()`` is what ``BENCHMARK.json`` and ``chipbench/configs/*.json``
cite as the source of the two benchmark configurations
(``ppo_tr_episode_large_d1024``, ``ppo_tr_episode_b512_u1024_bf16``;
``tests/test_config.py`` holds the benchmark's files to it) and what
``chip_smoke.py`` imports for its wide-model run. The runner that once timed
every entry here went with the rest of the pre-chip measurement stack
(PR 30): ``python3 -m chipbench.run`` measures, ``PERF_LEDGER.jsonl`` and
PERF.md record. Moving this function needs a ``benchmark`` issue, because
the files that cite its path are the benchmark's.
"""

from __future__ import annotations

from sharetrade_tpu.config import FrameworkConfig


def make_configs() -> dict[str, FrameworkConfig]:
    def base(**kw):
        cfg = FrameworkConfig()
        cfg.parallel.num_workers = 10
        cfg.runtime.chunk_steps = 500
        cfg.learner.unroll_len = 500
        for k, v in kw.items():
            parts, obj = k.split("__"), cfg
            for p in parts[:-1]:
                obj = getattr(obj, p)
            setattr(obj, parts[-1], v)
        return cfg

    return {
        # BASELINE.json config ladder (SURVEY.md §7.3 step 7)
        "qlearn_mlp": base(learner__algo="qlearn"),
        "pg_mlp": base(learner__algo="pg"),
        "dqn_replay": base(learner__algo="dqn"),
        "a2c_mlp": base(learner__algo="a2c"),
        "ppo_lstm": base(learner__algo="ppo", model__kind="lstm",
                         learner__unroll_len=128, runtime__chunk_steps=128),
        "ppo_tcn": base(learner__algo="ppo", model__kind="tcn",
                        model__hidden_dim=64,
                        learner__unroll_len=128, runtime__chunk_steps=128),
        "ppo_transformer": base(learner__algo="ppo", model__kind="transformer",
                                learner__unroll_len=32, runtime__chunk_steps=32,
                                model__num_layers=2, model__num_heads=4,
                                model__head_dim=64),
        # Saturating configs: the 10-agent reference shape is launch-bound
        # (round-1 VERDICT weak #4); these show the chip's actual ceiling.
        "qlearn_mlp_b4096": base(learner__algo="qlearn",
                                 parallel__num_workers=4096),
        "ppo_transformer_bf16": base(
            learner__algo="ppo", model__kind="transformer",
            learner__unroll_len=32, runtime__chunk_steps=32,
            model__num_layers=2, model__num_heads=2, model__head_dim=128,
            precision__mode="bf16_mixed"),
        "ppo_transformer_b1024_bf16": base(
            learner__algo="ppo", model__kind="transformer",
            parallel__num_workers=1024,
            learner__unroll_len=32, runtime__chunk_steps=32,
            learner__remat=True,
            model__num_layers=2, model__num_heads=2, model__head_dim=128,
            precision__mode="bf16_mixed"),
        # Episode-mode transformer (model.seq_mode="episode"): ticks embed
        # once, banded flash attention over the episode's tick stream, one
        # O(T+L*window) replay pass per chunk instead of T window forwards.
        "ppo_tr_episode": base(
            learner__algo="ppo", model__kind="transformer",
            model__seq_mode="episode",
            learner__unroll_len=32, runtime__chunk_steps=32,
            model__num_layers=2, model__num_heads=4, model__head_dim=64),
        "ppo_tr_episode_b256_bf16": base(
            learner__algo="ppo", model__kind="transformer",
            model__seq_mode="episode", parallel__num_workers=256,
            learner__unroll_len=128, runtime__chunk_steps=128,
            model__num_layers=2, model__num_heads=2, model__head_dim=128,
            precision__mode="bf16_mixed"),
        # Longer unrolls amortize the sequential rollout against the one
        # banded replay pass — the episode-mode throughput sweet spot.
        "ppo_tr_episode_b128_u1024_bf16": base(
            learner__algo="ppo", model__kind="transformer",
            model__seq_mode="episode", parallel__num_workers=128,
            learner__unroll_len=1024, runtime__chunk_steps=1024,
            model__num_layers=2, model__num_heads=2, model__head_dim=128,
            precision__mode="bf16_mixed"),
        # Wider agent batch on the precomputed-trunk rollout: the trunk is
        # shared across agents and the sequential loop is elementwise in B,
        # so batch width costs only the replay/update passes.
        "ppo_tr_episode_b512_u1024_bf16": base(
            learner__algo="ppo", model__kind="transformer",
            model__seq_mode="episode", parallel__num_workers=512,
            learner__unroll_len=1024, runtime__chunk_steps=1024,
            model__num_layers=2, model__num_heads=2, model__head_dim=128,
            precision__mode="bf16_mixed"),
        # Large-model tier: d_model=1024 x 4 layers (~50M params). The MXU
        # leaves the small-matmul regime (this chip sustains ~8-15 TF/s at
        # d=256 vs ~60% of peak at d>=2048), so MFU — not steps/s — is the
        # row's point.
        "ppo_tr_episode_large_d1024": base(
            learner__algo="ppo", model__kind="transformer",
            model__seq_mode="episode", parallel__num_workers=64,
            learner__unroll_len=512, runtime__chunk_steps=512,
            model__num_layers=4, model__num_heads=8, model__head_dim=128,
            precision__mode="bf16_mixed"),
        # d1024 with block-granular remat (model.remat_blocks): the MFU
        # experiment row — recomputing block internals in the backward
        # frees residual HBM for wider unrolls/batches; measure against
        # the exact row above to price the recompute.
        "ppo_tr_episode_large_d1024_remat": base(
            learner__algo="ppo", model__kind="transformer",
            model__seq_mode="episode", parallel__num_workers=64,
            learner__unroll_len=512, runtime__chunk_steps=512,
            model__num_layers=4, model__num_heads=8, model__head_dim=128,
            precision__mode="bf16_mixed", model__remat_blocks=True),
        # The reference's ENTIRE workload as one compiled chunk: 10 workers x
        # the full 5,845-step episode (6,046 prices - 201 window,
        # env/trading.py num_steps), rollout + GAE + clipped updates, with
        # the replay as a single ~6k-token banded pass (long-context tier).
        # Each timed rep starts from a fresh init so every step is live.
        "ppo_tr_episode_full_episode": base(
            learner__algo="ppo", model__kind="transformer",
            model__seq_mode="episode",
            learner__unroll_len=5845, runtime__chunk_steps=5845,
            model__num_layers=2, model__num_heads=2, model__head_dim=128,
            precision__mode="bf16_mixed"),
        # Long-context ceiling: a 32,768-step synthetic episode trained as
        # ONE chunk — the replay is a ~33k-token banded pass through the
        # STREAMING kernels (K/V one block per grid step; VMEM-unbounded).
        "ppo_tr_episode_32k_ctx": base(
            learner__algo="ppo", model__kind="transformer",
            model__seq_mode="episode",
            data__synthetic_length=32768 + 201,
            learner__unroll_len=32768, runtime__chunk_steps=32768,
            model__num_layers=2, model__num_heads=2, model__head_dim=128,
            precision__mode="bf16_mixed"),
        # Mesh-sharded row (ParallelConfig.mesh_shape): dp-sharded agents,
        # Megatron column/row tp split of the MLP. Skips unless the host
        # exposes 8 devices (v5e-8); capability is CPU-mesh-tested either way.
        "ppo_mlp_dp4_tp2": base(
            learner__algo="ppo", parallel__num_workers=64,
            parallel__mesh_shape={"dp": 4, "tp": 2},
            learner__unroll_len=128, runtime__chunk_steps=128),
    }
