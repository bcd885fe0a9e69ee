"""The latent-attention, routed-expert, hyper-connected trunk
(models/latent_moe_episode.py) at a small size on the CPU: the expert
layer's shares and routing rules, the Sinkhorn maps, the serve-only
boundary, and the counters a warm tick hands the engine. Agreement with the
plain reference is tests/chipbench/test_chipbench_xing4.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sharetrade_tpu.config import ConfigError, FrameworkConfig, ServeConfig
from sharetrade_tpu.models import build_model
from sharetrade_tpu.models import latent_moe_episode as lm

WINDOW = 9
D, FFN, ROUTED, TOP_K = 64, 32, 8, 2


def small_cfg(**over) -> FrameworkConfig:
    """d 64, 4 streams, 8 experts of which 4 held, top-2, 1 dense + 2
    expert layers, window 9."""
    cfg = FrameworkConfig()
    cfg.env.window = WINDOW
    cfg.learner.algo = "ppo"
    m = cfg.model
    m.kind, m.seq_mode = "latent_moe", "episode"
    m.hidden_dim, m.num_layers, m.num_heads = D, 3, 4
    m.q_lora_rank, m.kv_lora_rank = 24, 16
    m.qk_nope_head_dim = m.qk_rope_head_dim = m.v_head_dim = 8
    m.dense_layers, m.dense_ffn_dim, m.moe_ffn_dim = 1, 96, FFN
    m.moe_experts, m.moe_top_k, m.moe_held_experts = ROUTED, TOP_K, 4
    for key, value in over.items():
        setattr(m, key, value)
    return cfg


def lively(params, seed=9):
    """The hyper-connections' scales and biases, and the router's selection
    bias, drawn at a scale at which H_res is far from both the identity and
    the uniform matrix and the bias changes picks: at their initial values
    the tests would show nothing."""
    key = jax.random.PRNGKey(seed)
    for i, blk in enumerate(params["blocks"]):
        for j, name in enumerate(("hc_attn", "hc_ffn")):
            k = jax.random.fold_in(key, 2 * i + j)
            blk[name]["alpha"] = jax.random.normal(k, (3,))
            blk[name]["bias"] = jax.random.normal(
                jax.random.fold_in(k, 1), blk[name]["bias"].shape)
        if "moe" in blk:
            blk["moe"]["bias"] = 0.3 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), blk["moe"]["bias"].shape)
    return params


def expert_bank(seed=0, held=ROUTED, shared=1):
    """A whole bank of ``held`` experts + a router over all ROUTED."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5 + 3 * shared)

    def normal(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * 0.2

    return {"router": normal(keys[0], (D, ROUTED)),
            "bias": jnp.zeros((ROUTED,), jnp.float32),
            "w_gate": normal(keys[1], (D, held * FFN)),
            "w_up": normal(keys[2], (D, held * FFN)),
            "w_down": normal(keys[3], (held * FFN, D)),
            "shared": [{"w_gate": normal(keys[5 + 3 * j], (D, FFN)),
                        "w_up": normal(keys[6 + 3 * j], (D, FFN)),
                        "w_down": normal(keys[7 + 3 * j], (FFN, D))}
                       for j in range(shared)]}


def share_of(bank, lo, n):
    """Experts lo .. lo + n of a whole bank, as one chip would hold them."""
    def cols(w):
        return w.reshape(D, ROUTED, FFN)[:, lo:lo + n].reshape(D, n * FFN)
    return {**bank, "w_gate": cols(bank["w_gate"]), "w_up": cols(bank["w_up"]),
            "w_down": bank["w_down"].reshape(ROUTED, FFN, D)[lo:lo + n]
            .reshape(n * FFN, D)}


def layer(bank, x, lo, n, grouped):
    return lm.expert_layer(bank, x, top_k=TOP_K, scale=2.0, held_first=lo,
                           held=n, grouped=grouped)


def one_expert(bank, e, x):
    w = {k: v for k, v in share_of(bank, e, 1).items() if k.startswith("w_")}
    return lm.swiglu(w, x)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.normal(jax.random.PRNGKey(4), (37, D), jnp.float32)


@pytest.mark.parametrize("grouped", [False, True], ids=["dense", "grouped"])
def test_the_two_shares_add_up_to_the_uncut_layer(tokens, grouped):
    """What chip 0 (experts 0-3) and chip 1 (experts 4-7) compute, with the
    shared expert (which both compute) counted once, is the whole layer."""
    bank = expert_bank()
    whole, picks = layer(bank, tokens, 0, ROUTED, grouped)
    a, picks_a = layer(share_of(bank, 0, 4), tokens, 0, 4, grouped)
    b, picks_b = layer(share_of(bank, 4, 4), tokens, 4, 4, grouped)
    shared = lm.swiglu(bank["shared"][0], tokens)
    np.testing.assert_allclose(a + b - shared, whole, atol=2e-5)
    # every chip routes over ALL the experts and makes the same picks
    assert np.array_equal(picks, picks_a) and np.array_equal(picks, picks_b)
    assert not np.allclose(a, whole, atol=1e-3)       # the cut is a cut


@pytest.mark.parametrize("n_tokens", [1, 5, 64, 300])
def test_grouped_and_dense_banks_agree(n_tokens):
    bank = share_of(expert_bank(seed=n_tokens), 2, 4)
    x = jax.random.normal(jax.random.PRNGKey(n_tokens), (n_tokens, D))
    dense, _ = layer(bank, x, 2, 4, False)
    grouped, _ = layer(bank, x, 2, 4, True)
    np.testing.assert_allclose(grouped, dense, atol=2e-5)


def test_the_bias_moves_the_choice_and_not_the_weights(tokens):
    bank = expert_bank()
    plain_picks, plain_w = lm.route(bank, tokens, TOP_K, 2.0)
    biased = {**bank, "bias": bank["bias"].at[7].set(10.0)}
    picks, weights = lm.route(biased, tokens, TOP_K, 2.0)
    assert np.all(np.any(np.asarray(picks) == 7, axis=-1))     # the choice
    assert not np.array_equal(picks, plain_picks)
    scores = jax.nn.sigmoid(tokens @ bank["router"])           # no bias here
    chosen = jnp.take_along_axis(scores, picks, axis=-1)
    np.testing.assert_allclose(
        weights, 2.0 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.0, rtol=1e-6)
    assert plain_w.shape == weights.shape


@pytest.mark.parametrize("grouped", [False, True], ids=["dense", "grouped"])
def test_a_token_routed_wholly_elsewhere_still_gets_its_shared_expert(
        tokens, grouped):
    bank = expert_bank()
    bank["bias"] = bank["bias"].at[4:].set(10.0)    # every pick on 4..7
    here, picks = layer(share_of(bank, 0, 4), tokens, 0, 4, grouped)
    assert np.all(np.asarray(picks) >= 4)
    np.testing.assert_allclose(
        here, lm.swiglu(bank["shared"][0], tokens), atol=1e-6)


@pytest.mark.parametrize("grouped", [False, True], ids=["dense", "grouped"])
def test_no_token_is_dropped_at_64_rows_on_one_expert(grouped):
    """No capacity: all 64 rows pick expert 1, and each gets its whole
    weighted output."""
    bank = expert_bank(shared=0)
    bank["bias"] = bank["bias"].at[1].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(8), (64, D))
    out, picks = layer(share_of(bank, 0, 4), x, 0, 4, grouped)
    picks, weights = np.asarray(picks), np.asarray(
        lm.route(bank, x, TOP_K, 2.0)[1])
    assert np.all(np.any(picks == 1, axis=-1))
    want = sum(
        np.where((picks[:, j] == e)[:, None],
                 weights[:, j, None] * np.asarray(one_expert(bank, e, x)), 0.0)
        for j in range(TOP_K) for e in range(4))
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert np.all(np.abs(np.asarray(out)).max(-1) > 1e-4)      # none zeroed


def plain_sinkhorn(logits, iters, eps, clamp):
    m = np.exp(np.clip(np.asarray(logits, np.float64), -clamp, clamp))
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


@pytest.mark.parametrize("scale", [0.5, 1.0])
def test_h_res_is_doubly_stochastic(scale):
    logits = scale * jax.random.normal(jax.random.PRNGKey(2), (50, 4, 4))
    h = np.asarray(lm.sinkhorn(logits, 20, 1e-6, 30.0))
    assert np.abs(h.sum(-1) - 1.0).max() < 1e-3      # rows
    assert np.abs(h.sum(-2) - 1.0).max() < 1e-3      # columns
    np.testing.assert_allclose(h, plain_sinkhorn(logits, 20, 1e-6, 30.0),
                               atol=1e-5)
    # far from both the identity and the uniform matrix
    assert np.abs(h - np.eye(4)).max() > 0.2 and np.abs(h - 0.25).max() > 0.05


def test_the_clamp_holds_at_30():
    logits = jnp.asarray([[[100.0, -100.0], [-100.0, 100.0]],
                          [[50.0, 40.0], [-50.0, 31.0]]])
    h = np.asarray(lm.sinkhorn(logits, 20, 1e-6, 30.0))
    assert np.isfinite(h).all()
    np.testing.assert_allclose(
        h, lm.sinkhorn(jnp.clip(logits, -30.0, 30.0), 20, 1e-6, 30.0))
    # unclamped, exp(100) overflows float32 and the map is not finite
    assert not np.isfinite(np.asarray(
        lm.sinkhorn(logits, 20, 1e-6, 1000.0))).all()


def test_cli_train_refuses_the_kind_by_name(tmp_path, monkeypatch):
    from sharetrade_tpu.runtime.orchestrator import Orchestrator
    monkeypatch.chdir(tmp_path)
    orch = Orchestrator(small_cfg())
    with pytest.raises(ConfigError, match="latent_moe.*serve-only"):
        orch.send_training_data(np.linspace(50.0, 60.0, 64, dtype=np.float32))


@pytest.mark.parametrize("over,why", [
    ({"seq_mode": "window"}, "episode"),
    ({"moe_top_k": 0}, "moe_top_k"),
    ({"moe_held_first": 6}, "held range"),
    ({"dense_layers": 4}, "dense_layers"),
    ({"qk_rope_head_dim": 7}, "even")])
def test_impossible_compositions_are_refused_at_build(over, why):
    with pytest.raises(ConfigError, match=why):
        build_model(small_cfg(**over).model, WINDOW + 2, head="ac")


def test_value_based_learners_are_refused():
    with pytest.raises(ConfigError, match="actor-critic"):
        build_model(small_cfg().model, WINDOW + 2, head="q")


def test_the_carry_is_two_padded_latent_rings_and_a_clock():
    model = build_model(small_cfg().model, WINDOW + 2, head="ac")
    carry = model.init_carry()
    assert set(carry) == {"ckv", "kr", "t"}
    # window 9 -> 16 slots, ranks 16 and 8 -> 128 lanes each
    assert carry["ckv"].shape == carry["kr"].shape == (3, 16, 128)
    assert model.apply_prefill is not None and model.serve_stats is not None


def test_serve_stats_counts_picks_hits_and_load():
    model = build_model(small_cfg().model, WINDOW + 2, head="ac")
    picks = np.asarray([          # 3 rows, 2 expert layers, top-2; held 0..3
        [[0, 1], [4, 5]],
        [[0, 6], [2, 7]],
        [[0, 3], [6, 7]]], np.int32)
    counters, samples = model.serve_stats(picks)
    assert counters == {"serve_moe_picks_total": 12.0,
                        "serve_moe_local_picks_total": 6.0,
                        "serve_moe_experts_hit_total": 4.0,   # {0,1,3} + {2}
                        "serve_moe_ticks_total": 1.0}
    # busiest held expert: 3 rows on expert 0; mean 6 picks / (4 x 2)
    assert samples == {"serve_moe_max_load": 3 / (6 / 8)}
    assert model.serve_stats(picks[:0]) == ({}, {})
    elsewhere = np.full((2, 2, 2), 5, np.int32)
    counters, samples = model.serve_stats(elsewhere)
    assert counters["serve_moe_local_picks_total"] == 0.0 and samples == {}


def _observations(n_sessions, n_steps, seed=0):
    rng = np.random.default_rng(seed)
    prices = (50 * np.exp(np.cumsum(rng.normal(
        0, 0.02, (n_sessions, WINDOW + n_steps)), axis=1))).astype(np.float32)

    def obs(i, t):
        return np.concatenate([prices[i, t:t + WINDOW],
                               np.asarray([2400.0, 0.0], np.float32)])
    return obs


def test_the_engine_serves_the_kind_and_counts_its_picks():
    """Through ServeEngine: cold prefill, then warm ticks at heterogeneous
    steps; the counters cover the real rows of the warm ticks alone."""
    from sharetrade_tpu.serve.engine import ServeEngine
    cfg = small_cfg()
    model = build_model(cfg.model, WINDOW + 2, head="ac")
    params = lively(model.init(jax.random.PRNGKey(1)))
    engine = ServeEngine(model, ServeConfig(max_batch=4, slots=8), params)
    try:
        engine.warmup()
        base = engine.registry.counters().get("serve_moe_picks_total", 0.0)
        assert base == 0.0                     # warm-up rows are padding
        obs = _observations(3, 4)
        steps = {0: 4, 1: 2, 2: 3}             # heterogeneous session lengths
        warm_rows = 0
        for t in range(4):
            live = [i for i in steps if steps[i] > t]
            handles = [engine.submit(f"s{i}", obs(i, t)) for i in live]
            results = [h.wait(60.0) for h in handles]
            assert all(r is not None and np.isfinite(r.logits).all()
                       for r in results)
            warm_rows += len(live) if t else 0
        assert engine.drain(30.0)      # the last tick's counters are in
        counters = engine.registry.counters()
        assert counters["serve_moe_picks_total"] == warm_rows * TOP_K * 2
        assert 0 < counters["serve_moe_local_picks_total"] <= (
            counters["serve_moe_picks_total"])
        assert 0 < counters["serve_moe_experts_hit_total"] <= (
            counters["serve_moe_ticks_total"] * 4 * 2)
        hist = engine.registry.histograms()["serve_moe_max_load"]
        assert hist["count"] == counters["serve_moe_ticks_total"]
        row_bytes = engine.registry.latest("serve_arena_row_bytes")
        assert row_bytes == 2 * 3 * 16 * 128 * 4 + 4
    finally:
        engine.stop(drain=False)


def test_other_models_ticks_return_no_stats():
    """The present families' warm program is what it was: four results."""
    from sharetrade_tpu.serve.engine import ServeEngine
    cfg = FrameworkConfig()
    cfg.env.window = WINDOW
    cfg.model.kind, cfg.model.seq_mode = "transformer", "episode"
    cfg.model.num_layers, cfg.model.num_heads, cfg.model.head_dim = 1, 2, 8
    model = build_model(cfg.model, WINDOW + 2, head="ac")
    engine = ServeEngine(model, ServeConfig(max_batch=2, slots=4),
                         model.init(jax.random.PRNGKey(0)))
    try:
        obs = np.full((2, WINDOW + 2), 10.0, np.float32)
        idx = np.asarray([4, 5], np.int32)
        out = engine._warm_fn(engine._live.params, engine._pool, obs, idx)
        assert len(out) == 4
        engine._pool = out[3]
        assert "serve_moe_picks_total" not in engine.registry.counters()
    finally:
        engine.stop(drain=False)


def test_low_precision_weights_see_the_activations_whole():
    """Against bfloat16 weights the float32 activations go through as two
    rows (value and remainder): the product is the float32 one to a few
    1e-6, where rounding the activations first loses three digits."""
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (5, 7, 256), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (256, 48)).astype(
        jnp.bfloat16)
    exact = np.asarray(jnp.dot(x, w.astype(jnp.float32)), np.float64)
    scale = np.abs(exact).max()
    two_rows = np.abs(np.asarray(lm._mm(x, w)) - exact).max() / scale
    one_row = np.abs(np.asarray(jnp.dot(
        x.astype(jnp.bfloat16), w, preferred_element_type=jnp.float32))
        - exact).max() / scale
    assert lm._mm(x, w).shape == (5, 7, 48)
    assert two_rows < 2e-5 < 1e-3 < one_row
    # float32 weights: the plain product, bit for bit
    w32 = w.astype(jnp.float32)
    assert np.array_equal(lm._mm(x, w32), jnp.dot(x, w32))


def test_init_draws_weights_the_compute_copy_holds_exactly():
    from sharetrade_tpu.precision import PrecisionPolicy
    model = build_model(small_cfg().model, WINDOW + 2, head="ac")
    params = model.init(jax.random.PRNGKey(2))
    low = PrecisionPolicy(mode="bf16_mixed").cast_compute(params)
    same = jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b.astype(jnp.float32))),
        params, low)
    assert all(jax.tree.leaves(same))
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(params))
