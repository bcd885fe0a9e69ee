"""The ``xing4`` family at a small size on the CPU (d 64, 4 streams, 8
experts of which 4 held, top-2, 1 dense + 2 expert layers, window 9): the
program's trunk (sharetrade_tpu/models/latent_moe_episode.py), served through
``ServeEngine``, against the plain reference ``chipbench/models/xing4.py``;
the reference's own share arithmetic and control; the family through the
harness; the new readers' arithmetic; the configuration's file."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_toy as toy
from chipbench.harness import common, flops, reference, serve_window
from chipbench.models import xing4
from chipbench.readers import moe as moe_readers

WINDOW = 9
SMALL = {
    "learner.algo": "ppo", "model.kind": "latent_moe",
    "model.seq_mode": "episode", "model.hidden_dim": 64,
    "model.num_layers": 3, "model.num_heads": 4, "model.q_lora_rank": 24,
    "model.kv_lora_rank": 16, "model.qk_nope_head_dim": 8,
    "model.qk_rope_head_dim": 8, "model.v_head_dim": 8,
    "model.dense_layers": 1, "model.dense_ffn_dim": 96,
    "model.moe_ffn_dim": 32, "model.moe_experts": 8, "model.moe_top_k": 2,
    "model.moe_held_experts": 4, "env.window": WINDOW}

# fp32, program against reference: two XLA programs of one float32
# function. Observed on the CPU: 6e-8 on logits spanning 0.2, 9e-7 on latents
# spanning 2.7 (a few float32 eps each); the limits leave a decade of room
# and lie three decades under what the int8 control reads.
LOGIT_TOL_FP32 = 2e-6
CACHE_TOL_FP32 = 2e-5
# bf16_mixed (bf16 weights that hold the masters' values exactly, float32
# activations through the matrix unit as two rows, float32 latents):
# observed 3e-5 on logits spanning 0.2, no pick differing. Before the
# latents were float32 it read 8e-4, before the two-row products 2e-3: the
# limit sits between, so either going would fail here.
LOGIT_TOL_BF16 = 3e-4


def small_cfg(precision="fp32"):
    return common.build_config(
        {"overrides": {**SMALL, "precision.mode": precision}}, {}, seed=0)


def lively(params, seed=9):
    """Hyper-connection scales and biases and the selection bias drawn at a
    scale at which H_res is far from both the identity and the uniform
    matrix and the bias moves picks."""
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


@pytest.fixture(scope="module")
def small():
    from sharetrade_tpu.models import build_model
    cfg = small_cfg()
    model = build_model(cfg.model, WINDOW + 2, head="ac")
    sizes = flops.sizes(cfg, xing4)
    params = lively(model.init(jax.random.PRNGKey(3)))
    return cfg, model, sizes, params


class Sessions:
    """A few sessions with wallets that follow the served actions, stepped
    through an engine at heterogeneous lengths."""

    def __init__(self, n, steps, seed=0):
        rng = np.random.default_rng(seed)
        self.prices = (50 * np.exp(np.cumsum(rng.normal(
            0, 0.02, (n, WINDOW + PAD)), axis=1))).astype(np.float32)
        self.steps = steps
        self.wallet = [[2400.0, 0.0] for _ in range(n)]
        self.served = [[] for _ in range(n)]      # (budget, shares, logits)

    def obs(self, i, t):
        return np.concatenate([self.prices[i, t:t + WINDOW],
                               np.asarray(self.wallet[i], np.float32)])

    def serve(self, engine):
        for t in range(max(self.steps)):
            live = [i for i, n in enumerate(self.steps) if n > t]
            handles = [(i, engine.submit(f"s{i}", self.obs(i, t)))
                       for i in live]
            for i, handle in handles:
                result = handle.wait(120.0)
                assert result is not None
                self.served[i].append((*self.wallet[i], result.logits))
                self.wallet[i][1] += 1.0 if result.action == 0 else 0.0

    def reference_logits(self, i, params, sizes, quant=None):
        """Padded to PAD steps (causality keeps the padding out of every
        served row), so one compiled reference serves every session."""
        n = self.steps[i]
        budget = np.zeros((PAD,), np.float32)
        shares = np.zeros((PAD,), np.float32)
        budget[:n] = [s[0] for s in self.served[i]]
        shares[:n] = [s[1] for s in self.served[i]]
        return np.asarray(_reference(sizes, quant)(
            params, jnp.asarray(self.prices[i, :WINDOW + PAD - 1]), budget,
            shares))[:n]

    def reference_cache(self, i, params, sizes, picks_out=None):
        n, hist = self.steps[i], xing4.history(sizes)
        ticks = jnp.asarray(self.prices[i, :WINDOW + n - 1])
        series = jnp.concatenate([jnp.full((hist,), ticks[0]), ticks])

        def run(params, series):
            taps = []
            _, cache = xing4.trunk(params, series,
                                   jnp.arange(-hist, WINDOW + n - 1), sizes,
                                   cache_before=hist + WINDOW + n - 1,
                                   picks_out=taps)
            return cache, taps

        cache, taps = jax.jit(run)(params, series)
        if picks_out is not None:
            picks_out.extend(taps)
        return cache


PAD = 8
_REFERENCES: dict = {}


def _reference(sizes, quant):
    key = (tuple(sorted(sizes.items())), quant)
    if key not in _REFERENCES:
        import functools
        _REFERENCES[key] = jax.jit(functools.partial(
            serve_window.reference_logits, model=xing4, sizes=sizes,
            quant=quant))
    return _REFERENCES[key]


def engine_for(model, params, precision="fp32"):
    from sharetrade_tpu.config import PrecisionConfig, ServeConfig
    from sharetrade_tpu.precision import policy_from_config
    from sharetrade_tpu.serve.engine import ServeEngine
    return ServeEngine(model, ServeConfig(max_batch=4, slots=8), params,
                       precision=policy_from_config(
                           PrecisionConfig(mode=precision)))


def session_cache(engine, sid):
    """One session's arena row through the family's ``program_cache``."""
    slot = engine._slots.lookup(sid)
    row = jax.tree.map(lambda x: x[slot:slot + 1], engine._pool)
    return xing4.program_cache(row, WINDOW, 16, 8)


def test_the_program_and_the_reference_draw_the_same_weights(small):
    _, model, sizes, _ = small
    key = jax.random.PRNGKey(21)
    ours, theirs = model.init(key), xing4.init_params(key, sizes)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), ours, theirs)))


def test_served_sessions_agree_with_the_references_one_banded_pass(small):
    """(a) fp32: sessions prefilled and then served warm through ServeEngine
    at heterogeneous steps, against one banded pass of the reference with
    K and V expanded and no cache: logits at every served step, and the
    latent rings each session ends with."""
    _, model, sizes, params = small
    sessions = Sessions(3, [6, 3, 5])
    engine = engine_for(model, params)
    try:
        sessions.serve(engine)
        for i in range(3):
            served = np.stack([s[2] for s in sessions.served[i]])
            ref = sessions.reference_logits(i, params, sizes)
            assert np.abs(served - ref).max() <= LOGIT_TOL_FP32, i
            assert np.abs(ref).max() > 0.05          # logits worth comparing
            cache = sessions.reference_cache(i, params, sizes)
            ours = session_cache(engine, f"s{i}")
            for name in ("ckv", "kr"):
                assert ours[name].shape == cache[name].shape
                assert np.abs(np.asarray(ours[name] - cache[name])).max() <= (
                    CACHE_TOL_FP32), (i, name)
    finally:
        engine.stop(drain=False)


def test_bf16_mixed_serving_stays_near_the_reference(small, capsys):
    """(a) bf16_mixed: the same sessions served from a bf16 copy of the
    weights; the share of top-k picks on which the program and the float32
    reference differ is printed (a flipped pick moves a logit by a step,
    not by an ulp: PERF.md, section 2)."""
    _, model, sizes, params = small
    sessions = Sessions(3, [6, 3, 5])
    engine = engine_for(model, params, "bf16_mixed")
    try:
        # the weights are the policy's bf16 copy; the latent rings stay
        # float32 (the model's cast_carry hook)
        assert engine._live.params["blocks"][1]["moe"]["w_up"].dtype == (
            jnp.bfloat16)
        assert engine._pool["ckv"].dtype == engine._pool["kr"].dtype == (
            jnp.float32)
        sessions.serve(engine)
        worst = max(np.abs(np.stack([s[2] for s in sessions.served[i]])
                           - sessions.reference_logits(i, params, sizes)).max()
                    for i in range(3))
    finally:
        engine.stop(drain=False)
    assert LOGIT_TOL_FP32 < worst <= LOGIT_TOL_BF16

    # The picks, from the model's own two steps on one session's ticks.
    from sharetrade_tpu.precision import PrecisionPolicy
    low = PrecisionPolicy(mode="bf16_mixed").cast_compute(params)
    i, n = 0, sessions.steps[0]
    obs = [np.concatenate([sessions.prices[i, t:t + WINDOW],
                           [2400.0, 0.0]]).astype(np.float32)[None]
           for t in range(n)]
    _, carry = model.apply_prefill(low, obs[0])
    program = []
    for t in range(1, n):
        out, carry = model.apply_serve_batch(low, obs[t], carry)
        program.append(np.asarray(out.stats)[0])           # (layers, top_k)
    taps = []
    sessions.reference_cache(i, params, sizes, picks_out=taps)
    first_warm = xing4.history(sizes) + WINDOW
    ref = np.stack([np.asarray(p)[first_warm:first_warm + n - 1]
                    for p in taps], axis=1)        # (steps, layers, top_k)
    program = np.sort(np.stack(program), -1)
    differing = float(np.mean(program != np.sort(ref, -1)))
    with capsys.disabled():
        print(f"\nbf16_mixed: worst logit distance {worst:.2e}; picks that "
              f"differ from the float32 reference's: {100 * differing:.1f}% "
              f"of {program.size}")
    assert differing < 0.5


def test_the_int8_control_fails_the_fp32_tolerance(small):
    """(e) the reference computed in int8 in the program's place is not
    within (a)'s tolerance, nor within bf16's: the comparison can tell a
    lower precision."""
    _, _, sizes, params = small
    sessions = Sessions(2, [5, 4], seed=3)
    for i in range(2):
        sessions.served[i] = [(2400.0, 0.0, None)] * sessions.steps[i]
    gaps = [np.abs(
        sessions.reference_logits(i, params, sizes, reference.int8_quant)
        - sessions.reference_logits(i, params, sizes)).max()
        for i in range(2)]
    assert min(gaps) > 100 * LOGIT_TOL_FP32
    assert min(gaps) > 1e-3


def test_the_references_two_shares_add_up_to_the_uncut_layer(small):
    """(b) for the reference's own expert layer: experts 0-3 and 4-7, the
    shared expert counted once, give the whole layer's result."""
    _, _, sizes, _ = small
    s = {**sizes, "held_lo": 0, "held_n": 8}
    bank = xing4.init_params(jax.random.PRNGKey(5), s)["blocks"][1]["moe"]
    bank = {**bank, "w_gate": bank["w_gate"] * 10, "w_up": bank["w_up"] * 10,
            "w_down": bank["w_down"] * 10}
    x = jax.random.normal(jax.random.PRNGKey(6), (40, s["width"]))
    f, d = s["expert_ffn"], s["width"]

    def share(lo, n):
        def cols(w):
            return w.reshape(d, 8, f)[:, lo:lo + n].reshape(d, n * f)
        return {**bank, "w_gate": cols(bank["w_gate"]),
                "w_up": cols(bank["w_up"]),
                "w_down": bank["w_down"].reshape(8, f, d)[lo:lo + n].reshape(
                    n * f, d)}

    whole = xing4.expert_layer(bank, x, s, 0)
    parts = (xing4.expert_layer(share(0, 4), x, s, 0)
             + xing4.expert_layer(share(4, 4), x, s, 4)
             - xing4.swiglu(bank["shared"][0], x))
    np.testing.assert_allclose(parts, whole, atol=1e-5)
    assert np.abs(np.asarray(whole)).max() > 1e-2


def test_the_rope_frequencies_are_the_references(small):
    from sharetrade_tpu.models.latent_moe_episode import yarn_inv_freq
    cfg, _, sizes, _ = small
    np.testing.assert_allclose(yarn_inv_freq(cfg.model),
                               xing4.yarn_inv_freq(sizes), rtol=1e-6)
    published = flops.sizes(_published_cfg(), xing4)
    freq = np.asarray(xing4.yarn_inv_freq(published))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # fast dimensions keep the base's frequency, slow ones the base's / 64
    np.testing.assert_allclose(freq[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(freq[-4:], plain[-4:] / 64, rtol=1e-6)
    assert xing4.softmax_scale(published) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)


# ---- the family through the harness

def _published_cfg():
    manifest = common.Manifest()
    cell = manifest.cell("serve_xing4_steady")
    return common.build_config(manifest.config(cell["config"]),
                               manifest.traffic(cell["traffic"]), seed=1)


def test_the_family_has_the_whole_interface_and_real_counts():
    manifest = common.Manifest()
    doc = manifest.config("xing4_29b_ep2")
    assert manifest.model(doc) is xing4
    s = flops.sizes(_published_cfg(), xing4)
    assert xing4.history(s) == 4 * 200
    assert xing4.replay_seq_len(s) == 800 + 201 + s["unroll"] - 1
    expert = xing4.expert_flops(s)
    assert expert == pytest.approx(22.0e6, rel=0.01)      # 22.0 MFLOP
    per_row = xing4.serve_warm_step_flops(s)
    # 5 x attention's maps (56.8 M) + the dense layer (198 M) + 4 x (shared
    # + 4 picks x 32/64 of an expert = 66 M) + scores, router, maps, heads
    assert 0.70e9 < per_row < 0.85e9
    assert xing4.train_flops_per_agent_step(s) > 0
    assert xing4.further_numbers({}, {}) == {}


def test_the_configuration_keeps_every_published_width():
    doc = common.Manifest().config("xing4_29b_ep2")
    published = {
        "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
        "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "moe_intermediate_size": 1024,
        "num_attention_heads": 32, "num_key_value_heads": 32,
        "num_experts_per_tok": 4, "n_shared_experts": 1, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "routed_scaling_factor": 2, "n_group": 1,
        "topk_group": 1, "rope_theta": 10000, "max_position_embeddings": 262144}
    assert {k: doc[k] for k in published} == published
    assert doc["rope_scaling"]["factor"] == 64
    cut = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 32, "vocab_size": 0,
           "num_nextn_predict_layers": 0}
    assert {k: doc[k] for k in cut} == cut
    assert sorted(doc["reduced"]) == sorted(cut)
    assert set(doc["reduced"]) <= set(doc["reduced_why"])
    assert "2 chips" in doc["deployment"] and "16 chips" in doc["deployment"]
    over = doc["overrides"]
    assert (over["model.hidden_dim"], over["model.moe_experts"],
            over["model.moe_held_experts"], over["model.moe_top_k"]) == (
        3584, 64, 32, 4)
    assert over["precision.mode"] == "bf16_mixed"
    cfg = _published_cfg()
    assert (cfg.serve.slots, cfg.serve.max_batch) == (1024, 64)


def _toy_manifest(tmp_path, monkeypatch):
    """``chipbench_toy``'s toy benchmark with this family's small
    configuration in the toy configuration's place (the temporary copy's
    file, not the benchmark's)."""
    manifest = toy.make_toy(tmp_path, monkeypatch, "episode_transformer")
    path = os.path.join(manifest.data_dir, "configs", "toy.json")
    with open(path, "w") as fh:
        json.dump({"model": "xing4", "overrides": {
            **SMALL, "precision.mode": "fp32"}, "reduced": []}, fh)
    return manifest


def test_a_toy_cell_of_the_family_runs_through_the_serving_driver(
        tmp_path, monkeypatch, capsys):
    """(g) as ``stacked_kv`` does in test_chipbench_drivers.py."""
    before = toy.benchmark_files()
    manifest = _toy_manifest(tmp_path, monkeypatch)
    rc, line = toy.run_cell(manifest, "toy_serve", capsys, seed=2 ** 31 + 3)
    assert rc == 0 and line["correct"] is True, line
    assert toy.benchmark_files() == before
    assert line["attempted"] == 300 and line["failed"] == 0
    assert set(line["compared"]) == set(toy.TOY_LIMITS_SERVE)
    assert all(v["value"] <= v["limit"] for v in line["compared"].values())


def test_an_altered_answer_of_the_family_fails_logit_gap(
        tmp_path, monkeypatch, capsys):
    from sharetrade_tpu.serve.engine import ServeEngine
    sound = ServeEngine._warm_program

    def altered(self, params, pool, obs, idx):
        actions, *rest = sound(self, params, pool, obs, idx)
        return ((actions + 1) % 3, *rest)

    monkeypatch.setattr(ServeEngine, "_warm_program", altered)
    manifest = _toy_manifest(tmp_path, monkeypatch)
    rc, line = toy.run_cell(manifest, "toy_serve", capsys, seed=9)
    assert rc == 0 and line["correct"] is False, line
    assert (line["compared"]["logit_gap"]["value"]
            > line["compared"]["logit_gap"]["limit"])
    assert (line["compared"]["logit_err"]["value"]
            <= line["compared"]["logit_err"]["limit"])


# ---- the readers

class _Trace:
    module_counts = {"jit__warm_program": 100, "jit_other": 3}

    def __init__(self, seconds):
        self.seconds = seconds

    def matching(self, patterns):
        return self.seconds, 1200.0


def _context(counters, seconds=0.43):
    sizes = flops.sizes(_published_cfg(), xing4)
    return {"counters": counters, "sizes": sizes, "trace": _Trace(seconds),
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


FULL = {"serve_moe_ticks_total": 1000.0,
        "serve_moe_picks_total": 1000.0 * 50 * 4 * 4,
        "serve_moe_local_picks_total": 1000.0 * 50 * 4 * 2,
        "serve_moe_experts_hit_total": 1000.0 * 120}


def test_the_counter_readers_arithmetic():
    ctx = _context(FULL)
    assert moe_readers.local_pick_share(ctx) == pytest.approx(50.0)
    assert moe_readers.experts_hit_share(ctx) == pytest.approx(
        100.0 * 120 / 128)
    assert moe_readers.local_pick_share(_context({})) is None
    assert moe_readers.experts_hit_share(_context({})) is None


def test_the_expert_layers_cost_counts_necessary_work_alone():
    e = moe_readers.expert_sizes(flops.sizes(_published_cfg(), xing4))
    assert e == {"width": 3584, "ffn": 1024, "held": 32, "shared": 1,
                 "layers": 4}
    ops, nbytes = moe_readers.expert_layer_cost(e, 400.0, 120.0, 50.0)
    one = 3 * 3584 * 1024
    assert ops == 2.0 * one * 400
    assert nbytes == 2 * (one * 120 + 2 * 50 * 4 * 3584)
    # an expert no row picked is not read: fewer hits, fewer bytes
    assert moe_readers.expert_layer_cost(e, 400.0, 60.0, 50.0)[1] < nbytes


def test_the_roofline_share_is_least_time_over_measured_time():
    ctx = _context(FULL, seconds=0.43)           # 4.3 ms a tick of 100
    share = moe_readers.experts_roofline(ctx, ["x"], ["_warm_program"])
    e = moe_readers.expert_sizes(ctx["sizes"])
    ops, nbytes = moe_readers.expert_layer_cost(e, 400.0, 120.0, 50.0)
    least = max(ops / 197e12, nbytes / 819e9)
    assert least == nbytes / 819e9               # bandwidth-bound here
    assert share == pytest.approx(100.0 * least / 0.0043)
    assert 0 < share <= 100
    # nothing to read: no counters (the parent), or no matching event
    assert moe_readers.experts_roofline(
        _context({}), ["x"], ["_warm_program"]) is None
    assert moe_readers.experts_roofline(
        _context(FULL, seconds=0.0), ["x"], ["_warm_program"]) is None


def test_every_new_metric_reads_nothing_from_a_program_without_it():
    """On the parent commit the counters and the histogram do not exist:
    the readers return None and the line leaves the metrics out."""
    manifest = common.Manifest()
    ctx = {"counters": {"serve_batches_total": 10.0}, "histograms": {},
           "sizes": flops.sizes(_published_cfg(), xing4), "trace": _Trace(1.0),
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    for name in ("moe.local_pick_share", "moe.experts_hit_share",
                 "moe.max_load", "moe_experts_roofline"):
        fn, args = manifest.reader(name)
        assert fn(ctx, **args) is None, name
