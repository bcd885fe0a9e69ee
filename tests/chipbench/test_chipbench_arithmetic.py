"""The window's rate arithmetic, the percentiles, the operation counts and
the comparison."""

import math

import numpy as np
import pytest

import chipbench_toy as toy
from chipbench.harness import common, correct, flops
from chipbench.harness.common import histogram_delta
from chipbench.harness.train_window import window_rate
from chipbench.models import episode_transformer
from chipbench.readers import histograms as hist_readers
from chipbench.readers import kernels as kernel_readers
from chipbench.readers import loadgen as loadgen_readers
from chipbench.readers import step as step_readers


def test_window_rate_counts_a_stalled_chunk():
    # chunks of 10 env steps every second, one stalled for 5 s
    rows = [(0.5, 0), (1.0, 10), (2.0, 20), (7.0, 30), (8.0, 40), (9.5, 50)]
    rate, chunks = window_rate(rows, 0.9, 9.0, agents=4)
    assert chunks == 3 and rate == pytest.approx(30 * 4 / 7.0)
    steady, _ = window_rate([(t, 10 * t) for t in range(10)], 0, 9, agents=4)
    assert steady == pytest.approx(40.0) and rate < steady / 2


def test_window_rate_needs_two_rows():
    rate, chunks = window_rate([(1.0, 10)], 0.0, 2.0, agents=4)
    assert math.isnan(rate) and chunks == 0


@pytest.mark.parametrize("q,want", [(50, 3.0), (95, 5.0), (100, 5.0)])
def test_nearest_rank_percentile(q, want):
    assert common.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q) == want


def test_failed_requests_lie_beyond_any_percentile():
    assert common.percentile([1.0] * 9 + [math.inf], 95) == math.inf


def test_histogram_percentile_interpolates_in_its_bucket_and_returns_nothing_when_empty():
    snap = {"h": {"bounds": [1.0, 2.0, 4.0], "counts": [90, 5, 4, 1]}}
    assert hist_readers.percentile({"histograms": snap}, "h", 95) == 2.0
    assert hist_readers.percentile({"histograms": snap}, "h", 93) == 1.6
    assert hist_readers.percentile(
        {"histograms": snap}, "h", 50) == pytest.approx(50 / 90)
    assert hist_readers.percentile({"histograms": snap}, "h", 100) is None
    assert hist_readers.percentile({"histograms": {}}, "h", 95) is None
    empty = {"h": {"bounds": [1.0], "counts": [0, 0]}}
    assert hist_readers.percentile({"histograms": empty}, "h", 95) is None


def test_histogram_delta_keeps_only_the_windows_counts():
    before = {"h": {"bounds": [1.0], "counts": [3, 1]}}
    after = {"h": {"bounds": [1.0], "counts": [5, 4]},
             "new": {"bounds": [1.0], "counts": [2, 0]}}
    delta = histogram_delta(before, after)
    assert delta["h"]["counts"] == [2, 3] and delta["new"]["counts"] == [2, 0]


def test_histogram_mean_is_the_windows_sum_over_its_count():
    before = {"h": {"bounds": [1.0], "counts": [3, 1], "sum": 9.0, "count": 4}}
    after = {"h": {"bounds": [1.0], "counts": [5, 4], "sum": 30.0, "count": 9}}
    context = {"histograms": histogram_delta(before, after)}
    assert hist_readers.mean(context, "h") == pytest.approx(21.0 / 5)
    assert hist_readers.mean({"histograms": histogram_delta(after, after)},
                             "h") is None
    assert hist_readers.mean({"histograms": {}}, "h") is None


class _Trace:
    """27 runs of the tick program, 28.7 ms each, one cut by the trace's
    edge to half of that; another program beside it."""
    module_seconds = {"jit__warm_program": 26.5 * 0.0287, "jit_other": 1.0}
    module_counts = {"jit__warm_program": 27, "jit_other": 3}


def d1024_cfg():
    """tr_episode_d1024's shapes on a FrameworkConfig."""
    from sharetrade_tpu.config import FrameworkConfig
    cfg = FrameworkConfig()
    cfg.learner.algo, cfg.model.kind = "ppo", "transformer"
    cfg.model.seq_mode = "episode"
    cfg.model.num_layers, cfg.model.num_heads, cfg.model.head_dim = 4, 8, 128
    cfg.learner.unroll_len, cfg.parallel.num_workers = 512, 1024
    return cfg


@pytest.mark.parametrize("family", toy.FAMILIES)
@pytest.mark.parametrize("responses,batches", [(6400, 100), (5200, 100),
                                               (100, 100)])
def test_serve_tick_mfu_cannot_pass_what_a_full_tick_could_do(
        responses, batches, family, monkeypatch):
    from chipbench.harness.peaks import peaks_for
    peaks = peaks_for("TPU v5 lite")
    model = toy.family(family, monkeypatch)
    sizes = flops.sizes(d1024_cfg(), model)     # under the family's names
    context = {"trace": _Trace, "sizes": sizes, "peaks": peaks, "chips": 1,
               "model": model, "max_batch": 64, "counters": {
                   "serve_responses_total": float(responses),
                   "serve_batches_total": float(batches),
                   "serve_prefills_total": 0.0}}
    tick_s = 26.5 * 0.0287 / 27
    # each family's own count, over its own names: the same model, 104 MFLOP
    per_row = model.serve_warm_step_flops(sizes)
    assert per_row == episode_transformer.serve_warm_step_flops(toy.D1024)
    value = step_readers.serve_tick_mfu(context, ["_warm_program"])
    assert value == pytest.approx(100.0 * per_row * responses / batches
                                  / (tick_s * peaks["bf16_flops"]))
    ceiling = 100.0 * 64 * per_row / (tick_s * peaks["bf16_flops"])
    assert 0 < value <= ceiling < 0.2        # a 64-row tick of ~28 ms: ~0.12%
    assert loadgen_readers.batch_occupancy(context) == pytest.approx(
        100.0 * responses / batches / 64)


@pytest.mark.parametrize("family", toy.FAMILIES)
def test_a_steps_mfu_is_a_share_of_all_the_cells_chips(family, monkeypatch):
    from chipbench.harness.peaks import peaks_for
    model = toy.family(family, monkeypatch)
    sizes = flops.sizes(d1024_cfg(), model)     # under the family's names
    context = {"trace": _Trace, "sizes": sizes, "model": model, "chips": 1,
               "peaks": peaks_for("TPU v5 lite"), "max_batch": 64,
               "values": {"agent_steps_per_s": 3075167.0},
               "counters": {"serve_responses_total": 4890.0,
                            "serve_batches_total": 100.0}}
    train = step_readers.train_mfu(context)
    serve = step_readers.serve_tick_mfu(context, ["_warm_program"])
    # PERF.md section 6, PR 25: 3,075,167 agent-steps/s are 19.90% of a chip
    assert train == pytest.approx(19.90, abs=0.01)
    four = dict(context, chips=4)
    assert step_readers.train_mfu(four) == pytest.approx(train / 4)
    assert step_readers.serve_tick_mfu(
        four, ["_warm_program"]) == pytest.approx(serve / 4)
    assert step_readers.train_mfu(dict(context, values={})) is None


def test_serve_readers_return_nothing_without_a_tick():
    context = {"trace": _Trace, "sizes": toy.D1024, "max_batch": 64,
               "model": episode_transformer, "chips": 1,
               "peaks": {"bf16_flops": 1.0}, "counters": {}}
    assert step_readers.serve_tick_mfu(context, ["_warm_program"]) is None
    assert loadgen_readers.batch_occupancy(context) is None
    context["counters"] = {"serve_responses_total": 64.0,
                           "serve_batches_total": 1.0}
    assert step_readers.serve_tick_mfu(context, ["no_such_program"]) is None


D1024 = toy.D1024


def test_flop_count_matches_the_programs_own_and_shares_the_trunk():
    from sharetrade_tpu.utils.flops import train_flops_per_agent_step
    cfg = d1024_cfg()
    count = episode_transformer.train_flops_per_agent_step
    assert flops.sizes(cfg, episode_transformer) == D1024
    assert set(flops.algorithm_sizes(cfg)).isdisjoint(
        episode_transformer.sizes(cfg))
    assert count(D1024) == pytest.approx(train_flops_per_agent_step(cfg, 203))
    per_chunk = count(D1024) * 512 * 1024
    assert 6e12 < per_chunk < 8e12       # the issue's ~6.7 TFLOP a chunk
    double = dict(D1024, agents=2048)
    assert count(double) * 2048 < 1.01 * count(D1024) * 1024


def test_attention_cost_is_memory_bound_on_the_v5e():
    from chipbench.harness.peaks import peaks_for
    peaks = peaks_for("TPU v5 lite")
    seq = episode_transformer.replay_seq_len(D1024)
    assert episode_transformer.history(D1024) == 600
    assert seq == 600 + 201 + 511
    cost = kernel_readers.banded_attention_cost
    ops, nbytes = cost(D1024, seq, backward=False)
    assert ops == 4.0 * seq * 201 * 1024
    assert nbytes / peaks["hbm_bytes_per_s"] > ops / peaks["bf16_flops"]
    ops_b, bytes_b = cost(D1024, seq, backward=True)
    assert ops_b == 2.5 * ops and bytes_b > nbytes


# what a side holds after its first chunk: the cache's named arrays, each
# (L, H, W, D) here, and the shares
KV = np.arange(1.0, 49.0).reshape(2, 2, 1, 3, 4)
FIRST = {"cache": {"k": KV[0], "v": KV[1]},
         "shares": np.asarray([4.0, 0.0, 8.0, 12.0])}


def kv_err_as_pr23_defined_it(program, reference) -> float:
    """``cache_error`` as it stood while the limits were set: K and V
    stacked to (2, L, H, W, D)."""
    p, r = np.asarray(program, np.float64), np.asarray(reference, np.float64)
    err = np.sqrt(np.sum(np.square(p - r), axis=(2, 3, 4)))
    return float(np.max(err / np.maximum(
        np.sqrt(np.sum(np.square(r), axis=(2, 3, 4))), 1e-30)))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_cache_error_over_named_arrays_is_kv_err_to_the_last_digit(seed):
    rng = np.random.default_rng(seed)
    ref = rng.normal(size=(2, 4, 8, 201, 128)).astype(np.float32)
    prog = (ref + 0.01 * rng.normal(size=ref.shape)).astype(np.float32)
    named = correct.cache_error({"k": prog[0], "v": prog[1]},
                                {"k": ref[0], "v": ref[1]})
    assert named == kv_err_as_pr23_defined_it(prog, ref)     # bit for bit


def test_cache_error_takes_any_names_and_ranks_layer_axis_first():
    ref = {"latent": np.arange(1.0, 25.0).reshape(2, 3, 4),
           "state": np.ones((2, 5))}
    assert correct.cache_error(ref, ref) == 0.0
    off = dict(ref, state=ref["state"] * [[1.0], [1.5]])   # layer 1 alone
    assert correct.cache_error(off, ref) == pytest.approx(0.5)
    assert correct.cache_error({"latent": ref["latent"]}, ref) == math.inf
    assert math.isnan(correct.cache_error(
        dict(ref, latent=ref["latent"] * math.nan), ref))
    assert not correct.judge({"kv_err": math.nan}, {"kv_err": 0.03})[0]


def test_cache_error_is_the_worst_layers_and_shares_gap_the_mean_agents():
    held = FIRST["cache"]
    assert correct.cache_error(held, held) == 0.0
    off = dict(held, v=held["v"] * [[[[1.25]]], [[[1.0]]]])
    # the values of layer 0 alone, a quarter off
    assert correct.cache_error(off, held) == pytest.approx(0.25)
    assert correct.cache_error({n: 0.5 * x for n, x in held.items()},
                               held) == 0.5
    shares = FIRST["shares"]
    assert correct.shares_gap(shares, shares) == 0.0
    # one agent of four left where it started: 8 of a mean holding of 6
    assert correct.shares_gap(shares * [1, 1, 0, 1], shares) == pytest.approx(
        8 / 4 / 6)
    assert correct.shares_gap(shares * math.nan, shares) == math.inf


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": 10.0, "b": 1.0, "c": 1e-6}
    prog = {"a": 10.5, "b": 1.0, "c": 2e-6}
    # c doubles, but against the median leaf's norm (1.0) that is nothing
    assert correct.worst_leaf_gap(prog, ref) == pytest.approx(0.05)
    assert correct.worst_leaf_gap({**prog, "b": 2.0}, ref) == 1.0
    assert correct.worst_leaf_gap({**prog, "b": math.nan}, ref) == math.inf


def test_leaves_with_no_gradient_are_left_out_of_the_change():
    grad = {"a": 5.0, "b": 4.0, "c": 1e-5}
    assert correct.moved_leaves(grad) == {"a": True, "b": True, "c": False}
    ref = {"losses": [1.0, 1.0, 1.0], "grad": grad, **FIRST,
           "change": {"a": 1.0, "b": 1.0, "c": 1e-7}}
    prog = {"losses": [1.001, 5.0, 9.0], "grad": grad, **FIRST,
            "change": {"a": 1.0, "b": 1.0, "c": 1.0}}
    numbers = correct.training_numbers(prog, ref, episode_transformer)
    assert numbers["change_worst_gap"] == 0.0 == numbers["change_median_gap"]
    assert numbers["loss_step1"] == pytest.approx(1e-3)
    assert "loss_step2" not in numbers      # the later steps fork


def test_judge_holds_each_number_to_its_own_limit():
    ok, compared = correct.judge({"x": 0.1, "y": 5.0}, {"x": 0.2})
    assert ok and compared == {"x": (0.1, 0.2), "y": (5.0, None)}
    assert not correct.judge({"x": 0.3}, {"x": 0.2})[0]
    assert not correct.judge({"x": math.nan}, {"x": 0.2})[0]
    assert not correct.judge({}, {"x": 0.2})[0]      # a limit with no number


def test_a_state_left_unchanged_reads_one():
    ref = {"losses": [1.0] * 3, "grad": {"a": 1.0, "b": 2.0}, **FIRST,
           "change": {"a": 1.0, "b": 2.0}}
    still = {"losses": [1.0] * 3, "grad": {"a": 0.0, "b": 0.0},
             "change": {"a": 0.0, "b": 0.0},
             "cache": {n: np.zeros_like(x)
                       for n, x in FIRST["cache"].items()},
             "shares": np.zeros(4)}
    numbers = correct.training_numbers(still, ref, episode_transformer)
    assert numbers["kv_err"] == 1.0 == numbers["shares_gap"]
    assert numbers["grad_worst_gap"] == 1.0 == numbers["change_worst_gap"]
    assert numbers["grad_median_gap"] == 1.0 == numbers["change_median_gap"]


def test_median_leaf_gap_is_steady_where_one_small_leaf_is_noisy():
    ref = {"a": 10.0, "b": 1.0, "c": 0.1, "d": 5.0, "e": 2.0}
    prog = {"a": 10.1, "b": 1.01, "c": 0.2, "d": 5.05, "e": 2.02}
    assert correct.median_leaf_gap(prog, ref) == pytest.approx(0.01)
    assert correct.worst_leaf_gap(prog, ref) == pytest.approx(0.05)
