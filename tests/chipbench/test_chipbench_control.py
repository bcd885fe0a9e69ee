"""The control of ``correct``, kept at a size a test run can hold: the plain
reference computed in int8 and put in the program's place has to come out as
not correct, and so has the reference with each fault planted: for the
benchmark's own model family and for the tests' second one, which reaches
the same shared code under other names. (The chip readings the cells' limits
were set from are in PERF.md, section 2; the benchmark's own runs do not run
the control.)"""

import numpy as np
import pytest

import chipbench_toy as toy
from chipbench.harness import common, correct, loadgen, reference
from chipbench.harness import serve_window, train_window

SIZES = {"layers": 2, "heads": 2, "head_dim": 16, "window": 12, "actions": 3,
         "unroll": 16, "agents": 8, "epochs": 4, "minibatches": 4}


class Learner:
    learning_rate, gamma, gae_lambda = 0.01, 0.001, 0.95
    clip_eps, value_coef, entropy_coef = 0.2, 0.5, 0.01


@pytest.fixture(scope="module")
def prices():
    return common.make_prices(toy.PRICES)


@pytest.fixture(params=toy.FAMILIES)
def model(request, monkeypatch):
    return toy.family(request.param, monkeypatch)


def limits_of(model) -> dict:
    return toy.train_limits(model.__name__.rsplit(".", 1)[-1])


def training(model, prices, seed, **kwargs):
    """The reference's first chunks at the toy sizes as ``model`` names
    them (compiled once per family, control and fault: the driver's own
    cache of chunks)."""
    return train_window.reference_training(
        model, toy.toy_sizes(model), Learner, prices, seed, **kwargs)


def test_the_toy_sizes_are_the_first_familys_names(monkeypatch):
    model = toy.family("episode_transformer", monkeypatch)
    assert toy.toy_sizes(model) == SIZES


def test_the_reference_repeats_itself(prices, model):
    sound, again = (training(model, prices, seed=3) for _ in range(2))
    ok, compared = correct.judge(
        correct.training_numbers(again, sound, model), limits_of(model))
    assert ok and all(v == 0.0 for v, _ in compared.values())


@pytest.mark.parametrize("seed", [3, 4, 2 ** 31 + 5])
def test_int8_control_comes_out_not_correct_in_training(prices, model, seed):
    ref = training(model, prices, seed)
    control = training(model, prices, seed, quant=reference.int8_quant)
    numbers = correct.training_numbers(control, ref, model)
    ok, compared = correct.judge(numbers, limits_of(model))
    assert not ok, compared
    assert numbers["kv_err"] > 0.01      # the cells' limit is 0.03: PERF.md


@pytest.mark.parametrize("fault", ["half_batch", "token", "unchanged"])
def test_a_planted_fault_comes_out_not_correct(prices, model, fault):
    sound = training(model, prices, seed=3)
    broken = training(model, prices, seed=3, fault=fault)
    numbers = correct.training_numbers(broken, sound, model)
    ok, compared = correct.judge(numbers, limits_of(model))
    assert not ok, compared
    # the numbers that catch it at the cells' own sizes (PERF.md, section 2)
    assert numbers["shares_gap"] > 0.3
    assert numbers["kv_err"] == {"half_batch": 0.5, "token": 0.0,
                                 "unchanged": 1.0}[fault]
    if fault == "unchanged":
        assert numbers["grad_median_gap"] == 1.0
        assert numbers["change_median_gap"] == 1.0


def _served_by_reference(model, prices, seed, quant=None):
    """Sessions 'served' by the reference itself (greedy), so that the
    comparison can be driven without the program."""
    import jax
    sizes = toy.toy_sizes(model)
    sessions = loadgen.make_sessions(prices, SIZES["window"], 2, seed, 2400.0,
                                     max_steps=40)
    k_params, _ = jax.random.split(jax.random.PRNGKey(seed))
    params = model.init_params(k_params, sizes)
    for sess in sessions:
        for _ in range(6):
            n = len(sess.steps) + 1
            budget = np.asarray([s[0] for s in sess.steps] + [sess.budget],
                                np.float32)
            shares = np.asarray([s[1] for s in sess.steps] + [sess.shares],
                                np.float32)
            ticks = sess.prices[
                sess.start:sess.start + sizes["window"] + n - 1]
            logits = np.asarray(serve_window.reference_logits(
                params, ticks, budget, shares, model, sizes, quant))[-1]
            sess.advance(int(logits.argmax()), logits)
    return sessions


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 7])
def test_int8_control_comes_out_not_correct_in_serving(prices, model, seed):
    sessions = _served_by_reference(model, prices, seed)
    sizes = toy.toy_sizes(model)
    sound = serve_window.serving_numbers(sessions, seed, model, sizes)
    assert correct.judge(sound, toy.TOY_LIMITS_SERVE)[0], sound
    control = serve_window.serving_numbers(sessions, seed, model, sizes,
                                           quant=reference.int8_quant)
    assert not correct.judge(control, toy.TOY_LIMITS_SERVE)[0], control
    assert control["logit_err"] > 10 * max(sound["logit_err"], 1e-7)
