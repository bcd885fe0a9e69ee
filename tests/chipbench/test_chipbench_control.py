"""The control of ``correct``, kept at a size a test run can hold: the plain
reference computed in int8 and put in the program's place has to come out as
not correct, and so has the reference with each fault planted. (The chip
readings the cells' limits were set from are in PERF.md, section 2; the
benchmark's own runs do not run the control.)"""

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


@pytest.fixture(scope="module")
def sound(prices):
    return train_window.reference_training(SIZES, Learner, prices, seed=3)


def test_the_reference_repeats_itself(prices, sound):
    again = train_window.reference_training(SIZES, Learner, prices, seed=3)
    ok, compared = correct.judge(correct.training_numbers(again, sound),
                                 toy.TOY_LIMITS_TRAIN)
    assert ok and all(v == 0.0 for v, _ in compared.values())


@pytest.mark.parametrize("seed", [3, 4, 2 ** 31 + 5])
def test_int8_control_comes_out_not_correct_in_training(prices, seed):
    ref = train_window.reference_training(SIZES, Learner, prices, seed)
    control = train_window.reference_training(
        SIZES, Learner, prices, seed, quant=reference.int8_quant)
    numbers = correct.training_numbers(control, ref)
    ok, compared = correct.judge(numbers, toy.TOY_LIMITS_TRAIN)
    assert not ok, compared
    assert numbers["kv_err"] > 0.01      # the cells' limit is 0.03: PERF.md


@pytest.mark.parametrize("fault", ["half_batch", "token", "unchanged"])
def test_a_planted_fault_comes_out_not_correct(prices, sound, fault):
    broken = train_window.reference_training(SIZES, Learner, prices, seed=3,
                                             fault=fault)
    numbers = correct.training_numbers(broken, sound)
    ok, compared = correct.judge(numbers, toy.TOY_LIMITS_TRAIN)
    assert not ok, compared
    # the numbers that catch it at the cells' own sizes (PERF.md, section 2)
    assert numbers["shares_gap"] > 0.3
    assert numbers["kv_err"] == {"half_batch": 0.5, "token": 0.0,
                                 "unchanged": 1.0}[fault]
    if fault == "unchanged":
        assert numbers["grad_median_gap"] == 1.0 == numbers["change_median_gap"]


def _served_by_reference(prices, seed, quant=None):
    """Sessions 'served' by the reference itself (greedy), so that the
    comparison can be driven without the program."""
    import jax
    sessions = loadgen.make_sessions(prices, SIZES["window"], 2, seed, 2400.0,
                                     max_steps=40)
    k_params, _ = jax.random.split(jax.random.PRNGKey(seed))
    params = reference.init_params(k_params, SIZES)
    for sess in sessions:
        for _ in range(6):
            n = len(sess.steps) + 1
            budget = np.asarray([s[0] for s in sess.steps] + [sess.budget],
                                np.float32)
            shares = np.asarray([s[1] for s in sess.steps] + [sess.shares],
                                np.float32)
            ticks = sess.prices[sess.start:sess.start + SIZES["window"] + n - 1]
            logits = np.asarray(serve_window.reference_logits(
                params, ticks, budget, shares, SIZES, quant))[-1]
            sess.advance(int(logits.argmax()), logits)
    return sessions


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 7])
def test_int8_control_comes_out_not_correct_in_serving(prices, seed):
    sessions = _served_by_reference(prices, seed)
    sound = serve_window.serving_numbers(sessions, seed, SIZES)
    assert correct.judge(sound, toy.TOY_LIMITS_SERVE)[0], sound
    control = serve_window.serving_numbers(sessions, seed, SIZES,
                                           quant=reference.int8_quant)
    assert not correct.judge(control, toy.TOY_LIMITS_SERVE)[0], control
    assert control["logit_err"] > 10 * max(sound["logit_err"], 1e-7)
