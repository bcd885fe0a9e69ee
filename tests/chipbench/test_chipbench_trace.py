"""The trace reduction on a slice recorded on the chip (train_d1024, PR 23:
1.5 ms around the first flash-attention forward kernel of a replay pass)."""

import json
import os

import pytest

import chipbench_toy as toy
from chipbench.harness import common, trace_reduce
from chipbench.harness.peaks import peaks_for
from chipbench.models import episode_transformer
from chipbench.readers import device, kernels

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "train_d1024_trace_slice.json")
D1024 = toy.D1024


@pytest.fixture(scope="module")
def rows():
    with open(FIXTURE) as fh:
        return json.load(fh)


def test_busy_window_and_self_time_sums(rows):
    summary = trace_reduce.reduce_events(rows)
    ops = [r for r in rows if r[1] == trace_reduce.OPS_LINE]
    lo = min(r[3] for r in ops)
    hi = max(r[3] + r[4] for r in ops)
    assert summary.devices == 1
    assert summary.window_s == pytest.approx((hi - lo) / 1e9)
    assert 0 < summary.busy_s <= summary.window_s
    # self times partition the busy time: nothing is counted twice
    assert sum(summary.op_seconds.values()) == pytest.approx(summary.busy_s)
    assert sum(summary.op_counts.values()) == len(ops)
    assert device.idle_share({"trace": summary}) == pytest.approx(
        100.0 * (1 - summary.busy_s / summary.window_s))


def test_kernel_patterns_find_the_flash_kernels(rows):
    summary = trace_reduce.reduce_events(rows)
    args = common.Manifest().reader("attention_roofline")[1]
    seconds, events = summary.matching(args["forward"])
    wanted = [r for r in rows if r[1] == trace_reduce.OPS_LINE
              and r[2].startswith(("%jvp__", "%step"))
              and " custom-call(" in r[2]]
    assert events == len(wanted) >= 1
    assert seconds == pytest.approx(sum(r[4] for r in wanted) / 1e9)
    share = kernels.attention_roofline(
        {"trace": summary, "sizes": D1024, "model": episode_transformer,
         "peaks": peaks_for("TPU v5 lite")}, **args)
    assert 0 < share < 100


def test_nested_events_count_self_time_once():
    rows = [["/device:TPU:0", "XLA Ops", "%while.1 = x", 0, 100],
            ["/device:TPU:0", "XLA Ops", "%fusion.1 = y", 10, 30],
            ["/device:TPU:0", "XLA Ops", "%fusion.2 = z", 50, 20],
            ["/device:TPU:0", "XLA Ops", "%copy.1 = c", 150, 50],
            ["/host:CPU", "python", "np.asarray(jax.Array)", 90, 80]]
    summary = trace_reduce.reduce_events(rows)
    assert summary.busy_s == pytest.approx(150e-9)
    assert summary.window_s == pytest.approx(200e-9)
    assert summary.op_seconds["%while.1 = x"] == pytest.approx(50e-9)
    assert summary.idle_gaps == [["np.asarray(jax.Array)", 50e-9]]
    assert summary.breakdown()["device_ops"][0] == ["%while.1 x", 50e-9]


def test_program_runs_are_counted_beside_their_seconds():
    rows = [["/device:TPU:0", "XLA Modules", "jit__warm_program(7)", 0, 40],
            ["/device:TPU:0", "XLA Modules", "jit__warm_program(7)", 50, 40],
            ["/device:TPU:0", "XLA Modules", "jit_other(9)", 95, 5],
            ["/device:TPU:0", "XLA Ops", "%fusion.1 = y", 0, 100]]
    summary = trace_reduce.reduce_events(rows)
    assert summary.module_counts == {"jit__warm_program": 2, "jit_other": 1}
    assert summary.module_seconds["jit__warm_program"] == pytest.approx(80e-9)


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events([["/host:CPU", "python", "x", 0, 10]])


def test_a_reader_with_nothing_to_read_returns_nothing():
    summary = trace_reduce.reduce_events(
        [["/device:TPU:0", "XLA Ops", "%fusion.1 = y", 0, 10]])
    args = common.Manifest().reader("attention_roofline")[1]
    assert kernels.attention_roofline(
        {"trace": summary, "sizes": D1024, "model": episode_transformer,
         "peaks": peaks_for("TPU v5 lite")}, **args) is None
