"""Roofline telemetry (obs/roofline.py).

What this file pins, per the roofline PR's acceptance criteria:

- golden compiled-cost capture on the reference-shape MLP (CPU backend):
  XLA FLOPs, trip-count corrected, land within the analytic model's band;
- the live gauges (``mfu``/``achieved_tflops``/``hbm_gbps``/
  ``arithmetic_intensity``) reach the Prometheus textfile during an
  obs-enabled training run with ``obs.roofline=true``;
- ``obs.roofline=false`` (the default) produces ZERO roofline artifacts
  and no gauges — the knob is inert until asked for;
- the analytic-vs-XLA discrepancy warning fires (flight ring + log) on a
  deliberately wrong analytic count;
- the compile-time-only lint (tools/lint_hot_loop.py check 6) stays
  green on the shipped tree.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sharetrade_tpu.config import FrameworkConfig
from sharetrade_tpu.obs.roofline import (
    ARTIFACT,
    RooflineCapture,
    read_roofline,
    summarize_roofline,
)
from sharetrade_tpu.runtime import Orchestrator
from sharetrade_tpu.utils.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def _cfg(tmp_path, *, roofline: bool = True, megachunk: int = 1,
         hidden: int = 200) -> FrameworkConfig:
    """Reference-shape-flavored qlearn config (10 workers, h=200 MLP by
    default — the shape whose matmuls dominate enough for the golden
    cross-check), shrunk to a seconds-long CPU episode."""
    cfg = FrameworkConfig()
    cfg.learner.algo = "qlearn"
    cfg.parallel.num_workers = 10
    cfg.model.hidden_dim = hidden
    cfg.env.window = 8
    cfg.runtime.chunk_steps = 16
    cfg.runtime.megachunk_factor = megachunk
    cfg.runtime.metrics_every_chunks = 2
    cfg.runtime.checkpoint_dir = str(tmp_path / "ckpts")
    cfg.obs.enabled = True
    cfg.obs.roofline = roofline
    cfg.obs.dir = str(tmp_path / "obs")
    cfg.obs.export_interval_s = 0.1
    return cfg


def _train(cfg: FrameworkConfig, *, steps: int = 200) -> Orchestrator:
    orch = Orchestrator(cfg)
    orch.send_training_data(np.linspace(10.0, 20.0, steps,
                                        dtype=np.float32))
    orch.start_training(background=False)
    orch.stop()
    return orch


def test_golden_cost_capture_reference_mlp(tmp_path):
    """The tentpole's golden row: the captured chunk program's FLOPs are
    real numbers (trip-count corrected, not the loop-body-once HLO raw
    count) and agree with the analytic utils/flops.py model within the
    discrepancy band on the matmul-dominated reference MLP."""
    cfg = _cfg(tmp_path)
    _train(cfg)
    bundle = read_roofline(cfg.obs.dir)
    assert bundle is not None
    assert bundle["schema_version"] == 1
    # The CPU has no published peak: nothing relative to one is recorded
    # ("not measured"), never a figure relative to some other chip.
    assert bundle["peak_flops_per_s"] is None
    assert bundle["ridge_flops_per_byte"] is None
    chunk = bundle["programs"]["chunk"]
    assert chunk["flops"] > 0
    assert chunk["bytes_accessed"] > 0
    # Trip-count correction: the per-dispatch number must be the raw HLO
    # count scaled by the chunk's scan length (XLA counts loop bodies
    # once; obs/roofline.py probes and corrects).
    assert chunk["trip_count_corrected"]
    assert chunk["loop_iterations"] == cfg.runtime.chunk_steps
    assert chunk["flops"] == chunk["flops_hlo_once"] * cfg.runtime.chunk_steps
    # Golden cross-check: XLA within ±25% of the analytic model at h=200
    # (measured ~0.97 on the CPU backend; a drift past the band means one
    # of the countings broke).
    assert chunk["analytic_flops"] > 0
    assert not chunk["discrepancy"], (
        f"XLA vs analytic ratio {chunk['xla_vs_analytic']}")
    assert 0.75 <= chunk["xla_vs_analytic"] <= 1.25
    # Agreement keeps the measured XLA count as the gauge source.
    assert chunk["gauge_flops_source"] == "xla"
    assert chunk["gauge_flops"] == chunk["flops"]
    assert chunk["classification"] is None      # no ridge on this device
    assert chunk["arithmetic_intensity"] == pytest.approx(
        chunk["flops"] / chunk["bytes_accessed"])


def test_megachunk_program_captured(tmp_path):
    cfg = _cfg(tmp_path, megachunk=2)
    _train(cfg)
    bundle = read_roofline(cfg.obs.dir)
    programs = bundle["programs"]
    assert set(programs) == {"chunk", "megachunk_k2"}
    mega = programs["megachunk_k2"]
    assert mega["megachunk_factor"] == 2
    assert mega["loop_iterations"] == 2 * cfg.runtime.chunk_steps
    # The fused program does K chunks' work: per-dispatch FLOPs ~2x the
    # single-chunk program (identical body, twice the iterations).
    ratio = mega["flops"] / programs["chunk"]["flops"]
    assert 1.8 <= ratio <= 2.2


def test_gauges_reach_prometheus_textfile(tmp_path):
    """Acceptance: achieved_tflops/hbm_gbps exported via the existing
    Prometheus textfile during a CPU training run with obs.roofline — and
    the figures that need a published peak (mfu, the bound
    classification) are NOT: the CPU has none, so they are "not
    measured" rather than stated against another chip's peak."""
    cfg = _cfg(tmp_path, megachunk=2)
    orch = _train(cfg)
    prom = open(os.path.join(cfg.obs.dir, "metrics.prom")).read()
    for gauge in ("sharetrade_achieved_tflops", "sharetrade_hbm_gbps",
                  "sharetrade_arithmetic_intensity"):
        assert f"# TYPE {gauge} gauge" in prom, f"{gauge} missing"
    for gauge in ("sharetrade_mfu", "sharetrade_roofline_compute_bound"):
        assert gauge not in prom, f"{gauge} stated without a known peak"
    # And they are live numbers, not placeholders.
    assert orch.metrics.latest("mfu") is None
    assert orch.metrics.latest("achieved_tflops") > 0
    assert orch.metrics.latest("hbm_gbps") > 0


def test_off_by_default_zero_artifacts(tmp_path):
    """obs.roofline=false (the default): no roofline.json, no gauges, no
    capture object — the rest of obs/ unaffected."""
    cfg = _cfg(tmp_path, roofline=False)
    assert FrameworkConfig().obs.roofline is False   # the default
    orch = _train(cfg)
    assert orch.obs.roofline is None
    assert not os.path.exists(os.path.join(cfg.obs.dir, ARTIFACT))
    assert orch.metrics.latest("mfu") is None
    prom = open(os.path.join(cfg.obs.dir, "metrics.prom")).read()
    assert "sharetrade_mfu" not in prom
    # The non-roofline obs surfaces still ran.
    assert os.path.isfile(os.path.join(cfg.obs.dir, "metrics.jsonl"))


def test_discrepancy_warning_fires_on_wrong_analytic(tmp_path):
    """A deliberately wrong analytic count must warn through the flight
    recorder and mark the program's artifact row."""
    from sharetrade_tpu.obs.flight import FlightRecorder

    flight = FlightRecorder(16)
    cap = RooflineCapture(MetricsRegistry(), str(tmp_path),
                          flight_record=flight.record)
    cap.steps_per_chunk = 4
    cap.analytic_flops_per_chunk = 1.0        # absurdly wrong on purpose

    def step(x):
        def body(c, _):
            return c @ c, None
        c, _ = jax.lax.scan(body, x, None, length=4)
        return c

    cost = cap.capture(jax.jit(step), (jnp.ones((16, 16)),))
    assert cost is not None and cost.discrepancy
    # The warning lands in the flight ring (the RingLogHandler mirrors
    # WARNING+ logs there in a real run; here the direct record is the
    # contract): a later forensic dump names the miscounted program.
    events = [e for e in flight.snapshot()
              if e["kind"] == "roofline_discrepancy"]
    assert events and events[0]["program"] == "chunk"
    assert events[0]["ratio"] == pytest.approx(cost.xla_vs_analytic)
    # On disagreement the live gauges switch to the analytic count (the
    # model-FLOPs MFU convention): a structurally mis-corrected XLA
    # number must not inflate the MFU gauge ~150x, as the flagship
    # episode-PPO program otherwise would (its trunk/replay FLOPs live
    # outside the chunk-steps scan).
    assert cost.gauge_flops_source == "analytic"
    assert cost.gauge_flops == cost.analytic_flops
    # And the artifact records the mismatch for post-hoc forensics.
    bundle = read_roofline(str(tmp_path))
    assert bundle["programs"]["chunk"]["discrepancy"] is True


def test_multichip_analytic_is_per_device():
    """cost_analysis() describes ONE device's partition of an SPMD
    program; the analytic (global) model must be divided by the mesh size
    before the cross-check, or every multichip run false-alarms."""
    cap = RooflineCapture(MetricsRegistry(), None,
                          peak_flops=1e12, peak_hbm_bw=1e9)
    cap._trip_blind = True
    cap.steps_per_chunk = 10
    cap.analytic_flops_per_chunk = 8000.0   # global work, 8 devices
    costs = {"flops": 100.0, "bytes_accessed": 100.0,
             "argument_bytes": None, "temp_bytes": None,
             "output_bytes": None}
    cost = cap._build_cost("chunk", 1, costs, devices=8)
    assert cost.devices == 8
    # corrected per-device XLA = 100*10 = 1000; analytic/8 = 1000.
    assert cost.analytic_flops == pytest.approx(1000.0)
    assert cost.xla_vs_analytic == pytest.approx(1.0)
    assert not cost.discrepancy


def test_mesh_cost_hook_passes_device_count():
    """The jit_parallel_step seam hands the mesh size to the capture (the
    forced-8-device CPU mesh, the shard-audit platform)."""
    import numpy as np
    from jax.sharding import Mesh

    from sharetrade_tpu.agents import build_agent
    from sharetrade_tpu.env import trading
    from sharetrade_tpu.parallel import jit_parallel_step

    cfg = FrameworkConfig()
    cfg.learner.algo = "qlearn"
    cfg.env.window = 8
    cfg.model.hidden_dim = 8
    cfg.parallel.num_workers = 8
    cfg.runtime.chunk_steps = 4
    env = trading.env_from_prices(jnp.linspace(10.0, 20.0, 64),
                                  window=cfg.env.window)
    agent = build_agent(cfg, env)
    devices = np.asarray(jax.devices("cpu")[:8])
    mesh = Mesh(devices, ("dp",))
    cap = RooflineCapture(MetricsRegistry(), None)
    cap.steps_per_chunk = cfg.runtime.chunk_steps
    ts = agent.init(jax.random.PRNGKey(0))
    jit_parallel_step(agent, mesh, ts, cost_hook=cap.capture)
    assert cap.programs["chunk"].devices == 8


def test_capture_failure_degrades_not_raises(tmp_path):
    cap = RooflineCapture(MetricsRegistry(), str(tmp_path))
    assert cap.capture(object(), ()) is None   # not a jitted fn: swallowed


def test_on_boundary_without_capture_is_noop():
    reg = MetricsRegistry()
    cap = RooflineCapture(reg, None)
    cap.on_boundary(k=1, chunk_seconds=0.1)    # nothing captured yet
    cap.on_boundary(k=1, chunk_seconds=None)   # first tick has no timing
    assert reg.snapshot() == {}


def test_cli_obs_summarizes_roofline_and_counters(tmp_path, capsys):
    from sharetrade_tpu import cli

    cfg = _cfg(tmp_path, megachunk=2)
    _train(cfg)
    assert cli.main(["obs", "--dir", cfg.obs.dir]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert "roofline" in summary
    roof = summary["roofline"]
    assert roof["programs"] == 2
    # No published peak for the CPU: the programs are listed, unclassified.
    assert roof["compute_bound"] == roof["memory_bound"] == []
    named = [p["program"] for p in roof["unclassified"]]
    assert set(named) == {"chunk", "megachunk_k2"}
    # Counter totals surfaced (the cli-obs satellite): totals dict plus
    # the explicit pipeline health number.
    assert "counters" in summary["metrics"]
    assert "pipeline_stalls_total" in summary["metrics"]


def test_summarize_roofline_orders_by_flops():
    bundle = {
        "schema_version": 1, "ridge_flops_per_byte": 240.0,
        "programs": {
            "a": {"flops": 10.0, "bytes_accessed": 1.0,
                  "arithmetic_intensity": 10.0,
                  "classification": "memory-bound"},
            "b": {"flops": 1000.0, "bytes_accessed": 1.0,
                  "arithmetic_intensity": 1000.0,
                  "classification": "compute-bound"},
        },
    }
    s = summarize_roofline(bundle)
    assert s["compute_bound"][0]["program"] == "b"
    assert s["memory_bound"][0]["program"] == "a"


def test_roofline_lint_green():
    """tools/lint_hot_loop.py check 6 on the shipped tree: no capture
    sites in the dispatcher or traced closures."""
    import lint_hot_loop

    assert lint_hot_loop.lint_roofline_capture() == []


def test_shard_audit_manifest_has_roofline_rows():
    """The manifest the audit gates against carries FLOPs/HBM rows for
    every config in the matrix (regenerated with --update)."""
    with open(os.path.join(REPO, "tools",
                           "shard_audit_manifest.json")) as f:
        manifest = json.load(f)
    for name, entry in manifest["configs"].items():
        cost = entry.get("cost")
        assert cost, f"{name} missing roofline cost row"
        assert cost.get("flops", 0) > 0, f"{name} flops not recorded"
        assert cost.get("hbm_peak_bytes", 0) > 0


# ---------------------------------------------------------------------------
# peaks: a device that is not in the table is an error, not a v5e default
# ---------------------------------------------------------------------------

def test_unknown_device_kind_raises_known_kind_resolves():
    from types import SimpleNamespace

    from sharetrade_tpu.utils.flops import (UnknownDeviceKind,
                                            chip_peak_flops,
                                            chip_peak_hbm_bw)

    v5e = SimpleNamespace(device_kind="TPU v5 lite")
    assert chip_peak_flops(v5e) == 197e12
    assert chip_peak_hbm_bw(v5e) == 819e9
    for fn in (chip_peak_flops, chip_peak_hbm_bw):
        with pytest.raises(UnknownDeviceKind, match="cpu"):
            fn(jax.devices("cpu")[0])
        with pytest.raises(UnknownDeviceKind, match="TPU v9"):
            fn(SimpleNamespace(device_kind="TPU v9"))


def test_known_peaks_still_publish_utilisation():
    """With the chip's peaks known (passed here; looked up by device_kind
    on a TPU) the peak-relative gauges are live: mfu and the bound."""
    reg = MetricsRegistry()
    cap = RooflineCapture(reg, None, peak_flops=1e12, peak_hbm_bw=1e9)
    cap._trip_blind = False
    costs = {"flops": 4e9, "bytes_accessed": 1e6, "argument_bytes": None,
             "temp_bytes": None, "output_bytes": None}
    cost = cap._build_cost("chunk", 1, costs)
    assert cost.classification == "compute-bound"      # AI 4000 >= ridge 1000
    cap._by_factor[1] = cost
    cap.on_boundary(k=1, chunk_seconds=0.1)
    assert reg.latest("mfu") == pytest.approx(4e10 / 1e12)
    assert reg.latest("roofline_compute_bound") == 1.0
