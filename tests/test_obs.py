"""Telemetry subsystem (obs/): span trace, metrics export, flight recorder.

The acceptance contract of the obs PR: an obs-enabled run produces a
Perfetto-loadable trace, a metrics JSONL + Prometheus textfile, and a run
manifest; a run killed via the fault seam additionally dumps a forensic
flight-recorder bundle naming the failing chunk; disabled obs writes ZERO
files and keeps the hot loop structurally instrumentation-free. The
satellite surfaces (registry ring caps + counters, batched record_many,
StepTimer history cap, the extended hot-loop lint) are pinned here too.
"""

import json
import os
import time

import numpy as np
import pytest

from sharetrade_tpu.config import FrameworkConfig
from sharetrade_tpu.obs import (
    FlightRecorder,
    Obs,
    SpanTracer,
    build_obs,
    read_trace,
    summarize_run_dir,
)
from sharetrade_tpu.runtime import Orchestrator, Phase, ReplyState
from sharetrade_tpu.utils.metrics import MetricsRegistry
from sharetrade_tpu.utils.profiling import StepTimer

WINDOW = 8
PRICES = np.linspace(10.0, 20.0, 72, dtype=np.float32)  # 64-step episode


def obs_cfg(tmp_path, *, enabled=True, algo="qlearn"):
    cfg = FrameworkConfig()
    cfg.learner.algo = algo
    cfg.env.window = WINDOW
    cfg.model.hidden_dim = 8
    cfg.parallel.num_workers = 4
    cfg.runtime.chunk_steps = 16
    cfg.runtime.checkpoint_every_updates = 32
    cfg.runtime.checkpoint_dir = str(tmp_path / "ckpts")
    cfg.runtime.backoff_initial_s = 0.01
    cfg.runtime.backoff_max_s = 0.05
    cfg.runtime.max_restarts = 2
    cfg.obs.enabled = enabled
    cfg.obs.dir = str(tmp_path / "obs")
    cfg.obs.export_interval_s = 0.1
    return cfg


class TestSpanTracer:
    def test_spans_and_instants_written(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tracer = SpanTracer(path, flush_every=1)
        with tracer.span("alpha", chunk=3):
            time.sleep(0.002)
        tracer.instant("marker", reason="x")
        tracer.close()
        events = read_trace(path)
        # The leading clock event (epoch beside perf_counter), then ours.
        assert [e["name"] for e in events] == ["clock", "alpha", "marker"]
        assert events[0]["ts"] == 0.0
        assert abs(events[0]["args"]["epoch_ns"] / 1e9 - time.time()) < 60
        span = next(e for e in events if e["ph"] == "X")
        assert span["name"] == "alpha"
        assert span["dur"] > 0
        # The Perfetto/chrome trace-event required keys per event.
        for ev in events:
            for key in ("name", "ph", "ts", "pid", "tid"):
                assert key in ev
        assert span["args"] == {"chunk": 3}

    def test_unterminated_file_is_loadable(self, tmp_path):
        """Crash realism: the writer never appends the closing bracket —
        the spec makes it optional, and read_trace must cope."""
        path = str(tmp_path / "trace.jsonl")
        tracer = SpanTracer(path, flush_every=1)
        with tracer.span("s"):
            pass
        tracer.flush()   # no close(): simulates a killed process
        raw = open(path).read()
        assert raw.startswith("[") and not raw.rstrip().endswith("]")
        assert read_trace(path)[1]["name"] == "s"

    def test_disabled_writes_nothing_and_hands_back_the_bare_annotation(
            self, tmp_path):
        import jax
        tracer = SpanTracer(None)
        # No wrapper object: the profiler annotation itself, inert while
        # no profiler session runs.
        assert type(tracer.span("x")) is jax.profiler.TraceAnnotation
        with tracer.span("x"):
            pass
        tracer.instant("y")
        tracer.close()
        assert list(tmp_path.iterdir()) == []


class TestMetricsRegistrySatellites:
    def test_series_ring_cap(self):
        reg = MetricsRegistry(max_points=4)
        for i in range(10):
            reg.record("m", float(i))
        series = reg.series("m")
        assert len(series) == 4
        assert [v for _, v in series] == [6.0, 7.0, 8.0, 9.0]
        assert reg.latest("m") == 9.0

    def test_unbounded_when_cap_disabled(self):
        reg = MetricsRegistry(max_points=0)
        for i in range(10):
            reg.record("m", float(i))
        assert len(reg.series("m")) == 10

    def test_record_many_single_timestamp(self):
        """One lock/one clock read per row: every key in a record_many batch
        carries the identical timestamp."""
        reg = MetricsRegistry()
        reg.record_many({"a": 1.0, "b": 2.0, "c": 3.0})
        stamps = {reg.series(k)[0][0] for k in ("a", "b", "c")}
        assert len(stamps) == 1
        assert reg.snapshot() == {"a": 1.0, "b": 2.0, "c": 3.0}

    def test_counters_monotonic_and_separate_from_gauges(self):
        reg = MetricsRegistry()
        assert reg.inc("restarts_total") == 1.0
        assert reg.inc("restarts_total", 2) == 3.0
        reg.record("gauge", 5.0)
        assert reg.counters() == {"restarts_total": 3.0}
        assert "restarts_total" not in reg.snapshot()


class TestStepTimerCap:
    def test_history_ring_bounded_summary_exact(self):
        t = StepTimer(chunk_steps=10, num_agents=2, max_history=3)
        for _ in range(8):
            t.tick()
        assert len(t.history) == 3          # ring evicted the old entries
        s = t.summary()
        assert s["chunks_timed"] == 7.0     # ...but totals saw every tick
        assert s["total_seconds"] > 0

    def test_uncapped_default_keeps_list_behavior(self):
        t = StepTimer(chunk_steps=10, num_agents=2)
        for _ in range(5):
            t.tick()
        assert len(t.history) == 4


class TestFlightRecorder:
    def test_ring_capacity_and_dump_bundle(self, tmp_path):
        fr = FlightRecorder(capacity=3)
        for i in range(6):
            fr.record("chunk_metrics", chunk=i, loss=float(i))
        fr.record("lifecycle", frm="training", to="failed")
        path = fr.dump(str(tmp_path / "bundle.json"), reason="test",
                       error="boom")
        bundle = json.load(open(path))
        assert bundle["reason"] == "test"
        assert bundle["failing_chunk"] == 5   # newest chunk_metrics record
        assert bundle["context"] == {"error": "boom"}
        assert len(bundle["events"]) == 3     # ring bound, not the 7 records


class TestObsRun:
    def test_enabled_run_produces_all_artifacts(self, tmp_path):
        cfg = obs_cfg(tmp_path)
        orch = Orchestrator(cfg)
        orch.send_training_data(PRICES)
        orch.start_training(background=False)
        assert orch.is_everything_done().state is ReplyState.COMPLETED
        orch.stop()

        run_dir = cfg.obs.dir
        names = sorted(os.listdir(run_dir))
        assert names == ["manifest.json", "metrics.jsonl", "metrics.prom",
                         "trace.jsonl"]

        manifest = json.load(open(os.path.join(run_dir, "manifest.json")))
        assert manifest["config_hash"]
        assert manifest["backend"]
        assert manifest["config"]["runtime"]["chunk_steps"] == 16

        events = read_trace(os.path.join(run_dir, "trace.jsonl"))
        span_names = {e["name"] for e in events if e["ph"] == "X"}
        # The orchestrator phase decomposition the ISSUE names.
        assert {"train/dispatch", "train/readback", "train/host_process",
                "checkpoint_save"} <= span_names
        assert "phase:completed" in {
            e["name"] for e in events if e["ph"] == "i"}

        lines = [json.loads(ln) for ln in
                 open(os.path.join(run_dir, "metrics.jsonl"))]
        assert lines and "env_steps" in lines[-1]["gauges"]
        assert lines[-1]["counters"]["episodes_completed_total"] == 1.0
        prom = open(os.path.join(run_dir, "metrics.prom")).read()
        assert "# TYPE sharetrade_env_steps gauge" in prom
        assert "sharetrade_episodes_completed_total 1.0" in prom

        summary = summarize_run_dir(run_dir)
        assert summary["manifest"]["config_hash"] == manifest["config_hash"]
        assert summary["trace"]["train/dispatch"]["count"] >= 1
        assert summary["metrics"]["prom_file"]
        assert "flight_recorder" not in summary   # healthy run: no bundle

    def test_disabled_means_zero_files(self, tmp_path):
        cfg = obs_cfg(tmp_path, enabled=False)
        orch = Orchestrator(cfg)
        # Structural zero-cost: inert facade, the bare (inactive) profiler
        # annotation, no training histogram, and the run dir is never even
        # created.
        import jax
        assert not orch.obs.enabled
        assert (type(orch.obs.span("train/dispatch"))
                is jax.profiler.TraceAnnotation)
        assert not [n for n in orch.metrics.histograms()
                    if n.startswith("train_")]
        orch.send_training_data(PRICES)
        orch.start_training(background=False)
        assert orch.is_everything_done().state is ReplyState.COMPLETED
        orch.stop()
        assert not os.path.exists(cfg.obs.dir)

    def test_flight_recorder_dumped_on_supervision_trip(self, tmp_path):
        """Fault-injection acceptance: killing the run via fault_hook must
        leave a bundle naming the failing chunk, carrying the last-K chunk
        metric rows and the worker_failed event.

        Checkpointing is OFF and the restart budget zero: the non-slow tier
        deliberately avoids the CPU checkpoint save/restore interleavings
        (the known writer-thread wobble every supervision test in
        test_runtime.py quarantines under `slow`), so the single trip kills
        the run deterministically; the heal-and-complete restore variant
        below is slow-marked for the same reason."""
        cfg = obs_cfg(tmp_path)
        cfg.runtime.checkpoint_every_updates = 0
        cfg.runtime.max_restarts = 0

        def chaos(chunk_idx, metrics):
            if chunk_idx == 2:
                raise RuntimeError("injected PoisonPill")

        orch = Orchestrator(cfg, fault_hook=chaos)
        orch.send_training_data(PRICES)
        orch.start_training(background=False)
        assert orch.lifecycle.phase is Phase.FAILED
        orch.stop()
        bundle = json.load(open(
            os.path.join(cfg.obs.dir, "flight_recorder.json")))
        assert bundle["reason"] == "supervision"
        assert bundle["failing_chunk"] == 2
        assert bundle["context"]["verb"] == "restart"
        rows = [e for e in bundle["events"] if e["kind"] == "chunk_metrics"]
        assert [r["chunk"] for r in rows] == [0, 1, 2]  # last-K incl. failer
        assert all("loss" in r and "env_steps" in r for r in rows)
        failed = [e for e in bundle["events"]
                  if e["kind"] == "event" and e["event"] == "worker_failed"]
        assert failed and "PoisonPill" in failed[0]["error"]
        assert summarize_run_dir(cfg.obs.dir)[
            "flight_recorder"]["failing_chunk"] == 2

    @pytest.mark.slow
    def test_heal_and_complete_keeps_bundle(self, tmp_path):
        """The restore path end to end: trip → dump → checkpoint restore →
        heal → COMPLETED, bundle left behind. Slow tier, like every other
        restore-exercising supervision test (CPU restore interleavings)."""
        cfg = obs_cfg(tmp_path)
        fail_at = {2}

        def chaos(chunk_idx, metrics):
            if chunk_idx in fail_at:
                fail_at.discard(chunk_idx)   # fire once, not on the replay
                raise RuntimeError("injected PoisonPill")

        orch = Orchestrator(cfg, fault_hook=chaos)
        orch.send_training_data(PRICES)
        orch.start_training(background=False)
        assert orch.is_everything_done().state is ReplyState.COMPLETED
        orch.stop()
        bundle = json.load(open(
            os.path.join(cfg.obs.dir, "flight_recorder.json")))
        assert bundle["reason"] == "supervision"
        assert bundle["context"]["verb"] == "restart"
        # The CPU replay can wobble into a second trip after the restore
        # (the latest bundle wins), so pin the invariants, not the count:
        # a real failing chunk is named and the bundle matches the summary.
        assert bundle["failing_chunk"] >= 2
        assert summarize_run_dir(cfg.obs.dir)["flight_recorder"]["events"] \
            == len(bundle["events"])

    def test_flight_recorder_knob_off_means_no_bundle(self, tmp_path):
        """obs.flight_recorder=false disables the ring AND the dump — a
        failing run leaves the other artifacts but no bundle."""
        cfg = obs_cfg(tmp_path)
        cfg.obs.flight_recorder = False
        cfg.runtime.checkpoint_every_updates = 0   # non-slow-tier rule
        cfg.runtime.max_restarts = 0

        def always_fail(chunk_idx, metrics):
            raise RuntimeError("persistent failure")

        orch = Orchestrator(cfg, fault_hook=always_fail)
        assert not orch.obs._flight_on
        orch.send_training_data(PRICES)
        orch.start_training(background=False)
        assert orch.lifecycle.phase is Phase.FAILED
        orch.stop()
        assert not os.path.exists(
            os.path.join(cfg.obs.dir, "flight_recorder.json"))
        assert os.path.isfile(os.path.join(cfg.obs.dir, "trace.jsonl"))
        assert orch.obs.flight.snapshot() == []   # ring never fed

    def test_fatal_run_keeps_bundle_and_counters(self, tmp_path):
        # Checkpointing off: the one restart recovers via the REINIT path
        # (no checkpoint to restore, no writer threads — the non-slow-tier
        # rule above), which still exercises dump → backoff warning →
        # recovery → second dump → budget exhausted.
        cfg = obs_cfg(tmp_path)
        cfg.runtime.checkpoint_every_updates = 0
        cfg.runtime.max_restarts = 1

        def always_fail(chunk_idx, metrics):
            raise RuntimeError("persistent failure")

        orch = Orchestrator(cfg, fault_hook=always_fail)
        orch.send_training_data(PRICES)
        orch.start_training(background=False)
        assert orch.lifecycle.phase is Phase.FAILED
        orch.stop()
        bundle = json.load(open(
            os.path.join(cfg.obs.dir, "flight_recorder.json")))
        assert bundle["failing_chunk"] == 0
        # Lifecycle transitions and WARNING+ logs rode along in the ring.
        kinds = {e["kind"] for e in bundle["events"]}
        assert {"chunk_metrics", "lifecycle", "event", "log"} <= kinds
        # The exporter's final drain captured the monotonic counters.
        prom = open(os.path.join(cfg.obs.dir, "metrics.prom")).read()
        assert "sharetrade_restarts_total 2.0" in prom


class TestCliObs:
    def test_obs_command_summarizes_run_dir(self, tmp_path, capsys):
        from sharetrade_tpu import cli
        cfg = obs_cfg(tmp_path)
        orch = Orchestrator(cfg)
        orch.send_training_data(PRICES)
        orch.start_training(background=False)
        orch.stop()
        assert cli.main(["obs", "--dir", cfg.obs.dir]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["manifest"]["config_hash"]
        assert out["trace"]["train/dispatch"]["count"] >= 1

    def test_obs_command_rejects_missing_dir(self, tmp_path):
        from sharetrade_tpu import cli
        assert cli.main(["obs", "--dir", str(tmp_path / "nope")]) == 1


class TestLintExtension:
    def test_lints_pass_on_tree(self):
        import importlib.util
        import pathlib
        tool = (pathlib.Path(__file__).resolve().parent.parent
                / "tools" / "lint_hot_loop.py")
        spec = importlib.util.spec_from_file_location("lint_hot_loop", tool)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.lint_device_host_calls() == []
        bad, found = mod.lint_hot_loop_syncs()
        assert bad == [] and found == {"_run_supervised"}

    def test_jit_pattern_semantics(self):
        import importlib.util
        import pathlib
        tool = (pathlib.Path(__file__).resolve().parent.parent
                / "tools" / "lint_hot_loop.py")
        spec = importlib.util.spec_from_file_location("lint_hot_loop2", tool)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        hits = mod.JIT_PATTERN.search
        assert hits("t = time.time()")
        assert hits("log.info('x')")
        assert hits("print(x)")
        assert not hits("jax.debug.print('{}', x)")   # sanctioned in-jit
        assert not hits("pprint(x)")


class TestObsFacade:
    def test_build_obs_disabled_creates_nothing(self, tmp_path):
        cfg = obs_cfg(tmp_path, enabled=False)
        obs = build_obs(cfg, MetricsRegistry())
        assert isinstance(obs, Obs) and not obs.enabled
        obs.record("chunk_metrics", chunk=1)     # dropped, not buffered
        assert obs.dump_flight(reason="x") is None
        obs.flush()
        obs.close()
        assert not os.path.exists(cfg.obs.dir)

    def test_log_handler_detached_on_close(self, tmp_path):
        import logging
        cfg = obs_cfg(tmp_path)
        root = logging.getLogger("sharetrade")
        before = list(root.handlers)
        obs = build_obs(cfg, MetricsRegistry())
        assert len(root.handlers) == len(before) + 1
        obs.close()
        assert root.handlers == before


class TestPreemptionObs:
    def test_preemption_artifacts(self, tmp_path):
        """Satellite contract of the durability PR: a preempted obs-enabled
        run leaves a `preemption_drain` span, an `emergency_checkpoint`
        instant, and a flight bundle with reason "preemption"."""
        cfg = obs_cfg(tmp_path)
        cfg.runtime.episodes = 200          # long run: cannot complete
        orch = Orchestrator(cfg)
        orch.send_training_data(PRICES)
        orch.start_training(background=True)
        deadline = time.monotonic() + 30
        while not orch.snapshot() and time.monotonic() < deadline:
            time.sleep(0.02)
        orch.request_preempt()
        assert orch.wait(timeout=30)
        assert orch.preempted
        orch.stop()

        events = read_trace(os.path.join(cfg.obs.dir, "trace.jsonl"))
        names = {e["name"] for e in events}
        assert "preemption_drain" in names
        assert "emergency_checkpoint" in names
        bundle = json.load(open(os.path.join(cfg.obs.dir,
                                             "flight_recorder.json")))
        assert bundle["reason"] == "preemption"

    def test_restore_fallback_counters_exported(self, tmp_path):
        """The walk-back counters flow through the existing exporter into
        the Prometheus textfile."""
        cfg = obs_cfg(tmp_path)
        from sharetrade_tpu.runtime import run_end_to_end
        orch = run_end_to_end(cfg, PRICES)
        assert orch.is_everything_done().state is ReplyState.COMPLETED
        orch.stop()
        ckpt_dir = cfg.runtime.checkpoint_dir
        newest = sorted(n for n in os.listdir(ckpt_dir)
                        if n.startswith("ckpt_"))[-1]
        from test_checkpoint import _bitflip   # the one corruption helper
        _bitflip(os.path.join(ckpt_dir, newest, "state.msgpack"))

        cfg2 = obs_cfg(tmp_path)
        cfg2.obs.dir = str(tmp_path / "obs2")
        orch2 = Orchestrator(cfg2)
        orch2.send_training_data(PRICES, resume=True)
        orch2.obs.flush()
        prom = open(os.path.join(cfg2.obs.dir, "metrics.prom")).read()
        assert "sharetrade_ckpt_restore_fallbacks_total 1" in prom
        assert "sharetrade_ckpt_quarantined_total 1" in prom
        orch2.stop()
