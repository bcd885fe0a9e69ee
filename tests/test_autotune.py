"""Self-tuning runtime (ISSUE 14): tuned profile layer, the online serve
controller's state machine (fake clock, no threads), the adaptive ingest
cadence, lint check 13, and the quick end-to-end sweep.

The controller tests drive :class:`ServeController` through a STUB engine
with a fake clock and synthetic objective series, so the state-machine
contract — bounded step sizes, the hysteresis dead band (no oscillation
on a noisy p99), the overload relax-veto, the rate limit — is pinned
deterministically, independent of host scheduling.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

from sharetrade_tpu import tuning
from sharetrade_tpu.config import ConfigError, FrameworkConfig, ServeConfig
from sharetrade_tpu.obs.hist import Histogram
from sharetrade_tpu.serve.controller import ServeController
from sharetrade_tpu.serve.engine import _LiveKnobs
from sharetrade_tpu.utils.metrics import MetricsRegistry


# ---------------------------------------------------------------------------
# profile layer
# ---------------------------------------------------------------------------


def _write_profile(tmp_path, knobs, **kw):
    path = str(tmp_path / "tuned_profile.json")
    tuning.write_profile(path, tuning.build_profile(knobs, **kw))
    return path


class TestTunedProfile:
    def test_roundtrip_and_atomic_write(self, tmp_path):
        path = _write_profile(tmp_path, {"serve.batch_timeout_ms": 0.5},
                              seed=3, objectives={"serve": {"qps": 1.0}})
        doc = tuning.load_profile(path)
        assert doc["knobs"] == {"serve.batch_timeout_ms": 0.5}
        assert doc["schema_version"] == tuning.PROFILE_SCHEMA_VERSION
        assert doc["seed"] == 3
        # Atomic publish: no tmp debris next to the profile.
        assert [p.name for p in tmp_path.iterdir()] == [
            "tuned_profile.json"]

    def test_unknown_knob_refused_at_build(self):
        with pytest.raises(tuning.ProfileError, match="unregistered"):
            tuning.build_profile({"serve.nonsense_knob": 1})

    def test_bad_schema_version_refused(self, tmp_path):
        path = str(tmp_path / "p.json")
        doc = tuning.build_profile({"serve.max_queue": 64})
        doc["schema_version"] = 999
        with open(path, "w") as f:
            json.dump(doc, f)
        with pytest.raises(tuning.ProfileError, match="schema_version"):
            tuning.load_profile(path)

    def test_torn_profile_refused(self, tmp_path):
        path = str(tmp_path / "p.json")
        with open(path, "w") as f:
            f.write('{"knobs": {')
        with pytest.raises(tuning.ProfileError, match="unreadable"):
            tuning.load_profile(path)

    def test_missing_profile_loud(self, tmp_path):
        cfg = FrameworkConfig()
        cfg.tuning.profile = str(tmp_path / "absent.json")
        with pytest.raises(tuning.ProfileError, match="not found"):
            tuning.apply_profile(cfg)

    def test_precedence_explicit_beats_profile_beats_default(
            self, tmp_path):
        path = _write_profile(tmp_path, {"serve.batch_timeout_ms": 0.5,
                                         "runtime.megachunk_factor": 8})
        cfg = FrameworkConfig()
        cfg.tuning.profile = path
        cfg.serve.batch_timeout_ms = 7.0        # explicit: must win
        out = tuning.apply_profile(cfg)
        assert out.serve.batch_timeout_ms == 7.0
        assert out.runtime.megachunk_factor == 8    # profile over default
        assert out.serve.max_queue == 1024          # default untouched
        # Idempotent: a second application changes nothing.
        again = tuning.apply_profile(out)
        assert again.to_dict() == out.to_dict()
        desc = tuning.describe(out)
        assert desc["knobs"]["serve.batch_timeout_ms"]["source"] == \
            "explicit"
        assert desc["knobs"]["runtime.megachunk_factor"]["source"] == \
            "profile"
        assert desc["knobs"]["serve.max_queue"]["source"] == "default"

    def test_explicit_override_at_default_value_beats_profile(
            self, tmp_path):
        """`--set serve.max_queue=1024` (the default VALUE) is still an
        explicit operator decision: apply_overrides memoizes the dotted
        path and the profile must not override it — value-equality alone
        cannot see the pin."""
        path = _write_profile(tmp_path, {"serve.max_queue": 128})
        cfg = FrameworkConfig().apply_overrides(
            [f"tuning.profile={path}", "serve.max_queue=1024"])
        out = tuning.apply_profile(cfg)
        assert out.serve.max_queue == 1024
        assert tuning.describe(out)["knobs"]["serve.max_queue"][
            "source"] == "explicit"
        # Without the pin the same profile applies.
        cfg2 = FrameworkConfig().apply_overrides(
            [f"tuning.profile={path}"])
        assert tuning.apply_profile(cfg2).serve.max_queue == 128

    def test_fingerprint_mismatch_refused_loudly(self, tmp_path):
        path = str(tmp_path / "p.json")
        doc = tuning.build_profile({"runtime.megachunk_factor": 4})
        doc["fingerprint"] = dict(doc["fingerprint"], cpu_count=99999)
        tuning.write_profile(path, doc)
        cfg = FrameworkConfig()
        cfg.tuning.profile = path
        with pytest.raises(tuning.ProfileError, match="different host"):
            tuning.apply_profile(cfg)
        # ProfileError is ConfigError: the supervision decider's STOP verb.
        assert issubclass(tuning.ProfileError, ConfigError)
        cfg.tuning.allow_fingerprint_mismatch = True
        assert tuning.apply_profile(cfg).runtime.megachunk_factor == 4

    def test_orchestrator_applies_profile(self, tmp_path):
        from sharetrade_tpu.runtime.orchestrator import Orchestrator
        path = _write_profile(tmp_path, {"runtime.megachunk_factor": 4})
        cfg = FrameworkConfig()
        cfg.tuning.profile = path
        cfg.runtime.checkpoint_dir = str(tmp_path / "ck")
        orch = Orchestrator(cfg)
        try:
            assert orch.cfg.runtime.megachunk_factor == 4
        finally:
            orch.stop()


# ---------------------------------------------------------------------------
# online controller state machine (fake engine, fake clock)
# ---------------------------------------------------------------------------


class FakeEngine:
    """The duck-typed surface ServeController reads/actuates, with the
    REAL engine's clamp semantics (config values are ceilings)."""

    def __init__(self, cfg: ServeConfig):
        self.cfg = cfg
        self.knobs = _LiveKnobs(float(cfg.batch_timeout_ms),
                                int(cfg.max_queue))
        self.registry = MetricsRegistry()
        self.latency_histogram = Histogram()
        self.depth = 0
        self.history: list[_LiveKnobs] = []

    def queue_depth(self) -> int:
        return self.depth

    def set_knobs(self, *, batch_timeout_ms=None, max_queue=None):
        t = min(float(batch_timeout_ms), self.cfg.batch_timeout_ms)
        q = min(int(max_queue), self.cfg.max_queue)
        self.knobs = _LiveKnobs(t, q)
        self.history.append(self.knobs)
        return self.knobs


def make_controller(cfg=None, **kw):
    cfg = cfg or ServeConfig(max_batch=16, slots=64,
                             batch_timeout_ms=8.0, max_queue=512)
    engine = FakeEngine(cfg)
    now = [0.0]
    kw.setdefault("target_p99_ms", 50.0)
    kw.setdefault("interval_s", 1.0)
    ctl = ServeController(engine, clock=lambda: now[0], **kw)
    return engine, ctl, now


def feed_window(engine, p99_ms: float, n: int = 200):
    """Synthesize a completion window whose windowed p99 ~= p99_ms (bulk
    at p99/2, the tail pinned at p99; bucket interpolation keeps the
    estimate within one log-bucket of the intent)."""
    for _ in range(n - max(2, n // 100)):
        engine.latency_histogram.observe(p99_ms * 0.5)
    for _ in range(max(2, n // 100)):
        engine.latency_histogram.observe(p99_ms)


class TestControllerStateMachine:
    def tick(self, engine, ctl, now, p99, dt=1.0):
        now[0] += dt
        if p99 is not None:
            feed_window(engine, p99)
        return ctl.step(now=now[0])

    def test_tighten_is_bounded_per_tick(self):
        engine, ctl, now = make_controller()
        adj = self.tick(engine, ctl, now, 200.0)
        assert adj is not None and adj.action == "tighten"
        # ONE bounded multiplicative step, not a slam to the floor.
        assert adj.batch_timeout_ms == pytest.approx(8.0 * 0.5)
        assert adj.max_queue == 256
        adj2 = self.tick(engine, ctl, now, 200.0)
        assert adj2.batch_timeout_ms == pytest.approx(8.0 * 0.25)
        assert adj2.max_queue == 128

    def test_floors_and_ceilings(self):
        engine, ctl, now = make_controller()
        for _ in range(20):
            self.tick(engine, ctl, now, 500.0)
        assert engine.knobs.batch_timeout_ms == 0.0
        assert engine.knobs.max_queue == 16     # floor = max_batch
        # Recovery relaxes back up, but never past the CONFIG ceilings.
        for _ in range(40):
            self.tick(engine, ctl, now, 1.0)
        assert engine.knobs.batch_timeout_ms == pytest.approx(8.0)
        assert engine.knobs.max_queue == 512

    def test_dead_band_holds(self):
        engine, ctl, now = make_controller()
        # Between rearm (25) and target (50): no action, ever.
        for p99 in (30.0, 45.0, 27.0, 40.0, 35.0):
            assert self.tick(engine, ctl, now, p99) is None
        assert ctl.adjustments == 0

    def test_no_oscillation_on_noisy_p99(self):
        """A noisy p99 hovering around the target must only ever ratchet
        TIGHTER (or hold) — the hysteresis gap means relaxing requires a
        clear recovery below rearm_frac*target, so tighten→relax→tighten
        flapping cannot happen inside the noise band."""
        engine, ctl, now = make_controller()
        rng_series = [48, 53, 47, 52, 49, 55, 46, 51, 44, 56, 48, 53]
        actions = [self.tick(engine, ctl, now, float(p))
                   for p in rng_series]
        assert all(a is None or a.action == "tighten" for a in actions)
        # Knob trajectory is monotone non-increasing through the noise.
        timeouts = [k.batch_timeout_ms for k in engine.history]
        assert timeouts == sorted(timeouts, reverse=True)
        queues = [k.max_queue for k in engine.history]
        assert queues == sorted(queues, reverse=True)

    def test_overload_vetoes_relax(self):
        """With tight admission, a low p99 is the tight knobs' doing:
        relaxing while the window still shed would re-inflate the tail
        (the oscillation the veto kills)."""
        engine, ctl, now = make_controller()
        self.tick(engine, ctl, now, 200.0)      # tighten once
        tightened = engine.knobs
        # Low p99 but the window saw sheds: must HOLD, not relax.
        engine.registry.inc("serve_shed_total", 50)
        assert self.tick(engine, ctl, now, 5.0) is None
        assert engine.knobs == tightened
        # Same low p99 with a clean window: NOW it relaxes.
        adj = self.tick(engine, ctl, now, 5.0)
        assert adj is not None and adj.action == "relax"

    def test_rate_limit_one_adjustment_per_interval(self):
        engine, ctl, now = make_controller()
        self.tick(engine, ctl, now, 200.0, dt=1.0)
        # A second call 0.1s later must not act (and must not consume
        # the histogram window).
        assert self.tick(engine, ctl, now, 200.0, dt=0.1) is None
        assert ctl.adjustments == 1

    def test_no_signal_holds(self):
        engine, ctl, now = make_controller()
        now[0] += 1.0
        assert ctl.step(now=now[0]) is None     # empty window: hold
        assert ctl.adjustments == 0

    def test_adjustments_visible_as_gauges_and_counters(self):
        engine, ctl, now = make_controller()
        self.tick(engine, ctl, now, 200.0)
        counters = engine.registry.counters()
        assert counters["serve_controller_adjustments_total"] == 1
        snap = engine.registry.snapshot()
        assert snap["serve_controller_p99_ms"] > 50.0
        assert snap["serve_controller_target_p99_ms"] == 50.0

    def test_bad_params_refused(self):
        engine = FakeEngine(ServeConfig())
        with pytest.raises(ConfigError):
            ServeController(engine, target_p99_ms=0.0)
        with pytest.raises(ConfigError):
            ServeController(engine, target_p99_ms=50.0, interval_s=0.0)
        with pytest.raises(ConfigError):
            ServeController(engine, target_p99_ms=50.0, shrink=1.5)


# ---------------------------------------------------------------------------
# engine live knobs (the real ServeEngine)
# ---------------------------------------------------------------------------


class TestEngineLiveKnobs:
    @pytest.fixture(scope="class")
    def engine(self):
        import serve_soak
        from sharetrade_tpu.serve import ServeEngine
        model, params, _, _ = serve_soak.build_workload(
            mlp=True, window=8, length=256)
        engine = ServeEngine(
            model, ServeConfig(max_batch=4, slots=16,
                               batch_timeout_ms=5.0, max_queue=64,
                               swap_poll_s=0.0), params)
        yield engine
        engine.stop(drain=False)

    def test_config_is_the_ceiling(self, engine):
        new = engine.set_knobs(batch_timeout_ms=500.0, max_queue=10_000)
        assert new.batch_timeout_ms == 5.0      # clamped to config
        assert new.max_queue == 64
        new = engine.set_knobs(batch_timeout_ms=1.0, max_queue=8)
        assert new == engine.knobs == _LiveKnobs(1.0, 8)
        # The physical ingress bound follows the knob.
        assert engine._q.maxsize == 8
        snap = engine.registry.snapshot()
        assert snap["serve_knob_batch_timeout_ms"] == 1.0
        assert snap["serve_knob_max_queue"] == 8.0
        engine.set_knobs(batch_timeout_ms=5.0, max_queue=64)

    def test_invalid_knobs_refused(self, engine):
        with pytest.raises(ConfigError):
            engine.set_knobs(batch_timeout_ms=-1.0)
        with pytest.raises(ConfigError):
            engine.set_knobs(max_queue=0)

    def test_serving_works_across_knob_changes(self, engine):
        import numpy as np
        engine.set_knobs(batch_timeout_ms=0.5, max_queue=16)
        obs = np.full((10,), 10.0, np.float32)
        handles = [engine.submit(f"knob-{i}", obs) for i in range(8)]
        for h in handles:
            assert h.wait(10.0) is not None
        engine.set_knobs(batch_timeout_ms=5.0, max_queue=64)


# ---------------------------------------------------------------------------
# adaptive ingest cadence (orchestrator)
# ---------------------------------------------------------------------------


class TestAdaptiveIngest:
    def make_orch(self, tmp_path, adaptive=True, every=8):
        from sharetrade_tpu.runtime.orchestrator import Orchestrator
        cfg = FrameworkConfig()
        cfg.learner.algo = "dqn"
        cfg.distrib.num_actors = 1
        cfg.distrib.ingest_every_updates = every
        cfg.distrib.actor_dir = str(tmp_path / "actors")
        cfg.tuning.adaptive_ingest = adaptive
        cfg.runtime.checkpoint_dir = str(tmp_path / "ck")
        return Orchestrator(cfg)

    def test_dry_backoff_and_snap_recovery(self, tmp_path):
        orch = self.make_orch(tmp_path)
        try:
            base = 8
            assert orch._ingest_every == base
            # One or two dry ticks: scheduling noise, no move yet.
            orch._adapt_ingest_cadence(0, False)
            orch._adapt_ingest_cadence(0, False)
            assert orch._ingest_every == base
            orch._adapt_ingest_cadence(0, False)    # third: back off
            assert orch._ingest_every == 2 * base
            for _ in range(10):                      # bounded at 8x base
                orch._adapt_ingest_cadence(0, False)
            assert orch._ingest_every == 8 * base
            # Rows arrive: snap straight back to the configured base.
            orch._adapt_ingest_cadence(100, False)
            assert orch._ingest_every == base
            counters = orch.metrics.counters()
            assert counters["ingest_adjustments_total"] >= 3
            assert orch.metrics.latest(
                "ingest_every_updates_current") == base
        finally:
            orch.stop()

    def test_backlog_tightens_to_floor(self, tmp_path):
        orch = self.make_orch(tmp_path)
        try:
            for _ in range(10):
                orch._adapt_ingest_cadence(4096, True)
            assert orch._ingest_every == 2     # max(1, 8 // 4)
            # Backlog cleared: cadence stays (below base is not "backed
            # off"; it only returns toward base via the dry path).
            orch._adapt_ingest_cadence(10, False)
            assert orch._ingest_every == 2
        finally:
            orch.stop()

    def test_adaptive_off_never_moves(self, tmp_path):
        orch = self.make_orch(tmp_path, adaptive=False)
        try:
            for _ in range(5):
                orch._adapt_ingest_cadence(0, False)
                orch._adapt_ingest_cadence(4096, True)
            assert orch._ingest_every == 8
            assert "ingest_adjustments_total" not in \
                orch.metrics.counters()
        finally:
            orch.stop()


# ---------------------------------------------------------------------------
# lint check 13
# ---------------------------------------------------------------------------


class TestLintAndGate:
    def test_tuned_knob_shadow_semantics(self, tmp_path):
        import lint_hot_loop as lint
        fixture = tmp_path / "serve"
        fixture.mkdir()
        (fixture / "bad.py").write_text(
            "class E:\n"
            "    def f(self):\n"
            "        self.batch_timeout_ms = 2.0\n"
            "        max_queue = 64\n"
            "        # tuned-knob-ok: test fixture escape\n"
            "        self.pipeline_depth = 4\n"
            "        other_name = 3.0\n"
            "        self.max_batch = compute()\n")
        bad, found = lint.lint_tuned_knob_shadows(roots=[fixture])
        lines = sorted(ln for _, ln, _ in bad)
        # Literal assignments to registered leaves flagged (3, 4); the
        # marker-escaped one (6), an unrelated name (7), and a
        # non-literal value (8) stay legal.
        assert lines == [3, 4]
        assert set(lint.TUNED_KNOB_PATHS) <= found | set(
            lint.TUNED_KNOB_PATHS)

    def test_registry_existence_check(self, tmp_path):
        import lint_hot_loop as lint
        empty = tmp_path / "serve2"
        empty.mkdir()
        reg = tmp_path / "not_the_registry.py"
        reg.write_text("KNOBS = ()\n")
        _, found = lint.lint_tuned_knob_shadows(roots=[empty],
                                                registry=reg)
        assert found == set()   # every registered path reported missing

    def test_repo_is_clean(self):
        import lint_hot_loop as lint
        bad, found = lint.lint_tuned_knob_shadows()
        assert bad == []
        assert found == set(lint.TUNED_KNOB_PATHS)


# ---------------------------------------------------------------------------
# manifest + cli obs tuning section
# ---------------------------------------------------------------------------


class TestTuningObservability:
    def test_manifest_and_summary_tuning_section(self, tmp_path):
        from sharetrade_tpu.obs import summarize_run_dir
        from sharetrade_tpu.obs.manifest import write_manifest
        profile = _write_profile(tmp_path,
                                 {"runtime.megachunk_factor": 4})
        cfg = FrameworkConfig()
        cfg.tuning.profile = profile
        cfg = tuning.apply_profile(cfg)
        run_dir = tmp_path / "obs"
        run_dir.mkdir()
        write_manifest(str(run_dir / "manifest.json"), cfg)
        summary = summarize_run_dir(str(run_dir))
        t = summary["tuning"]
        assert t["profile"] == profile
        assert t["knobs"]["runtime.megachunk_factor"]["source"] == \
            "profile"
        assert t["knobs"]["runtime.megachunk_factor"]["value"] == 4
        assert t["knobs"]["serve.max_queue"]["source"] == "default"

    def test_summary_live_controller_gauges(self, tmp_path):
        from sharetrade_tpu.obs import summarize_run_dir
        run_dir = tmp_path / "obs"
        run_dir.mkdir()
        record = {
            "gauges": {"serve_knob_batch_timeout_ms": 0.5,
                       "serve_knob_max_queue": 32.0,
                       "serve_controller_p99_ms": 41.0,
                       "serve_controller_target_p99_ms": 50.0},
            "counters": {"serve_controller_adjustments_total": 7.0},
        }
        (run_dir / "metrics.jsonl").write_text(json.dumps(record) + "\n")
        live = summarize_run_dir(str(run_dir))["tuning"]["live"]
        assert live["serve_batch_timeout_ms"] == 0.5
        assert live["serve_max_queue"] == 32.0
        assert live["controller_adjustments_total"] == 7.0
        assert live["controller_last_p99_ms"] == 41.0


# ---------------------------------------------------------------------------
# quick end-to-end sweep (the make-check profile, train spec only)
# ---------------------------------------------------------------------------


class TestQuickSweep:
    def test_train_sweep_writes_loadable_profile(self, tmp_path):
        import autotune
        out = str(tmp_path / "tuned_profile.json")
        summary = autotune.run_autotune(
            ("train",), quick=True, out_path=out, seed=0,
            log_fn=lambda msg: None)
        assert summary["out"] == out
        assert set(summary["knobs"]) == {"runtime.megachunk_factor",
                                         "runtime.pipeline_depth"}
        # The written profile loads and applies on THIS host.
        cfg = FrameworkConfig()
        cfg.tuning.profile = out
        cfg = tuning.apply_profile(cfg)
        assert cfg.runtime.megachunk_factor == \
            summary["knobs"]["runtime.megachunk_factor"]
        desc = tuning.describe(cfg)
        assert desc["profile_mismatches"] == []


class TestControllerUnderChaos:
    def test_chaos_quick_profile_with_controller_on(self, tmp_path):
        """ISSUE-14 acceptance: the chaos invariants (every request
        terminal, queue bounded, counters reconcile exactly) hold with
        the online controller adjusting LIVE."""
        import serve_chaos
        summary = serve_chaos.run_chaos(
            injections=2, seed=5, workdir=str(tmp_path / "chaos"),
            verbose=False, controller=True)
        assert summary["controller"] is True
        assert summary["decomposition_errors"] == 0
