"""Fleet serving tier (fleet/ — ISSUE 15): network front-end, telemetry-
routed engine fleet, flywheel journaling.

The load-bearing contracts:

- **Wire fidelity**: serving over HTTP is the SAME serving — the engine's
  logits bit for bit through JSON (and so the reference's answer within
  the written tolerance, ``serving_parity.assert_same_answer``), every engine-side outcome reconstructed as its
  exact exception class from a distinct wire status.
- **Deadline propagation**: the client's ``X-Deadline-Ms`` header flows
  into ``submit(deadline_ms=)`` and expiry happens at the ENGINE's
  batch-collection gate (the engine-side counter moves), never on a
  router/front-end timer.
- **Exact merge**: fleet p50/p99 come from bucket-wise merged
  ``_bucket`` expositions — merged-shard quantiles equal concatenated-
  sample quantiles within one bucket width, through a full
  render→scrape→rebuild round trip.
- **Migration**: kill a session's affine engine mid-conversation and its
  next request lands on a survivor COLD — bit for bit what that survivor
  answers a fresh session, and the reference's fresh first step within
  the written tolerance (the PR-8 prefill contract stretched across processes).
- **Degrade**: all engines gone ⇒ the router answers ServeEngineFailed
  (503) loudly, never a wedge; the EnginePool's ladder (shared with
  distrib/) classifies crashes, backs off seeded, and fails terminally
  past the budget.
- **Flywheel**: journaling sessions write learner-ingestible transition
  journals with monotone stamps that survive writer restarts.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from sharetrade_tpu.config import FleetConfig, ModelConfig, ServeConfig
from sharetrade_tpu.fleet import (
    EngineBackend,
    EnginePool,
    FleetClient,
    FleetRouter,
    ServeFrontend,
    StaticEndpoints,
    WireEngine,
)
from sharetrade_tpu.fleet import wire
from sharetrade_tpu.models import build_model
from sharetrade_tpu.obs.exporter import parse_prom_text, render_prom_text
from sharetrade_tpu.obs.hist import Histogram, from_prom_buckets, merge
from sharetrade_tpu.serve import ServeEngine
from sharetrade_tpu.serve.engine import (
    ServeDeadlineExceeded,
    ServeEngineFailed,
    ServeRejected,
    latency_percentiles,
)
from sharetrade_tpu.utils.metrics import MetricsRegistry

from serving_parity import (
    SequentialReference,
    assert_other_answer,
    assert_same_answer,
)

WINDOW = 8
OBS_DIM = WINDOW + 2


def _obs(seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.uniform(1.0, 2.0, OBS_DIM).astype(np.float32)


@pytest.fixture(scope="module")
def mlp_model():
    model = build_model(ModelConfig(kind="mlp", hidden_dim=16), OBS_DIM,
                        head="ac")
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def lstm_model():
    model = build_model(ModelConfig(kind="lstm", hidden_dim=8), OBS_DIM,
                        head="ac")
    return model, model.init(jax.random.PRNGKey(1))


def _boot_engine(model, params, *, step=0, registry=None, **serve_kw):
    serve_kw.setdefault("max_batch", 4)
    serve_kw.setdefault("slots", 8)
    serve_kw.setdefault("batch_timeout_ms", 1.0)
    serve_kw.setdefault("stats_interval_s", 0.2)
    registry = registry or MetricsRegistry()
    engine = ServeEngine(model, ServeConfig(**serve_kw), params,
                         params_step=step, registry=registry)
    engine.warmup()
    frontend = ServeFrontend(EngineBackend(engine), registry).start()
    return engine, frontend, registry


# ---------------------------------------------------------------------------
# wire protocol


class TestWireProtocol:
    def test_status_mapping_roundtrip(self):
        for exc, status in [
                (ServeRejected("q full", reason="queue_full"),
                 wire.STATUS_REJECTED),
                (ServeDeadlineExceeded("late"), wire.STATUS_DEADLINE),
                (ServeEngineFailed("dead"), wire.STATUS_UNAVAILABLE),
                (ValueError("bad obs"), wire.STATUS_BAD_REQUEST)]:
            code, body = wire.error_to_status(exc)
            assert code == status
            back = wire.status_to_error(code, body)
            assert type(back) is type(exc)
        rej = wire.status_to_error(
            *wire.error_to_status(
                ServeRejected("shed", reason="shed_oldest")))
        assert rej.reason == "shed_oldest"

    def test_submit_over_wire_bitwise(self, mlp_model):
        model, params = mlp_model
        engine, frontend, _ = _boot_engine(model, params, step=11)
        try:
            client = FleetClient(frontend.host, frontend.port)
            obs = _obs(3)
            out = client.submit("w1", obs)
            wire_logits = np.asarray(out["logits"], np.float32)
            # float64 JSON round-trips float32 exactly: the wire reply is
            # bit for bit this engine's in-process reply (the same
            # program on the same bytes; the MLP keeps no carry) ...
            direct = engine.submit("w1-direct", obs).wait(30.0)
            assert wire_logits.tobytes() \
                == np.asarray(direct.logits, np.float32).tobytes()
            # ... and the one-row reference's answer within the written
            # tolerance.
            ref_action, ref_logits = SequentialReference(
                model, params).step("w1", obs)
            assert_same_answer(wire_logits, ref_logits, "over the wire")
            assert out["params_step"] == 11
            assert out["action"] == direct.action == ref_action
            stages = out["stages"]
            assert abs(sum(stages.values()) - out["latency_ms"]) < 1e-6
            client.close()
        finally:
            frontend.stop()
            engine.stop(drain=False)

    def test_malformed_and_missing(self, mlp_model):
        model, params = mlp_model
        engine, frontend, _ = _boot_engine(model, params)
        try:
            client = FleetClient(frontend.host, frontend.port)
            with pytest.raises(ValueError):
                client.submit("w2", [float("nan")] * OBS_DIM)
            status, _ = client._request("POST", "/nope", body=b"{}")
            assert status == 404
            status, _ = client._request("POST", wire.SUBMIT_PATH,
                                        body=b"not json")
            assert status == wire.STATUS_BAD_REQUEST
            client.close()
        finally:
            frontend.stop()
            engine.stop(drain=False)

    def test_metrics_exposition_valid(self, mlp_model):
        model, params = mlp_model
        engine, frontend, _ = _boot_engine(model, params)
        try:
            client = FleetClient(frontend.host, frontend.port)
            client.submit("w3", _obs())
            parsed = parse_prom_text(client.metrics())   # strict parser
            assert "sharetrade_serve_request_ms" in parsed["histograms"]
            assert parsed["counters"][
                "sharetrade_serve_requests_total"] >= 1
            client.close()
        finally:
            frontend.stop()
            engine.stop(drain=False)


# ---------------------------------------------------------------------------
# exact histogram merge at the router


class TestFleetHistogramMerge:
    def test_merged_shards_equal_concatenation(self):
        """Fleet p50/p99 from bucket-wise-merged scraped shards == the
        quantile of the concatenated raw samples, within one bucket
        width — through the FULL wire round trip (render → strict parse
        → rebuild → merge)."""
        rng = np.random.default_rng(7)
        shards, all_samples = [], []
        for e in range(4):
            h = Histogram()
            samples = rng.lognormal(mean=1.0 + 0.3 * e, sigma=1.0,
                                    size=500)
            for s in samples:
                h.observe(float(s))
            all_samples.extend(float(s) for s in samples)
            text = render_prom_text({}, {},
                                    {"serve_request_ms": h.snapshot()})
            parsed = parse_prom_text(text)[
                "histograms"]["sharetrade_serve_request_ms"]
            rebuilt = from_prom_buckets(parsed["buckets"], parsed["sum"],
                                        int(parsed["count"]))
            # The scrape is lossless: exact integer counts, exact bounds.
            assert rebuilt.snapshot()["counts"] == h.snapshot()["counts"]
            assert rebuilt.bounds == h.bounds
            shards.append(rebuilt)
        fleet = merge(shards)
        assert fleet.count == len(all_samples)
        exact = latency_percentiles(all_samples)
        for q, key in ((0.50, "p50_ms"), (0.99, "p99_ms")):
            est = fleet.quantile(q)
            idx = np.searchsorted(fleet.bounds, exact[key])
            lo = fleet.bounds[idx - 1] if idx > 0 else 0.0
            hi = (fleet.bounds[idx] if idx < len(fleet.bounds)
                  else fleet.bounds[-1])
            assert abs(est - exact[key]) <= (hi - lo) + 1e-9, \
                f"{key}: est {est} vs exact {exact[key]}"

    def test_from_prom_refuses_garbage(self):
        with pytest.raises(ValueError):
            from_prom_buckets([("1", 5), ("2", 3), ("+Inf", 3)], 0.0, 3)
        with pytest.raises(ValueError):
            from_prom_buckets([("1", 5)], 0.0, 5)      # no +Inf terminal
        with pytest.raises(ValueError):
            from_prom_buckets([("1", 2), ("+Inf", 5)], 0.0, 9)  # != count


# ---------------------------------------------------------------------------
# deadline propagation over the wire


class TestWireDeadline:
    def test_deadline_expires_engine_side(self, mlp_model):
        """A 50 ms-deadline request expires at the ENGINE's batch-
        collection gate (its counter moves), not on a router/front-end
        timer — routed through the full router→engine wire path."""
        model, params = mlp_model
        engine, frontend, ereg = _boot_engine(
            model, params, batch_timeout_ms=250.0, max_batch=4)
        rreg = MetricsRegistry()
        router = FleetRouter(
            StaticEndpoints({"e0": (frontend.host, frontend.port)}),
            FleetConfig(), rreg, workdir="")
        rfe = ServeFrontend(router, rreg).start()
        try:
            client = FleetClient(rfe.host, rfe.port)
            w1 = WireEngine(rfe.host, rfe.port, workers=3)
            # Tick 1 collects s-dl's FIRST request and coalesces for the
            # full 250 ms window (no deadline on it); the same-session
            # follower with a 50 ms deadline sits DEFERRED past its
            # expiry and dies at the next collection pop — engine-side.
            h1 = w1.submit("s-dl", _obs(1))
            time.sleep(0.01)
            before = ereg.counters().get(
                "serve_deadline_expired_total", 0)
            with pytest.raises(ServeDeadlineExceeded):
                client.submit("s-dl", _obs(2), deadline_ms=50.0)
            after = ereg.counters().get("serve_deadline_expired_total", 0)
            assert after == before + 1, \
                "expiry must be the engine's, not a proxy timeout"
            assert h1.wait(5.0) is not None
            w1.stop()
            client.close()
        finally:
            rfe.stop()
            router.stop()
            frontend.stop()
            engine.stop(drain=False)


# ---------------------------------------------------------------------------
# router: affinity, migration, degrade


class TestRouterMigration:
    def test_affinity_sticks_and_migrates_bitwise(self, lstm_model):
        """A session sticks to its engine's slot-pool carry; killing the
        engine mid-conversation re-routes the next request to a survivor
        where the session re-enters COLD through the prefill — bit for
        bit what the survivor answers a fresh session, and the
        reference's fresh first step within the written tolerance (an
        LSTM makes warm≠cold observable: a surviving warm carry would
        change the logits in the first digits)."""
        model, params = lstm_model
        e1, f1, _ = _boot_engine(model, params, step=1)
        e2, f2, _ = _boot_engine(model, params, step=1)
        reg = MetricsRegistry()
        endpoints = StaticEndpoints({"e0": (f1.host, f1.port),
                                     "e1": (f2.host, f2.port)})
        router = FleetRouter(endpoints, FleetConfig(), reg,
                             workdir="")
        rfe = ServeFrontend(router, reg).start()
        try:
            client = FleetClient(rfe.host, rfe.port)
            obs_a, obs_b = _obs(10), _obs(11)
            first = client.submit("mig", obs_a)
            home = first["engine"]
            warm = client.submit("mig", obs_b)
            assert warm["engine"] == home
            # Warm logits differ from a cold first step on obs_b — the
            # carry is real, so the migration claim below is non-trivial.
            _, cold_logits = SequentialReference(model, params).step(
                "fresh", obs_b)
            assert_other_answer(warm["logits"], cold_logits)
            # Kill the home engine (process-death stand-in).
            victim_fe, victim_eng = (f1, e1) if home == "e0" else (f2, e2)
            survivor = e2 if home == "e0" else e1
            victim_fe.stop()
            victim_eng.stop(drain=False)
            migrated = client.submit("mig", obs_b)
            assert migrated["engine"] != home
            assert_same_answer(
                migrated["logits"], cold_logits,
                "migrated session must answer as a fresh session")
            # The same program on the same bytes: the survivor's own
            # answer to a session it has never seen.
            fresh = survivor.submit("never-seen", obs_b).wait(30.0)
            assert np.asarray(migrated["logits"], np.float32).tobytes() \
                == np.asarray(fresh.logits, np.float32).tobytes()
            assert reg.counters().get("fleet_migrations_total", 0) == 1
            client.close()
        finally:
            rfe.stop()
            router.stop()
            for fe, eng in ((f1, e1), (f2, e2)):
                fe.stop()
                eng.stop(drain=False)

    def test_degrade_when_all_engines_gone(self, mlp_model):
        model, params = mlp_model
        engine, frontend, _ = _boot_engine(model, params)
        reg = MetricsRegistry()
        router = FleetRouter(
            StaticEndpoints({"e0": (frontend.host, frontend.port)}),
            FleetConfig(), reg, workdir="")
        try:
            assert router.serve_request("d1", _obs(), None)["engine"] \
                == "e0"
            frontend.stop()
            engine.stop(drain=False)
            with pytest.raises(ServeEngineFailed):
                router.serve_request("d1", _obs(), None)
            assert reg.counters().get("fleet_unrouted_total", 0) >= 1
            # Still degraded, still loud — never a wedge.
            with pytest.raises(ServeEngineFailed):
                router.serve_request("d2", _obs(), None)
        finally:
            router.stop()
            frontend.stop()
            engine.stop(drain=False)


# ---------------------------------------------------------------------------
# engine pool supervision (stub children — no jax bring-up)


_HEALTHY_STUB = r"""
import json, sys
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
class H(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    def log_message(self, *a): pass
    def do_GET(self):
        body = json.dumps({"ok": True, "queue_depth": 1, "overload": 0,
                           "params_step": 3, "swaps_total": 0}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)
srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
print(json.dumps({"event": "engine_listening", "host": "127.0.0.1",
                  "port": srv.server_address[1]}), flush=True)
srv.serve_forever()
"""


def _stub_spawn(script: str):
    def spawn(engine_id: str, log_path: str):
        with open(log_path, "ab") as log_f:
            return subprocess.Popen([sys.executable, "-c", script],
                                    stdout=log_f,
                                    stderr=subprocess.STDOUT)
    return spawn


def _fleet_cfg(tmp_path, **kw):
    from sharetrade_tpu.config import FrameworkConfig
    cfg = FrameworkConfig()
    cfg.fleet.dir = str(tmp_path / "fleet")
    cfg.fleet.num_engines = kw.pop("num_engines", 2)
    cfg.fleet.engine_backoff_initial_s = 0.05
    cfg.fleet.engine_backoff_max_s = 0.2
    cfg.fleet.startup_timeout_s = kw.pop("startup_timeout_s", 30.0)
    cfg.fleet.health_timeout_s = kw.pop("health_timeout_s", 0.0)
    cfg.fleet.max_engine_restarts = kw.pop("max_engine_restarts", 2)
    for k, v in kw.items():
        setattr(cfg.fleet, k, v)
    return cfg


def _pump(pool, predicate, timeout_s=15.0, desc="condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        pool.poll_once()
        if predicate():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {desc}")


class TestEnginePool:
    def test_ready_health_crash_respawn_terminal(self, tmp_path):
        cfg = _fleet_cfg(tmp_path, max_engine_restarts=1)
        pool = EnginePool(cfg, spawn_fn=_stub_spawn(_HEALTHY_STUB))
        # No supervise thread: the test steps the pool deterministically.
        pool.target = 2
        with pool._lock:
            pool._spawn_new_locked()
            pool._spawn_new_locked()
        try:
            _pump(pool, lambda: pool.counts()["alive"] == 2
                  and len(pool.endpoints()) == 2
                  and all(h.state == "alive"
                          for h in pool._engines.values()),
                  desc="both stubs alive via healthz")
            status = pool.status()
            assert status["engines"]["e0"]["params_step"] == 3
            assert status["engines"]["e0"]["queue_depth"] == 1
            # SIGKILL e0: crash → seeded backoff → respawn → healthy
            # again, streak reset.
            h0 = pool._engines["e0"]
            pid0 = h0.pid
            h0.proc.kill()
            _pump(pool, lambda: pool.restarts_total == 1
                  and pool._engines["e0"].state == "alive"
                  and pool._engines["e0"].pid != pid0,
                  desc="e0 respawned and healthy")
            assert pool._engines["e0"].streak == 0
            # Now make e0 die repeatedly: replace its spawn with a
            # fail-fast stub → streak past max_engine_restarts=1 →
            # terminal FAILED, e1 untouched (degrade onto survivors).
            pool._spawn_fn = _stub_spawn("raise SystemExit(9)")
            pool._engines["e0"].proc.kill()
            _pump(pool, lambda: pool._engines["e0"].state == "failed",
                  desc="e0 terminally failed")
            assert pool._engines["e1"].state == "alive"
            assert pool.counts()["failed"] == 1
            assert "e0" not in pool.endpoints()
            assert "e1" in pool.endpoints()
        finally:
            pool.kill_all()
            pool.stop(grace_s=2.0)

    def test_startup_timeout_kills_wedged_bringup(self, tmp_path):
        cfg = _fleet_cfg(tmp_path, num_engines=1, startup_timeout_s=0.3,
                         max_engine_restarts=0)
        # Child that never prints a listening line = wedged bring-up.
        pool = EnginePool(
            cfg, spawn_fn=_stub_spawn("import time; time.sleep(60)"))
        pool.target = 1
        with pool._lock:
            pool._spawn_new_locked()
        try:
            _pump(pool, lambda: pool._engines["e0"].state == "failed",
                  desc="wedged bring-up killed and failed terminally")
            assert pool.restarts_total == 1
        finally:
            pool.kill_all()
            pool.stop(grace_s=2.0)

    def test_quiesced_exits_retire(self, tmp_path):
        cfg = _fleet_cfg(tmp_path, num_engines=1)
        pool = EnginePool(cfg, spawn_fn=_stub_spawn(_HEALTHY_STUB))
        pool.target = 1
        with pool._lock:
            pool._spawn_new_locked()
        try:
            _pump(pool, lambda: pool.counts()["alive"] == 1,
                  desc="stub alive")
            pool.quiesce()
            pool._engines["e0"].proc.kill()
            _pump(pool, lambda: pool._engines["e0"].state == "retired",
                  desc="quiesced exit retires, not crashes")
            assert pool.restarts_total == 0
        finally:
            pool.kill_all()
            pool.stop(grace_s=2.0)


# ---------------------------------------------------------------------------
# flywheel journaling


class TestFlywheelJournal:
    def test_sessions_journal_ingestible_rows(self, tmp_path):
        from sharetrade_tpu.data.transitions import read_new_transitions
        from sharetrade_tpu.fleet.flywheel import (
            SessionTransitionJournal, make_journaling_sessions)
        root = str(tmp_path / "actors")
        journal = SessionTransitionJournal(root, "fleet-w0",
                                           obs_dim=OBS_DIM,
                                           flush_rows=8)
        prices = np.linspace(10, 20, 64).astype(np.float32)
        sessions = make_journaling_sessions(prices, WINDOW, 3,
                                            journal=journal, seed=0)
        for step in range(10):
            for s in sessions:
                s.advance(action=step % 3)
        journal.flush()
        out = read_new_transitions(journal.path, 0, 10_000)
        assert out is not None
        obs, action, reward, next_obs, high_water = out
        assert obs.shape[1] == OBS_DIM          # the learner's obs_dim
        assert next_obs.shape == obs.shape
        assert np.isfinite(reward).all()
        rows0 = obs.shape[0]
        assert rows0 == journal.rows_journaled
        assert high_water == rows0              # monotone row stamps
        journal.close()
        # A writer restart continues past the recovered high-water:
        # stamps never reuse, so a learner cursor never re-reads rows.
        journal2 = SessionTransitionJournal(root, "fleet-w0",
                                            obs_dim=OBS_DIM,
                                            flush_rows=4)
        sessions2 = make_journaling_sessions(prices, WINDOW, 1,
                                             journal=journal2, seed=1)
        for _ in range(4):
            sessions2[0].advance(action=0)
        journal2.close()
        out2 = read_new_transitions(journal.path, rows0, 10_000)
        assert out2 is not None and out2[0].shape[0] == 4
        assert out2[4] == rows0 + 4

    def test_wrap_boundary_rows_skipped(self, tmp_path):
        from sharetrade_tpu.fleet.flywheel import (
            SessionTransitionJournal, JournalingSession)
        journal = SessionTransitionJournal(str(tmp_path / "a"), "w",
                                           obs_dim=OBS_DIM,
                                           flush_rows=1)
        prices = np.linspace(10, 20, WINDOW + 2).astype(np.float32)
        sess = JournalingSession("s", prices, WINDOW, 0, journal=journal)
        sess.advance(0)     # t 0→1: records one row
        gen = sess.generation
        sess.advance(0)     # wraps: boundary row must be skipped
        assert sess.generation == gen + 1
        journal.close()
        from sharetrade_tpu.data.transitions import read_new_transitions
        out = read_new_transitions(journal.path, 0, 100)
        assert out is not None and out[0].shape[0] == 1


# ---------------------------------------------------------------------------
# lint check 14 + cli obs fleet section


class TestFleetLintAndObs:
    def test_lint_fleet_net_semantics(self, tmp_path):
        import lint_hot_loop
        pkg = tmp_path / "pkg"
        (pkg / "fleet").mkdir(parents=True)
        (pkg / "serve").mkdir()
        (pkg / "fleet" / "fe.py").write_text(
            "import socketserver\nsrv = socketserver.TCPServer(a, h)\n")
        (pkg / "serve" / "bad.py").write_text(
            "import socket\ns = socket.socket()\n")
        (pkg / "serve" / "ok.py").write_text(
            "import socket\n"
            "s = socket.socket()  # fleet-net-ok: test probe\n")
        listener_bad, _ = lint_hot_loop.lint_fleet_net(root=pkg)
        assert [(r, ln) for r, ln, _ in listener_bad] \
            == [("serve/bad.py", 2)]
        # The real tree is clean (the repo-level invariant).
        real_listeners, real_dispatch = lint_hot_loop.lint_fleet_net()
        assert real_listeners == [] and real_dispatch == []

    def test_cli_obs_fleet_section(self, tmp_path):
        from sharetrade_tpu.obs import summarize_run_dir
        status = {
            "ts": 1.0,
            "router": {"ok": True, "engines_live": 2,
                       "affinity_sessions": 17, "params_steps": [4, 6]},
            "pool": {"alive": 2, "failed": 1, "restarts_total": 3,
                     "engines": {
                         "e0": {"state": "alive", "pid": 10, "port": 1,
                                "restarts": 0, "params_step": 6,
                                "queue_depth": 2},
                         "e1": {"state": "failed", "pid": None,
                                "port": None, "restarts": 3,
                                "params_step": None,
                                "queue_depth": None}}},
            "telemetry": {"e0": {"healthy": True,
                                 "window_p99_ms": 12.5}},
            "gauges": {"fleet_p50_ms": 2.5, "fleet_p99_ms": 12.5,
                       "fleet_swap_lag_steps": 2.0,
                       "fleet_proto_backend_native": 1.0,
                       "fleet_evloop_open_conns": 5.0},
            "counters": {"fleet_requests_total": 100,
                         "fleet_evloop_backpressure_pauses_total": 2,
                         "fleet_evloop_deadline_expiries_total": 1},
            "fleet_request_ms": {"count": 100, "p50_ms": 2.5,
                                 "p99_ms": 12.5},
        }
        with open(tmp_path / "fleet_status.json", "w") as f:
            json.dump(status, f)
        out = summarize_run_dir(str(tmp_path))
        fleet = out["fleet"]
        assert fleet["alive"] == 2 and fleet["failed"] == 1
        assert fleet["restarts_total"] == 3
        assert fleet["merged_p99_ms"] == 12.5
        assert fleet["affinity_sessions"] == 17
        assert fleet["swap_lag_steps"] == 2.0
        assert fleet["engines"]["e0"]["window_p99_ms"] == 12.5
        assert fleet["engines"]["e1"]["state"] == "failed"
        assert fleet["counters"]["fleet_requests_total"] == 100
        assert fleet["evloop"] == {
            "proto_backend": "native",
            "open_conns": 5.0,
            "backpressure_pauses_total": 2,
            "deadline_expiries_total": 1,
        }


# ---------------------------------------------------------------------------
# wire load harness adapter


class TestWireEngine:
    def test_closed_loop_over_wire(self, mlp_model):
        from sharetrade_tpu.serve.driver import (
            make_sessions, run_closed_loop)
        model, params = mlp_model
        engine, frontend, _ = _boot_engine(model, params)
        try:
            w = WireEngine(frontend.host, frontend.port, workers=4)
            prices = np.linspace(10, 20, 128).astype(np.float32)
            sessions = make_sessions(prices, WINDOW, 8, prefix="wl-")
            stats = run_closed_loop(w, sessions, concurrency=4,
                                    duration_s=1.0)
            assert stats["completed"] > 0
            assert stats["failed"] == 0
            assert stats["p99_ms"] > 0
            assert w.stop()
        finally:
            frontend.stop()
            engine.stop(drain=False)
