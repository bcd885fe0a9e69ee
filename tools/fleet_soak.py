#!/usr/bin/env python
"""Fleet kill-test: offered-load ramp, whole-engine SIGKILL chaos, and
the train→serve→train flywheel — end to end through the public surface.

The system under test is ONE ``cli fleet --learner`` subprocess: the
telemetry-driven router on its public port, N supervised ``cli serve
--listen`` engine workers, and the live in-process learner. This soak is
the CLIENT: it drives a closed-loop ramp over the wire with JOURNALING
sessions (every served action becomes a transition row in the learner's
ingest path — fleet/flywheel.py), SIGKILLs whole engines mid-ramp, and
asserts after EVERY kill and at the end:

- **router never wedges** — a probe request on a fresh session completes
  within its budget immediately after each kill, and the ramp's sessions
  keep completing (the router's transport-retry migration path absorbs
  requests in flight on the corpse);
- **supervised recovery** — the pool's restart counter reconciles
  EXACTLY with the injected kill count (no spurious restarts), and
  ``fleet_engines_live`` returns to N within the recovery budget;
- **migration through prefill** — sessions stuck to a killed engine
  keep completing on survivors (their slot carries re-enter cold; the
  bitwise prefill contract itself is pinned by tests/test_fleet.py —
  here it must hold under real process death and load);
- **flywheel** — ``distrib_rows_ingested_total`` moves (the learner is
  eating the sessions' journals), a fresh ``tag_best`` is published, and
  EVERY surviving engine hot-swaps it in (healthz ``params_step``
  advances from the boot step on all of them, swap counters move) while
  a settle window of requests completes with zero failures;
- **fleet SLO gauges** — merged-histogram ``fleet_p50/p99_ms`` are
  present and finite in ``fleet_status.json`` (the exact bucket-wise
  merge is pinned by tests; here it must be LIVE);
- **counter reconciliation** — router counters balance exactly:
  ``fleet_requests_total == fleet_completed_total + fleet_refused_total
  + fleet_unrouted_total``, and the client's completed+failed matches
  its submissions;
- **drain** — SIGTERM ends the whole tier with exit 75, engine journals
  stay CRC-clean through the segmented reader;
- **stitched kill forensics** — the client mints a trace per request
  (span journal under ``<workdir>/obs/spans`` beside the fleet's own),
  and after the drain at least one MIGRATED request stitches into ONE
  trace holding spans from BOTH the killed engine (its eagerly-flushed
  ``engine_recv`` ingress marker survives the SIGKILL) and a survivor,
  plus the router's ``migrate:``-annotated relay attempt — with zero
  stitch errors (every parent resolves, intervals nest after clock
  alignment). That is asserted after a kill that FOUND a request inside
  its victim (a request lives in an engine a millisecond or two, and
  affinity can leave an engine nearly idle): when none of the planned
  kills did, the soak kills again, at most ``MAX_EXTRA_KILLS`` times,
  and if none of those did either it reports ``witness: None`` (there is
  no such trace to stitch) instead of failing.

Usage:
    python tools/fleet_soak.py                     # full (~3 engines, >=3 kills)
    python tools/fleet_soak.py --quick             # tier-1 profile (2 engines, 1 kill)
    python tools/fleet_soak.py --engines 4 --kills 5
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from soak_common import (  # noqa: E402
    REPO,
    SoakError,
    launch_cli,
    log_tail,
    prom_value,
    read_json,
    wait_until,
)

WINDOW = 16
OBS_DIM = WINDOW + 2

#: Kills beyond the planned ones while no kill has yet found a request
#: inside its victim (``kill_caught_request``).
MAX_EXTRA_KILLS = 2


def eprint(*args):
    print(*args, file=sys.stderr, flush=True)


def build_config(workdir: str, engines: int,
                 wire_backend: str = "evloop", *,
                 autoscale_ceiling: int = 0,
                 spill_profile: bool = False,
                 spill_control: bool = False) -> str:
    """The soak's config: tiny MLP serve workload, journaled-DQN
    learner with session-feed ingest, fast swap/telemetry cadences.
    All paths ABSOLUTE into the scratch dir (children run from the
    repo root). ``wire_backend`` picks the front-end/router data path
    (the default soaks the evloop; ``threaded`` soaks the oracle).
    ``autoscale_ceiling`` > 0 switches to the diurnal-autoscale
    profile: membership [1, ceiling], fast controller cadences, and a
    LARGE batch window so a client surge visibly queues on CPU (the
    queue-depth signal the autoscaler scales on)."""
    from sharetrade_tpu.config import FrameworkConfig
    cfg = FrameworkConfig()
    cfg.seed = 7
    cfg.env.window = WINDOW
    cfg.model.kind = "mlp"
    cfg.model.hidden_dim = 32
    cfg.data.csv_path = None
    cfg.data.synthetic_length = 900
    cfg.data.journal_dir = os.path.join(workdir, "journal")
    cfg.data.journal_segment_records = 64
    cfg.learner.algo = "dqn"
    cfg.learner.replay_capacity = 4096
    cfg.learner.replay_batch = 32
    cfg.learner.journal_replay = False
    cfg.parallel.num_workers = 4
    cfg.runtime.chunk_steps = 50
    cfg.runtime.episodes = 200            # keep the learner LIVE all soak
    cfg.runtime.eval_every_updates = 8    # republish tag_best early+often
    cfg.runtime.checkpoint_every_updates = 50
    cfg.runtime.checkpoint_dir = os.path.join(workdir, "checkpoints")
    cfg.serve.max_batch = 8
    cfg.serve.slots = 64
    cfg.serve.batch_timeout_ms = 2.0
    cfg.serve.swap_poll_s = 0.5           # fast flywheel propagation
    cfg.serve.stats_interval_s = 0.5
    cfg.distrib.actor_dir = os.path.join(workdir, "actors")
    cfg.distrib.ingest_every_updates = 4
    cfg.fleet.num_engines = engines
    cfg.fleet.wire_backend = wire_backend
    cfg.fleet.dir = os.path.join(workdir, "fleet")
    cfg.fleet.telemetry_poll_s = 0.3
    cfg.fleet.health_timeout_s = 5.0
    cfg.fleet.supervise_interval_s = 0.2
    cfg.fleet.engine_backoff_initial_s = 0.2
    cfg.fleet.engine_backoff_max_s = 1.0
    cfg.obs.enabled = True
    cfg.obs.dir = os.path.join(workdir, "obs")
    cfg.obs.slo_availability = 0.999
    if autoscale_ceiling:
        cfg.fleet.autoscale = True
        cfg.fleet.min_engines = 1
        cfg.fleet.max_engines = autoscale_ceiling
        cfg.fleet.autoscale_interval_s = 0.4
        cfg.fleet.autoscale_cooldown_s = 1.5
        cfg.fleet.autoscale_window = 3
        cfg.fleet.autoscale_queue_high = 3.0
        cfg.fleet.autoscale_queue_low = 0.5
        # A wide batch window makes the surge QUEUE instead of racing
        # through sub-ms MLP batches: with the closed loop's concurrency
        # well above max_batch, the overflow sits in the ingress queue
        # where the telemetry poller (and so the autoscaler) sees it.
        cfg.serve.batch_timeout_ms = 50.0
    if spill_profile:
        # Kill-under-population profile (ISSUE 20): an episode model
        # whose sessions carry REAL state (a per-session K/V carry the
        # warm/spill tiers page), tiny slot + warm budgets so a modest
        # session population overflows device -> RAM-warm -> disk, and
        # a shared crash-consistent arena under the fleet dir. The
        # CONTROL variant is byte-identical except the spill tier is
        # off — state dies with the engine and every re-request after
        # a kill cold-restarts through prefill.
        cfg.learner.algo = "a2c"    # dqn is mlp-only; the policy net is
        cfg.model.kind = "transformer"  # what matters here, not the algo
        cfg.model.seq_mode = "episode"
        cfg.model.num_layers = 2
        cfg.model.num_heads = 2
        cfg.model.head_dim = 8
        cfg.model.hidden_dim = 32
        cfg.serve.slots = 2
        cfg.serve.max_batch = 2
        import jax
        from sharetrade_tpu.models import build_model
        carry = build_model(cfg.model, OBS_DIM).init_carry()
        nbytes = sum(int(leaf.size) * leaf.dtype.itemsize
                     for leaf in jax.tree.leaves(carry))
        # Room for ~2 carries RAM-warm per engine: the third park
        # demotes the stalest carry to disk (or drops it, control).
        cfg.serve.warm_bytes = int(2.5 * nbytes)
        if not spill_control:
            cfg.serve.spill_bytes = 64 << 20
            cfg.serve.spill_dir = os.path.join(workdir, "fleet", "spill")
    path = os.path.join(workdir, "fleet_soak_config.json")
    cfg.save(path)
    return path


def wait_ready(proc, log_path: str, timeout_s: float) -> dict:
    ready: dict = {}

    def probe() -> bool:
        if proc.poll() is not None:
            raise SoakError(
                f"fleet process died during bring-up (rc={proc.returncode})"
                f": {log_tail(proc)}")
        try:
            with open(log_path) as f:
                for line in f:
                    if '"fleet_ready"' in line:
                        ready.update(json.loads(line))
                        return True
        except OSError:
            pass
        return False

    wait_until(probe, timeout_s, desc="fleet_ready line")
    return ready


class Load:
    """Closed-loop journaling load over the wire, runnable across the
    whole chaos phase. Counts every terminal outcome client-side."""

    def __init__(self, host: str, port: int, workdir: str,
                 sessions: int, concurrency: int):
        import numpy as np
        from sharetrade_tpu.data.synthetic import synthetic_price_series
        from sharetrade_tpu.fleet.flywheel import (
            SessionTransitionJournal, make_journaling_sessions)
        from sharetrade_tpu.fleet.loadgen import WireEngine
        from sharetrade_tpu.obs.trace import SpanJournal, SpanSink
        prices = np.asarray(
            synthetic_price_series(length=900, seed=7).prices, np.float32)
        self.journal = SessionTransitionJournal(
            os.path.join(workdir, "actors"), "fleet-client",
            obs_dim=OBS_DIM, flush_rows=32)
        self.sessions = make_journaling_sessions(
            prices, WINDOW, sessions, journal=self.journal, seed=7)
        # The client end of the distributed trace: every load request
        # mints a trace id and journals its client_submit root span into
        # the SAME spans dir the fleet processes write (cli fleet points
        # obs.span_dir at <obs.dir>/spans when tracing is on).
        self.spans = SpanSink(SpanJournal(
            os.path.join(workdir, "obs", "spans"), "client"))
        self.engine = WireEngine(host, port, workers=concurrency,
                                 timeout_s=20.0, sink=self.spans)
        self.concurrency = concurrency
        self.completed = 0
        self.failed = 0
        self.submitted = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []

    def start(self) -> "Load":
        per = max(1, len(self.sessions) // self.concurrency)
        for i in range(self.concurrency):
            chunk = self.sessions[i * per:(i + 1) * per] or \
                [self.sessions[i % len(self.sessions)]]
            t = threading.Thread(target=self._loop, args=(chunk,),
                                 daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def _loop(self, sessions) -> None:
        # One request in flight per worker thread, round-robin over its
        # session slice — a closed loop that survives engine kills (a
        # failure counts and the loop moves on).
        idx = 0
        while not self._stop.is_set():
            sess = sessions[idx % len(sessions)]
            idx += 1
            with self._lock:
                self.submitted += 1
            handle = self.engine.submit(sess.sid, sess.observation())
            result = handle.wait(25.0)
            if result is not None:
                sess.advance(result.action)
                with self._lock:
                    self.completed += 1
            else:
                with self._lock:
                    self.failed += 1

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=30.0)
        self.engine.stop()
        self.journal.close()
        self.spans.close()


def probe_request(host: str, port: int, sid: str,
                  timeout_s: float = 15.0) -> dict:
    import numpy as np
    from sharetrade_tpu.fleet.wire import FleetClient
    client = FleetClient(host, port, timeout_s=timeout_s)
    try:
        rng = np.random.default_rng(abs(hash(sid)) % 2**32)
        return client.submit(sid, rng.uniform(1, 2, OBS_DIM))
    finally:
        client.close()


def kill_caught_request(spans_dir: str, victim_pid: int) -> bool:
    """True when a request was INSIDE the killed engine at the kill: its
    trace has the victim's eagerly-flushed ``engine_recv`` marker and
    another engine process's too (the router relays a request a second
    time only after the first engine failed it). Read once the pool has
    recovered, so the retry has long been served; ingress markers are
    flushed as they are written, so this needs no process to exit."""
    from sharetrade_tpu.obs import collect
    inside, elsewhere = set(), set()
    for span in collect.read_span_dir(spans_dir):
        if span["name"] == "engine_recv":
            (inside if span["pid"] == victim_pid
             else elsewhere).add(span["trace"])
    return bool(inside & elsewhere)


def live_engine_pids(status_path: str) -> dict[str, int]:
    status = read_json(status_path) or {}
    engines = ((status.get("pool") or {}).get("engines")) or {}
    return {eid: e["pid"] for eid, e in engines.items()
            if e.get("state") == "alive" and e.get("pid")}


def run_soak(*, engines: int, kills: int, ramp_s: float,
             sessions: int, concurrency: int,
             workdir: str | None = None, keep: bool = False,
             wire_backend: str = "evloop") -> dict:
    own_dir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="fleet_soak_")
    cfg_path = build_config(workdir, engines, wire_backend)
    status_path = os.path.join(workdir, "fleet", "fleet_status.json")
    learner_prom = os.path.join(workdir, "obs", "learner", "metrics.prom")
    log_path = os.path.join(workdir, "fleet.log")
    result: dict = {"engines": engines, "kills_planned": kills,
                    "wire_backend": wire_backend, "workdir": workdir}
    proc = launch_cli("fleet", cfg_path, log_path, symbol="MSFT",
                      extra_args=["--learner", "--engines", str(engines),
                                  "--duration", "0"])
    load = None
    try:
        ready = wait_ready(proc, log_path, timeout_s=240.0)
        host, port = ready["host"], ready["port"]
        result["proto_backend"] = ready.get("proto_backend")
        eprint(f"fleet ready on {host}:{port} with "
               f"{ready['engines']}/{engines} engines (pid {proc.pid}, "
               f"proto_backend={ready.get('proto_backend', '?')})")
        if ready["engines"] != engines:
            raise SoakError(
                f"only {ready['engines']}/{engines} engines came up")
        boot_step = probe_request(host, port, "boot-probe")["params_step"]
        eprint(f"boot params_step = {boot_step}")

        load = Load(host, port, workdir, sessions=sessions,
                    concurrency=concurrency).start()
        # Let the ramp establish warm sessions + journal rows.
        time.sleep(ramp_s)

        # ---- chaos: whole-engine SIGKILLs mid-load ------------------
        injected = 0
        victims: list[str] = []
        spans_dir = os.path.join(workdir, "obs", "spans")
        caught = False
        while injected < kills or (
                not caught and injected < kills + MAX_EXTRA_KILLS):
            k = injected
            pids = live_engine_pids(status_path)
            if len(pids) < 2:
                wait_until(lambda: len(live_engine_pids(status_path)) >= 2,
                           60.0, desc="two live engines before a kill")
                pids = live_engine_pids(status_path)
            victim_id, victim_pid = sorted(pids.items())[k % len(pids)]
            eprint(f"kill {k + 1}/{kills}: SIGKILL engine {victim_id} "
                   f"(pid {victim_pid})"
                   + ("" if k < kills else " — no kill has found a "
                      "request inside its victim yet"))
            os.kill(victim_pid, signal.SIGKILL)
            injected += 1
            victims.append(victim_id)
            # Router must answer IMMEDIATELY (survivors absorb).
            out = probe_request(host, port, f"post-kill-{k}")
            if out.get("action") is None:
                raise SoakError(f"post-kill probe returned {out}")
            # Supervised recovery: restart counter reconciles exactly,
            # membership returns to N.
            wait_until(
                lambda: ((read_json(status_path) or {}).get("pool") or {})
                .get("restarts_total", -1) == injected,
                60.0, desc=f"restarts_total == {injected}")
            wait_until(
                lambda: len(live_engine_pids(status_path)) == engines,
                120.0, desc="membership back to N after the kill")
            pool = (read_json(status_path) or {}).get("pool") or {}
            if pool.get("restarts_total") != injected:
                raise SoakError(
                    f"spurious restarts: {pool.get('restarts_total')} "
                    f"!= injected {injected}")
            caught = caught or kill_caught_request(spans_dir, victim_pid)
            time.sleep(1.0)
        result["kills_injected"] = injected

        # ---- flywheel: production traffic retrains the policy -------
        eprint("waiting for the flywheel: ingest -> tag_best -> swap")
        load.journal.flush()
        wait_until(
            lambda: (prom_value(learner_prom,
                                "distrib_rows_ingested_total") or 0) > 0,
            120.0, desc="learner ingested journaled session rows")
        rows_ingested = prom_value(learner_prom,
                                   "distrib_rows_ingested_total")

        def all_swapped() -> bool:
            status = read_json(status_path) or {}
            engines_st = ((status.get("pool") or {})
                          .get("engines")) or {}
            live = [e for e in engines_st.values()
                    if e.get("state") == "alive"]
            return (len(live) == engines
                    and all((e.get("params_step") or 0) > boot_step
                            and (e.get("swaps_total") or 0) >= 1
                            for e in live))
        wait_until(all_swapped, 180.0,
                   desc="every live engine swapped past the boot step")
        status = read_json(status_path) or {}
        steps = sorted({e.get("params_step") for e in
                        ((status.get("pool") or {}).get("engines") or {})
                        .values() if e.get("state") == "alive"})
        result["flywheel"] = {
            "boot_params_step": boot_step,
            "rows_ingested": rows_ingested,
            "post_swap_params_steps": steps,
        }
        eprint(f"flywheel closed: ingested {rows_ingested:.0f} rows, "
               f"live params_steps {steps}")

        # Swap-settle window: traffic through the freshly-swapped fleet
        # drops nothing.
        settle_fail_before = load.failed
        time.sleep(3.0)
        settled = load.failed - settle_fail_before
        if settled:
            raise SoakError(
                f"{settled} requests failed in the post-swap settle "
                "window (swap must drop nothing)")

        # ---- fleet SLO gauges from the merged histograms ------------
        gauges = (read_json(status_path) or {}).get("gauges") or {}
        merged = (read_json(status_path) or {}).get(
            "fleet_request_ms") or {}
        if not merged.get("count"):
            raise SoakError("merged fleet histogram is empty")
        for key in ("p50_ms", "p99_ms"):
            v = merged.get(key)
            if v is None or not (0 < v < 1e5):
                raise SoakError(f"merged {key} not live/finite: {v}")
        result["fleet_slo"] = {"merged": merged,
                               "window_p50_ms": gauges.get("fleet_p50_ms"),
                               "window_p99_ms": gauges.get("fleet_p99_ms")}

        # ---- stop load, reconcile counters --------------------------
        load.stop()
        rows_journaled = load.journal.rows_journaled
        time.sleep(1.5)     # let the router's poller publish a last pass
        status = read_json(status_path) or {}
        counters = status.get("counters") or {}
        req = counters.get("fleet_requests_total", 0)
        done = counters.get("fleet_completed_total", 0)
        refused = counters.get("fleet_refused_total", 0)
        unrouted = counters.get("fleet_unrouted_total", 0)
        if req != done + refused + unrouted:
            raise SoakError(
                f"router counters do not reconcile: requests {req} != "
                f"completed {done} + refused {refused} + unrouted "
                f"{unrouted}")
        client_total = load.completed + load.failed
        if client_total != load.submitted:
            raise SoakError(
                f"client accounting leak: {load.completed}+{load.failed}"
                f" != submitted {load.submitted}")
        result["traffic"] = {
            "submitted": load.submitted, "completed": load.completed,
            "failed": load.failed, "rows_journaled": rows_journaled,
            "router": {"requests": req, "completed": done,
                       "refused": refused, "unrouted": unrouted,
                       "migrations": counters.get(
                           "fleet_migrations_total", 0)},
        }
        eprint(f"traffic: {load.completed} completed / {load.failed} "
               f"failed of {load.submitted}; router saw {req} "
               f"({counters.get('fleet_migrations_total', 0)} migrations)")
        load = None

        # ---- drain: SIGTERM ends the whole tier with 75 -------------
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        if rc != 75:
            raise SoakError(
                f"fleet drain exited {rc}, want 75: {log_tail(proc)}")
        result["drain_rc"] = rc

        # Session journal stays CRC-clean through the segmented reader.
        from soak_common import journal_high_water
        hw = journal_high_water(os.path.join(
            workdir, "actors", "fleet-client", "transitions.journal"))
        if hw != rows_journaled:
            raise SoakError(
                f"session journal high-water {hw} != rows journaled "
                f"{rows_journaled}")

        # ---- stitched kill forensics --------------------------------
        # Every process has now flushed its span journal (client on
        # load.stop(), fleet + engine workers on the drain; the victim's
        # ingress markers were eagerly flushed BEFORE it died). If a kill
        # found a request inside its victim, at least one migrated
        # request must stitch into one clean trace spanning the corpse,
        # a survivor, and the router's annotated migration.
        from sharetrade_tpu.obs import collect
        wire_spans = collect.read_span_dir(spans_dir)
        if not wire_spans:
            raise SoakError("no wire spans journaled (tracing is on)")
        migrated_tr = collect.migrated_traces(wire_spans)
        if not migrated_tr:
            raise SoakError(
                "no stitched trace carries a migrate-annotated relay "
                f"attempt despite {injected} kill(s)")
        victim_procs = {f"engine-{v}" for v in victims}
        witnesses = [
            t for t in migrated_tr
            if len(t["engines"]) >= 2 and "client" in t["procs"]
            and victim_procs & set(t["engines"]) and not t["errors"]]
        if caught and not witnesses:
            raise SoakError(
                "no CLEAN migrated trace spans both the killed engine "
                "and a survivor; migrated traces: "
                + json.dumps([{k: t[k] for k in
                               ("trace_id", "procs", "engines", "errors")}
                              for t in migrated_tr]))
        pick = witnesses[0] if witnesses else None
        result["tracing"] = {
            "wire_spans": len(wire_spans),
            "traces": len(collect.trace_ids(wire_spans)),
            "migrated_traces": len(migrated_tr),
            "kill_caught_request": caught,
            "witness": pick and {"trace_id": pick["trace_id"],
                                 "procs": pick["procs"],
                                 "engines": pick["engines"],
                                 "spans": len(pick["spans"])},
        }
        eprint(f"stitched kill forensics: trace {pick['trace_id']} "
               f"spans {pick['engines']} through the migration"
               if pick else
               f"stitched kill forensics: none of {injected} kills found "
               "a request inside its victim; no trace to stitch")
        result["ok"] = True
        return result
    finally:
        if load is not None:
            try:
                load.stop()
            except Exception:   # noqa: BLE001
                pass
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        if own_dir and not keep:
            shutil.rmtree(workdir, ignore_errors=True)


def run_spill_soak(*, engines: int = 2, sessions: int = 24,
                   rounds: int = 3, control: bool = False,
                   workdir: str | None = None, keep: bool = False,
                   wire_backend: str = "evloop") -> dict:
    """Kill-under-population profile (ISSUE 20): SIGKILL an engine
    whose sessions straddle every tier of the paging hierarchy and
    assert the spill arena turns the crash into WARM adoptions.

    One serve-only fleet (episode model — real per-session carries),
    slot + warm budgets tiny enough that a sequential round-robin
    population pushes most carries onto the shared disk arena. Then:
    census which engine owns each session (the router splices the
    serving engine id into every 200) and which sessions have a sealed
    arena record; corrupt ONE record of the victim's (bit flip in the
    payload); SIGKILL the victim; sweep every one of its sessions once
    and reconcile the fleet counters EXACTLY:

    - ``fleet_adopt_warm_total``  == victim's spilled sessions - 1
      (every sealed record adopts warm on a foreign incarnation...),
    - ``fleet_spill_corrupt_total`` == 1 and the corrupted session's
      request still COMPLETES (...except the flipped one, which the
      CRC demotes to a cold restart — latency, never wrong bytes),
    - ``fleet_adopt_cold_total``  == victim's in-memory sessions + 1
      (slot/warm carries died with the process, plus the corrupt one),
    - ``fleet_spill_stale_total`` == 0 (the router's session clock
      matches every sealed stamp once traffic quiesces),
    - majority-warm: warm adoptions strictly outnumber cold ones.

    The SIGTERM drain then seals EVERY live carry (exit 75), so the
    arena ends the run holding one record per session. ``control=True``
    runs the identical scenario with the spill tier OFF — the latency
    control of the kill-recovery comparison (ROADMAP W4). The sweep metric
    is STATE-EQUIVALENT recovery per session (time until the session's
    carry is back at pre-kill depth plus one fresh step): one warm
    adoption with spill on; a full observation-history REPLAY through
    prefill with it off — the recompute the arena exists to avoid. A
    raw one-request comparison would flatter the control by silently
    downgrading every recovered session to an empty carry."""
    import numpy as np
    from sharetrade_tpu.fleet.wire import FleetClient
    from sharetrade_tpu.serve.spill import SPILL_SUFFIX, record_name
    own_dir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="fleet_spill_")
    cfg_path = build_config(workdir, engines, wire_backend,
                            spill_profile=True, spill_control=control)
    status_path = os.path.join(workdir, "fleet", "fleet_status.json")
    arena_dir = os.path.join(workdir, "fleet", "spill")
    log_path = os.path.join(workdir, "fleet.log")
    profile = "spill-control" if control else "spill"
    result: dict = {"profile": profile, "engines": engines,
                    "sessions": sessions, "rounds": rounds,
                    "workdir": workdir}
    sids = [f"spill-{i:03d}" for i in range(sessions)]
    rngs = {sid: np.random.default_rng(1000 + i)
            for i, sid in enumerate(sids)}

    def counters() -> dict:
        return ((read_json(status_path) or {}).get("counters")) or {}

    def sealed() -> set:
        try:
            return {f for f in os.listdir(arena_dir)
                    if f.endswith(SPILL_SUFFIX)}
        except OSError:
            return set()

    proc = launch_cli("fleet", cfg_path, log_path, symbol="MSFT",
                      extra_args=["--engines", str(engines),
                                  "--duration", "0"])
    client = None
    try:
        ready = wait_ready(proc, log_path, timeout_s=240.0)
        host, port = ready["host"], ready["port"]
        eprint(f"[{profile}] fleet ready on {host}:{port} "
               f"({ready['engines']}/{engines} engines, pid {proc.pid})")
        if ready["engines"] != engines:
            raise SoakError(
                f"only {ready['engines']}/{engines} engines came up")
        client = FleetClient(host, port, timeout_s=30.0)

        def step(sid: str, obs) -> dict:
            try:
                return client.submit(sid, obs, timeout_s=30.0)
            except Exception as exc:   # noqa: BLE001
                raise SoakError(
                    f"[{profile}] request for {sid} failed: {exc!r}")

        # ---- populate: sequential round-robin over every session ----
        # Sequential on purpose: each session's clock and its sealed
        # stamp advance in lockstep with NOTHING in flight, so the
        # post-kill reconciliation below can demand exact equality.
        # Every obs is kept: the control's recovery path replays it.
        census: dict[str, str] = {}
        hist: dict[str, list] = {sid: [] for sid in sids}
        for _ in range(rounds):
            for sid in sids:
                obs = rngs[sid].uniform(1.0, 2.0, OBS_DIM)
                hist[sid].append(obs)
                out = step(sid, obs)
                census[sid] = out.get("engine", "?")
        time.sleep(2.0)     # quiesce: trailing demotions + a poll pass

        spilled_all = {sid for sid in sids
                       if record_name(sid) in sealed()}
        by_engine: dict[str, list[str]] = {}
        for sid, eid in census.items():
            by_engine.setdefault(eid, []).append(sid)
        if not control:
            # The shared-arena census gauges are LIVE on the status
            # file (each engine scans the whole shared dir, so the
            # fleet sum over-counts by the sharing factor — a load
            # signal, not an exact census; >= is the honest bound).
            gauges = ((read_json(status_path) or {}).get("gauges")) or {}
            if gauges.get("fleet_spill_sessions", 0) < len(spilled_all):
                raise SoakError(
                    f"fleet_spill_sessions gauge "
                    f"{gauges.get('fleet_spill_sessions')} < sealed "
                    f"census {len(spilled_all)}")
            if not gauges.get("fleet_spill_bytes", 0) > 0:
                raise SoakError("fleet_spill_bytes gauge not live")
        # Victim: the engine owning the most spilled sessions (most
        # state to carry over); any engine in the control run.
        victim_id = max(by_engine,
                        key=lambda e: (len([s for s in by_engine[e]
                                            if s in spilled_all]),
                                       len(by_engine[e])))
        v_sids = sorted(by_engine[victim_id])
        v_spill = [s for s in v_sids if s in spilled_all]
        v_mem = [s for s in v_sids if s not in spilled_all]
        result["census"] = {
            "victim": victim_id, "victim_sessions": len(v_sids),
            "victim_spilled": len(v_spill),
            "victim_memory": len(v_mem),
            "sealed_total": len(spilled_all)}
        eprint(f"[{profile}] census: victim {victim_id} holds "
               f"{len(v_sids)} sessions ({len(v_spill)} sealed on disk, "
               f"{len(v_mem)} in memory); arena holds "
               f"{len(spilled_all)} records")
        corrupted = None
        if not control:
            if len(v_spill) < 3:
                raise SoakError(
                    f"population too shallow: victim has only "
                    f"{len(v_spill)} spilled sessions (need >= 3)")
            # Bit-flip the PAYLOAD tail of one sealed record: the CRC
            # must demote this session to a cold restart — injected
            # corruption may cost latency, never wrong bytes.
            corrupted = v_spill[0]
            from soak_common import flip_byte
            flip_byte(os.path.join(arena_dir, record_name(corrupted)),
                      offset_frac=0.99)
            eprint(f"[{profile}] corrupted the sealed record of "
                   f"{corrupted}")

        # ---- SIGKILL the victim, sweep its sessions once ------------
        base = counters()
        pids = live_engine_pids(status_path)
        if victim_id not in pids:
            raise SoakError(f"victim {victim_id} not alive in {pids}")
        eprint(f"[{profile}] SIGKILL engine {victim_id} "
               f"(pid {pids[victim_id]})")
        os.kill(pids[victim_id], signal.SIGKILL)
        # Per-session STATE-EQUIVALENT recovery: with spill on, one
        # request adopts the sealed carry warm; with it off the carry
        # died with the process and equivalence costs a full history
        # replay through prefill. Both end one fresh step past the
        # session's pre-kill depth.
        sweep_ms: list[float] = []
        for sid in v_sids:
            nxt = rngs[sid].uniform(1.0, 2.0, OBS_DIM)
            t0 = time.perf_counter()
            if control:
                for obs in hist[sid]:
                    step(sid, obs)
            out = step(sid, nxt)
            sweep_ms.append((time.perf_counter() - t0) * 1e3)
            if out.get("action") is None:
                raise SoakError(
                    f"[{profile}] post-kill sweep of {sid} returned "
                    f"{out}")
        sweep_sorted = sorted(sweep_ms)
        result["recovery_p50_ms"] = round(
            sweep_sorted[len(sweep_sorted) // 2], 2)
        result["recovery_p99_ms"] = round(
            sweep_sorted[min(len(sweep_sorted) - 1,
                             int(0.99 * len(sweep_sorted)))], 2)
        eprint(f"[{profile}] recovery sweep of {len(v_sids)} sessions: "
               f"p50 {result['recovery_p50_ms']}ms "
               f"p99 {result['recovery_p99_ms']}ms")

        # ---- exact reconciliation -----------------------------------
        if control:
            expect = {"fleet_adopt_warm_total": 0,
                      "fleet_adopt_cold_total": len(v_sids),
                      "fleet_spill_corrupt_total": 0,
                      "fleet_spill_stale_total": 0}
        else:
            expect = {"fleet_adopt_warm_total": len(v_spill) - 1,
                      "fleet_adopt_cold_total": len(v_mem) + 1,
                      "fleet_spill_corrupt_total": 1,
                      "fleet_spill_stale_total": 0}

        def deltas() -> dict:
            cur = counters()
            return {k: cur.get(k, 0) - base.get(k, 0) for k in expect}

        wait_until(lambda: deltas() == expect, 30.0,
                   desc=f"[{profile}] adoption counters reconcile")
        time.sleep(1.0)     # stability: one more poll, still exact
        got = deltas()
        if got != expect:
            raise SoakError(
                f"[{profile}] adoption counters drifted after "
                f"reconciling: {got} != {expect}")
        result["recon"] = got
        if not control:
            warm, cold = got["fleet_adopt_warm_total"], \
                got["fleet_adopt_cold_total"]
            if not warm > cold:
                raise SoakError(
                    f"no warm majority: {warm} warm vs {cold} cold "
                    "adoptions (the arena should carry most sessions)")
            eprint(f"[{profile}] reconciled exactly: {warm} warm / "
                   f"{cold} cold adoptions, 1 corrupt, 0 stale")
        # Supervised recovery: exactly the one injected kill.
        wait_until(
            lambda: ((read_json(status_path) or {}).get("pool") or {})
            .get("restarts_total", -1) == 1,
            60.0, desc="restarts_total == 1")
        wait_until(lambda: len(live_engine_pids(status_path)) == engines,
                   120.0, desc="membership back to N")

        # ---- drain: every live carry seals into the arena -----------
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        if rc != 75:
            raise SoakError(
                f"fleet drain exited {rc}, want 75: {log_tail(proc)}")
        result["drain_rc"] = rc
        if not control:
            missing = [sid for sid in sids
                       if record_name(sid) not in sealed()]
            if missing:
                raise SoakError(
                    f"drain page-out left {len(missing)} sessions "
                    f"unsealed: {missing[:5]}")
            result["arena_records_after_drain"] = len(sealed())
            eprint(f"[{profile}] drain sealed every session: "
                   f"{len(sealed())} records for {sessions} sessions")
        result["ok"] = True
        return result
    finally:
        if client is not None:
            try:
                client.close()
            except Exception:   # noqa: BLE001
                pass
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        if own_dir and not keep:
            shutil.rmtree(workdir, ignore_errors=True)


def run_autoscale_soak(*, ceiling: int = 2, sessions: int = 32,
                       concurrency: int = 16,
                       surge_budget_s: float = 120.0,
                       quiet_budget_s: float = 90.0,
                       workdir: str | None = None,
                       keep: bool = False) -> dict:
    """Diurnal-load autoscale profile: one ``cli fleet --autoscale``
    tier starting at the floor (1 engine, ceiling ``ceiling``), a
    client SURGE whose queue depth drives the autoscaler up to the
    ceiling, then a QUIET phase whose sustained silence walks it back
    down to the floor. Asserts the membership controller's operational
    contract under real processes:

    - **engine count tracks load** — live membership reaches the
      ceiling during the surge and returns to the floor in the quiet
      (engines retire via the SIGTERM drain, never SIGKILL);
    - **zero restart storms** — ``restarts_total`` stays 0 and no
      engine lands in ``failed``: every membership change is a
      deliberate spawn or retirement, never a crash-respawn loop;
    - **SLO burn < 1** — the surge queues but does not burn the
      availability budget (the closed loop drops nothing), read from
      the router's own telemetry history ring — the same rows the
      autoscaler decided on;
    - the autoscaler's state file records both decisions, and SIGTERM
      still drains the whole tier with exit 75.
    """
    own_dir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="fleet_autoscale_")
    cfg_path = build_config(workdir, engines=1,
                            autoscale_ceiling=ceiling)
    status_path = os.path.join(workdir, "fleet", "fleet_status.json")
    state_path = os.path.join(workdir, "fleet", "fleet_autoscale.json")
    log_path = os.path.join(workdir, "fleet.log")
    result: dict = {"ceiling": ceiling, "workdir": workdir}
    proc = launch_cli("fleet", cfg_path, log_path, symbol="MSFT",
                      extra_args=["--engines", "1", "--autoscale",
                                  "--duration", "0"])
    load = None
    try:
        ready = wait_ready(proc, log_path, timeout_s=240.0)
        host, port = ready["host"], ready["port"]
        eprint(f"fleet ready on {host}:{port} at the floor "
               f"(1 engine, ceiling {ceiling}; pid {proc.pid})")

        def pool_state() -> dict:
            return ((read_json(status_path) or {}).get("pool")) or {}

        # ---- surge: closed-loop concurrency >> one engine's batch ----
        t_surge = time.monotonic()
        load = Load(host, port, workdir, sessions=sessions,
                    concurrency=concurrency).start()
        wait_until(
            lambda: len(live_engine_pids(status_path)) >= ceiling,
            surge_budget_s,
            desc=f"autoscaler grows membership to the ceiling ({ceiling})")
        result["surge_scale_up_s"] = round(time.monotonic() - t_surge, 1)
        pool = pool_state()
        if pool.get("restarts_total", 0) != 0:
            raise SoakError(
                "restart storm during the surge: restarts_total = "
                f"{pool.get('restarts_total')} (scale-ups must be "
                "spawns, not crash-respawns)")
        eprint(f"surge: membership at ceiling in "
               f"{result['surge_scale_up_s']}s, restarts 0")

        # ---- quiet: the load stops; silence walks membership down ----
        load.stop()
        surge_traffic = {"submitted": load.submitted,
                         "completed": load.completed,
                         "failed": load.failed}
        load = None
        if surge_traffic["failed"]:
            raise SoakError(
                f"{surge_traffic['failed']} requests failed during the "
                "surge (queueing must delay, never drop)")
        t_quiet = time.monotonic()
        wait_until(
            lambda: len(live_engine_pids(status_path)) == 1,
            quiet_budget_s,
            desc="autoscaler retires back to the floor (1 engine)")
        result["quiet_scale_down_s"] = round(time.monotonic() - t_quiet, 1)
        pool = pool_state()
        if pool.get("restarts_total", 0) != 0:
            raise SoakError(
                "restart storm: retirements were misclassified — "
                f"restarts_total = {pool.get('restarts_total')}")
        eprint(f"quiet: membership back at the floor in "
               f"{result['quiet_scale_down_s']}s, restarts still 0")

        # ---- the controller's own ledger + the ring it decided on ----
        state = read_json(state_path) or {}
        if state.get("decisions", 0) < 2:
            raise SoakError(
                f"autoscaler state records {state.get('decisions')} "
                "decisions; the diurnal profile needs >= 2 (up + down)")
        if state.get("target") != 1:
            raise SoakError(
                f"autoscaler target settled at {state.get('target')}, "
                "want the floor (1)")
        sys.path.insert(0, REPO)
        from sharetrade_tpu.obs.tsdb import read_history
        rows = read_history(os.path.join(workdir, "fleet",
                                         "fleet_history.jsonl"),
                            last_n=64)
        burns = [float(r.get("fleet_slo_availability_burn", 0.0) or 0.0)
                 for r in rows]
        if burns and max(burns) >= 1.0:
            raise SoakError(
                f"availability burn peaked at {max(burns):.2f} >= 1.0: "
                "the surge ate the error budget")
        result["autoscaler"] = {
            "decisions": state.get("decisions"),
            "last_decision": state.get("last_decision"),
            "peak_burn": max(burns) if burns else 0.0,
            "history_rows": len(rows),
        }
        result["traffic"] = surge_traffic

        # ---- drain --------------------------------------------------
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        if rc != 75:
            raise SoakError(
                f"fleet drain exited {rc}, want 75: {log_tail(proc)}")
        result["drain_rc"] = rc
        result["ok"] = True
        return result
    finally:
        if load is not None:
            try:
                load.stop()
            except Exception:   # noqa: BLE001
                pass
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        if own_dir and not keep:
            shutil.rmtree(workdir, ignore_errors=True)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--engines", type=int, default=3)
    parser.add_argument("--kills", type=int, default=3)
    parser.add_argument("--ramp", type=float, default=6.0)
    parser.add_argument("--sessions", type=int, default=64)
    parser.add_argument("--concurrency", type=int, default=12)
    parser.add_argument("--wire-backend", default="evloop",
                        choices=("evloop", "threaded"),
                        help="front-end/router data path to soak "
                             "(threaded = the differential oracle)")
    parser.add_argument("--quick", action="store_true",
                        help="tier-1 profile: 2 engines, 1 kill, short "
                             "ramp")
    parser.add_argument("--autoscale", action="store_true",
                        help="diurnal autoscale profile instead of the "
                             "kill-test: surge to the ceiling, quiet "
                             "back to the floor, zero restart storms")
    parser.add_argument("--spill", action="store_true",
                        help="kill-under-population profile: SIGKILL an "
                             "engine whose sessions straddle the paging "
                             "tiers, reconcile warm/cold adoptions "
                             "exactly; the full (non-quick) run also "
                             "measures the no-spill control")
    parser.add_argument("--rounds", type=int, default=3,
                        help="spill profile: population passes over the "
                             "session list before the kill")
    parser.add_argument("--ceiling", type=int, default=2,
                        help="autoscale profile's membership ceiling")
    parser.add_argument("--keep", action="store_true",
                        help="keep the scratch dir for forensics")
    args = parser.parse_args()
    if args.spill:
        sessions = min(args.sessions, 24) if args.quick else args.sessions
        rounds = min(args.rounds, 2) if args.quick else args.rounds
        t0 = time.monotonic()
        try:
            result = run_spill_soak(engines=2, sessions=sessions,
                                    rounds=rounds, keep=args.keep,
                                    wire_backend=args.wire_backend)
            if not args.quick:
                # The no-spill control: identical scenario, arena off.
                # Its sweep is all cold restarts — the latency baseline
                # the spill arm must beat.
                result["control"] = run_spill_soak(
                    engines=2, sessions=sessions, rounds=rounds,
                    control=True, keep=args.keep,
                    wire_backend=args.wire_backend)
                spill_p99 = result["recovery_p99_ms"]
                ctrl_p99 = result["control"]["recovery_p99_ms"]
                if not spill_p99 < ctrl_p99:
                    raise SoakError(
                        f"post-kill state-equivalent recovery p99 "
                        f"{spill_p99}ms is not strictly better than "
                        f"the no-spill control's {ctrl_p99}ms")
        except SoakError as exc:
            print(json.dumps({"ok": False, "error": str(exc)}),
                  flush=True)
            eprint(f"FLEET SPILL SOAK FAILED: {exc}")
            return 1
        result["elapsed_s"] = round(time.monotonic() - t0, 1)
        print(json.dumps(result), flush=True)
        eprint(f"fleet spill soak OK in {result['elapsed_s']}s")
        return 0
    if args.autoscale:
        t0 = time.monotonic()
        try:
            result = run_autoscale_soak(ceiling=args.ceiling,
                                        sessions=args.sessions,
                                        concurrency=args.concurrency,
                                        keep=args.keep)
        except SoakError as exc:
            print(json.dumps({"ok": False, "error": str(exc)}),
                  flush=True)
            eprint(f"FLEET AUTOSCALE SOAK FAILED: {exc}")
            return 1
        result["elapsed_s"] = round(time.monotonic() - t0, 1)
        print(json.dumps(result), flush=True)
        eprint(f"fleet autoscale soak OK in {result['elapsed_s']}s")
        return 0
    if args.quick:
        args.engines = min(args.engines, 2)
        args.kills = min(args.kills, 1)
        args.ramp = min(args.ramp, 3.0)
        args.sessions = min(args.sessions, 32)
        args.concurrency = min(args.concurrency, 8)
    t0 = time.monotonic()
    try:
        result = run_soak(engines=args.engines, kills=args.kills,
                          ramp_s=args.ramp, sessions=args.sessions,
                          concurrency=args.concurrency, keep=args.keep,
                          wire_backend=args.wire_backend)
    except SoakError as exc:
        print(json.dumps({"ok": False, "error": str(exc)}), flush=True)
        eprint(f"FLEET SOAK FAILED: {exc}")
        return 1
    result["elapsed_s"] = round(time.monotonic() - t0, 1)
    print(json.dumps(result), flush=True)
    eprint(f"fleet soak OK in {result['elapsed_s']}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
