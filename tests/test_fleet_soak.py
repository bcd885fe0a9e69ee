"""Fleet kill-test (tools/fleet_soak.py) — REAL router + engine worker
subprocesses + live learner, real SIGKILLs, driven in-process.

The quick profile (2 engines, 1 whole-engine SIGKILL under closed-loop
journaling load) is the tier-1 guard for the fleet contract: the router
never wedges (a post-kill probe answers immediately and ZERO client
requests fail — migration absorbs the corpse's in-flight work), the
pool's restart counter reconciles exactly with the injected kills, the
flywheel closes (journaled session transitions ingested by the live
learner, a fresh ``tag_best`` hot-swapped into EVERY engine — healthz
``params_step`` advances fleet-wide), the merged-histogram fleet SLO
gauges are live, router counters balance exactly, and SIGTERM drains
the whole tier with exit 75. The full soak — >=3 engines, >=3 kills —
is the ``slow``-marked variant (also ``make fleet-soak``).

The spill soak (ISSUE 20) kills an engine UNDER a populated spill
arena: survivors must adopt the victim's sessions warm from disk (the
majority — only the injected-corruption record and the in-memory tail
restart cold), the fleet adoption counters must reconcile exactly, and
the drain must seal the entire population for the next incarnation.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import fleet_soak  # noqa: E402


class TestQuickSoak:
    def test_one_kill_flywheel_and_reconciliation(self, tmp_path):
        summary = fleet_soak.run_soak(
            engines=2, kills=1, ramp_s=3.0, sessions=32, concurrency=8,
            workdir=str(tmp_path))
        assert summary["ok"] is True
        # One kill, or more (bounded) until one found a request inside
        # its victim: the forensics below need such a kill.
        assert 1 <= summary["kills_injected"] \
            <= 1 + fleet_soak.MAX_EXTRA_KILLS
        # Migration absorbed the kill: the closed loop dropped nothing.
        assert summary["traffic"]["failed"] == 0
        assert summary["traffic"]["completed"] > 0
        # Flywheel: sessions' journals fed the learner and the republished
        # tag_best reached every live engine.
        fw = summary["flywheel"]
        assert fw["rows_ingested"] > 0
        assert all(s > fw["boot_params_step"]
                   for s in fw["post_swap_params_steps"])
        # Live merged-histogram SLO gauges.
        assert summary["fleet_slo"]["merged"]["count"] > 0
        assert summary["drain_rc"] == 75
        # Stitched kill forensics: when a kill found a request inside its
        # victim, one CLEAN trace spans the killed engine (eagerly-flushed
        # ingress marker), a survivor, the client's root span, and the
        # router's migrate-annotated relay attempt (run_soak raises
        # unless all of that held); when none of the kills did, there is
        # no such trace and the soak says so.
        tr = summary["tracing"]
        assert tr["migrated_traces"] >= 1
        if tr["kill_caught_request"]:
            assert tr["witness"] is not None
        if tr["witness"] is not None:
            assert len(tr["witness"]["engines"]) >= 2
            assert "client" in tr["witness"]["procs"]
            assert "fleet" in tr["witness"]["procs"]


class TestAutoscaleSoak:
    def test_diurnal_profile_tracks_load(self, tmp_path):
        """One ``cli fleet --autoscale`` tier through a surge/quiet
        cycle: membership grows to the ceiling under queueing load and
        retires back to the floor in silence, with ZERO restart storms
        (every change a deliberate spawn/retirement), no dropped
        requests, availability burn < 1 in the same history ring the
        autoscaler decided on, and a clean exit-75 drain."""
        summary = fleet_soak.run_autoscale_soak(
            ceiling=2, sessions=32, concurrency=16,
            workdir=str(tmp_path))
        assert summary["ok"] is True
        assert summary["autoscaler"]["decisions"] >= 2
        assert summary["autoscaler"]["last_decision"]["action"] == "down"
        assert summary["autoscaler"]["peak_burn"] < 1.0
        assert summary["traffic"]["failed"] == 0
        assert summary["traffic"]["completed"] > 0
        assert summary["drain_rc"] == 75


class TestSpillSoak:
    def test_kill_under_population_warm_majority(self, tmp_path):
        """SIGKILL the engine holding the most spilled carries while the
        arena holds a populated session census (one record injected with
        corruption): survivors adopt the MAJORITY warm, the fleet
        adoption/corruption counters reconcile EXACTLY against the
        census, and the final drain seals every session's carry for the
        next incarnation (the warm-handoff half of ISSUE 20)."""
        summary = fleet_soak.run_spill_soak(
            engines=2, sessions=24, rounds=2, workdir=str(tmp_path))
        assert summary["ok"] is True
        recon = summary["recon"]
        census = summary["census"]
        # Exact reconciliation: every spilled victim session adopted
        # warm except the one corrupted record; every in-memory victim
        # session (plus the corrupt one) restarted cold.
        assert recon["fleet_adopt_warm_total"] == \
            census["victim_spilled"] - 1
        assert recon["fleet_adopt_cold_total"] == \
            census["victim_memory"] + 1
        assert recon["fleet_spill_corrupt_total"] == 1
        assert recon["fleet_spill_stale_total"] == 0
        assert recon["fleet_adopt_warm_total"] > \
            recon["fleet_adopt_cold_total"]
        assert summary["drain_rc"] == 75
        # Drain-time page-out: one sealed record per session, none lost.
        assert summary["arena_records_after_drain"] == 24


@pytest.mark.slow
class TestFullSoak:
    def test_multi_engine_multi_kill(self, tmp_path):
        summary = fleet_soak.run_soak(
            engines=3, kills=3, ramp_s=6.0, sessions=64, concurrency=12,
            workdir=str(tmp_path))
        assert summary["ok"] is True
        assert summary["kills_injected"] >= 3
        assert summary["traffic"]["failed"] == 0
