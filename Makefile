# Round-end gate and developer entry points.
#
# `make check` is the gate to run before every milestone commit: the fast
# test subset (compile-heavy tests are marked `slow`) on the CPU backend.
# Interpret-mode tests cannot catch Pallas tiling legality, so
# tests/test_chip_compile.py compiles the main path's kernels and whole
# programs for a DESCRIBED v5e (no chip needed), and `python chip_smoke.py`
# (through the chip tool) is the proof on the chip.

PYTHON ?= python

.PHONY: check test slow native autotune autotune-quick actor-soak crash-soak fleet-soak fleet-soak-autoscale obs-demo lint serve-chaos serve-soak shard-audit clean

check: native lint
	$(PYTHON) -m pytest tests/ -q -m "not slow" -x
	$(PYTHON) tools/obs_demo.py
	$(PYTHON) tools/serve_chaos.py --injections 2
	$(PYTHON) tools/actor_soak.py --kills 2 --actors 2 --quick --no-scale
	$(PYTHON) tools/fleet_soak.py --quick
	$(PYTHON) tools/autotune.py --quick --out /tmp/tuned_profile_quick.json --json
	$(PYTHON) tools/shard_audit.py

test: native
	$(PYTHON) -m pytest tests/ -q

slow: native
	$(PYTHON) -m pytest tests/ -q -m slow

native:
	$(MAKE) -C native

# Zero-to-summary telemetry demo: short obs-enabled training, artifact
# checks, then the `cli obs` summary of the run dir (also part of check).
obs-demo:
	$(PYTHON) tools/obs_demo.py

# Compile-time shard audit (also part of check): every mesh-config in the
# matrix must compile with zero XLA "Involuntary full rematerialization"
# warnings and collective counts within tools/shard_audit_manifest.json.
# Regenerate the manifest after an intentional change with
# `python tools/shard_audit.py --update`.
shard-audit:
	$(PYTHON) tools/shard_audit.py

# Serving-tier load soak: thousands of synthetic sessions, open-loop rate
# sweep, continuous batching vs the batch=1 server head-to-head; --strict
# enforces the >=3x-QPS-at-equal-or-better-p99 acceptance (ISSUE 8).
serve-soak:
	$(PYTHON) tools/serve_soak.py --strict

# Serve chaos soak: >= 20 seeded fault injections (dispatch exception,
# slow consumer, corrupt swap candidate, queue flood, deadline burst)
# against the real continuous-batching engine, asserting after every one:
# no wedge (every request reaches a terminal outcome), queue depth stays
# <= serve.max_queue, post-restart sessions match fresh sessions bitwise,
# and shed/restart/breaker counters reconcile exactly with the injected
# counts (tools/serve_chaos.py; the 2-injection quick profile runs in
# tier-1 and in `make check`).
serve-chaos:
	$(PYTHON) tools/serve_chaos.py --injections 20

# Actor-process kill soak: >= 20 seeded SIGKILL/SIGTERM injections into
# LIVE actor subprocesses under a training learner (N=4 pool), asserting
# after every kill that the learner never restarts, journal CRC /
# high-water invariants hold through the segmented reader, and
# membership/restart counters reconcile exactly — plus the mid-soak
# elastic-membership scale() join and the terminal-failure degrade
# (tools/actor_soak.py; the 2-kill quick profile runs in tier-1 via
# tests/test_actor_soak.py and in `make check`).
actor-soak:
	$(PYTHON) tools/actor_soak.py --kills 20 --actors 4

# Fleet kill-test (tools/fleet_soak.py): one cli fleet tier (router +
# N cli serve --listen engine workers + live learner) under closed-loop
# journaling load; whole-engine SIGKILLs mid-ramp, asserting after every
# kill: router answers immediately, zero client requests fail (migration
# through prefill), restart counters reconcile exactly — then the
# flywheel closes (session journals ingested, tag_best republished,
# every engine hot-swaps) and SIGTERM drains the tier with exit 75. The
# quick 1-kill profile rides tier-1 (tests/test_fleet_soak.py) and
# `make check`.
fleet-soak:
	$(PYTHON) tools/fleet_soak.py --engines 3 --kills 3

# Diurnal autoscale profile (tools/fleet_soak.py --autoscale): one
# cli fleet --autoscale tier through a surge/quiet cycle — membership
# grows to the ceiling under queueing load and retires back to the
# floor in silence, zero restart storms, availability burn < 1, clean
# exit-75 drain. The same profile rides tier-1 via
# tests/test_fleet_soak.py::TestAutoscaleSoak.
fleet-soak-autoscale:
	$(PYTHON) tools/fleet_soak.py --autoscale --ceiling 2

# Process-kill chaos soak: >= 20 seeded SIGKILL/SIGTERM injections into real
# training subprocesses (journaled DQN config), each followed by --resume,
# plus the bit-flip walk-back scenario — the crash-safety invariants end to
# end (tools/crash_soak.py; the 2-kill quick profile runs in tier-1 via
# tests/test_crash_soak.py).
crash-soak:
	$(PYTHON) tools/crash_soak.py --kills 20

# Offline autotune sweep (tools/autotune.py): successive-halving search
# over the knob registry's train (megachunk K x pipeline depth) and
# serve (max_batch x batch_timeout_ms x max_queue) grids on short
# measured windows, writing the per-host tuned_profile.json that
# `tuning.profile` loads (explicit config > profile > defaults). Add
# `--spec train,serve,distrib --exhaustive` for the acceptance
# comparison against the full hand-sweep grid.
autotune:
	$(PYTHON) tools/autotune.py --out tuned_profile.json

# Seconds-scale profile of the same sweep (tiny grid, short windows) —
# wired into `make check` as the end-to-end gate that the sweep ->
# profile -> load path stays green; writes to /tmp, never the repo.
autotune-quick:
	$(PYTHON) tools/autotune.py --quick --out /tmp/tuned_profile_quick.json --json

# Static guard: no bare scalar device syncs in the orchestrator hot loop.
lint:
	$(PYTHON) tools/lint_hot_loop.py

clean:
	$(MAKE) -C native clean
