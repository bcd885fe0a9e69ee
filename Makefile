# Round-end gate and developer entry points.
#
# `make check` is the gate to run before every milestone commit: the fast
# test subset (compile-heavy tests are marked `slow`) on the CPU backend.
# Interpret-mode tests cannot catch Pallas tiling legality, so
# tests/test_chip_compile.py compiles the main path's kernels and whole
# programs for a DESCRIBED v5e (no chip needed), and `python chip_smoke.py`
# (through the chip tool) is the proof on the chip.

PYTHON ?= python

.PHONY: check test slow native bench autotune autotune-quick bench-actor bench-async bench-autotune bench-ckpt bench-dispatch bench-fleet bench-obs bench-paging bench-router bench-precision bench-replay bench-reshard bench-roofline bench-serve bench-serve-overload actor-soak crash-soak fleet-soak fleet-soak-autoscale obs-demo lint perf-gate serve-chaos serve-soak shard-audit clean

check: native lint
	$(PYTHON) -m pytest tests/ -q -m "not slow" -x
	$(PYTHON) tools/obs_demo.py
	$(PYTHON) tools/serve_chaos.py --injections 2
	$(PYTHON) tools/actor_soak.py --kills 2 --actors 2 --quick --no-scale
	$(PYTHON) tools/fleet_soak.py --quick
	$(PYTHON) tools/autotune.py --quick --out /tmp/tuned_profile_quick.json --json
	$(PYTHON) tools/shard_audit.py
	$(PYTHON) tools/perf_gate.py

test: native
	$(PYTHON) -m pytest tests/ -q

slow: native
	$(PYTHON) -m pytest tests/ -q -m slow

native:
	$(MAKE) -C native

bench:
	$(PYTHON) bench.py

# The dispatch-floor ladder alone (megachunk K in {1, 8, 64}): the lever
# behind runtime.megachunk_factor, runnable on CPU in ~a minute.
bench-dispatch:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_dispatch_floor(), indent=2))"

# The host-offload pipeline alone (runtime.async_pipeline off vs on at
# K in {1, 8}): inter-dispatch gap p50/p99 from the obs trace's dispatch
# spans plus steps/s — the async-readback lever, recorded in BASELINE.md
# "Host-offload pipeline". Runnable on CPU in ~a minute.
bench-async:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_async_pipeline(), indent=2))"

# Telemetry overhead alone (obs.enabled off vs on at K in {1, 8}, with an
# A/A noise-floor control, plus the direct per-sample cost): the <2%
# budget recorded in BASELINE.md "Telemetry overhead".
bench-obs:
	$(PYTHON) -c "import json, bench; \
	r = bench.bench_obs_overhead(); \
	r['per_sample'] = bench.bench_obs_sample_cost(); \
	print(json.dumps(r, indent=2))"

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

# The resharding-constraint row alone (parallel.shard_constraints on vs off
# on the forced-8-device host mesh): steps/s + per-dispatch collective
# bytes, recorded in BASELINE.md "Multichip resharding".
bench-reshard:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_reshard(), indent=2))"

# The checkpoint durability tax alone (checkpoint.fsync on vs off, two
# payload sizes): the numbers behind the fsync-on default, recorded in
# BASELINE.md "Checkpoint fsync".
bench-ckpt:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_ckpt_fsync(), indent=2))"

# Roofline telemetry alone (obs.roofline off vs on, with an A/A control):
# the <2% capture+gauge budget plus the captured per-program FLOPs /
# arithmetic intensity / classification, recorded in BASELINE.md
# "Roofline". Runnable on CPU in ~a minute.
bench-roofline:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_roofline(), indent=2))"

# Precision-policy A/B (precision.mode fp32 vs bf16_mixed): reference-MLP
# steps/s + static costs, flagship episode-PPO compile-only static bytes —
# the measured state-bytes reduction behind bf16_mixed, recorded in
# BASELINE.md "Precision". Runnable on CPU in ~a minute (CPU-framed: bf16
# compute is f32-emulated there; see the bench row's note).
bench-precision:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_precision(), indent=2))"

# Serving tier A/B (continuous batching vs the batch=1 closed-loop
# baseline, rate sweep + saturation + the cache-bound episode row): the
# numbers behind BASELINE.md "Serving" and the serve_qps / serve_p99_ms
# perf-gate series. Runnable on CPU in ~a minute; the full soak is
# `python tools/serve_soak.py` (with --strict for the 3x acceptance).
bench-serve:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_serve(), indent=2))"

# Replay data plane A/B (journaled DQN uniform vs PER steps/s, in-chunk
# sum-tree sample latency, journal bytes/record with rotation on, and the
# seeded PER sample-efficiency race): the numbers behind BASELINE.md
# "Replay data plane" and the replay_* / journal_* perf-gate series.
# Runnable on CPU in a few minutes.
bench-replay:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_replay(), indent=2))"

# Perf-regression gate (also part of check): the newest BENCH_*.json row
# per (metric, backend, precision) series must sit within the tolerance
# band of the prior best — steps/s and MFU both gate (tools/perf_gate.py).
perf-gate:
	$(PYTHON) tools/perf_gate.py

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

# Serving-tier overload A/B (bounded+shedding engine vs the unbounded
# PR-8 shape at 8x the engine's own saturation rate): shed rate + p99,
# the numbers behind BASELINE.md "Serve under overload".
bench-serve-overload:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_serve_overload(), indent=2))"

# Tiered-session-paging capacity ladder (bench.py bench_session_paging):
# one engine's device arena vs 1x/8x/64x-slots session populations, warm
# host-RAM tier vs the no-warm cold-re-prefill control — the numbers
# behind BASELINE.md "Session tiers" and the session_capacity_qps /
# warm_unpark_ms perf-gate series.
bench-paging:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_session_paging(), indent=2))"

# Actor/learner disaggregation scaling (distrib/): experience produced
# (summed actor rollouts) and ingested by the live learner at N in
# {1,2,4} actor subprocesses vs the single-process train baseline — the
# numbers behind BASELINE.md "Actor/learner disaggregation" and the
# actor_rows_ingested_per_sec perf-gate series. CPU-framed (host-core
# contention); the TPU row rides the item-4 measurement campaign.
bench-actor:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_actor_scaling(), indent=2))"

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

# Fleet scale-out bench (bench.py bench_fleet): single-engine saturation
# vs N=2/4 engines behind the router, wire-framed, each engine pinned to
# its own core slice — the numbers behind BASELINE.md "Fleet serving"
# and the fleet_qps / fleet_p99_ms perf-gate series (acceptance: N=4 >=
# 2.5x single-engine saturation).
bench-fleet:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_fleet(), indent=2))"

# Router-ONLY relay throughput: threaded oracle vs the evloop wire
# path against loopback echo engines (ISSUE 16's >=10x acceptance).
bench-router:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_router_relay(), indent=2))"

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
# comparison against the full hand-sweep grid (BASELINE.md
# "Self-tuning").
autotune:
	$(PYTHON) tools/autotune.py --out tuned_profile.json

# Seconds-scale profile of the same sweep (tiny grid, short windows) —
# wired into `make check` as the end-to-end gate that the sweep ->
# profile -> load path stays green; writes to /tmp, never the repo.
autotune-quick:
	$(PYTHON) tools/autotune.py --quick --out /tmp/tuned_profile_quick.json --json

# Online-controller A/B (bench.py bench_autotune): a ramping open-loop
# arrival schedule where the static default config misses the target
# p99, static arm vs the ServeController arm holding it (or shedding
# within SLO) — the autotune_controller_p99_ms perf-gate row.
bench-autotune:
	$(PYTHON) -c "import json, bench; \
	print(json.dumps(bench.bench_autotune(), indent=2))"

# Static guard: no bare scalar device syncs in the orchestrator hot loop.
lint:
	$(PYTHON) tools/lint_hot_loop.py

clean:
	$(MAKE) -C native clean
