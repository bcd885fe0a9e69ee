#!/usr/bin/env python
"""Compile-time shard-audit gate: prove the partitioned step program never
involuntarily reshards, for a whole matrix of mesh configs, without a TPU.

What the gate certifies (the anti-resharding tentpole, round 8):

1. **Zero involuntary rematerialization.** The XLA SPMD partitioner logs
   ``Involuntary full rematerialization`` (C++ LOG(WARNING), stderr) when it
   must bridge two program regions by replicating a tensor and re-slicing it
   under a different mesh layout — a full all-gather + repartition of e.g.
   the episode carry's ``hist`` buffer on EVERY chunk. The audit compiles
   each config in a subprocess on the CPU backend (``JAX_PLATFORMS=cpu``,
   ``--xla_force_host_platform_device_count=8`` — the multichip dryrun
   recipe; the warning is C++ stderr, so it needs a process of its own)
   and scans the child's stderr; any hit fails the audit.
2. **No collective-count regression.** Collectives (all-reduce, all-gather,
   collective-permute, all-to-all, reduce-scatter) counted from the
   optimized HLO must not exceed the checked-in manifest
   (``tools/shard_audit_manifest.json``). Counts are partitioner-version
   dependent, so the manifest records the jax version it was measured
   under; under a different jax the count gate downgrades to a warning
   (the remat gate always applies). ``--update`` re-measures and rewrites
   the manifest.
3. **Memory report.** ``compiled.memory_analysis()`` (arguments / temps /
   output bytes) per config, recorded in the report.
4. **Roofline rows (the obs/roofline PR).** Per-config FLOPs and HBM
   bytes of the compiled megachunk program — ``cost_analysis()`` FLOPs /
   bytes-accessed (raw HLO counts: loop bodies counted once, so the
   numbers are compile-deterministic identities, not per-dispatch work —
   obs/roofline.py owns the trip-count-corrected runtime view) plus the
   ``memory_analysis()`` peak footprint — gated against manifest ceilings
   exactly like the collective counts: an unexplained FLOP or HBM growth
   fails the audit under the manifest's jax version, warns under any
   other, and ``--update`` re-measures. This is the ROADMAP item-4 gate:
   MFU regressions caused by program-cost changes trip here at compile
   time, before a single benchmark runs.

The compiled program is built by ``parallel.sharding.jit_parallel_step`` —
the SAME constructor the orchestrator dispatches through — so the audit
certifies the production program, not a lookalike.

Usage:
    python tools/shard_audit.py              # run the gate (exit != 0 on fail)
    python tools/shard_audit.py --update     # refresh the manifest
    python tools/shard_audit.py --json       # machine-readable report line
"""

from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import re
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
MANIFEST = pathlib.Path(__file__).resolve().parent / "shard_audit_manifest.json"
REMAT = "Involuntary full rematerialization"
N_DEVICES = 8
COLLECTIVE_OPS = ("all-reduce", "all-gather", "collective-permute",
                  "all-to-all", "reduce-scatter")
#: Per-child compile budget: the episode-sp config is the slowest (~2 min on
#: a throttled 2-core host); a hang — the failure mode the subprocess design
#: guards — never finishes, so generous is fine.
CHILD_TIMEOUT_S = 900

#: The config matrix: every mesh-axis kind the parallel layer supports
#: (dp / dp+tp / dp+sp / dp+pp), the megachunk scan seam (K>1), and the
#: journaled-transitions metrics path whose out-sharding regression the
#: round-8 satellite fixed. Keys map onto FrameworkConfig fields in
#: ``_child_build``.
CONFIGS: list[dict] = [
    {"name": "dp8_qlearn", "mesh": {"dp": 8}, "algo": "qlearn"},
    {"name": "dp8_qlearn_k8", "mesh": {"dp": 8}, "algo": "qlearn", "mega": 8},
    {"name": "dp2_tp2_ppo_mlp", "mesh": {"dp": 2, "tp": 2}, "algo": "ppo",
     "tp_rules": True},
    {"name": "dp4_dqn_k4_journal", "mesh": {"dp": 4}, "algo": "dqn",
     "mega": 4, "journal": True},
    {"name": "dp2_sp2_ppo_episode", "mesh": {"dp": 2, "sp": 2}, "algo": "ppo",
     "model": {"kind": "transformer", "seq_mode": "episode",
               "attention": "ring", "num_layers": 2, "num_heads": 2,
               "head_dim": 16},
     "window": 16, "unroll": 34, "chunk": 34, "workers": 4, "series": 80},
    # The three configs that actually reproduced the involuntary-remat
    # warnings before the round-8 fix (PPO's permuted minibatch gathers
    # over dp-sharded rollout products; the multichip dry runs'
    # [4,1,2]→[1,2,4] on ts.carry['hist'] is dp4_sp2's signature) — kept in
    # the matrix verbatim so the gate would re-catch a regression at the
    # shapes that exposed it, not just at neighbors.
    {"name": "dp4_sp2_ppo_episode", "mesh": {"dp": 4, "sp": 2}, "algo": "ppo",
     "model": {"kind": "transformer", "seq_mode": "episode",
               "attention": "ring", "num_layers": 2, "num_heads": 2,
               "head_dim": 8},
     "window": 14, "unroll": 4, "chunk": 4, "workers": 8, "series": 40},
    {"name": "dp2_sp4_ppo_ring_window", "mesh": {"dp": 2, "sp": 4},
     "algo": "ppo",
     "model": {"kind": "transformer", "attention": "ring", "num_layers": 1,
               "num_heads": 2, "head_dim": 8},
     "window": 14, "unroll": 4, "chunk": 4, "workers": 4, "series": 40},
    {"name": "dp2_ep4_episode_moe_a2a", "mesh": {"dp": 2, "ep": 4},
     "algo": "ppo",
     "model": {"kind": "transformer", "seq_mode": "episode",
               "moe_experts": 4, "moe_top_k": 2, "moe_dispatch": "a2a",
               "num_layers": 2, "num_heads": 2, "head_dim": 8},
     "window": 14, "unroll": 4, "chunk": 4, "workers": 4, "series": 40},
    {"name": "dp2_pp2_transformer", "mesh": {"dp": 2, "pp": 2}, "algo": "ppo",
     "model": {"kind": "transformer", "pipeline_blocks": True,
               "num_layers": 2, "num_heads": 2, "head_dim": 16},
     "window": 14, "unroll": 4, "chunk": 4, "workers": 4, "series": 40},
    # Per-precision rows (the mixed-precision PR): the SAME programs under
    # precision.mode=bf16_mixed get their own byte/HBM ceilings — a bf16
    # program gating against fp32 ceilings would always pass (and the
    # reverse always fail), hiding regressions in exactly the tier the
    # policy exists to shrink. The episode row doubles as the remat gate
    # for the bf16 carry: the K/V cache changes dtype, and the seam pins
    # must keep the compile involuntary-remat-clean regardless.
    {"name": "dp8_qlearn_k8_bf16", "mesh": {"dp": 8}, "algo": "qlearn",
     "mega": 8, "precision": "bf16_mixed"},
    {"name": "dp4_sp2_ppo_episode_bf16", "mesh": {"dp": 4, "sp": 2},
     "algo": "ppo", "precision": "bf16_mixed",
     "model": {"kind": "transformer", "seq_mode": "episode",
               "attention": "ring", "num_layers": 2, "num_heads": 2,
               "head_dim": 8},
     "window": 14, "unroll": 4, "chunk": 4, "workers": 8, "series": 40},
]


# ---------------------------------------------------------------------------
# HLO text analysis (shared with the tier-1 sharding-consistency tests —
# parent-side only, no jax import needed)
# ---------------------------------------------------------------------------

#: ``<shapes> <op>(`` — group 1 is the result-shape text, group 2 the op.
#: ``-done`` variants are intentionally unmatched (same transfer as their
#: ``-start``; counting both would double every async collective).
_COLLECTIVE_RE = re.compile(
    r"=\s*([^=\n]*?)\s*\b(" + "|".join(COLLECTIVE_OPS) + r")(?:-start)?\(")
_SHAPE_RE = re.compile(r"\b(pred|[a-z]+[0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8, "c64": 8, "c128": 16}


def collective_counts(hlo_text: str) -> dict[str, int]:
    """Count collective ops in optimized-HLO text, async pairs counted once."""
    counts = {op: 0 for op in COLLECTIVE_OPS}
    for _, op in _COLLECTIVE_RE.findall(hlo_text):
        counts[op] += 1
    return counts


def collective_bytes(hlo_text: str) -> int:
    """Total result bytes of all collective ops — a per-dispatch collective
    traffic proxy (result size; a same-size all-reduce
    moves ~2x this on a ring, but the METRIC only needs to move when the
    program's collectives do)."""
    total = 0
    for shapes, _ in _COLLECTIVE_RE.findall(hlo_text):
        for dtype, dims in _SHAPE_RE.findall(shapes):
            n = math.prod(int(d) for d in dims.split(",") if d) if dims else 1
            total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def scan_remat_warnings(stderr_text: str) -> list[str]:
    """The involuntary-reshard lines from an XLA SPMD compile log."""
    return [ln.strip() for ln in stderr_text.splitlines() if REMAT in ln]


# ---------------------------------------------------------------------------
# child: compile ONE config on the forced-8-device host platform
# ---------------------------------------------------------------------------

def _child_build(spec: dict):
    """Build (agent, mesh, placed-ts, jitted fn) for one matrix entry via the
    production constructor."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sharetrade_tpu.agents import build_agent
    from sharetrade_tpu.config import FrameworkConfig
    from sharetrade_tpu.env import trading
    from sharetrade_tpu.parallel import jit_parallel_step, mlp_tp_rules
    from jax.sharding import Mesh

    cfg = FrameworkConfig()
    cfg.learner.algo = spec["algo"]
    cfg.env.window = spec.get("window", 8)
    cfg.model.hidden_dim = 16
    cfg.parallel.num_workers = spec.get("workers", 8)
    cfg.runtime.chunk_steps = spec.get("chunk", 4)
    cfg.learner.unroll_len = spec.get("unroll", 4)
    if spec["algo"] == "dqn":
        cfg.learner.replay_capacity = 64
        cfg.learner.replay_batch = 8
        cfg.learner.journal_replay = bool(spec.get("journal"))
    for key, val in spec.get("model", {}).items():
        setattr(cfg.model, key, val)
    cfg.precision.mode = spec.get("precision", "fp32")
    cfg.parallel.mesh_shape = dict(spec["mesh"])

    sizes = list(spec["mesh"].values())
    total = math.prod(sizes)
    devices = np.asarray(jax.devices("cpu")[:total]).reshape(sizes)
    mesh = Mesh(devices, tuple(spec["mesh"]))

    env = trading.env_from_prices(
        jnp.linspace(10.0, 20.0, spec.get("series", 64)),
        window=cfg.env.window)
    agent = build_agent(cfg, env, mesh=mesh)
    ts = agent.init(jax.random.PRNGKey(0))
    rules = mlp_tp_rules() if spec.get("tp_rules") else None
    sh, fn = jit_parallel_step(
        agent, mesh, ts, param_rules=rules,
        megachunk_factor=spec.get("mega", 1),
        constrain=spec.get("constrain", True))
    ts_placed = jax.device_put(ts, sh)
    return ts_placed, fn


def run_child(spec: dict) -> None:
    """Compile one config; print ONE JSON result line on stdout. The SPMD
    warnings go to OUR stderr, which the parent captures and scans."""
    result: dict = {"name": spec["name"], "ok": True}
    try:
        ts, fn = _child_build(spec)
        compiled = fn.lower(ts).compile()
        hlo = compiled.as_text()
        result["collectives"] = collective_counts(hlo)
        result["collective_bytes"] = collective_bytes(hlo)
        try:
            mem = compiled.memory_analysis()
            result["memory"] = {
                "arguments": int(mem.argument_size_in_bytes),
                "temps": int(mem.temp_size_in_bytes),
                "output": int(mem.output_size_in_bytes),
            }
        except Exception:            # backend without the analysis: report-only
            result["memory"] = None
        # Roofline row: HLO cost analysis (FLOPs / bytes accessed, loop
        # bodies counted once — a deterministic program identity under a
        # fixed jax version) plus the memory footprint as the HBM-bytes-
        # per-megachunk number. Quirk handling (list-vs-dict returns,
        # -1 = unavailable) lives in ONE place: obs/roofline.py
        # compiled_costs, the same reader the live telemetry uses. None
        # where a backend lacks the counter; the parent's ceiling gate
        # skips None on either side.
        from sharetrade_tpu.obs.roofline import compiled_costs
        costs = compiled_costs(compiled)
        cost: dict | None = {
            "flops": costs["flops"],
            "bytes_accessed": costs["bytes_accessed"],
        }
        if result["memory"] is not None:
            cost["hbm_peak_bytes"] = sum(result["memory"].values())
        result["cost"] = cost
    except AttributeError as exc:
        # Missing jax API (the parallel layer targets the installed jax):
        # report SKIPPED rather than failing the gate.
        result.update(ok=False, skipped=True, error=repr(exc))
    except Exception as exc:
        result.update(ok=False, skipped=False, error=repr(exc))
    print(json.dumps(result), flush=True)


# ---------------------------------------------------------------------------
# parent: scrubbed subprocess per config, manifest gate
# ---------------------------------------------------------------------------

def _scrubbed_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={N_DEVICES}"
        ).strip()
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    return env


def audit_config(spec: dict) -> dict:
    """Run one config's child; merge its JSON result with the stderr scan."""
    try:
        proc = subprocess.run(
            [sys.executable, str(pathlib.Path(__file__).resolve()),
             "--child", json.dumps(spec)],
            env=_scrubbed_env(), cwd=str(REPO), capture_output=True,
            text=True, timeout=CHILD_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        # Same named-row shape as every other child failure: a hung child
        # (loaded host, dead toolchain) must fail ITS config, not crash the
        # whole audit with a raw traceback and no report.
        return {"name": spec["name"], "ok": False, "skipped": False,
                "error": f"child exceeded {CHILD_TIMEOUT_S}s compile budget",
                "involuntary_remat": 0}
    remat = scan_remat_warnings(proc.stderr)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    if not lines or proc.returncode != 0:
        return {"name": spec["name"], "ok": False, "skipped": False,
                "error": f"child rc={proc.returncode}: "
                         + " ".join(proc.stderr.split()[-60:]),
                "involuntary_remat": len(remat), "remat_lines": remat[:4]}
    result = json.loads(lines[-1])
    result["involuntary_remat"] = len(remat)
    if remat:
        result["remat_lines"] = remat[:4]
    return result


def run_audit(update: bool = False, as_json: bool = False) -> int:
    import concurrent.futures

    manifest = (json.loads(MANIFEST.read_text()) if MANIFEST.exists()
                else {"jax_version": None, "configs": {}})
    # Children are independent subprocesses; overlap them to hide the
    # per-child jax import + compile latency (bounded: these hosts are small).
    workers = min(2, max(1, (os.cpu_count() or 1)))
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        results = list(pool.map(audit_config, CONFIGS))

    child_jax = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.__version__)"],
        env=_scrubbed_env(), capture_output=True, text=True).stdout.strip()
    same_jax = manifest.get("jax_version") == child_jax

    failures: list[str] = []
    warnings: list[str] = []
    for res in results:
        name = res["name"]
        if res.get("skipped"):
            warnings.append(f"{name}: SKIPPED ({res.get('error')})")
            continue
        if not res.get("ok"):
            failures.append(f"{name}: compile failed: {res.get('error')}")
            continue
        if res["involuntary_remat"]:
            failures.append(
                f"{name}: {res['involuntary_remat']} involuntary "
                f"rematerialization warning(s): "
                + "; ".join(res.get("remat_lines", [])[:2]))
        want = manifest["configs"].get(name)
        if want is None:
            msg = f"{name}: not in manifest (run --update)"
            (warnings if update else failures).append(msg)
            continue
        for op, count in res["collectives"].items():
            ceiling = want["collectives"].get(op, 0)
            if count > ceiling:
                msg = (f"{name}: {op} count {count} exceeds manifest "
                       f"ceiling {ceiling}")
                if same_jax and not update:
                    failures.append(msg)
                else:
                    warnings.append(
                        msg + ("" if same_jax else
                               f" (measured under jax "
                               f"{manifest.get('jax_version')}, running "
                               f"{child_jax}: count gate downgraded)"))
        # Roofline ceilings (FLOPs / HLO bytes accessed / HBM footprint):
        # the same contract as the collective counts — exceeding the
        # manifest under its own jax version fails, under a different
        # version warns, and --update re-measures. A key missing on
        # either side (older manifest, backend without the counter)
        # gates nothing.
        want_cost = want.get("cost") or {}
        got_cost = res.get("cost") or {}
        for key, unit in (("flops", "FLOPs"),
                          ("bytes_accessed", "HLO bytes accessed"),
                          ("hbm_peak_bytes", "HBM footprint bytes")):
            ceiling = want_cost.get(key)
            got = got_cost.get(key)
            if ceiling is None or got is None:
                continue
            if got > ceiling * (1 + 1e-9):
                msg = (f"{name}: {unit} {got:.6g} exceeds manifest "
                       f"ceiling {ceiling:.6g}")
                if same_jax and not update:
                    failures.append(msg)
                else:
                    warnings.append(
                        msg + ("" if same_jax else
                               f" (measured under jax "
                               f"{manifest.get('jax_version')}, running "
                               f"{child_jax}: roofline gate downgraded)"))

    if update:
        manifest = {
            "jax_version": child_jax,
            "note": ("Collective-count and roofline (FLOPs / HLO bytes "
                     "accessed / HBM footprint) ceilings per audit config, "
                     "measured on the forced-8-device host platform. "
                     "Roofline numbers are raw HLO cost_analysis counts "
                     "(loop bodies counted once) — compile-deterministic "
                     "identities of the program, gated as ceilings; "
                     "obs/roofline.py owns the trip-count-corrected "
                     "per-dispatch view. Regenerate with "
                     "`python tools/shard_audit.py --update` after an "
                     "intentional program-cost change or a jax upgrade."),
            "configs": {
                res["name"]: {
                    "collectives": res["collectives"],
                    "collective_bytes": res["collective_bytes"],
                    "memory": res.get("memory"),
                    "cost": res.get("cost"),
                }
                for res in results if res.get("ok")
            },
        }
        MANIFEST.write_text(json.dumps(manifest, indent=2) + "\n")

    report = {
        "jax_version": child_jax,
        "manifest_jax_version": manifest.get("jax_version"),
        "configs": results,
        "failures": failures,
        "warnings": warnings,
        "ok": not failures,
    }
    if as_json:
        print(json.dumps(report), flush=True)
    else:
        for res in results:
            if res.get("ok"):
                mem = res.get("memory") or {}
                cost = res.get("cost") or {}
                print(f"  {res['name']}: remat={res['involuntary_remat']} "
                      f"collectives={res['collectives']} "
                      f"bytes={res['collective_bytes']} "
                      f"temps={mem.get('temps')} "
                      f"flops={cost.get('flops')} "
                      f"hbm={cost.get('hbm_peak_bytes')}")
            else:
                print(f"  {res['name']}: "
                      + ("SKIPPED" if res.get("skipped") else "FAILED")
                      + f" ({res.get('error')})")
        for w in warnings:
            print(f"  warning: {w}")
        for f in failures:
            print(f"  FAIL: {f}")
        print(("shard audit OK" if not failures else "shard audit FAILED")
              + (" (manifest updated)" if update else ""))
    return 0 if not failures else 1


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--child", help="internal: JSON config spec")
    parser.add_argument("--update", action="store_true",
                        help="rewrite the manifest from this run")
    parser.add_argument("--json", action="store_true",
                        help="print one machine-readable report line")
    args = parser.parse_args()
    if args.child:
        run_child(json.loads(args.child))
        return 0
    return run_audit(update=args.update, as_json=args.json)


if __name__ == "__main__":
    sys.exit(main())
