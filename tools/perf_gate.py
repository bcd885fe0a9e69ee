#!/usr/bin/env python
"""Perf-regression gate: fail CI when a bench row regresses.

The repo accumulates one ``BENCH_rNN.json`` snapshot per round (the driver
runs ``bench.py`` and records its one-line JSON result), but until now no
machinery noticed when a row regressed — five snapshots, zero gates. This
tool turns the trajectory into a gate (``make perf-gate``, wired into
``make check``):

1. **Parse** ``BASELINE.json`` plus every ``BENCH_*.json`` in the repo
   root (and, with ``--candidate``, a fresh ``bench.py`` output file).
   New-schema results carry ``schema_version`` / ``backend`` / ``git_rev``
   (the bench.py satellite of the roofline PR); old snapshots are read by
   a fallback parser that walks the driver's ``parsed`` object — and its
   raw ``tail`` line when parsing failed — for ``{metric, value, mfu}``
   rows, labeling legacy rows ``tpu`` (they predate the ``backend`` key)
   except under a ``cpu_fallback`` subtree or an explicit ``backend`` key.
2. **Group** rows into series per ``(metric, backend, precision)`` — a
   CPU row must never gate against TPU numbers, and a ``bf16_mixed`` row
   must never gate against fp32 history (different compute tier, different roofline; the
   precision PR). Rows carry ``precision`` from the new-schema envelope;
   legacy rows without one gate as ``fp32`` — which they were. Ordered by
   the driver's round number ``n`` (file order as the tiebreak).
3. **Gate** each series' NEWEST value against the best PRIOR value with a
   per-quantity relative tolerance band: ``value`` (steps/s) and ``mfu``
   each default to 25% — wide enough for the measured round-to-round host
   noise (r01→r03 qlearn moved -11% with no code regression), tight
   enough to catch a real floor change. Direction is metric-aware
   (``lower_is_better``): throughput rows (``serve_qps``, steps/s) fail
   when they FALL below the band, latency rows (``serve_p99_ms`` — any
   ``*_ms`` metric) fail when they RISE above it, both on the same 25%
   band. A series with fewer than two points records a note, never a
   failure — absent-history rows (the serve tier's first round) seed.

Exit 0 = no regression; exit 1 = at least one metric fell out of its
band (each named with its series, prior best, and observed value).

Usage:
    python tools/perf_gate.py                 # gate the checked-in rows
    python tools/perf_gate.py --json          # machine-readable report
    python tools/perf_gate.py --candidate out.json   # gate a fresh run
    python tools/perf_gate.py --tolerance 0.10       # tighten both bands
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

#: Relative drop tolerated before a series fails, per gated quantity.
DEFAULT_TOLERANCES = {"value": 0.25, "mfu": 0.25}


def lower_is_better(metric: str) -> bool:
    """Gate direction per metric: throughput-like metrics fail when the
    newest value falls BELOW the band; latency-like metrics (``*_ms`` —
    the serve tier's ``serve_p99_ms``/``serve_p50_ms``, the self-tuning
    PR's ``autotune_controller_p99_ms``), size-like metrics (``*_bytes``
    / ``*_bytes_per_record`` — the replay data plane's
    ``journal_bytes_per_record``), and cost-fraction metrics (``*_frac``
    / ``*_cost_s`` — the autotune sweep's cost vs the exhaustive grid)
    fail when it rises ABOVE it. Suffix-based so future latency/size/
    cost rows inherit the right direction without touching the gate."""
    return (metric.endswith("_ms") or metric.endswith("_latency")
            or metric.endswith("_bytes")
            or metric.endswith("_bytes_per_record")
            or metric.endswith("_frac") or metric.endswith("_cost_s"))


def _legacy_backend(path_keys: tuple[str, ...], row: dict) -> str:
    """Backend label for a pre-schema row: explicit key wins, a
    ``cpu_fallback`` subtree is CPU, anything else was the TPU era."""
    if row.get("backend"):
        return str(row["backend"])
    if any("cpu_fallback" in k for k in path_keys):
        return "cpu"
    return "tpu"


def extract_rows(obj, *, default_backend: str | None = None,
                 default_precision: str | None = None,
                 _path: tuple[str, ...] = ()) -> list[dict]:
    """Recursively pull ``{metric, value[, mfu]}`` rows out of one parsed
    bench result (works on both the new schema-versioned envelope and the
    legacy nested objects). ``precision`` rides along when the row or the
    envelope declares one; absent means fp32 (every pre-policy row)."""
    rows: list[dict] = []
    if not isinstance(obj, dict):
        return rows
    if "metric" in obj and "value" in obj:
        try:
            value = float(obj["value"])
        except (TypeError, ValueError):
            value = None
        if value is not None:
            row = {
                "metric": str(obj["metric"]),
                "value": value,
                "backend": (default_backend
                            or _legacy_backend(_path, obj)),
            }
            precision = obj.get("precision") or default_precision
            if precision:
                row["precision"] = str(precision)
            try:
                # Tolerant like the value parse above: one malformed
                # legacy field drops the quantity, never the gate run.
                if obj.get("mfu") is not None:
                    row["mfu"] = float(obj["mfu"])
            except (TypeError, ValueError):
                pass
            rows.append(row)
    for key, child in obj.items():
        if isinstance(child, dict):
            rows.extend(extract_rows(child, default_backend=default_backend,
                                     default_precision=default_precision,
                                     _path=_path + (key,)))
    return rows


def parse_bench_file(path: str) -> dict | None:
    """One BENCH_*.json (driver snapshot) or raw bench.py output file →
    ``{"n": round, "rows": [...]}``; None when nothing parseable."""
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except Exception:
        return None
    n = doc.get("n")
    parsed = doc.get("parsed")
    if parsed is None and "metric" in doc:
        parsed = doc          # a raw bench.py result file, not a snapshot
    if parsed is None and doc.get("tail"):
        # Fallback of the fallback: the driver failed to parse but the
        # tail still holds bench.py's one JSON line (the FIRST parseable
        # one — a later {-prefixed log line must not overwrite the rows).
        for line in str(doc["tail"]).splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    parsed = json.loads(line)
                except json.JSONDecodeError:
                    continue
                break
    if not isinstance(parsed, dict):
        return None
    # Pure error snapshots (r04) have no top-level rows; extract_rows
    # still walks any cpu_fallback subtree for the rows it carries.
    default_backend = default_precision = None
    if parsed.get("schema_version"):
        default_backend = parsed.get("backend")
        default_precision = parsed.get("precision")
    rows = extract_rows(parsed, default_backend=default_backend,
                        default_precision=default_precision)
    return {"n": n, "path": os.path.basename(path), "rows": rows}


def parse_baseline(path: str) -> dict | None:
    """BASELINE.json carries the reference identity and any published
    numbers; today ``published`` is empty, so it contributes context (and
    future rows), never a silent failure."""
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except Exception:
        return None
    rows = extract_rows(doc.get("published") or {})
    return {"n": 0, "path": os.path.basename(path), "rows": rows}


def collect_series(snapshots: list[dict]) -> dict[tuple, list[dict]]:
    """(metric, backend, precision, quantity) → chronological
    [{round, value}, ...]. Rows without a precision label gate as fp32
    (every pre-policy snapshot ran fp32 — or its whole-model-cast
    ancestor, whose rows the fp32 series absorbs as history)."""
    series: dict[tuple, list[dict]] = {}
    ordered = sorted(
        (s for s in snapshots if s is not None),
        key=lambda s: (s["n"] if isinstance(s.get("n"), (int, float))
                       else float("inf"), s["path"]))
    for snap in ordered:
        for row in snap["rows"]:
            for quantity in ("value", "mfu"):
                if quantity not in row:
                    continue
                key = (row["metric"], row["backend"],
                       row.get("precision", "fp32"), quantity)
                series.setdefault(key, []).append(
                    {"round": snap["n"], "path": snap["path"],
                     "value": row[quantity]})
    return series


def gate(series: dict[tuple, list[dict]],
         tolerances: dict[str, float]) -> dict:
    failures: list[str] = []
    notes: list[str] = []
    checked = 0
    for (metric, backend, precision, quantity), points in sorted(
            series.items()):
        name = f"{metric}[{backend},{precision}].{quantity}"
        if len(points) < 2:
            notes.append(f"{name}: only {len(points)} point(s); nothing to "
                         "gate yet")
            continue
        checked += 1
        newest = points[-1]
        tol = tolerances.get(quantity, 0.25)
        if lower_is_better(metric):
            # Latency series: prior best is the MINIMUM, regression is a
            # rise past the (1 + tol) ceiling.
            prior_best = min(points[:-1], key=lambda p: p["value"])
            ceiling = prior_best["value"] * (1.0 + tol)
            if newest["value"] > ceiling:
                failures.append(
                    f"{name}: {newest['value']:.6g} ({newest['path']}) is "
                    f"{100 * (newest['value'] / max(prior_best['value'], 1e-12) - 1):.1f}% "
                    f"above prior best {prior_best['value']:.6g} "
                    f"({prior_best['path']}); tolerance {tol:.0%} "
                    "(lower is better)")
            else:
                notes.append(
                    f"{name}: {newest['value']:.6g} vs prior best "
                    f"{prior_best['value']:.6g} — within {tol:.0%} "
                    "(lower is better)")
            continue
        prior_best = max(points[:-1], key=lambda p: p["value"])
        floor = prior_best["value"] * (1.0 - tol)
        if newest["value"] < floor:
            failures.append(
                f"{name}: {newest['value']:.6g} ({newest['path']}) is "
                f"{100 * (1 - newest['value'] / prior_best['value']):.1f}% "
                f"below prior best {prior_best['value']:.6g} "
                f"({prior_best['path']}); tolerance {tol:.0%}")
        else:
            notes.append(
                f"{name}: {newest['value']:.6g} vs prior best "
                f"{prior_best['value']:.6g} — within {tol:.0%}")
    return {"checked": checked, "failures": failures, "notes": notes,
            "ok": not failures}


def run_gate(root: str | os.PathLike = REPO, *,
             candidate: str | None = None,
             tolerances: dict[str, float] | None = None,
             as_json: bool = False) -> int:
    tolerances = tolerances or dict(DEFAULT_TOLERANCES)
    root = pathlib.Path(root)
    snapshots: list[dict] = []
    baseline = root / "BASELINE.json"
    if baseline.is_file():
        snapshots.append(parse_baseline(str(baseline)))
    bench_files = sorted(
        glob.glob(str(root / "BENCH_*.json")),
        key=lambda p: (_round_of(p), p))
    snapshots.extend(parse_bench_file(p) for p in bench_files)
    if candidate:
        cand = parse_bench_file(candidate)
        if cand is None:
            print(f"perf gate: candidate {candidate} is not parseable")
            return 1
        if not isinstance(cand.get("n"), (int, float)):
            cand["n"] = float("inf")    # the candidate is the newest point
        snapshots.append(cand)
    series = collect_series(snapshots)
    report = gate(series, tolerances)
    report["snapshots"] = [
        {"path": s["path"], "rows": len(s["rows"])}
        for s in snapshots if s is not None]
    report["tolerances"] = tolerances
    if as_json:
        print(json.dumps(report), flush=True)
    else:
        for note in report["notes"]:
            print(f"  {note}")
        for fail in report["failures"]:
            print(f"  FAIL: {fail}")
        print(f"perf gate {'OK' if report['ok'] else 'FAILED'} "
              f"({report['checked']} gated series, "
              f"{len(report['failures'])} regression(s))")
    return 0 if report["ok"] else 1


def _round_of(path: str) -> float:
    m = re.search(r"r(\d+)", os.path.basename(path))
    return float(m.group(1)) if m else float("inf")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--dir", default=str(REPO),
                        help="repo root holding BASELINE.json + BENCH_*.json")
    parser.add_argument("--candidate", default=None,
                        help="fresh bench.py output file to gate as the "
                             "newest point")
    parser.add_argument("--tolerance", type=float, default=None,
                        help="override BOTH tolerance bands (relative, "
                             "e.g. 0.10)")
    parser.add_argument("--json", action="store_true",
                        help="print one machine-readable report line")
    args = parser.parse_args()
    tol = dict(DEFAULT_TOLERANCES)
    if args.tolerance is not None:
        tol = {k: args.tolerance for k in tol}
    return run_gate(args.dir, candidate=args.candidate, tolerances=tol,
                    as_json=args.json)


if __name__ == "__main__":
    sys.exit(main())
