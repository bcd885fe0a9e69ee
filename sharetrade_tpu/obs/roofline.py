"""Roofline telemetry: compiled-cost capture + live MFU/HBM gauges.

The ROADMAP's MFU push starts with measurement: MFU existed only as an
after-the-fact analytic number (utils/flops.py), invisible during
training. (What the benchmark reports is ``train_step.mfu`` of
``chipbench/``, PERF.md §3; this module is ROADMAP D2's named debt.) It
makes device utilization a
first-class run-time health signal (the Podracer stance, arxiv 2104.06272):

- **Compile time** — :meth:`RooflineCapture.capture` records XLA
  ``cost_analysis()`` (FLOPs, bytes accessed) and ``memory_analysis()``
  (argument/temp/output bytes) for every jitted (mega)chunk program, via
  the ``cost_hook`` seam in ``parallel/sharding.py jit_parallel_step`` (the
  mesh path) and the orchestrator's CPU-fallback build. Capture costs ONE
  extra AOT lowering+compile per program at build time — never a per-step
  cost — and a capture failure degrades observability, never the run.
  The XLA FLOP count is cross-checked against the analytic
  ``utils/flops.py`` model: a >25% discrepancy is a counting bug in one of
  the two and warns through the flight recorder.
- **Run time** — :meth:`RooflineCapture.on_boundary`, called from the
  pipeline CONSUMER thread (never the dispatcher), divides the captured
  static costs by the measured per-chunk wall time (StepTimer's sampled
  ``chunk_seconds``) and publishes ``mfu``, ``achieved_tflops``,
  ``hbm_gbps``, ``arithmetic_intensity`` and ``roofline_compute_bound``
  gauges through the existing MetricsRegistry → Prometheus path.
- **Artifact** — a schema-versioned ``roofline.json`` in the run dir (one
  entry per captured program: static costs, arithmetic intensity, the
  compute-bound vs memory-bound classification against the chip's ridge
  point), summarized by ``cli obs`` and regression-gated by
  ``tools/shard_audit.py`` (manifest FLOPs/HBM rows).

Everything is gated by ``ObsConfig.roofline`` (off by default): disabled
means no capture compile, no gauges, no file.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
from dataclasses import dataclass
from typing import Any, Callable

from sharetrade_tpu.utils.logging import get_logger

log = get_logger("obs.roofline")

SCHEMA_VERSION = 1
ARTIFACT = "roofline.json"

#: Analytic-vs-XLA FLOP band: outside ±25% one of the two counts is wrong
#: (the analytic model drifted from the model code, or the workload's
#: non-matmul FLOPs stopped being negligible) — worth a flight-recorder
#: warning either way.
DISCREPANCY_BAND = 0.25


@dataclass
class ProgramCost:
    """Static compiled-cost record for ONE (mega)chunk program.

    ``flops``/``bytes_accessed`` are PER DISPATCH, trip-count corrected:
    XLA's ``HloCostAnalysis`` counts a while/scan body ONCE (the trip
    count is not statically known to it), so a chunk program — a
    ``lax.scan`` over ``chunk_steps`` env steps, possibly nested in the
    K-megachunk scan — reports ~1/(chunk_steps*K) of the dispatch's real
    arithmetic. :class:`RooflineCapture` probes the attached backend once
    (two tiny scans of different lengths — equal counts mean blind) and
    multiplies by the known loop iterations when, and only when, the
    probe shows blindness; the uncorrected numbers stay in
    ``flops_hlo_once``/``bytes_hlo_once`` so the artifact is auditable.

    The uniform correction is exact for the value-based chunk programs (a
    scan of ``chunk_steps`` identical env-step bodies) but OVERCOUNTS
    programs whose dominant FLOPs live outside that scan — the episode-
    mode PPO chunk runs its banded trunk as ONE pass and its replay as
    epoch×minibatch passes, none of them ``chunk_steps``-deep (measured:
    ~150x over on the flagship). The analytic cross-check catches exactly
    this: when the corrected XLA count leaves the ±25% band and the
    analytic model is available, the LIVE GAUGES switch to the analytic
    count (``gauge_flops_source="analytic"`` — the PaLM-convention
    model-FLOPs MFU), with bytes scaled by the same factor (intensity is
    scale-invariant under the uniform correction, so the classification
    holds either way). Agreement keeps the XLA count
    (``gauge_flops_source="xla"``). Both numbers, the ratio, and the
    chosen source are in the artifact — nothing is silently blended."""

    label: str
    megachunk_factor: int
    devices: int                  # mesh size the program was partitioned for
    flops: float | None           # per DEVICE per dispatch (SPMD programs
                                  # report the per-device partition; the
                                  # chip-relative gauges want exactly that)
    bytes_accessed: float | None
    flops_hlo_once: float | None  # raw cost_analysis (loop body once)
    bytes_hlo_once: float | None
    loop_iterations: int          # chunk_steps x megachunk_factor
    trip_count_corrected: bool
    argument_bytes: int | None
    temp_bytes: int | None
    output_bytes: int | None
    peak_bytes: int | None        # args + temps + output: the HBM footprint
    arithmetic_intensity: float | None   # FLOPs per byte accessed
    classification: str | None    # "compute-bound" | "memory-bound"
    analytic_flops: float | None  # utils/flops.py model, same dispatch span
    xla_vs_analytic: float | None
    discrepancy: bool = False
    gauge_flops: float | None = None       # what the live gauges divide
    gauge_bytes: float | None = None
    gauge_flops_source: str | None = None  # "xla" | "analytic"

    def flops_per_chunk(self) -> float | None:
        if self.gauge_flops is None:
            return None
        return self.gauge_flops / max(1, self.megachunk_factor)

    def bytes_per_chunk(self) -> float | None:
        if self.gauge_bytes is None:
            return None
        return self.gauge_bytes / max(1, self.megachunk_factor)


def compiled_costs(compiled: Any) -> dict[str, float | int | None]:
    """FLOPs / bytes-accessed / memory split of one ``jax.stages.Compiled``.

    Tolerates every backend quirk seen so far: ``cost_analysis()`` returns
    a dict on some jax versions and a one-per-device list on others; either
    analysis may be missing or raise; absent keys report None (the
    consumers treat None as "unavailable", never zero)."""
    out: dict[str, float | int | None] = {
        "flops": None, "bytes_accessed": None, "argument_bytes": None,
        "temp_bytes": None, "output_bytes": None,
    }
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", -1.0))
        ba = float(ca.get("bytes accessed", -1.0))
        # XLA reports -1 where a backend doesn't implement the counter.
        out["flops"] = flops if flops >= 0 else None
        out["bytes_accessed"] = ba if ba >= 0 else None
    except Exception:
        log.debug("cost_analysis unavailable", exc_info=True)
    try:
        mem = compiled.memory_analysis()
        out["argument_bytes"] = int(mem.argument_size_in_bytes)
        out["temp_bytes"] = int(mem.temp_size_in_bytes)
        out["output_bytes"] = int(mem.output_size_in_bytes)
    except Exception:
        log.debug("memory_analysis unavailable", exc_info=True)
    return out


def _probe_trip_count_blind() -> bool:
    """Does this backend's cost analysis count loop bodies once?

    Compiles two tiny scans differing only in length; equal FLOP counts
    mean the analysis is trip-count blind (XLA's documented
    ``HandleWhile`` behavior) and per-dispatch costs need the known-
    iteration correction. Probed empirically rather than assumed so a
    backend that starts multiplying trip counts is never double-counted.
    Defaults to True (the documented behavior) when the probe can't run.
    """
    try:
        import jax
        import jax.numpy as jnp

        def make(n):
            def f(x):
                def body(c, _):
                    return c @ c, None
                c, _ = jax.lax.scan(body, x, None, length=n)
                return c
            return jax.jit(f)

        x = jnp.ones((8, 8))
        counts = []
        for n in (2, 8):
            costs = compiled_costs(make(n).lower(x).compile())
            if costs["flops"] is None:
                return True
            counts.append(costs["flops"])
        return counts[0] == counts[1]
    except Exception:
        return True


class RooflineCapture:
    """Per-run roofline state: captured program costs + live gauge math.

    Thread contract: :meth:`capture` runs at build time (host, before
    training); :meth:`on_boundary` runs on the pipeline consumer thread;
    the artifact write is lock-guarded so a late capture (megachunk
    program built after the chunk program) can't tear the JSON.
    """

    def __init__(self, registry: Any, run_dir: str | None, *,
                 peak_flops: float | None = None,
                 peak_hbm_bw: float | None = None,
                 flight_record: Callable[..., None] | None = None):
        if peak_flops is None or peak_hbm_bw is None:
            from sharetrade_tpu.utils.flops import (UnknownDeviceKind,
                                                    chip_peak_flops,
                                                    chip_peak_hbm_bw)
            try:
                peak_flops = peak_flops or chip_peak_flops()
                peak_hbm_bw = peak_hbm_bw or chip_peak_hbm_bw()
            except UnknownDeviceKind as exc:
                # No published peak for this device (the CPU included):
                # program costs and achieved rates are still captured, but
                # nothing relative to a peak — no mfu, no ridge, no bound
                # classification — is recorded. "Not measured", never a
                # figure relative to some other chip.
                log.info("roofline: %s", exc)
                peak_flops = peak_hbm_bw = None
        self.registry = registry
        self.run_dir = run_dir
        self.peak_flops = None if peak_flops is None else float(peak_flops)
        self.peak_hbm_bw = None if peak_hbm_bw is None else float(peak_hbm_bw)
        #: FLOPs/byte above which a program is compute-bound on this chip
        #: (None where the chip's peaks are not known).
        self.ridge = (self.peak_flops / self.peak_hbm_bw
                      if self.peak_flops and self.peak_hbm_bw else None)
        #: Analytic model FLOPs for ONE chunk's dispatch span
        #: (train_flops_per_agent_step x workers x chunk_steps); the
        #: orchestrator sets it once the env's obs_dim is known. None
        #: disables the cross-check, never the capture.
        self.analytic_flops_per_chunk: float | None = None
        #: Env steps one chunk scans over (runtime.chunk_steps) — the
        #: inner loop trip count of every captured program; the
        #: orchestrator sets it before the programs build.
        self.steps_per_chunk: int = 1
        #: Precision mode the captured programs compiled under
        #: (config.PrecisionConfig.mode) — recorded in the artifact so a
        #: bytes/AI movement is attributable to the compute tier, and so
        #: perf tooling never compares rooflines across precisions.
        self.precision_mode: str | None = None
        self.programs: dict[str, ProgramCost] = {}
        self._by_factor: dict[int, ProgramCost] = {}
        self._flight_record = flight_record
        self._lock = threading.Lock()
        self._trip_blind: bool | None = None   # probed lazily, once

    # -- compile-time capture -------------------------------------------

    def capture(self, fn: Any, args: tuple, *, megachunk_factor: int = 1,
                devices: int = 1,
                label: str | None = None) -> ProgramCost | None:
        """AOT-lower ``fn(*args)``, record its compiled costs, cross-check
        the analytic model, refresh the artifact. Never raises.

        ``devices``: the mesh size the program is partitioned over. XLA's
        ``cost_analysis()`` describes the PER-DEVICE partition of an SPMD
        program, so the analytic (global-work) model is divided by the
        device count before the cross-check — and the gauges stay
        per-chip, which is what MFU against a per-chip peak means."""
        label = label or (f"megachunk_k{megachunk_factor}"
                          if megachunk_factor > 1 else "chunk")
        try:
            compiled = fn.lower(*args).compile()
            costs = compiled_costs(compiled)
        except Exception:
            log.warning("roofline capture failed for %r; program stays "
                        "uninstrumented", label, exc_info=True)
            return None
        cost = self._build_cost(label, megachunk_factor, costs,
                                devices=max(1, int(devices)))
        with self._lock:
            self.programs[label] = cost
            self._by_factor[megachunk_factor] = cost
            self._write_artifact_locked()
        self._cross_check(cost)
        return cost

    def _build_cost(self, label: str, k: int, costs: dict[str, Any],
                    *, devices: int = 1) -> ProgramCost:
        raw_flops, raw_ba = costs["flops"], costs["bytes_accessed"]
        if self._trip_blind is None:
            self._trip_blind = _probe_trip_count_blind()
        iters = max(1, self.steps_per_chunk) * max(1, k)
        corrected = self._trip_blind and iters > 1
        scale = iters if corrected else 1
        flops = raw_flops * scale if raw_flops is not None else None
        ba = raw_ba * scale if raw_ba is not None else None
        ai = (flops / ba) if flops and ba else None
        classification = None
        if ai is not None and self.ridge is not None:
            classification = ("compute-bound" if ai >= self.ridge
                              else "memory-bound")
        peak_bytes = None
        if costs["argument_bytes"] is not None:
            peak_bytes = (costs["argument_bytes"]
                          + (costs["temp_bytes"] or 0)
                          + (costs["output_bytes"] or 0))
        # The analytic model counts GLOBAL work (all workers); the SPMD
        # program's cost_analysis describes one device's partition, so the
        # comparison (and the analytic gauge fallback) is per device.
        analytic = (self.analytic_flops_per_chunk * k / devices
                    if self.analytic_flops_per_chunk else None)
        ratio = (flops / analytic) if flops and analytic else None
        discrepancy = (ratio is not None
                       and abs(ratio - 1.0) > DISCREPANCY_BAND)
        # Gauge source selection (see the ProgramCost docstring): XLA when
        # it agrees with (or there is no) analytic model; analytic when the
        # trip-count correction structurally misfits the program. Bytes
        # ride the same factor — arithmetic intensity is preserved.
        if discrepancy and analytic:
            gauge_flops, source = analytic, "analytic"
            gauge_bytes = ba * (analytic / flops) if ba and flops else ba
        else:
            gauge_flops = flops if flops is not None else analytic
            source = ("xla" if flops is not None
                      else ("analytic" if analytic else None))
            gauge_bytes = ba
        return ProgramCost(
            label=label, megachunk_factor=k, devices=devices, flops=flops,
            bytes_accessed=ba,
            flops_hlo_once=raw_flops, bytes_hlo_once=raw_ba,
            loop_iterations=iters, trip_count_corrected=corrected,
            argument_bytes=costs["argument_bytes"],
            temp_bytes=costs["temp_bytes"],
            output_bytes=costs["output_bytes"],
            peak_bytes=peak_bytes,
            arithmetic_intensity=ai, classification=classification,
            analytic_flops=analytic, xla_vs_analytic=ratio,
            discrepancy=discrepancy,
            gauge_flops=gauge_flops, gauge_bytes=gauge_bytes,
            gauge_flops_source=source)

    def _cross_check(self, cost: ProgramCost) -> None:
        if not cost.discrepancy:
            return
        msg = (f"roofline FLOP cross-check: XLA counts "
               f"{cost.flops:.3e} FLOPs for {cost.label} but the analytic "
               f"model (utils/flops.py) expects {cost.analytic_flops:.3e} "
               f"(ratio {cost.xla_vs_analytic:.2f}) — one of the two "
               "countings is wrong (or the program's FLOPs live outside "
               "its chunk-steps scan); live gauges use the analytic count")
        log.warning(msg)
        if self._flight_record is not None:
            self._flight_record("roofline_discrepancy", program=cost.label,
                                xla_flops=cost.flops,
                                analytic_flops=cost.analytic_flops,
                                ratio=cost.xla_vs_analytic)

    # -- run-time gauges (consumer thread) ------------------------------

    def on_boundary(self, *, k: int, chunk_seconds: float | None) -> None:
        """Combine static costs with the sampled per-chunk wall time into
        live gauges. Rides the metrics sampling cadence on the pipeline
        consumer thread — gauge math never touches the dispatcher."""
        if not chunk_seconds or chunk_seconds <= 0:
            return
        cost = self._by_factor.get(k) or self._by_factor.get(1)
        if cost is None:
            return
        flops = cost.flops_per_chunk()
        ba = cost.bytes_per_chunk()
        gauges: dict[str, float] = {}
        if flops:
            achieved = flops / chunk_seconds
            gauges["achieved_tflops"] = achieved / 1e12
            if self.peak_flops:
                gauges["mfu"] = achieved / self.peak_flops
        if ba:
            gauges["hbm_gbps"] = ba / chunk_seconds / 1e9
        if cost.arithmetic_intensity is not None:
            gauges["arithmetic_intensity"] = cost.arithmetic_intensity
        if cost.classification is not None:
            gauges["roofline_compute_bound"] = float(
                cost.classification == "compute-bound")
        if gauges:
            self.registry.record_many(gauges)

    # -- artifact -------------------------------------------------------

    def _bundle_locked(self) -> dict:
        """The artifact/summary object — caller holds ``self._lock``."""
        return {
            "schema_version": SCHEMA_VERSION,
            "precision_mode": self.precision_mode,
            "peak_flops_per_s": self.peak_flops,
            "peak_hbm_bytes_per_s": self.peak_hbm_bw,
            "ridge_flops_per_byte": self.ridge,
            "analytic_flops_per_chunk": self.analytic_flops_per_chunk,
            "programs": {name: dataclasses.asdict(cost)
                         for name, cost in self.programs.items()},
        }

    def summary(self) -> dict:
        with self._lock:
            return self._bundle_locked()

    def _write_artifact_locked(self) -> None:
        if self.run_dir is None:
            return
        path = os.path.join(self.run_dir, ARTIFACT)
        try:
            bundle = self._bundle_locked()
            tmp = f"{path}.tmp-{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(bundle, f, indent=2, default=str)
            os.replace(tmp, path)
        except Exception:       # artifact IO never outranks the run
            log.exception("roofline artifact write failed")


def read_roofline(run_dir: str) -> dict | None:
    """Load a run dir's roofline artifact; None when absent/unreadable."""
    path = os.path.join(run_dir, ARTIFACT)
    if not os.path.isfile(path):
        return None
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except Exception:
        return None


def summarize_roofline(bundle: dict, *, top: int = 3) -> dict:
    """The ``cli obs`` condensation: per-program headline numbers plus the
    top compute-bound / memory-bound programs by FLOPs."""
    programs = bundle.get("programs", {})

    def _brief(name: str) -> dict:
        p = programs[name]
        return {
            "program": name,
            "flops": p.get("flops"),
            "bytes_accessed": p.get("bytes_accessed"),
            "arithmetic_intensity": p.get("arithmetic_intensity"),
            "discrepancy": p.get("discrepancy", False),
        }

    by_flops = sorted(
        (n for n in programs if programs[n].get("flops")),
        key=lambda n: programs[n]["flops"], reverse=True)
    return {
        "schema_version": bundle.get("schema_version"),
        "ridge_flops_per_byte": bundle.get("ridge_flops_per_byte"),
        "programs": len(programs),
        "compute_bound": [
            _brief(n) for n in by_flops
            if programs[n].get("classification") == "compute-bound"][:top],
        "memory_bound": [
            _brief(n) for n in by_flops
            if programs[n].get("classification") == "memory-bound"][:top],
        # No published peak for the device the run used (the CPU
        # included): costs captured, bound not measured.
        "unclassified": [
            _brief(n) for n in by_flops
            if programs[n].get("classification") is None][:top],
    }
