"""What every cell's run shares: the manifest, the configuration, the device
check and the result line."""

from __future__ import annotations

import importlib
import json
import os
import shutil
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


# What a module under chipbench/models/ exports (chipbench/README.md, "To
# add a model family").
MODEL_INTERFACE = ("sizes", "history", "init_params", "trunk",
                   "program_cache", "further_numbers",
                   "train_flops_per_agent_step", "serve_warm_step_flops",
                   "replay_seq_len")


class Refused(Exception):
    """The run cannot be made here (no chip, unknown cell): exit code 2, no
    result line."""


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


class Manifest:
    """``BENCHMARK.json`` plus the data files it names. ``data_dir`` holds
    ``configs/``, ``traffic/``, ``metrics/`` and ``limits/``; everything
    that belongs to one configuration, one traffic mix or one per-layer
    metric is found there by name. A reader and a model family are code,
    found by name in the packages ``chipbench.readers`` and
    ``chipbench.models``."""

    def __init__(self, benchmark_json: str | None = None,
                 data_dir: str | None = None):
        self.root = ROOT
        self.data_dir = data_dir or BENCH_DIR
        self.doc = load_json(benchmark_json
                             or os.path.join(ROOT, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise Refused(f"unknown workload {name!r}")

    def config(self, name: str) -> dict:
        return load_json(os.path.join(self.data_dir, "configs", name + ".json"))

    def model(self, config_doc: dict):
        """The module of the configuration's model family: the file under
        ``chipbench/models/`` that its ``model`` key names. No default
        stands in for a missing key or module."""
        name = config_doc.get("model")
        if not name:
            raise Refused("the configuration's file names no model")
        try:
            module = importlib.import_module("chipbench.models." + name)
        except ModuleNotFoundError as exc:
            raise Refused(f"no model family {name!r}: {exc}") from exc
        missing = [m for m in MODEL_INTERFACE if not callable(
            getattr(module, m, None))]
        if missing:
            raise Refused(f"model family {name!r} lacks {missing}")
        return module

    def traffic(self, name: str) -> dict:
        return load_json(os.path.join(self.data_dir, "traffic", name + ".json"))

    def limits(self, cell: str) -> dict:
        return load_json(os.path.join(self.data_dir, "limits", cell + ".json"))

    def metrics_for(self, cell: str, group: str) -> list[dict]:
        """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
        out = []
        for m in self.doc[group]:
            cells = m.get("workloads")
            if cells is None:
                moved = m.get("moves")
                cells = [w["name"] for w in self.doc["workloads"]
                         if moved is None
                         or w["name"] in self._cells_reporting(moved)]
            if cell in cells:
                out.append(m)
        return out

    def _cells_reporting(self, e2e_name: str) -> list[str]:
        for m in self.doc["end_to_end"]:
            if m["name"] == e2e_name:
                return m.get("workloads",
                             [w["name"] for w in self.doc["workloads"]])
        return []

    def reader(self, metric_name: str):
        """(function, arguments) of a per-layer metric's reader."""
        spec = load_json(os.path.join(self.data_dir, "metrics",
                                      metric_name + ".json"))["reader"]
        mod = importlib.import_module("chipbench.readers." + spec["module"])
        return getattr(mod, spec["function"]), spec.get("args", {})


def build_config(config_doc: dict, traffic_doc: dict, seed: int):
    """FrameworkConfig with the configuration's overrides, then the traffic
    mix's, applied by dotted key."""
    from sharetrade_tpu.config import FrameworkConfig
    cfg = FrameworkConfig()
    for doc in (config_doc, traffic_doc):
        for key, value in doc.get("overrides", {}).items():
            obj, parts = cfg, key.split(".")
            for part in parts[:-1]:
                obj = getattr(obj, part)
            if not hasattr(obj, parts[-1]):
                raise KeyError(f"no configuration key {key!r}")
            setattr(obj, parts[-1], value)
    cfg.seed = int(seed)
    return cfg


def require_chip(chips: int) -> dict:
    """The device block of the result line; refuses anything but a TPU the
    peak table knows, with at least ``chips`` chips."""
    import jax
    from chipbench.harness.peaks import UnknownDeviceKind, peaks_for
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX shows "
                      f"{len(devices)}")
    try:
        peaks_for(devices[0].device_kind)
    except UnknownDeviceKind as exc:
        raise Refused(str(exc)) from exc
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def fresh_cwd(cell: str) -> str:
    """A fixed, emptied working directory under ``chipbench/out/``: the
    program writes ``journal/``, ``checkpoints/`` and ``obs/`` relative to
    the working directory, and would pick up what a stale one holds."""
    path = os.path.join(BENCH_DIR, "out", cell)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    os.chdir(path)
    return path


def make_prices(params: dict) -> np.ndarray:
    """The cell's market: a mean-reverting log-price walk (daily volatility
    ``sigma``, pull ``theta`` toward the first price), so that a series long
    enough to outlast any run stays in the range the budget can trade. Made
    from the traffic mix's own ``price_seed``, not from ``--seed``: the
    program compiles the series into its step as a constant, so a series
    per seed would compile anew in every run (PERF.md, open questions)."""
    rng = np.random.default_rng(params["price_seed"])
    noise = rng.normal(0.0, params["sigma"], size=params["length"] - 1)
    # x[i+1] = (1 - theta) * x[i] + noise[i], x[0] = 0, in blocks short
    # enough for the closed form's powers to stay in range.
    block, last, parts = 1024, 0.0, [np.zeros(1)]
    powers = (1.0 - params["theta"]) ** np.arange(1, block + 1)
    for lo in range(0, len(noise), block):
        n = noise[lo:lo + block]
        x = powers[:len(n)] * (last + np.cumsum(n / powers[:len(n)]))
        parts.append(x)
        last = x[-1]
    return (params["first_price"]
            * np.exp(np.concatenate(parts))).astype(np.float32)


def histogram_delta(before: dict, after: dict) -> dict:
    """What each histogram counted between two snapshots."""
    out = {}
    for name, snap in after.items():
        old = before.get(name, {})
        out[name] = {"bounds": snap["bounds"],
                     "counts": [a - b for a, b in zip(
                         snap["counts"],
                         old.get("counts", [0] * len(snap["counts"])))],
                     "sum": snap.get("sum", 0.0) - old.get("sum", 0.0),
                     "count": snap.get("count", 0) - old.get("count", 0)}
    return out


def assemble(ok: bool, attempted: int, failed: int, device: dict, peak: int,
             values: dict, profile, context: dict, readers) -> dict:
    """The result of a run: the end-to-end values, or, where a stretch was
    profiled, the trace's busy and window seconds, its breakdown and the
    per-layer metrics the readers take from ``context``."""
    device = dict(device, memory_peak_bytes=peak)
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "device": device, "metrics": values}
    if profile is not None:
        summary = profile.reduce()
        device["busy_s"], device["window_s"] = summary.busy_s, summary.window_s
        result["breakdown"] = summary.breakdown()
        result["metrics"] = readers(dict(context, trace=summary))
    return result


def open_cell(workload: str, suffix: str):
    """What the chip tools share: the cell, its configuration's file, its
    traffic and its model family, the compile cache, the device check and a
    fresh working directory."""
    from sharetrade_tpu.utils.runtime_env import configure_compile_cache
    manifest = Manifest()
    cell = manifest.cell(workload)
    traffic = manifest.traffic(cell["traffic"])
    config_doc = manifest.config(cell["config"])
    model = manifest.model(config_doc)
    configure_compile_cache()
    device = require_chip(cell["chips"])
    fresh_cwd(cell["name"] + suffix)
    return cell, config_doc, traffic, model, device


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list (q in 0..100)."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return float(ordered[int(rank) - 1])


def emit_result(result: dict, compared: dict) -> None:
    """Each number compared beside its limit on standard error, then the one
    result line, with ``compared`` as its last key."""
    for name, (value, limit) in compared.items():
        print(f"compared {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in compared.items()}
    print(json.dumps(line), flush=True)
