"""A toy benchmark in a temporary copy of the data directories: the real
manifest's metrics, two toy cells of one toy configuration of the model
family asked for. The tests lift the harness's look for a chip here, not
through a flag of the command."""

from __future__ import annotations

import json
import os
import shutil

from chipbench.harness import common, peaks

TOY_LIMITS_TRAIN = {"kv_err": 1e-3, "shares_gap": 1e-3,
                    "loss_step1": 1e-3, "grad_median_gap": 1e-3,
                    "change_median_gap": 1e-3, "grad_worst_gap": 1e-3,
                    "change_worst_gap": 1e-3}
TOY_LIMITS_SERVE = {"logit_gap": 1e-4, "logit_err": 1e-4}
D1024 = {"layers": 4, "heads": 8, "head_dim": 128, "window": 201,
         "actions": 3, "unroll": 512, "agents": 1024, "epochs": 4,
         "minibatches": 4}        # the sizes of tr_episode_d1024
# The model families the tests run: the benchmark's own, and a second one
# that exists under tests/chipbench/families/ alone.
FAMILIES = ("episode_transformer", "stacked_kv")
FAMILY_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "families")
FURTHER_LIMITS = {"episode_transformer": {},
                  "stacked_kv": {"newest_tick_err": 1e-3}}


def add_families(monkeypatch):
    """Put the tests' families beside the benchmark's own, as files: the
    package ``chipbench.models`` is searched in their directory too."""
    import chipbench.models
    monkeypatch.setattr(chipbench.models, "__path__",
                        [*chipbench.models.__path__, FAMILY_DIR])


def family(name: str, monkeypatch):
    import importlib
    add_families(monkeypatch)
    return importlib.import_module("chipbench.models." + name)


def train_limits(model: str) -> dict:
    return {**TOY_LIMITS_TRAIN, **FURTHER_LIMITS[model]}


def toy_sizes(model) -> dict:
    """The toy configuration's plain sizes as ``model`` names them."""
    from chipbench.harness import flops
    cfg = common.build_config({"overrides": TOY_OVERRIDES}, {}, seed=0)
    return flops.sizes(cfg, model)


def benchmark_files() -> dict:
    """{path: mtime} of every file of ``chipbench/`` that git would keep:
    what adding a family, a cell or a metric may not touch."""
    stamps = {}
    for folder, dirs, files in os.walk(common.BENCH_DIR):
        dirs[:] = [d for d in dirs if d not in ("out", "__pycache__")]
        for name in files:
            path = os.path.join(folder, name)
            stamps[path] = os.path.getmtime(path)
    return stamps
PRICES = {"price_seed": 7, "length": 20000, "first_price": 56.08,
          "sigma": 0.02, "theta": 0.002}
TOY_OVERRIDES = {
    "model.num_layers": 2, "model.num_heads": 2, "model.head_dim": 16,
    "learner.unroll_len": 16, "runtime.chunk_steps": 16,
    "parallel.num_workers": 8, "env.window": 12, "precision.mode": "fp32"}


def make_toy(tmp_path, monkeypatch,
             model: str = "episode_transformer") -> common.Manifest:
    data = str(tmp_path / "data")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(data, sub))
    shutil.copytree(os.path.join(common.BENCH_DIR, "metrics"),
                    os.path.join(data, "metrics"))

    def put(sub, name, doc):
        with open(os.path.join(data, sub, name + ".json"), "w") as fh:
            json.dump(doc, fh)

    cfg = common.load_json(os.path.join(common.BENCH_DIR, "configs",
                                        "tr_episode_d256.json"))
    cfg["overrides"].update(TOY_OVERRIDES)
    cfg["model"] = model
    put("configs", "toy", cfg)
    put("traffic", "toy_train", {"kind": "train", "overrides": {},
                                 "prices": PRICES})
    put("traffic", "toy_serve", {
        "kind": "serve", "prices": PRICES,
        "overrides": {"serve.max_batch": 4, "serve.slots": 32,
                      "serve.warm_bytes": 0, "serve.swap_poll_s": 0},
        "load": {"sessions": 32, "rate": 200.0, "wave": 16,
                 "check_sessions": 4}})
    put("limits", "toy_train", {"limits": train_limits(model)})
    put("limits", "toy_serve", {"limits": TOY_LIMITS_SERVE})

    doc = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    kinds = {w["name"]: common.load_json(os.path.join(
        common.BENCH_DIR, "traffic", w["traffic"] + ".json"))["kind"]
        for w in doc["workloads"]}
    toy_of = {"train": "toy_train", "serve": "toy_serve"}
    for group in ("end_to_end", "per_layer"):
        for metric in doc[group]:
            if "workloads" in metric:
                metric["workloads"] = sorted(
                    {toy_of[kinds[c]] for c in metric["workloads"]})
    doc["configs"] = [{"name": "toy", "source": "toy", "reduced": [],
                       "file": "configs/toy.json", "why": "toy"}]
    doc["workloads"] = [
        {"name": "toy_train", "config": "toy", "traffic": "toy_train",
         "chips": 1, "why": "toy"},
        {"name": "toy_serve", "config": "toy", "traffic": "toy_serve",
         "chips": 1, "why": "toy"}]
    path = str(tmp_path / "BENCHMARK.json")
    with open(path, "w") as fh:
        json.dump(doc, fh)

    add_families(monkeypatch)
    monkeypatch.setattr(common, "require_chip", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["v5 lite"])
    monkeypatch.setattr(common, "fresh_cwd",
                        lambda cell: _fresh(tmp_path, cell))
    return common.Manifest(path, data)


def _fresh(tmp_path, cell):
    path = str(tmp_path / "out" / cell)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    os.chdir(path)
    return path


def run_cell(manifest, cell, capsys, *, seed=7, seconds=1.5):
    """``chipbench.run.main`` on a toy cell -> (exit code, result line)."""
    from chipbench import run
    cwd = os.getcwd()
    try:
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0"],
                      manifest=manifest)
    finally:
        os.chdir(cwd)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)
