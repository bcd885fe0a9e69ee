"""The manifest is consistent and the harness is driven by data."""

import ast
import glob
import json
import os
import re
import shutil

import pytest

import chipbench_toy as toy
from chipbench.harness import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
DOC = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in DOC["workloads"]]
PER_LAYER = [m["name"] for m in DOC["per_layer"]]
ALL_NAMES = (CELLS + PER_LAYER + [m["name"] for m in DOC["end_to_end"]]
             + [c["name"] for c in DOC["configs"]]
             + [w["traffic"] for w in DOC["workloads"]])


def test_keys_are_exactly_the_contracts():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert any(m["name"] == "setup_s" for m in DOC["end_to_end"])
    assert all(0 < m["bound"] <= 0.1 for m in DOC["end_to_end"])
    assert sum(w["chips"] == 4 for w in DOC["workloads"]) <= max(
        1, len(DOC["workloads"]) // 4)


@pytest.mark.parametrize("name", sorted(set(ALL_NAMES)))
def test_names_keep_to_the_allowed_characters(name):
    assert NAME.match(name)


@pytest.mark.parametrize("metric", DOC["end_to_end"] + DOC["per_layer"],
                         ids=lambda m: m["name"])
def test_units_sources_and_directions(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert len(set(metric.get("workloads", CELLS))) == len(
        metric.get("workloads", CELLS))
    assert set(metric.get("workloads", [])) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cells_files_are_found_by_name(cell):
    manifest = common.Manifest()
    entry = manifest.cell(cell)
    config = manifest.config(entry["config"])
    traffic = manifest.traffic(entry["traffic"])
    assert traffic["kind"] in ("train", "serve")
    assert isinstance(manifest.limits(cell)["limits"], dict)
    declared = next(c for c in DOC["configs"] if c["name"] == entry["config"])
    assert sorted(declared["reduced"]) == sorted(config["reduced"])
    assert os.path.exists(os.path.join(common.ROOT, declared["file"]))
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    cfg = common.build_config(config, traffic, seed=2 ** 31 + 5)
    assert cfg.seed == 2 ** 31 + 5
    assert callable(manifest.model(config).trunk)
    reported = {m["name"] for m in manifest.metrics_for(cell, "end_to_end")}
    assert "setup_s" in reported and len(reported) >= 2
    assert manifest.metrics_for(cell, "per_layer")


@pytest.mark.parametrize("name", PER_LAYER)
def test_every_per_layer_metric_has_a_reader_and_moves_what_its_cells_report(
        name):
    manifest = common.Manifest()
    fn, args = manifest.reader(name)
    assert callable(fn) and isinstance(args, dict)
    metric = next(m for m in DOC["per_layer"] if m["name"] == name)
    for cell in metric.get("workloads", CELLS):
        reported = {m["name"]
                    for m in manifest.metrics_for(cell, "end_to_end")}
        assert metric["moves"] in reported
    if name.endswith("_roofline") or "mfu" in name.split("."):
        assert metric["unit"] == "%"


def test_a_cell_and_a_metric_can_be_added_as_files(tmp_path):
    """A later PR adds a cell, a configuration and a per-layer metric as new
    files and new entries; no file that exists is edited."""
    data = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(os.path.join(common.BENCH_DIR, sub), data / sub)
    before = {p: os.path.getmtime(os.path.join(dp, p))
              for dp, _, fs in os.walk(data) for p in fs}
    config = common.load_json(str(data / "configs" / "tr_episode_d256.json"))
    config["overrides"]["parallel.num_workers"] = 2048
    (data / "configs" / "tr_episode_d256_b2048.json").write_text(
        json.dumps(config))
    (data / "limits" / "train_d256_b2048.json").write_text(
        json.dumps({"limits": {"loss_step1": 0.01}}))
    (data / "metrics" / "train.dispatch_gap_p50_ms.json").write_text(
        json.dumps({"reader": {"module": "histograms",
                               "function": "percentile",
                               "args": {"histogram": "train_dispatch_gap_ms",
                                        "q": 50}}}))
    doc = json.loads(json.dumps(DOC))
    doc["workloads"].append({"name": "train_d256_b2048", "chips": 1,
                             "config": "tr_episode_d256_b2048",
                             "traffic": "train_steady", "why": "half batch"})
    doc["per_layer"].append({
        "name": "train.dispatch_gap_p50_ms", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "runtime",
        "moves": "agent_steps_per_s", "workloads": ["train_d256_b2048"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    manifest = common.Manifest(str(tmp_path / "BENCHMARK.json"), str(data))
    cell = manifest.cell("train_d256_b2048")
    cfg = common.build_config(manifest.config(cell["config"]),
                              manifest.traffic(cell["traffic"]), seed=3)
    assert cfg.parallel.num_workers == 2048 and cfg.model.head_dim == 128
    names = [m["name"] for m in manifest.metrics_for(
        "train_d256_b2048", "per_layer")]
    assert names == ["train.dispatch_gap_p50_ms"]
    fn, args = manifest.reader("train.dispatch_gap_p50_ms")
    snap = {"train_dispatch_gap_ms": {"bounds": [1.0, 2.0, 4.0],
                                      "counts": [1, 5, 1, 0]}}
    assert fn({"histograms": snap}, **args) == 1.6   # rank 4: 3 of 5 into 1..2
    assert all(os.path.getmtime(os.path.join(dp, p)) == before[p]
               for dp, _, fs in os.walk(data) for p in fs if p in before)


@pytest.mark.parametrize("config", [c["name"] for c in DOC["configs"]])
def test_every_configuration_names_a_model_family_with_the_whole_interface(
        config):
    from chipbench.harness import flops
    manifest = common.Manifest()
    doc = manifest.config(config)
    model = manifest.model(doc)
    assert model.__name__ == "chipbench.models." + doc["model"]
    assert os.path.dirname(model.__file__) == os.path.join(
        common.BENCH_DIR, "models")
    assert all(callable(getattr(model, member))
               for member in common.MODEL_INTERFACE)
    cfg = common.build_config(doc, {}, seed=1)
    sizes = flops.sizes(cfg, model)
    assert model.history(sizes) >= 0
    assert model.replay_seq_len(sizes) == (
        model.history(sizes) + sizes["window"] + sizes["unroll"] - 1)
    assert model.train_flops_per_agent_step(sizes) > 0
    assert model.serve_warm_step_flops(sizes) > 0


@pytest.mark.parametrize("doc,why", [
    ({}, "names no model"),
    ({"model": "no_such_family"}, "no model family"),
    ({"model": "half_a_family"}, "lacks")])
def test_a_configuration_without_a_whole_model_family_is_refused(
        doc, why, tmp_path, monkeypatch):
    import chipbench.models
    (tmp_path / "half_a_family.py").write_text(
        "def sizes(cfg):\n    return {}\n")
    monkeypatch.setattr(chipbench.models, "__path__",
                        [*chipbench.models.__path__, str(tmp_path)])
    with pytest.raises(common.Refused, match=why):
        common.Manifest().model(doc)


def test_a_model_family_can_be_added_as_files(tmp_path, monkeypatch):
    """A later ``model_config`` PR adds its family as a module of its own
    and a configuration that names it; no file that exists is edited. (Both
    drivers run such a family, sound and broken, in
    test_chipbench_drivers.py and test_chipbench_control.py.)"""
    from chipbench.harness import flops
    before = toy.benchmark_files()
    manifest = toy.make_toy(tmp_path, monkeypatch, "stacked_kv")
    doc = manifest.config(manifest.cell("toy_train")["config"])
    model = manifest.model(doc)
    assert os.path.dirname(model.__file__) == toy.FAMILY_DIR
    sizes = flops.sizes(common.build_config(doc, {}, seed=1), model)
    assert {"depth", "width", "head_count"} <= set(sizes)
    assert not {"layers", "heads", "head_dim"} & set(sizes)
    assert manifest.limits("toy_train")["limits"]["newest_tick_err"] == 1e-3
    assert toy.benchmark_files() == before


def _spells_a_model(tree: ast.AST) -> list[str]:
    """What in a file of the shared harness belongs to one model family: an
    import of a module under ``chipbench.models``, a model's size by its
    name, or the program's carry read by its arrays' names."""
    sizes = {"layers", "heads", "head_dim", "num_layers", "num_heads"}
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and (node.module or "").startswith(
                "chipbench.models"):
            found.append(f"from {node.module} import ...")
        elif isinstance(node, ast.Import):
            found += [f"import {a.name}" for a in node.names
                      if a.name.startswith("chipbench.models")]
        elif isinstance(node, ast.Constant) and node.value in sizes:
            found.append(repr(node.value))
        elif isinstance(node, ast.Attribute) and node.attr in sizes:
            found.append("." + node.attr)
        elif (isinstance(node, ast.Subscript)
              and isinstance(node.value, ast.Name)
              and node.value.id == "carry"):
            found.append("carry[...]")
    return found


SHARED = sorted(
    os.path.relpath(p, common.ROOT) for pattern in
    ("run.py", "harness/*.py", "readers/step.py", "tools/*.py")
    for p in glob.glob(os.path.join(common.BENCH_DIR, pattern))
    if not p.endswith("__init__.py"))


@pytest.mark.parametrize("path", SHARED)
def test_the_shared_harness_spells_no_models_name_or_sizes(path):
    with open(os.path.join(common.ROOT, path)) as fh:
        assert _spells_a_model(ast.parse(fh.read())) == []


def test_the_scan_finds_what_the_drivers_spelt_before():
    spelt = _spells_a_model(ast.parse(
        "from chipbench.models import episode_transformer\n"
        "import chipbench.models.episode_transformer\n"
        "d = s['heads'] * s['head_dim']\n"
        "n = cfg.model.num_layers\n"
        "w = carry['k'].shape[3]\n"))
    assert len(spelt) == 6 and "carry[...]" in spelt and ".num_layers" in spelt


def test_unknown_cell_and_missing_chip_are_refused(capsys):
    from chipbench import run
    assert run.main(["--workload", "no_such_cell", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert run.main(["--workload", CELLS[0], "--seed", "1",
                     "--seconds", "1"]) == 2      # the tests run on the CPU
    out = capsys.readouterr()
    assert out.out.strip() == "" and "refused" in out.err


def test_peak_table_refuses_an_unknown_device():
    from chipbench.harness import peaks
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(peaks.UnknownDeviceKind):
        peaks.peaks_for("cpu")
