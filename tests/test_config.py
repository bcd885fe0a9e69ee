import json
import os
import re

import pytest

from benchmarks.run_all import make_configs
from sharetrade_tpu.config import FrameworkConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_defaults_match_reference_constants():
    # Reference hyperparameters: QDecisionPolicyActor.scala:17-22,
    # ShareTradeHelper.scala:20-21, TrainerRouterActor.scala:36.
    cfg = FrameworkConfig()
    assert cfg.env.window == 201
    assert cfg.env.initial_budget == 2400.0
    assert cfg.model.hidden_dim == 200
    assert cfg.model.num_actions == 3
    assert cfg.learner.epsilon == 0.9
    assert cfg.learner.gamma == 0.001
    assert cfg.learner.learning_rate == 0.01
    assert cfg.parallel.num_workers == 10


def test_roundtrip_dict():
    cfg = FrameworkConfig()
    cfg2 = FrameworkConfig.from_dict(cfg.to_dict())
    assert cfg2.to_dict() == cfg.to_dict()


def test_roundtrip_file(tmp_path):
    cfg = FrameworkConfig()
    cfg.learner.gamma = 0.99
    path = str(tmp_path / "cfg.json")
    cfg.save(path)
    loaded = FrameworkConfig.from_file(path)
    assert loaded.learner.gamma == 0.99
    assert loaded.to_dict() == cfg.to_dict()


def test_overrides():
    cfg = FrameworkConfig()
    out = cfg.apply_overrides([
        "learner.gamma=0.95",
        "model.kind=lstm",
        'parallel.mesh_shape={"dp": 4, "tp": 2}',
        "data.csv_path=/tmp/x.csv",
    ])
    assert out.learner.gamma == 0.95
    assert out.model.kind == "lstm"
    assert out.parallel.mesh_shape == {"dp": 4, "tp": 2}
    assert out.data.csv_path == "/tmp/x.csv"
    # original untouched
    assert cfg.learner.gamma == 0.001


def test_override_unknown_key_raises():
    cfg = FrameworkConfig()
    import pytest
    with pytest.raises(KeyError):
        cfg.apply_overrides(["learner.nope=1"])
    with pytest.raises(ValueError):
        cfg.apply_overrides(["learner.gamma"])


@pytest.mark.parametrize("name", ["tr_episode_d1024", "tr_episode_d256"])
def test_benchmark_config_matches_cited_source(name):
    """The benchmark's configuration files cite
    ``benchmarks/run_all.py make_configs()["<key>"]`` as their source: every
    override they do not list as ``reduced``, and every ``assumed`` field,
    is that entry's value (the one reason that module is still here). The
    ``reduced`` keys really differ from it, or they are not reductions."""
    with open(os.path.join(REPO, "chipbench", "configs",
                           f"{name}.json")) as f:
        doc = json.load(f)
    cited = re.search(r'make_configs\(\)\["(\w+)"\]', doc["source"])
    assert cited, doc["source"]
    source = make_configs()[cited.group(1)]

    def field(path):
        obj = source
        for part in path.split("."):
            obj = getattr(obj, part)
        return obj

    reduced = set(doc["reduced"])
    assert reduced <= set(doc["overrides"])
    for path, value in doc["overrides"].items():
        if path in reduced:
            assert field(path) != value, path
        else:
            assert field(path) == value, path
    for path, value in doc["assumed"].items():
        assert field(path) == value, path
