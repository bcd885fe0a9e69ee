"""Both drivers end to end at a toy size, sound and with the timed path
broken underneath: ``correct`` has to come out false for every fault a cell
can have."""

import pytest

import chipbench_toy as toy


@pytest.fixture(params=toy.FAMILIES)
def family(request):
    """The name of a model family: the benchmark's own, then the one that
    the tests add as files (tests/chipbench/families/)."""
    return request.param


def test_training_cell_runs_and_proves_correct(family, tmp_path, monkeypatch,
                                               capsys):
    before = toy.benchmark_files()
    manifest = toy.make_toy(tmp_path, monkeypatch, family)
    rc, line = toy.run_cell(manifest, "toy_train", capsys,
                            seed=2 ** 31 + 11)
    assert rc == 0 and line["correct"] is True, line
    assert toy.benchmark_files() == before     # the family came as files
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"agent_steps_per_s", "setup_s"}
    assert line["metrics"]["agent_steps_per_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"     # named, never hidden
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == set(toy.train_limits(family))
    assert all(v["value"] <= v["limit"] for v in line["compared"].values())


def _half_batch(monkeypatch):
    """Every other agent left out of the step: it does not trade, and the
    loss is the mean over the rest."""
    import jax
    import jax.numpy as jnp
    from sharetrade_tpu.agents import ppo
    sound = ppo.collect_rollout

    def broken(model, env, ts, *args, **kwargs):
        new, traj, bootstrap, carry = sound(model, env, ts, *args, **kwargs)
        keep = jnp.arange(traj.active.shape[1]) % 2 == 0
        wallets = jax.tree.map(
            lambda after, before: jnp.where(keep, after, before),
            new.env_state, ts.env_state)
        traj = traj._replace(active=traj.active * keep[None, :])
        return new.replace(env_state=wallets), traj, bootstrap, carry

    monkeypatch.setattr(ppo, "collect_rollout", broken)


def _token_altered(monkeypatch):
    """Every fourth agent's action moved on by one where the rollout
    samples it (``jnp.argmax`` over logits + noise, as that module sees
    it)."""
    import jax.numpy as jnp
    from sharetrade_tpu.agents import rollout

    class Altered:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def argmax(x, *args, **kwargs):
            picked = jnp.argmax(x, *args, **kwargs)
            if x.ndim != 2:
                return picked
            hit = jnp.arange(x.shape[0]) % 4 == 0
            return jnp.where(hit, (picked + 1) % x.shape[1], picked)

    monkeypatch.setattr(rollout, "jnp", Altered())


def _state_unchanged(monkeypatch):
    from sharetrade_tpu.runtime.orchestrator import Orchestrator
    build = Orchestrator._build_step

    def build_then_break(self):
        build(self)
        sound = self._step_fn

        def unchanged(ts):
            new, metrics = sound(ts)
            return ts.replace(env_steps=new.env_steps), metrics

        self._step_fn = unchanged

    monkeypatch.setattr(Orchestrator, "_build_step", build_then_break)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_training_faults_come_out_not_correct(fault, family, tmp_path,
                                              monkeypatch, capsys):
    manifest = toy.make_toy(tmp_path, monkeypatch, family)
    {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
     "token_altered": _token_altered}[fault](monkeypatch)
    rc, line = toy.run_cell(manifest, "toy_train", capsys, seed=5)
    assert rc == 0 and line["correct"] is False, line
    over = {k for k, v in line["compared"].items()
            if not v["value"] <= v["limit"]}
    # the number that catches each fault at the cells' own sizes (PERF.md)
    assert {"state_unchanged": "change_median_gap", "half_batch":
            "shares_gap", "token_altered": "shares_gap"}[fault] in over, line
    if fault == "state_unchanged":
        assert line["compared"]["change_median_gap"]["value"] == 1.0
    else:
        assert line["compared"]["kv_err"]["value"] <= 1e-3


def test_serving_cell_runs_and_proves_correct(family, tmp_path, monkeypatch,
                                              capsys):
    before = toy.benchmark_files()
    manifest = toy.make_toy(tmp_path, monkeypatch, family)
    rc, line = toy.run_cell(manifest, "toy_serve", capsys, seed=2 ** 31 + 3)
    assert rc == 0 and line["correct"] is True, line
    assert toy.benchmark_files() == before
    assert line["attempted"] == 300 and line["failed"] == 0
    assert set(line["metrics"]) >= {"setup_s"}
    assert set(line["compared"]) == set(toy.TOY_LIMITS_SERVE)


def test_an_altered_answer_comes_out_not_correct(family, tmp_path,
                                                 monkeypatch, capsys):
    from sharetrade_tpu.serve.engine import ServeEngine
    sound = ServeEngine._warm_program

    def altered(self, params, pool, obs, idx):
        actions, logits, value, new_pool = sound(self, params, pool, obs, idx)
        return (actions + 1) % 3, logits, value, new_pool

    monkeypatch.setattr(ServeEngine, "_warm_program", altered)
    manifest = toy.make_toy(tmp_path, monkeypatch, family)
    rc, line = toy.run_cell(manifest, "toy_serve", capsys, seed=9)
    assert rc == 0 and line["correct"] is False, line
    assert (line["compared"]["logit_gap"]["value"]
            > line["compared"]["logit_gap"]["limit"])
