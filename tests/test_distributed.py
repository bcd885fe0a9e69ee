"""Multi-host (DCN) bring-up — the reference's dormant remoting tier
(build.sbt:13 akka-remote on the classpath, README.md:13 "Akka Clustering
will come later") made explicit, testable, AND runnable.

Two tiers of coverage:

- ``TestInitDistributedGating`` pins the gating contract of
  ``init_distributed`` (which tier fires, with which arguments, idempotence)
  against a recorded ``jax.distributed.initialize``.
- ``TestTwoProcessSmoke`` runs the real thing: two OS processes, each its
  own jax runtime (CPU backend, gloo standing in for DCN), brought up via
  ``init_distributed`` and running sharded PPO training chunks over a dp
  mesh that SPANS the processes (tools/dist_smoke_worker.py). The children
  run CPU-only with one device each — the same code path a real multi-host
  TPU pod takes, with DCN collectives swapped for gloo.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

from sharetrade_tpu.parallel import init_distributed
from sharetrade_tpu.parallel import mesh as mesh_mod

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO_ROOT, "tools", "dist_smoke_worker.py")


class _Recorder:
    def __init__(self):
        self.calls = []

    def __call__(self, **kwargs):
        self.calls.append(kwargs)


@pytest.fixture
def recorded_initialize(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(mesh_mod.jax.distributed, "initialize", rec)
    # Ensure the idempotence guard sees "not yet initialized".
    monkeypatch.setattr(
        mesh_mod.jax.distributed, "is_initialized", lambda: False)
    for var in ("JAX_COORDINATOR_ADDRESS", "MEGASCALE_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(var, raising=False)
    return rec


class TestInitDistributedGating:
    def test_single_process_noop(self, recorded_initialize):
        assert init_distributed() is False
        assert recorded_initialize.calls == []

    def test_env_var_triggers_initialize(self, recorded_initialize,
                                         monkeypatch):
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:8476")
        assert init_distributed() is True
        assert recorded_initialize.calls == [{}]  # env-discovered

    def test_megascale_env_var_triggers_initialize(self, recorded_initialize,
                                                   monkeypatch):
        monkeypatch.setenv("MEGASCALE_COORDINATOR_ADDRESS", "10.0.0.1:8476")
        assert init_distributed() is True
        assert recorded_initialize.calls == [{}]

    def test_explicit_args_take_precedence(self, recorded_initialize,
                                           monkeypatch):
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "ignored:1")
        init_distributed("host0:8476", num_processes=2, process_id=1)
        assert recorded_initialize.calls == [{
            "coordinator_address": "host0:8476",
            "num_processes": 2, "process_id": 1}]

    def test_idempotent_after_bringup(self, recorded_initialize, monkeypatch):
        # Simulate an already-initialized runtime: no second initialize.
        monkeypatch.setattr(
            mesh_mod.jax.distributed, "is_initialized", lambda: True)
        init_distributed("host0:8476", num_processes=2, process_id=0)
        assert recorded_initialize.calls == []


@pytest.mark.slow
class TestTwoProcessSmoke:
    """The multi-process training path, executed for real (not mocked)."""

    NPROC = 2

    def _spawn(self, pid: int, port: int, model: str) -> subprocess.Popen:
        env = dict(os.environ)
        # CPU-only children (a chip belongs to one process), without the
        # parent's 8-virtual-device flag: one CPU device per process makes
        # the global mesh genuinely cross-process.
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ""
        return subprocess.Popen(
            [sys.executable, WORKER, f"127.0.0.1:{port}",
             str(self.NPROC), str(pid), model],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=REPO_ROOT)

    @pytest.mark.parametrize("model", ["mlp", "transformer_episode"])
    def test_sharded_training_across_processes(self, model):
        """Both the MLP family and the flagship episode transformer cross
        the process boundary: for the latter the representative-row trunk
        broadcast and the shared-trunk replay's collectives run over a dp
        mesh spanning two OS processes, which single-process meshes never
        exercise."""
        with socket.socket() as s:  # reserve a free coordinator port
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        procs = [self._spawn(pid, port, model) for pid in range(self.NPROC)]
        outs = []
        try:
            for p in procs:
                out, err = p.communicate(timeout=600)
                assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
                outs.append(json.loads(out.strip().splitlines()[-1]))
        finally:
            # A failed/timed-out rank must not leak its peer: the survivor
            # blocks forever in the gloo/coordinator barrier, holding the
            # port and hanging the run.
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.communicate()
        assert sorted(o["process_id"] for o in outs) == [0, 1]
        for o in outs:
            assert o["process_count"] == self.NPROC
            assert o["num_devices"] == self.NPROC
            assert o["env_steps"] > 0
        # The dp gradient all-reduce crossed the process boundary and both
        # replicas hold identical post-update parameters.
        assert outs[0]["param_sum"] == outs[1]["param_sum"]
        assert outs[0]["env_steps"] == outs[1]["env_steps"]
