"""Process-level JAX environment (sharetrade_tpu/utils/runtime_env.py):
where the compile cache lives, which device a run names, and the
one-process-for-each-chip rules of the multi-process entry points."""

import json
import os

import jax
import pytest

from sharetrade_tpu import cli
from sharetrade_tpu.config import FrameworkConfig
from sharetrade_tpu.utils import runtime_env


class TestCompileCache:
    def test_env_var_wins_and_nothing_is_set_in_code(self, monkeypatch,
                                                     tmp_path):
        placed = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        before = jax.config.jax_compilation_cache_dir
        assert runtime_env.configure_compile_cache() == placed
        # jax reads the variable itself; a config.update would override it.
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_inside_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            got = runtime_env.configure_compile_cache()
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_no_cache_path_from_temp_pid_uid_or_time(self):
        """The path is part of the cache key: one made from a temp name, a
        pid, a uid or a time never hits."""
        import re
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        offenders = []
        for root, dirs, files in os.walk(repo):
            dirs[:] = [d for d in dirs
                       if not d.startswith((".", "_")) and d != "chiprun_out"]
            for name in files:
                if not name.endswith(".py") or name == "test_runtime_env.py":
                    continue
                path = os.path.join(root, name)
                for i, line in enumerate(open(path, encoding="utf-8"), 1):
                    if ("compilation_cache_dir" in line
                            and re.search(r"gettempdir|getpid|getuid|time\.",
                                          line)):
                        offenders.append(f"{path}:{i}")
        assert offenders == []


def test_device_block_names_what_jax_reports():
    block = runtime_env.device_block()
    assert block == {"platform": jax.devices()[0].platform,
                     "device_kind": jax.devices()[0].device_kind,
                     "count": len(jax.devices())}


class TestOneProcessForEachChip:
    def test_cpu_pinned_host_shows_no_chips(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert runtime_env.host_chip_count() == 0
        # ... so multi-process topologies are never refused on the CPU tier.
        assert runtime_env.device_process_refusal(5, "anything") is None

    def test_chips_counted_from_device_files_not_jax(self, monkeypatch):
        import glob
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(
            glob, "glob",
            lambda pat: (["/dev/vfio/0", "/dev/vfio/1"]
                         if pat.startswith("/dev/vfio") else []))
        assert runtime_env.host_chip_count() == 2

    def test_second_device_process_is_refused_in_one_message(self,
                                                             monkeypatch):
        monkeypatch.setattr(runtime_env, "host_chip_count", lambda: 4)
        assert runtime_env.device_process_refusal(1, "cli fleet (1)") is None
        message = runtime_env.device_process_refusal(2, "cli fleet (2)")
        assert "cli fleet (2) needs 2 device-owning processes" in message
        assert "shows 4 TPU chip(s)" in message

    @pytest.mark.parametrize("argv", [
        ["fleet", "--engines", "2"],
        ["fleet", "--engines", "1", "--learner",
         "--set", "learner.algo=dqn"],
        ["fleet", "--engines", "1", "--autoscale",
         "--set", "fleet.max_engines=2"],
        ["learner", "--set", "learner.algo=dqn",
         "--set", "distrib.num_actors=2"],
    ], ids=["engines>1", "learner+engine", "autoscale>1", "learner+actors"])
    def test_cli_refuses_at_start_on_a_chip_host(self, monkeypatch, tmp_path,
                                                 argv):
        """On a host that shows chips, a topology with more than one
        device-owning process exits 1 at once — nothing is spawned, no
        child waits in STARTING for a timeout."""
        monkeypatch.chdir(tmp_path)
        monkeypatch.setattr(runtime_env, "host_chip_count", lambda: 1)
        assert cli.main(argv) == 1
        assert not (tmp_path / "fleet").exists()
        assert not (tmp_path / "actors").exists()

    def test_supervisor_records_no_device_and_restores(self, tmp_path):
        from sharetrade_tpu.obs.manifest import build_manifest
        from sharetrade_tpu.tuning import (fingerprint_mismatches,
                                           host_fingerprint)
        cfg = FrameworkConfig()
        assert runtime_env.owns_devices()
        with runtime_env.supervising_only():
            assert not runtime_env.owns_devices()
            manifest = build_manifest(cfg)
            assert manifest["backend"] is None
            assert manifest["device_count"] is None
            fp = host_fingerprint()
            assert fp["backend"] is None and fp["device_count"] is None
            # A profile tuned where the devices were visible still applies:
            # the supervisor's device-owning children gate it themselves.
            profile_fp = dict(fp, backend="tpu", device_count=1)
            assert fingerprint_mismatches(profile_fp) == []
        assert runtime_env.owns_devices()
        assert build_manifest(cfg)["backend"] == jax.default_backend()
        assert json.dumps(build_manifest(cfg))  # still serializable
