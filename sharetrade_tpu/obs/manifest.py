"""Run manifest: the identity card every telemetry consumer needs first.

One ``manifest.json`` per run dir, written at orchestrator construction:
the full config plus a stable hash of it (so two run dirs are comparable at
a glance), the device backend and mesh shape the run actually got, and the
git revision of the code that produced the numbers. Everything is
best-effort — a missing git binary or a detached workdir must not block
training — and written atomically like every other obs artifact.
"""

from __future__ import annotations

import hashlib
import json
import os
import platform
import subprocess
import sys
import time
from typing import Any


def _git_rev() -> str | None:
    try:
        repo_dir = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=repo_dir, timeout=5,
            capture_output=True, text=True)
        return out.stdout.strip() or None if out.returncode == 0 else None
    except Exception:
        return None


def config_hash(cfg: Any) -> str:
    """THE stable 16-char config identity: manifest.json's
    ``config_hash``, the one recipe every run dir is joined on."""
    blob = json.dumps(cfg.to_dict(), sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def build_manifest(cfg: Any, *, mesh: Any = None) -> dict:
    cfg_dict = cfg.to_dict()
    from sharetrade_tpu.utils.runtime_env import owns_devices
    try:
        import jax
        jax_version = jax.__version__
        if owns_devices():
            backend = jax.default_backend()
            device_count = jax.device_count()
        else:
            # A supervising parent (cli fleet without --learner): asking
            # would take the chip from the child that serves on it.
            backend = device_count = None
    except Exception:       # manifest must not force device discovery to work
        backend, device_count, jax_version = None, None, None
    manifest = {
        "created_at": time.time(),
        "config_hash": config_hash(cfg),
        "config": cfg_dict,
        "backend": backend,
        "device_count": device_count,
        "mesh_shape": dict(mesh.shape) if mesh is not None else None,
        "git_rev": _git_rev(),
        "jax_version": jax_version,
        "python_version": sys.version.split()[0],
        "hostname": platform.node(),
        "pid": os.getpid(),
    }
    try:
        # Tuned-knob provenance (tuning.py): which registered knobs ran
        # at default / profile / explicit values, and under which
        # profile + host fingerprint — the ``cli obs`` tuning section's
        # source. Best-effort like the git probe: a vanished profile
        # must not block a run from writing its manifest.
        if hasattr(cfg, "tuning"):
            from sharetrade_tpu.tuning import describe
            manifest["tuning"] = describe(cfg)
    except Exception:
        pass
    return manifest


def write_manifest(path: str, cfg: Any, *, mesh: Any = None) -> dict:
    manifest = build_manifest(cfg, mesh=mesh)
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(manifest, f, indent=2, default=str)
    os.replace(tmp, path)
    return manifest
