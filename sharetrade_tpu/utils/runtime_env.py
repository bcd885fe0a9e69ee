"""Process-level JAX environment: where compiled programs are cached and
which device a run's numbers belong to.

One definition of each, shared by every entry point (``cli.main``,
``chip_smoke.py``, ``tests/conftest.py``), so no record can
pass a CPU number off as a chip number and no two entry points disagree
about the cache's location (the path is part of the cache key — a
directory that moves never hits).
"""

from __future__ import annotations

import contextlib
import os

#: Fixed fallback location of the persistent compile cache: inside the
#: checkout (git-ignored), never derived from a temp name, pid, uid or time.
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Place JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, nothing is set in code —
    jax reads the variable itself, and a ``jax.config.update`` here would
    override it. Otherwise the cache lives at ``<checkout>/.jax_cache``.
    Touches ``jax.config`` only: no backend is initialized, so a parent
    that merely supervises children stays off the chip."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE


def device_block() -> dict:
    """``{"platform", "device_kind", "count"}`` exactly as JAX reports the
    devices this process computes on. Initializes the backend — call it
    only from a process that owns (or is about to own) the device."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# One process for each chip
# ---------------------------------------------------------------------------
# A chip belongs to one process at a time: a parent that has touched JAX
# holds it, and a child that needs it then fails or hangs. A JAX process
# takes every chip its host shows (binding a worker to one chip of several
# is ROADMAP W2/W6), so on a host with chips exactly one process of a
# topology may compute, and a process that only supervises must stay off
# the backend altogether.

_supervises_only = False


@contextlib.contextmanager
def supervising_only():
    """Run a command as a supervisor of device-owning children: inside,
    :func:`owns_devices` is False, and the best-effort backend probes (obs
    manifest, tuning fingerprint) record "no device" instead of
    initializing a backend — which would take the chip from the child."""
    global _supervises_only
    prev, _supervises_only = _supervises_only, True
    try:
        yield
    finally:
        _supervises_only = prev


def owns_devices() -> bool:
    return not _supervises_only


def host_chip_count() -> int:
    """TPU chips this host shows, counted WITHOUT touching JAX (asking jax
    would take them). 0 where ``JAX_PLATFORMS`` keeps children off the TPU
    (the CPU test tier) or the host has no accelerator device files."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.lower().split(","):
        return 0
    import glob
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def device_process_refusal(processes: int, topology: str) -> str | None:
    """The one message a command refuses to start with when its topology's
    device-owning processes cannot all have a chip — instead of leaving
    children in STARTING until a timeout. None on a host without chips
    (the CPU backend: the tests' multi-process fleets keep working)."""
    chips = host_chip_count()
    if not chips or processes <= 1:
        return None
    return (f"{topology} needs {processes} device-owning processes, but "
            f"this host shows {chips} TPU chip(s) and every JAX process "
            "takes all of them (a chip belongs to one process; binding "
            "workers to chips is not built yet — ROADMAP W2/W6). Run one "
            "device-owning process here, or set JAX_PLATFORMS=cpu.")
