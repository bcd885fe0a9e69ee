"""Device mesh construction (replacing the Akka Router fan-out, SURVEY.md §2.2).

The reference's "cluster" is 10 actors in one JVM with remoting stubbed
(build.sbt:13, README.md:13). Here scale-out is a named ``jax.sharding.Mesh``:
axes dp/tp/sp/pp/ep are declared up front and shardings annotate how each
tensor spreads over them; XLA inserts the ICI/DCN collectives (scaling-book
recipe: pick a mesh, annotate, let the compiler place communication).
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh

from sharetrade_tpu.config import ParallelConfig
from sharetrade_tpu.utils.logging import get_logger

log = get_logger("parallel.mesh")

AXIS_ORDER = ("dp", "tp", "sp", "pp", "ep")


def mesh_platform(mesh: Mesh) -> str:
    """THE platform probe for a mesh ("cpu" | "tpu" | "gpu" | ...).

    One definition so every platform-keyed carve-out — the CPU no-donation
    seam in ``parallel/sharding.py``, the Pallas-kernel gate in
    ``models/__init__.py`` — keys off the same predicate and can never
    drift (a probe that checked ``jax.default_backend()`` instead of the
    MESH's devices would misfire exactly on the forced-8-device host
    platform the shard audit and the multichip dryrun run on)."""
    return next(iter(mesh.devices.flat)).platform


def is_cpu_mesh(mesh: Mesh) -> bool:
    """True when the mesh is backed by (possibly virtual) CPU devices —
    the forced-8-device host platform of tests/the shard audit, or the
    orchestrator's CPU fallback."""
    return mesh_platform(mesh) == "cpu"


#: Mesh axes whose code paths run shard_map-partitioned programs (sp
#: sequence parallelism, ep expert dispatch) — the axes that can propagate
#: a transposed-mesh spec back onto dp-sharded state.
SHARD_MAP_AXES = ("sp", "ep")


def has_shard_map_axis(mesh: Mesh | None) -> bool:
    """THE scope predicate for the round-8 replicate seams (PPO's
    rollout→update seam, the episode transformer's carry→series pin):
    True when the mesh carries a >1-sized shard_map axis. One definition
    so the two seams can never silently diverge; meshes without such an
    axis compile the permuted gathers clean already and must keep their
    exact (byte-identical) programs."""
    return (mesh is not None
            and any(dict(mesh.shape).get(a, 1) > 1 for a in SHARD_MAP_AXES))


def build_mesh(cfg: ParallelConfig | None = None, devices=None) -> Mesh:
    """Build a mesh from ``cfg.mesh_shape`` (e.g. ``{"dp": 4, "tp": 2}``).

    Empty/missing shape puts every device on the data axis — the moral
    equivalent of the reference's "all workers under one broadcast router".
    Axis sizes must multiply to the device count (a partial mesh would
    silently idle chips).
    """
    cfg = cfg or ParallelConfig()
    if devices is None:
        devices = jax.devices()
    devices = np.asarray(devices)

    shape = dict(cfg.mesh_shape) if cfg.mesh_shape else {}
    if not shape:
        shape = {cfg.data_axis: devices.size}
    names = [a for a in AXIS_ORDER if shape.get(a, 1) > 1]
    if not names:
        names = [cfg.data_axis]
    sizes = [shape.get(a, 1) for a in names]
    total = int(np.prod(sizes))
    if total != devices.size:
        raise ValueError(
            f"mesh shape {dict(zip(names, sizes))} needs {total} devices, "
            f"got {devices.size}")
    mesh = Mesh(devices.reshape(sizes), tuple(names))
    log.info("mesh %s over %d devices", dict(zip(names, sizes)), devices.size)
    return mesh


def init_distributed(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None,
                     cpu_collectives: str | None = None) -> bool:
    """Multi-host bring-up (the reference's never-built Akka Cluster tier,
    README.md:13, build.sbt:13 akka-remote on the classpath but dormant).

    Three tiers, in precedence order:

    1. Explicit args — manual bring-up on any cluster:
       ``init_distributed("host0:8476", num_processes=2, process_id=i)``
       on every host, then ``build_mesh`` sees the GLOBAL device set and
       shardings spanning hosts ride DCN (jax inserts the cross-host
       collectives; lay dp over hosts, tp/sp within a host so the heavy
       collectives stay on ICI).
    2. Env-gated — ``JAX_COORDINATOR_ADDRESS`` (set by TPU pod runtimes and
       GKE) or ``MEGASCALE_COORDINATOR_ADDRESS``: ``jax.distributed
       .initialize()`` discovers everything from the environment.
    3. No-op — single-process: returns whether jax already reports multiple
       processes.

    ``cpu_collectives`` selects the CPU cross-process collective backend
    ("gloo" or "mpi") — on TPU the collectives ride ICI/DCN and this is
    unused, but it makes the multi-process path runnable (and tested,
    tests/test_distributed.py::TestTwoProcessSmoke) on CPU-only hosts.

    Returns True when running multi-process. Idempotent: a second call after
    successful bring-up is a no-op (jax raises on double-initialize).
    """
    import os
    if jax.distributed.is_initialized():
        return jax.process_count() > 1
    if cpu_collectives is not None:
        jax.config.update("jax_cpu_collectives_implementation", cpu_collectives)
    if coordinator_address is not None:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
        log.info("distributed: process %d of %d (explicit coordinator %s)",
                 jax.process_index(), jax.process_count(), coordinator_address)
        return jax.process_count() > 1
    if os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
            "MEGASCALE_COORDINATOR_ADDRESS"):
        jax.distributed.initialize()
        log.info("distributed: process %d of %d",
                 jax.process_index(), jax.process_count())
        return True
    return jax.process_count() > 1
