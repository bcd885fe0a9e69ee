"""Self-tuning runtime: the knob registry and the per-host tuned profile.

Every performance lever in this stack used to be a hand-set constant —
``runtime.megachunk_factor``, ``runtime.pipeline_depth``,
``serve.batch_timeout_ms``, ``serve.max_batch``, ``serve.max_queue``,
``distrib.ingest_every_updates`` — while every signal needed to SET them
is already a live gauge (roofline MFU/AI, dispatch-gap spans,
``serve_overload``/occupancy/windowed p99 histograms, actor-ingest
rows/s). This module is the seam that closes that loop (ROADMAP item 5):

- **KNOBS** — the registry of tunable performance knobs: dotted config
  path, tier (``train``/``serve``/``distrib``), and bounds metadata. A
  knob not in this registry is a constant; a knob IN it must be read
  through this layer (tools/lint_hot_loop.py check 13 guards serve/ and
  runtime/ against fresh hard-coded shadows).
- **tuned profile** — ``tools/autotune.py`` sweeps the registry's knobs
  with a seeded successive-halving search over short measured windows and
  writes a schema-versioned, per-host ``tuned_profile.json`` (atomic
  rename; host fingerprint: cores, backend, device count). ``config.py``
  loads it through the ``tuning.profile`` knob.
- **precedence** — EXPLICIT config always wins over the profile, the
  profile wins over defaults (:func:`apply_profile`); a field counts as
  explicit when its value differs from the dataclass default, so a
  profile can never silently override an operator's decision. Provenance
  (:func:`describe`) is stamped into the run manifest and surfaced by
  ``cli obs``.
- **fingerprint contract** — a profile measured on a different host
  shape (cores/backend/device count) is refused LOUDLY
  (:class:`ProfileError`), never silently applied; the escape hatch is
  the explicit ``tuning.allow_fingerprint_mismatch`` knob.

The ONLINE half of the loop lives next door: ``serve/controller.py``
adapts ``serve.batch_timeout_ms``/``serve.max_queue`` against the
engine's own windowed latency histogram, and the orchestrator adapts the
learner-ingest cadence (``runtime/orchestrator.py`` — the
``tuning.adaptive_ingest`` knob). Both treat the CONFIGURED values as
ceilings: the online controllers only ever tighten below what the
operator (or the offline profile) allowed, so the PR-10/PR-12 safety
rails (queue bounds, shed accounting, supervision) are never fought, only
tracked.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any

from sharetrade_tpu.config import ConfigError, FrameworkConfig
from sharetrade_tpu.utils.logging import get_logger

log = get_logger("tuning")

#: Version of the tuned-profile schema. Bump on layout changes; a
#: mismatched profile is refused loudly (never best-effort-parsed: a
#: half-understood profile silently mis-tunes every run that loads it).
PROFILE_SCHEMA_VERSION = 1


class ProfileError(ConfigError):
    """A tuned profile that must not be applied: unreadable, wrong
    schema version, unknown knobs, or a host-fingerprint mismatch.
    Subclasses :class:`ConfigError` so the supervision decider maps it to
    STOP — re-running cannot make a foreign profile fit this host."""


@dataclass(frozen=True)
class Knob:
    """One registered tunable: the dotted config path is its identity
    (the profile file's key, the manifest's knob-vector key, and the
    lint's shadow-detection leaf)."""

    path: str           # dotted config path, e.g. "serve.batch_timeout_ms"
    tier: str           # "train" | "serve" | "distrib"
    kind: type          # int | float
    description: str


#: THE registry. Order is presentation order (cli obs, profiles).
KNOBS: tuple[Knob, ...] = (
    Knob("runtime.megachunk_factor", "train", int,
         "chunks fused into one jitted program (dispatch-floor lever)"),
    Knob("runtime.pipeline_depth", "train", int,
         "async-readback boundaries in flight (HBM vs stall tradeoff)"),
    Knob("serve.max_batch", "serve", int,
         "padded device batch per serving tick"),
    Knob("serve.batch_timeout_ms", "serve", float,
         "partial-batch coalescing deadline"),
    Knob("serve.max_queue", "serve", int,
         "bounded ingress depth (queueing-delay vs shed-rate tradeoff)"),
    Knob("distrib.ingest_every_updates", "distrib", int,
         "learner-ingest cadence over the actor feeds"),
    Knob("distrib.ingest_max_rows", "distrib", int,
         "per-tick per-actor ingest row bound (0 = replay capacity)"),
)

_KNOBS_BY_PATH = {k.path: k for k in KNOBS}

#: Fingerprint fields that must MATCH for a profile to apply: a sweep
#: tuned for 2 cores or a TPU backend is wrong (not just stale) on any
#: other shape. Informational fields (hostname, jax version) ride along
#: in the profile but never gate.
_FINGERPRINT_MATCH_KEYS = ("cpu_count", "backend", "device_count")


def host_fingerprint() -> dict:
    """This host's identity as the autotuner sees it. Backend probing is
    best-effort (a profile written where jax could not initialize carries
    ``None`` and only matches hosts in the same state)."""
    from sharetrade_tpu.utils.runtime_env import owns_devices
    backend = device_count = None
    # A supervising parent must not take the chip to fingerprint it; its
    # device-owning children gate the profile themselves.
    if owns_devices():
        try:
            import jax
            backend = jax.default_backend()
            device_count = jax.device_count()
        except Exception:   # fingerprinting must never block a run
            pass
    import platform
    return {
        "cpu_count": os.cpu_count(),
        "backend": backend,
        "device_count": device_count,
        "machine": platform.machine(),
        "hostname": platform.node(),
    }


def get_knob(cfg: FrameworkConfig, path: str) -> Any:
    """Read a dotted knob off a config tree."""
    target: Any = cfg
    for part in path.split("."):
        target = getattr(target, part)
    return target


def set_knob(cfg: FrameworkConfig, path: str, value: Any) -> None:
    """Write a dotted knob into a config tree (in place)."""
    *sections, leaf = path.split(".")
    target: Any = cfg
    for part in sections:
        target = getattr(target, part)
    setattr(target, leaf, value)


def knob_vector(cfg: FrameworkConfig) -> dict[str, Any]:
    """The RESOLVED value of every registered knob — what a run actually
    executed under, so autotune trials and run dirs join on actual knob
    values, not just ``config_hash``."""
    return {k.path: get_knob(cfg, k.path) for k in KNOBS}


_DEFAULTS: dict[str, Any] | None = None


def default_knob_values() -> dict[str, Any]:
    """Registry knob values of a pristine :class:`FrameworkConfig` — the
    baseline the explicit-vs-default precedence test compares against."""
    global _DEFAULTS
    if _DEFAULTS is None:
        _DEFAULTS = knob_vector(FrameworkConfig())
    return dict(_DEFAULTS)


# ---------------------------------------------------------------------------
# profile file IO
# ---------------------------------------------------------------------------


def build_profile(knobs: dict[str, Any], *, objectives: dict | None = None,
                  trials: list | None = None, seed: int | None = None,
                  config_hash: str | None = None,
                  notes: str | None = None) -> dict:
    """Assemble a profile document (the autotuner's output). ``knobs``
    keys must be registered dotted paths — a typo'd knob must fail at
    WRITE time, where the author is watching, not at every later load."""
    unknown = sorted(set(knobs) - set(_KNOBS_BY_PATH))
    if unknown:
        raise ProfileError(
            f"unregistered knob(s) {unknown}; the registry "
            f"(sharetrade_tpu/tuning.py KNOBS) is the contract")
    coerced = {}
    for path, value in knobs.items():
        coerced[path] = _KNOBS_BY_PATH[path].kind(value)
    doc = {
        "schema_version": PROFILE_SCHEMA_VERSION,
        "fingerprint": host_fingerprint(),
        "knobs": coerced,
    }
    if objectives:
        doc["objectives"] = objectives
    if trials:
        doc["trials"] = trials
    if seed is not None:
        doc["seed"] = seed
    if config_hash:
        doc["config_hash"] = config_hash
    if notes:
        doc["notes"] = notes
    return doc


def write_profile(path: str, profile: dict) -> dict:
    """Atomically publish a profile document (tmp + rename — a crashed
    autotune run must never leave a torn profile a later training run
    would half-parse). Durability-fsync is deliberately NOT needed here:
    a lost profile after power loss re-tunes; a torn one mis-tunes."""
    if profile.get("schema_version") != PROFILE_SCHEMA_VERSION:
        raise ProfileError(
            f"refusing to write schema_version="
            f"{profile.get('schema_version')!r} (writer is "
            f"{PROFILE_SCHEMA_VERSION})")
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(profile, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    return profile


def load_profile(path: str) -> dict:
    """Read + validate a tuned profile. Loud on every failure mode: a
    missing/torn/mis-versioned/unknown-knob profile raises
    :class:`ProfileError` instead of degrading to defaults silently —
    an operator who POINTED at a profile wants to know it didn't load."""
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except FileNotFoundError:
        raise ProfileError(f"tuned profile not found: {path}") from None
    except (OSError, json.JSONDecodeError) as exc:
        raise ProfileError(f"tuned profile {path} unreadable: {exc}") from exc
    if not isinstance(doc, dict) or "knobs" not in doc:
        raise ProfileError(f"tuned profile {path} has no 'knobs' object")
    if doc.get("schema_version") != PROFILE_SCHEMA_VERSION:
        raise ProfileError(
            f"tuned profile {path} schema_version="
            f"{doc.get('schema_version')!r} != {PROFILE_SCHEMA_VERSION}; "
            "re-run tools/autotune.py")
    unknown = sorted(set(doc["knobs"]) - set(_KNOBS_BY_PATH))
    if unknown:
        raise ProfileError(
            f"tuned profile {path} carries unregistered knob(s) {unknown}")
    return doc


def fingerprint_mismatches(profile_fp: dict | None,
                           fp: dict | None = None) -> list[str]:
    """Which gating fingerprint fields disagree between a profile and
    this host (empty = the profile applies here)."""
    if not isinstance(profile_fp, dict):
        return list(_FINGERPRINT_MATCH_KEYS)
    from sharetrade_tpu.utils.runtime_env import owns_devices
    fp = fp or host_fingerprint()
    keys = (_FINGERPRINT_MATCH_KEYS if owns_devices()
            else ("cpu_count",))    # a supervisor cannot see the devices
    return [k for k in keys if profile_fp.get(k) != fp.get(k)]


# ---------------------------------------------------------------------------
# precedence: explicit config > profile > default
# ---------------------------------------------------------------------------


def apply_profile(cfg: FrameworkConfig, *, path: str | None = None
                  ) -> FrameworkConfig:
    """Resolve the config's registered knobs against its tuned profile.

    No-op (returns ``cfg`` unchanged) when ``tuning.profile`` is unset.
    Otherwise returns a NEW config where every registry knob still at its
    dataclass default takes the profile's value; knobs the operator set
    explicitly are untouched — explicit config always wins. "Explicit"
    means: the value differs from the dataclass default, OR the dotted
    path was applied through ``apply_overrides`` (its
    ``_explicit_overrides`` memo — so ``--set serve.max_queue=1024``
    pins the knob even when 1024 IS the default). The one remaining
    blind spot: a config FILE carrying a knob at its default value reads
    as default (file loading keeps no explicitness memo). Idempotent:
    re-applying sees the profile values as "explicit" and changes
    nothing, so cli bootstrap and the Orchestrator can both call it
    safely.

    Raises :class:`ProfileError` on a missing/invalid profile or a
    host-fingerprint mismatch (``tuning.allow_fingerprint_mismatch``
    downgrades the mismatch to a warning — for deliberately shipping one
    host's profile to a fleet of identical-enough machines)."""
    path = path if path is not None else getattr(cfg.tuning, "profile", None)
    if not path:
        return cfg
    profile = load_profile(path)
    mismatches = fingerprint_mismatches(profile.get("fingerprint"))
    if mismatches:
        fp = host_fingerprint()
        detail = ", ".join(
            f"{k}: profile={profile.get('fingerprint', {}).get(k)!r} "
            f"host={fp.get(k)!r}" for k in mismatches)
        if not cfg.tuning.allow_fingerprint_mismatch:
            raise ProfileError(
                f"tuned profile {path} was measured on a different host "
                f"shape ({detail}); re-run tools/autotune.py here, or set "
                "tuning.allow_fingerprint_mismatch=true to apply it "
                "anyway")
        log.warning("applying tuned profile %s despite fingerprint "
                    "mismatch (%s): tuning.allow_fingerprint_mismatch",
                    path, detail)
    defaults = default_knob_values()
    explicit = frozenset(getattr(cfg, "_explicit_overrides", ()))
    new = FrameworkConfig.from_dict(cfg.to_dict())
    new._explicit_overrides = explicit      # survives re-application
    applied: dict[str, Any] = {}
    for kpath, value in profile["knobs"].items():
        if kpath in explicit or get_knob(cfg, kpath) != defaults[kpath]:
            continue            # explicit config wins
        value = _KNOBS_BY_PATH[kpath].kind(value)
        set_knob(new, kpath, value)
        applied[kpath] = value
    if applied:
        log.info("tuned profile %s applied: %s", path,
                 ", ".join(f"{k}={v}" for k, v in sorted(applied.items())))
    return new


def describe(cfg: FrameworkConfig) -> dict:
    """Provenance of every registered knob under ``cfg`` — the run
    manifest's ``tuning`` block and the ``cli obs`` tuning section.

    Deterministic re-derivation (no hidden state): re-loads the profile
    named by the config and recomputes the same precedence
    :func:`apply_profile` used. Best-effort on the profile read — a
    manifest write must never fail because a profile went missing after
    bring-up; the error is recorded instead."""
    defaults = default_knob_values()
    path = getattr(cfg.tuning, "profile", None)
    profile_knobs: dict[str, Any] = {}
    out: dict[str, Any] = {
        "profile": path,
        "fingerprint": host_fingerprint(),
    }
    if path:
        try:
            profile = load_profile(path)
            profile_knobs = profile["knobs"]
            out["profile_fingerprint"] = profile.get("fingerprint")
            out["profile_mismatches"] = fingerprint_mismatches(
                profile.get("fingerprint"))
        except ProfileError as exc:
            out["profile_error"] = str(exc)
    explicit = frozenset(getattr(cfg, "_explicit_overrides", ()))
    knobs: dict[str, dict] = {}
    for knob in KNOBS:
        value = get_knob(cfg, knob.path)
        if knob.path in explicit:
            source = "explicit"     # a --set pin, even at default value
        elif value != defaults[knob.path]:
            source = ("profile"
                      if (knob.path in profile_knobs
                          and knob.kind(profile_knobs[knob.path]) == value)
                      else "explicit")
        else:
            source = "default"
        knobs[knob.path] = {
            "value": value,
            "default": defaults[knob.path],
            "source": source,
            "tier": knob.tier,
        }
    out["knobs"] = knobs
    return out
