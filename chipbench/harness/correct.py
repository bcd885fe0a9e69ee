"""The comparison that decides ``correct``: numbers from the timed path
against the plain reference, each held to a limit of its own from
``chipbench/limits/<cell>.json`` (how each was set: PERF.md, section 2)."""

from __future__ import annotations

import math
import statistics


def relative_gap(value: float, reference: float) -> float:
    if not (math.isfinite(value) and math.isfinite(reference)):
        return float("inf")
    return abs(value - reference) / max(abs(reference), 1e-30)


def worst_leaf_gap(program: dict, reference: dict,
                   keep: dict | None = None) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger. ``keep`` drops the leaves it marks False."""
    names = [n for n in reference if keep is None or keep[n]]
    floor = statistics.median(reference[n] for n in names)
    worst = 0.0
    for n in names:
        value, ref = program[n], reference[n]
        if not (math.isfinite(value) and math.isfinite(ref)):
            return float("inf")
        worst = max(worst, abs(value - ref) / max(ref, floor, 1e-30))
    return worst


def moved_leaves(reference_grad: dict) -> dict:
    """Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone: they are left out of the change."""
    floor = statistics.median(reference_grad.values()) * 1e-3
    return {n: g >= floor for n, g in reference_grad.items()}


def median_leaf_gap(program: dict, reference: dict,
                    keep: dict | None = None) -> float:
    """The median over the leaves of the gap between the program's norm of
    a leaf and the reference's, against the reference's norm of that leaf:
    steady where one small leaf is noisy; a state left unchanged reads 1."""
    names = [n for n in reference if keep is None or keep[n]]
    gaps = []
    for n in names:
        value, ref = program[n], reference[n]
        if not (math.isfinite(value) and math.isfinite(ref)):
            return float("inf")
        gaps.append(abs(value - ref) / max(ref, 1e-30))
    return statistics.median(gaps)


def cache_errors(program: dict, reference: dict) -> dict:
    """{name: per-layer array} of the norm of the difference between the
    program's cache and the reference's against the reference's norm. The
    named arrays are the model's own (``{"k", "v"}`` of (L, H, W, D) for
    the episode transformer), the layer axis first, in tick order; a name
    the program's side lacks reads infinite."""
    import numpy as np
    out = {}
    for name, ref in reference.items():
        if name not in program:
            out[name] = np.asarray([np.inf])
            continue
        p, r = (np.asarray(x, np.float64) for x in (program[name], ref))
        axes = tuple(range(1, r.ndim))
        err = np.sqrt(np.sum(np.square(p - r), axis=axes))
        out[name] = err / np.maximum(
            np.sqrt(np.sum(np.square(r), axis=axes)), 1e-30)
    return out


def cache_error(program: dict, reference: dict) -> float:
    """The widest of ``cache_errors`` over the names and the layers (a NaN
    anywhere reads NaN). It is the first chunk's rollout trunk alone, before
    any update: the one number here that the precision moves and the
    training's fork does not."""
    import numpy as np
    return float(np.max([np.max(by_layer) for by_layer in cache_errors(
        program, reference).values()]))


def shares_gap(program, reference) -> float:
    """The mean over the agents of the distance between the shares an agent
    holds after the first chunk and the reference's agent's, against the
    reference's mean holding: an agent left out, or actions altered where
    they are sampled, move it; so do the few actions a rounding flips."""
    import numpy as np
    p, r = np.asarray(program, np.float64), np.asarray(reference, np.float64)
    if not np.all(np.isfinite(p)):
        return float("inf")
    return float(np.mean(np.abs(p - r)) / max(np.mean(np.abs(r)), 1.0))


def training_numbers(program: dict, reference: dict, model) -> dict:
    """``program`` and ``reference``: ``losses`` (one per step), ``grad``
    and ``change`` ({leaf: norm}), and after the first step ``cache`` (the
    rolling cache, the model's named arrays) and ``shares`` (per agent). The
    later steps' losses are not among the numbers: this configuration's
    training forks after its first chunk (PERF.md, section 2), so the first
    step's loss stands for them. ``model.further_numbers`` adds what the
    family compares besides; ``judge`` holds a number only where the cell's
    limits name it."""
    moved = moved_leaves(reference["grad"])
    return {
        "kv_err": cache_error(program["cache"], reference["cache"]),
        "shares_gap": shares_gap(program["shares"], reference["shares"]),
        "loss_step1": relative_gap(program["losses"][0],
                                   reference["losses"][0]),
        "grad_median_gap": median_leaf_gap(program["grad"],
                                           reference["grad"]),
        "change_median_gap": median_leaf_gap(
            program["change"], reference["change"], keep=moved),
        "grad_worst_gap": worst_leaf_gap(program["grad"], reference["grad"]),
        "change_worst_gap": worst_leaf_gap(
            program["change"], reference["change"], keep=moved),
        **model.further_numbers(program, reference)}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: (value, limit)}) over the numbers that have a
    limit; a number without one is reported with limit None and not held."""
    compared, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        compared[name] = (value, limit)
        if limit is not None and not value <= limit:
            ok = False
    missing = [n for n in limits if n not in numbers]
    if missing:
        ok = False
        for n in missing:
            compared[n] = (None, limits[n])
    return ok, compared


def flat_norms(tree) -> dict:
    """{path: float} of a pytree of scalar norms."""
    import jax
    leaves = jax.tree_util.tree_leaves_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(path): float(v) for path, v in leaves}
