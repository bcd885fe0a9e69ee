"""A second model family, for the tests alone: the seam the harness reaches
a configuration's model through is shown by a family that shares no name
with the first. It wraps the episode transformer's reference (the program
it is compared with is that model), but

- its sizes go by names of its own (``depth``, ``width``, ``head_count``),
  so shared code that spelt the first family's would fail here;
- it hands its cache over under another name and rank: keys and values
  stacked and flattened to ``{"kv": (L, W, 2 * H * D)}``;
- it counts its own operations, over its own sizes;
- it hands ``correct`` a further number, ``newest_tick_err``.

Found as ``chipbench.models.stacked_kv`` once ``chipbench_toy.add_families``
has put this directory on that package's path; no file of ``chipbench/`` is
edited for it.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from chipbench.models import episode_transformer as wrapped


def sizes(cfg) -> dict:
    return {"depth": cfg.model.num_layers, "head_count": cfg.model.num_heads,
            "width": cfg.model.num_heads * cfg.model.head_dim}


def _wrapped_sizes(s: dict) -> dict:
    return {**s, "layers": s["depth"], "heads": s["head_count"],
            "head_dim": s["width"] // s["head_count"]}


def history(s: dict) -> int:
    return wrapped.history(_wrapped_sizes(s))


def init_params(key, s):
    return wrapped.init_params(key, _wrapped_sizes(s))


def _stacked(cache: dict) -> dict:
    """{"k", "v"} of (L, H, W, D) -> {"kv": (L, W, 2 * H * D)}."""
    both = jnp.concatenate([cache["k"], cache["v"]], axis=1)
    layers, _, window, _ = both.shape
    return {"kv": both.transpose(0, 2, 1, 3).reshape(layers, window, -1)}


def trunk(params, series, positions, s, quant=None, cache_before=None):
    out = wrapped.trunk(params, series, positions, _wrapped_sizes(s), quant,
                        cache_before)
    if cache_before is None:
        return out
    return out[0], _stacked(out[1])


def program_cache(carry) -> dict:
    return _stacked(wrapped.program_cache(carry))


def further_numbers(program: dict, reference: dict) -> dict:
    """The newest tick's row of the cache alone: the row the next step
    reads first."""
    p, r = (np.asarray(side["cache"]["kv"], np.float64)[:, -1]
            for side in (program, reference))
    return {"newest_tick_err": float(
        np.linalg.norm(p - r) / max(np.linalg.norm(r), 1e-30))}


def serve_warm_step_flops(s: dict) -> float:
    """One tick forward: qkv, projection and the 4x MLP (24 width^2), the
    scores and the mix over ``window`` keys (4 W width), the embedding, the
    wallet's map and the heads."""
    return (s["depth"] * (24.0 * s["width"] ** 2
                          + 4.0 * s["window"] * s["width"])
            + 2.0 * s["width"] * (3 + 3 + s["actions"] + 1))


def train_flops_per_agent_step(s: dict) -> float:
    return wrapped.train_flops_per_agent_step(_wrapped_sizes(s))


def replay_seq_len(s: dict) -> int:
    return wrapped.replay_seq_len(_wrapped_sizes(s))
