"""Sequence-parallel banded attention for the episode-mode transformer.

Ring attention (parallel/ring_attention.py) rotates FULL K/V shards all the
way around the sp axis because causal attention can reach arbitrarily far
back. Banded attention can't: a query's band covers exactly ``window`` keys,
so with the tick sequence sharded over sp (shard length >= window-1) the
band crosses AT MOST ONE shard boundary. The whole exchange collapses to a
single ``ppermute`` of the previous shard's last ``window-1`` K/V rows — a
halo exchange, the cheapest possible sequence-parallel communication
pattern (one neighbor hop on ICI instead of sp-1 rotations).

Alignment trick: after attaching the halo the local keys are
``[halo(window-1) | local(S)]`` while queries are the local S rows. Left-
padding the queries with ``window-1`` zero rows restores ``q_len == kv_len``
with query row j aligned to key row j, and the ordinary causal+banded flash
kernel (ops/attention.py ``local_window``) computes exactly the halo-band
semantics; the pad rows' outputs are sliced off.

Shard 0 has no predecessor: its ``ppermute`` destination is unwritten and
arrives as ZEROS. Zero keys would still receive softmax weight (score 0,
not -inf), so shard 0's first ``window-1`` outputs are CORRECTED exactly:
those queries' bands lie entirely inside the local prefix (query j < w-1
attends keys 0..j), so one small causal pass over the first ``window-1``
local rows computes their true outputs, selected by ``axis_index == 0``.
The function is therefore exact for any caller — not just ones (like
models/transformer_episode.py) whose leading positions are never read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from sharetrade_tpu.config import ConfigError

from sharetrade_tpu.ops.attention import flash_attention


def halo_banded_attention_sharded(mesh: Mesh, *, seq_axis: str = "sp",
                                  batch_axis: str | None = None,
                                  use_pallas: bool | None = None):
    """Build ``fn(q, k, v, window) -> out`` attending a banded causal mask
    with the sequence dim sharded over ``mesh``'s ``seq_axis``.

    Shapes are (batch, heads, seq, head_dim); ``batch_axis`` optionally
    shards the batch dim (usually "dp"). The sequence is padded up to a
    multiple of the sp size with zero rows — trailing pad positions are
    later than every real query, so causality keeps them invisible.
    """
    n = mesh.shape[seq_axis]

    def attend(q, k, v, window: int):
        if n == 1 or window == 1:
            # One shard, or a 1-wide band (each query attends only itself:
            # the halo is empty and kl[:, :, -0:] would grab the WHOLE
            # shard) — the local kernel is exact either way.
            return flash_attention(q, k, v, causal=True, local_window=window,
                                   use_pallas=use_pallas)
        seq = q.shape[2]
        pad = (-seq) % n
        if pad:
            widths = [(0, 0), (0, 0), (0, pad), (0, 0)]
            q, k, v = (jnp.pad(x, widths) for x in (q, k, v))
        if (seq + pad) // n < window - 1:
            raise ConfigError(
                f"sp shard length {(seq + pad) // n} < window-1 "
                f"({window - 1}); the halo band would span multiple shards "
                f"— use fewer sp shards or longer unrolls")

        b_axis = batch_axis
        if b_axis is not None and q.shape[0] % mesh.shape[b_axis]:
            b_axis = None   # odd batch (e.g. 1-agent minibatch): replicate
        spec = P(b_axis, None, seq_axis, None)

        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=False)
        def sharded(ql, kl, vl):
            halo = window - 1
            perm = [(i, i + 1) for i in range(n - 1)]  # no wrap: shard 0 -> zeros
            halo_k = jax.lax.ppermute(kl[:, :, -halo:], seq_axis, perm)
            halo_v = jax.lax.ppermute(vl[:, :, -halo:], seq_axis, perm)
            kv_k = jnp.concatenate([halo_k, kl], axis=2)
            kv_v = jnp.concatenate([halo_v, vl], axis=2)
            qp = jnp.pad(ql, [(0, 0), (0, 0), (halo, 0), (0, 0)])
            out = flash_attention(qp, kv_k, kv_v, causal=True,
                                  local_window=window, use_pallas=use_pallas)
            out = out[:, :, halo:]
            # Shard 0's zero-filled halo rows would otherwise take softmax
            # weight (score 0, not -inf) in its first `halo` outputs. Those
            # queries' true bands sit entirely inside the local prefix
            # (query j < window-1 attends keys 0..j), so a small plain-causal
            # pass over the first `halo` local rows is their exact answer.
            # O(window^2) per shard vs the O(S*window) main pass; computed
            # everywhere, used only where axis_index == 0.
            head_exact = flash_attention(
                ql[:, :, :halo], kl[:, :, :halo], vl[:, :, :halo],
                causal=True, use_pallas=use_pallas)
            first = (jax.lax.axis_index(seq_axis) == 0)
            head = jnp.where(first, head_exact, out[:, :, :halo])
            return jnp.concatenate([head, out[:, :, halo:]], axis=2)

        out = sharded(q, k, v)
        return out[:, :, :seq] if pad else out

    return attend
