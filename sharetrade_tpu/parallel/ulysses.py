"""Ulysses-style sequence parallelism: all_to_all head↔sequence re-partition.

The second long-context scheme next to ring attention (absent from the
reference, which only slides a 201-price window — SURVEY.md §5). Inputs
arrive sequence-sharded over ``sp`` like the ring's; two all_to_alls
re-partition them so each device holds H/S *heads* with the FULL sequence,
runs ordinary local attention — on TPU, the Pallas flash kernel unchanged
(sharetrade_tpu/ops/attention.py) — and re-partitions back.

Trade-offs vs the ring (parallel/ring_attention.py):

- Communication: activations cross the ICI once per direction (2 all_to_alls
  of O(B·H·T·D/S) bytes per tensor) instead of S-1 ppermute hops of the full
  K/V; no per-hop latency on the critical path.
- Compute: full-sequence attention per head group — the local flash kernel's
  blocked online softmax applies as-is; the ring re-derives it across hops.
- Constraint: S must divide the head count (the ring scales to arbitrary S),
  and per-device K/V memory is O(T·H/S) instead of O(T/S·H).

Both are reachable from the public config surface (``model.attention=
"ring" | "ulysses"``) so the scheme is a measured choice, not a rewrite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from sharetrade_tpu.config import ConfigError

from sharetrade_tpu.ops.attention import flash_attention


def ulysses_attention(q, k, v, mesh: Mesh, *, seq_axis: str = "sp",
                      causal: bool = True, sm_scale: float | None = None,
                      batch_axis: str | None = None,
                      use_pallas: bool | None = None):
    """Causal MHA with (batch, heads, seq, head_dim) inputs sharded over
    ``seq_axis``; returns output with the same sharding. ``batch_axis``
    names a mesh axis the batch dim is already sharded over (e.g. "dp")."""
    num_shards = mesh.shape[seq_axis]
    heads, seq = q.shape[1], q.shape[2]
    if heads % num_shards != 0:
        raise ConfigError(
            f"ulysses needs heads divisible by {seq_axis}: "
            f"{heads} % {num_shards} != 0 (use ring attention for rings "
            f"wider than the head count)")
    if seq % num_shards != 0:
        raise ConfigError(
            f"seq len {seq} not divisible by {seq_axis}={num_shards}")

    def local_fn(q_loc, k_loc, v_loc):
        # (B, H, T/S, D) seq-sharded -> (B, H/S, T, D) head-sharded: the
        # tiled all_to_all splits the head axis S ways and concatenates the
        # received sequence shards.
        def to_heads(x):
            return jax.lax.all_to_all(x, seq_axis, split_axis=1,
                                      concat_axis=2, tiled=True)

        out = flash_attention(
            to_heads(q_loc), to_heads(k_loc), to_heads(v_loc),
            causal=causal, sm_scale=sm_scale, use_pallas=use_pallas)
        # (B, H/S, T, D) -> (B, H, T/S, D): the inverse re-partition.
        return jax.lax.all_to_all(out, seq_axis, split_axis=2,
                                  concat_axis=1, tiled=True)

    spec = P(batch_axis, None, seq_axis, None)
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(
        q, k, v)


def ulysses_attention_padded(q, k, v, mesh: Mesh, *, seq_axis: str = "sp",
                             causal: bool = True,
                             sm_scale: float | None = None,
                             batch_axis: str | None = None,
                             use_pallas: bool | None = None):
    """Ulysses attention for sequence lengths not divisible by the sp size.

    Pads q/k/v with trailing zero tokens to the next multiple of the sp size
    and slices the output back — causal-safe for the same reason as
    ring_attention_padded: padded KEY positions sit strictly after every real
    query's row, padded QUERY rows are sliced off."""
    if not causal:
        raise ConfigError("ulysses_attention_padded requires causal=True "
                         "(non-causal padding would attend to zero tokens)")
    if batch_axis is not None and q.shape[0] % mesh.shape[batch_axis]:
        batch_axis = None   # odd batch (e.g. eval's batch-1): replicate it
    num_shards = mesh.shape[seq_axis]
    seq = q.shape[2]
    pad = (-seq) % num_shards
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        q, k, v = (jnp.pad(x, widths) for x in (q, k, v))
    out = ulysses_attention(q, k, v, mesh, seq_axis=seq_axis, causal=causal,
                            sm_scale=sm_scale, batch_axis=batch_axis,
                            use_pallas=use_pallas)
    return out[:, :, :seq] if pad else out


def ulysses_attention_sharded(mesh: Mesh, seq_axis: str = "sp",
                              batch_axis: str | None = None,
                              use_pallas: bool | None = None):
    """Convenience partial with the mesh bound (for model wiring); handles
    non-divisible sequence lengths via padding."""
    return functools.partial(ulysses_attention_padded, mesh=mesh,
                             seq_axis=seq_axis, batch_axis=batch_axis,
                             use_pallas=use_pallas)
