"""Ring attention: sequence-parallel causal attention over the ``sp`` axis.

Long-context capability (absent from the reference, which only ever slides a
201-price window — SURVEY.md §5): the sequence axis is sharded across
devices, each holding T/S queries and one rotating K/V block. At every ring
step a device contracts its queries against the resident K/V block with
online-softmax accumulation, then passes the block to its neighbor via
``ppermute`` — S-1 hops that ride the ICI ring while the next block's matmul
overlaps with the transfer. Peak memory per device is O(T/S), so context
scales linearly with the ring size.

Built on ``shard_map`` + XLA collectives (the scaling-book recipe), with the
same online-softmax algebra as the local Pallas flash kernel
(sharetrade_tpu/ops/attention.py) — the kernel handles intra-block locality,
the ring handles inter-device locality.

Why the per-hop contraction is plain XLA rather than the Pallas kernel
(measured, TPU v5e, 2026-07-30): the flash kernel returns only the
normalized output, so ring composition through it would need per-hop
(out, logsumexp) pairs with a custom VJP across hops; that machinery buys
nothing at the shapes this path serves. Window mode bounds the sequence at
window+1 tokens, so a hop block is T/S ≲ 1k rows — chained-timing both
implementations at (8, 4, T, 64): T=256 fwd XLA 1 µs vs Pallas 2 µs,
fwd+bwd 2 µs vs 5 µs; T=1024 fwd 1 µs vs 2 µs, fwd+bwd 2 µs vs 2 µs —
dispatch-bound and equal within noise. The XLA hop's real limit is
the BACKWARD's O((T/S)²) score residuals (a T=4096 50-step grad chain
asked for a 100 GB allocation), but sequences that long ride episode mode,
whose sp path routes through the kernel's banded streaming form
(parallel/episode_sp.py) — so no supported window-mode configuration
reaches the regime where the kernel would win.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sharetrade_tpu.config import ConfigError

_NEG_INF = -1e30


def _block_contract(q, k, v, q_offset, k_offset, causal, sm_scale, acc, m, l):
    """Online-softmax accumulate one (q-block, k-block) pair.

    q: (B, H, Tq, D); k/v: (B, H, Tk, D); acc/m/l carry the running
    numerator, row max, and row normalizer.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        rows = q_offset + jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 0)
        cols = k_offset + jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return acc_new, m_new, l_new


def ring_attention(q, k, v, mesh: Mesh, *, seq_axis: str = "sp",
                   causal: bool = True, sm_scale: float | None = None,
                   batch_axis: str | None = None):
    """Causal MHA with (batch, heads, seq, head_dim) inputs sharded over
    ``seq_axis``. Returns output with the same sharding. ``batch_axis``
    names a mesh axis the batch dim is already sharded over (e.g. "dp" in a
    dp x sp mesh) so the shard_map doesn't force an all-gather of the batch."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    num_shards = mesh.shape[seq_axis]
    if q.shape[2] % num_shards != 0:
        raise ConfigError(
            f"seq len {q.shape[2]} not divisible by {seq_axis}={num_shards}")
    local_len = q.shape[2] // num_shards

    def local_fn(q_loc, k_loc, v_loc):
        # q_loc/k_loc/v_loc: (B, H, T/S, D) — this device's shard.
        my_idx = jax.lax.axis_index(seq_axis)
        q_offset = my_idx * local_len

        batch, heads, t_loc, d = q_loc.shape
        acc = jnp.zeros((batch, heads, t_loc, d), jnp.float32)
        m = jnp.full((batch, heads, t_loc), _NEG_INF, jnp.float32)
        l = jnp.zeros((batch, heads, t_loc), jnp.float32)

        k_cur, v_cur = k_loc, v_loc
        perm = [(i, (i + 1) % num_shards) for i in range(num_shards)]
        for step in range(num_shards):  # static unroll: S ring stages
            src = (my_idx - step) % num_shards  # whose block we now hold
            acc, m, l = _block_contract(
                q_loc, k_cur, v_cur, q_offset, src * local_len,
                causal, sm_scale, acc, m, l)
            if step + 1 < num_shards:
                # Rotate K/V around the ring; XLA overlaps the ppermute
                # with the next stage's contraction where possible.
                k_cur = jax.lax.ppermute(k_cur, seq_axis, perm)
                v_cur = jax.lax.ppermute(v_cur, seq_axis, perm)

        l_safe = jnp.where(l > 0, l, 1.0)
        return (acc / l_safe[..., None]).astype(q_loc.dtype)

    spec = P(batch_axis, None, seq_axis, None)
    shmap = jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return shmap(q, k, v)


def ring_attention_padded(q, k, v, mesh: Mesh, *, seq_axis: str = "sp",
                          causal: bool = True, sm_scale: float | None = None,
                          batch_axis: str | None = None):
    """Ring attention for sequence lengths not divisible by the ring size.

    Pads queries/keys/values with trailing zero tokens up to the next
    multiple of the sp size and slices the output back. Safe under the
    causal mask: padded KEY positions sit strictly after every real query's
    row, so no real output attends to padding; padded QUERY rows produce
    garbage that is sliced off."""
    if not causal:
        raise ConfigError("ring_attention_padded requires causal=True "
                         "(non-causal padding would attend to zero tokens)")
    if batch_axis is not None and q.shape[0] % mesh.shape[batch_axis]:
        batch_axis = None   # odd batch (e.g. eval's batch-1): replicate it
    num_shards = mesh.shape[seq_axis]
    seq = q.shape[2]
    pad = (-seq) % num_shards
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        q, k, v = (jnp.pad(x, widths) for x in (q, k, v))
    out = ring_attention(q, k, v, mesh, seq_axis=seq_axis, causal=causal,
                         sm_scale=sm_scale, batch_axis=batch_axis)
    return out[:, :, :seq] if pad else out


def ring_attention_sharded(mesh: Mesh, seq_axis: str = "sp",
                           batch_axis: str | None = None):
    """Convenience partial with the mesh bound (for model wiring); handles
    non-divisible sequence lengths via padding."""
    return functools.partial(ring_attention_padded, mesh=mesh,
                             seq_axis=seq_axis, batch_axis=batch_axis)


def sequence_sharding(mesh: Mesh, seq_axis: str = "sp") -> NamedSharding:
    return NamedSharding(mesh, P(None, None, seq_axis, None))
