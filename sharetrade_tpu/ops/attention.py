"""Causal multi-head attention: Pallas flash kernel + XLA fallback.

The transformer tick-series policy (BASELINE.json config 5) attends over price
windows. On TPU the forward pass runs as a Pallas flash-attention kernel —
blocked online softmax, O(T) VMEM instead of the O(T²) score matrix in HBM —
following the playbook in /opt/skills/guides/pallas_guide.md (grid/BlockSpec
tiling, fori_loop over K blocks, broadcasted_iota masks).

Gradients: ``flash_attention`` carries a ``jax.custom_vjp`` with FUSED Pallas
backward kernels (the standard flash-attention backward): the forward saves
only the per-row logsumexp (O(T) residual instead of the T² probability
matrix), and two kernels recompute score blocks on the fly — one tiled over
query blocks producing dQ, one tiled over key blocks producing dK/dV — so
the backward never materializes T² in HBM either.

Shapes: (batch, heads, seq, head_dim) throughout. Sequence and head_dim are
padded to TPU tile multiples inside the wrapper (lane = 128, guide §Tiling);
zero-padded K columns are masked to -inf, zero-padded D columns contribute
nothing to QKᵀ and are sliced off the output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from sharetrade_tpu.config import ConfigError

BLOCK_Q = 128
BLOCK_K = 128
LANE = 128

_NEG_INF = -1e30

# Kernel identities (``pallas_call(metadata=...)``): on TPU the custom call
# carries them as ``frontend_attributes={kernel_metadata=...}``, the text a
# device trace prints as the event's name. NOT ``name=``: that enters the
# name stack and renames the instruction itself.
KERNEL_FWD, KERNEL_DQ, KERNEL_DKDV = "flash_fwd", "flash_dq", "flash_dkdv"


def _dot(a, b):
    """MXU matmul with f32 accumulation. For bf16 operands the precision is
    pinned to DEFAULT (native single-pass bf16): a globally-configured
    "highest" precision (the test suite pins it for f32 parity) has no bf16
    meaning and crashes Mosaic's matmul lowering."""
    precision = (jax.lax.Precision.DEFAULT
                 if a.dtype == jnp.bfloat16 or b.dtype == jnp.bfloat16
                 else None)
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=precision)


def _block_size(padded: int) -> int:
    """Adaptive tiling: when the (128-padded) extent is a 256 multiple, use
    256-wide blocks — short sequences (the 202-token tick window pads to 256)
    then run one block per program, collapsing the K loop and the q-block
    grid dimension whose overhead dominates these shapes. Other extents keep
    the classic 128 tiles (a block must divide the padded extent)."""
    return 256 if padded % 256 == 0 else 128


def reference_attention(q, k, v, *, causal: bool = True, sm_scale: float | None = None,
                        local_window: int | None = None):
    """Plain XLA attention — the numeric ground truth for the kernel.

    ``local_window=W`` restricts each query row p to the band of keys
    ``(p-W, p]`` — sliding-window (banded) causal attention: a query sees
    exactly the W keys ending at itself, so a sliding price window can be
    attended inside one long sequence without reprocessing it per step.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if local_window is not None and not causal:
        raise ConfigError("local_window requires causal attention")
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        t_q, t_k = scores.shape[-2], scores.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (t_q, t_k), 1)
        mask = col <= row
        if local_window is not None:
            mask = mask & (col > row - local_window)
        scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                  causal: bool, sm_scale: float, kv_len: int, kv_pad: int,
                  local_window: int | None):
    """One (batch*head, q-block) program: online-softmax over K blocks.

    ``kv_len`` is the true key count (padding columns beyond it are masked);
    ``kv_pad`` is the padded extent the loop tiles over. ``local_window=W``
    bands the causal mask to keys ``(row-W, row]`` and skips K blocks
    entirely below the band, so compute is O(T·W) instead of O(T²).
    """
    q_block = q_ref.shape[1]
    head_dim = q_ref.shape[2]
    qi = pl.program_id(1)

    # Inputs stay in their native dtype (bf16 rides the MXU single-pass);
    # accumulation and softmax run in f32 via preferred_element_type.
    q = q_ref[0]  # (block_q, d)

    first_k_block = 0
    num_k_blocks = pl.cdiv(kv_pad, block_k)
    if causal:
        # Blocks entirely above the causal frontier contribute nothing.
        last_row = (qi + 1) * q_block - 1
        num_k_blocks = jnp.minimum(num_k_blocks, pl.cdiv(last_row + 1, block_k))
    if local_window is not None:
        # Blocks entirely below the band contribute nothing either.
        first_row = qi * q_block
        first_k_block = jnp.maximum(
            0, (first_row - local_window + 1) // block_k)

    row_ids = qi * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, block_k), 0)

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = _dot(q, k_blk.T) * sm_scale  # (bq, bk)

        col_ids = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, block_k), 1)
        mask = col_ids < kv_len  # padding columns are not real keys
        if causal:
            mask = mask & (col_ids <= row_ids)
        if local_window is not None:
            mask = mask & (col_ids > row_ids - local_window)
        s = jnp.where(mask, s, _NEG_INF)

        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + _dot(p.astype(v_blk.dtype), v_blk)
        return acc, m_new, l_new

    acc0 = jnp.zeros((q_block, head_dim), jnp.float32)
    m0 = jnp.full((q_block,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((q_block,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(first_k_block, num_k_blocks, body,
                                  (acc0, m0, l0))

    # Fully-masked (padding) query rows have l == 0; emit zeros, not NaNs.
    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # Per-row logsumexp of the (scaled, masked) scores — the O(T) residual
    # the backward kernels rebuild probabilities from: p = exp(s - lse).
    # Stored broadcast across an 8-row sublane axis: TPU block shapes need
    # the last two dims divisible by (8, 128), so a flat (1, block_q) row
    # is not a legal block (pallas_guide.md §Tiling).
    lse_row = jnp.where(l > 0, m + jnp.log(l_safe), 0.0)
    lse_ref[0] = jnp.broadcast_to(lse_row[None, :], (8, q_block))


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_inputs(q, k, v):
    """Pad q/k/v to tile multiples and collapse (batch, heads)."""
    batch, heads = q.shape[:2]
    qp = _pad_to(_pad_to(q, 2, BLOCK_Q), 3, LANE)
    kp = _pad_to(_pad_to(k, 2, BLOCK_K), 3, LANE)
    vp = _pad_to(_pad_to(v, 2, BLOCK_K), 3, LANE)
    d_pad = qp.shape[-1]  # post-padding width (a LANE multiple, any head_dim)
    qp = qp.reshape(batch * heads, -1, d_pad)
    kp = kp.reshape(batch * heads, -1, d_pad)
    vp = vp.reshape(batch * heads, -1, d_pad)
    return qp, kp, vp, d_pad


def _flash_forward(q, k, v, causal, sm_scale, local_window, interpret):
    """Returns ``(out, lse)`` — lse is the backward's O(T) residual."""
    batch, heads, seq_len, head_dim = q.shape
    kv_len = k.shape[2]
    if causal and kv_len != seq_len:
        # Causal alignment between unequal q/kv lengths is ambiguous
        # (prefix vs suffix); refuse rather than guess.
        raise ConfigError(
            f"causal attention requires q_len == kv_len, got {seq_len} vs {kv_len}")

    qp, kp, vp, d_pad = _pad_inputs(q, k, v)
    bh, t_pad, _ = qp.shape
    kv_pad = kp.shape[1]
    block_q, block_k = _block_size(t_pad), _block_size(kv_pad)

    if local_window is not None and kv_pad * d_pad > _STREAM_KV_ELEMS:
        # Banded long sequence: stream K/V one block per grid step — VMEM
        # holds O(block + window) regardless of sequence length.
        out, lse = _banded_forward(
            qp, kp, vp, d_pad, (kv_len, block_q, block_k), sm_scale,
            local_window, interpret)
        out = out.reshape(batch, heads, t_pad, d_pad)[:, :, :seq_len, :head_dim]
        lse = lse.reshape(batch, heads, 8, t_pad)[:, :, 0, :seq_len]
        return out, lse

    kernel = functools.partial(
        _flash_kernel, block_k=block_k, causal=causal,
        sm_scale=sm_scale, kv_len=kv_len, kv_pad=kv_pad,
        local_window=local_window)

    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, t_pad // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, kv_pad, d_pad), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, kv_pad, d_pad), lambda b, i: (b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d_pad), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t_pad, d_pad), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, t_pad), jnp.float32),
        ),
        interpret=interpret,
        metadata={"kernel": KERNEL_FWD},
    )(qp, kp, vp)

    out = out.reshape(batch, heads, t_pad, d_pad)[:, :, :seq_len, :head_dim]
    lse = lse.reshape(batch, heads, 8, t_pad)[:, :, 0, :seq_len]
    return out, lse


# ---------------------------------------------------------------------------
# Streaming banded kernels: when local_window is set, K/V stream through VMEM
# one block per grid step (a third grid axis walks the band) instead of the
# whole padded K/V staging per program. VMEM then holds O(block + window)
# regardless of sequence length, so episode-mode replay spans are bounded by
# HBM, not by the ~16 MB VMEM (the full-KV kernels above keep serving the
# local_window=None paths, which genuinely need all keys).
#
# The band for query block i spans key rows [i*bq - W + 1, (i+1)*bq - 1]:
# at most cdiv(bq + W - 1, bk) + 1 key blocks — a STATIC count, so the grid
# axis has fixed extent and out-of-range steps (clamped by the index_map)
# are masked via virtual-vs-clipped block-index comparison.
#
# Short sequences stay on the full-KV kernels (streaming's extra grid steps
# cost ~20% there); the dispatch threshold is the per-tensor K/V element
# count beyond which full staging approaches the VMEM budget.

_STREAM_KV_ELEMS = 1 << 19          # 512k elems ≈ 2 MB f32 per K/V tensor


def _band_extent(window: int, span_block: int, other_block: int,
                 num_other_blocks: int) -> int:
    return min(num_other_blocks, -(-(span_block + window - 1) // other_block) + 1)


def _band_first_k(i, block_q: int, block_k: int, window: int):
    """First key block of query block ``i``'s band — the ONE definition the
    index_maps and the in-kernel virtual/clipped masks must share."""
    return jnp.maximum(0, (i * block_q - window + 1) // block_k)


def _band_first_q(i, block_q: int, block_k: int):
    """First query block that can see key block ``i`` (causal lower bound)
    — shared by the dkv kernel and its q/lse/delta index_maps."""
    return (i * block_k) // block_q


def _band_k_index(block_q: int, block_k: int, window: int,
                  num_k_blocks: int):
    """BlockSpec index_map walking query block ``i``'s band at step ``j``."""
    def index(b, i, j):
        return (b, jnp.minimum(_band_first_k(i, block_q, block_k, window) + j,
                               num_k_blocks - 1), 0)
    return index


def _flash_banded_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                             acc_ref, m_ref, l_ref, *, block_k: int,
                             sm_scale: float, kv_len: int,
                             num_k_blocks: int, window: int,
                             band_blocks: int):
    q_block = q_ref.shape[1]
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    virtual = _band_first_k(qi, q_block, block_k, window) + j
    clipped = jnp.minimum(virtual, num_k_blocks - 1)  # what index_map fetched

    q = q_ref[0]
    k_blk = k_ref[0]
    v_blk = v_ref[0]
    s = _dot(q, k_blk.T) * sm_scale
    row_ids = qi * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, block_k), 0)
    col_ids = clipped * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, block_k), 1)
    mask = ((col_ids < kv_len) & (col_ids <= row_ids)
            & (col_ids > row_ids - window) & (virtual == clipped))
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[0]
    l_prev = l_ref[0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + _dot(p.astype(v_blk.dtype), v_blk))
    m_ref[...] = jnp.broadcast_to(m_new[None, :], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new[None, :], l_ref.shape)

    @pl.when(j == band_blocks - 1)
    def _finish():
        l = l_ref[0]
        m = m_ref[0]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_row = jnp.where(l > 0, m + jnp.log(l_safe), 0.0)
        lse_ref[0] = jnp.broadcast_to(lse_row[None, :], (8, q_block))


def _banded_forward(qp, kp, vp, d_pad, seq_params, sm_scale, window,
                    interpret):
    """Streaming-banded forward over padded (bh, t_pad, d_pad) inputs."""
    bh, t_pad, _ = qp.shape
    kv_pad = kp.shape[1]
    kv_len, block_q, block_k = seq_params
    num_k_blocks = kv_pad // block_k
    band_blocks = _band_extent(window, block_q, block_k, num_k_blocks)

    k_index = _band_k_index(block_q, block_k, window, num_k_blocks)

    kernel = functools.partial(
        _flash_banded_fwd_kernel, block_k=block_k, sm_scale=sm_scale,
        kv_len=kv_len, num_k_blocks=num_k_blocks, window=window,
        band_blocks=band_blocks)
    return pl.pallas_call(
        kernel,
        grid=(bh, t_pad // block_q, band_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_pad), k_index),
            pl.BlockSpec((1, block_k, d_pad), k_index),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t_pad, d_pad), qp.dtype),
            jax.ShapeDtypeStruct((bh, 8, t_pad), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, d_pad), jnp.float32),
            pltpu.VMEM((8, block_q), jnp.float32),
            pltpu.VMEM((8, block_q), jnp.float32),
        ],
        interpret=interpret,
        metadata={"kernel": KERNEL_FWD},
    )(qp, kp, vp)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k: int, causal: bool,
                         sm_scale: float, kv_len: int, kv_pad: int,
                         local_window: int | None):
    """dQ, tiled over query blocks: dq = Σ_kb (p∘(dpᵀv − δ))·scale @ k."""
    q_block = q_ref.shape[1]
    qi = pl.program_id(1)

    q = q_ref[0]                                # (bq, d) native dtype
    do = do_ref[0]                              # (bq, d)
    # lse/delta arrive broadcast over an 8-row sublane axis — the same
    # (8, 128)-legality workaround the forward uses to store lse (see
    # _flash_kernel); row 0 carries the real values.
    lse = lse_ref[0][0]                         # (bq,)
    delta = delta_ref[0][0]                     # (bq,)
    row_ids = qi * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, block_k), 0)

    first_k_block = 0
    num_k_blocks = pl.cdiv(kv_pad, block_k)
    if causal:
        last_row = (qi + 1) * q_block - 1
        num_k_blocks = jnp.minimum(num_k_blocks, pl.cdiv(last_row + 1, block_k))
    if local_window is not None:
        first_k_block = jnp.maximum(
            0, (qi * q_block - local_window + 1) // block_k)

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = _dot(q, k_blk.T) * sm_scale
        col_ids = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, block_k), 1)
        mask = col_ids < kv_len
        if causal:
            mask = mask & (col_ids <= row_ids)
        if local_window is not None:
            mask = mask & (col_ids > row_ids - local_window)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = _dot(do, v_blk.T)
        ds = (p * (dp - delta[:, None]) * sm_scale).astype(k_blk.dtype)
        return dq + _dot(ds, k_blk)

    dq0 = jnp.zeros((q_block, q_ref.shape[2]), jnp.float32)
    dq = jax.lax.fori_loop(first_k_block, num_k_blocks, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, causal: bool,
                          sm_scale: float, kv_len: int, t_pad: int,
                          local_window: int | None):
    """dK/dV, tiled over key blocks: dv = Σ_qb pᵀ·do; dk = Σ_qb dsᵀ·q·scale."""
    block_k = k_ref.shape[1]
    kb = pl.program_id(1)

    k_blk = k_ref[0]                            # (bk, d) native dtype
    v_blk = v_ref[0]
    col_ids = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    col_valid = col_ids < kv_len

    num_q_blocks = t_pad // block_q
    # Causal: query blocks strictly before this key block see none of it.
    qb_start = _band_first_q(kb, block_q, block_k) if causal else 0
    qb_end = num_q_blocks
    if local_window is not None:
        # Banded: key c is seen only by queries p ≤ c + W - 1; blocks past
        # that frontier contribute nothing.
        last_q_row = (kb + 1) * block_k - 1 + local_window - 1
        qb_end = jnp.minimum(num_q_blocks, pl.cdiv(last_q_row + 1, block_q))

    def body(qb, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(qb * block_q, block_q), :]
        lse_blk = lse_ref[0, 0, pl.ds(qb * block_q, block_q)]
        delta_blk = delta_ref[0, 0, pl.ds(qb * block_q, block_q)]

        s = _dot(q_blk, k_blk.T) * sm_scale
        mask = col_valid
        if causal:
            row_ids = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = mask & (col_ids <= row_ids)
            if local_window is not None:
                mask = mask & (col_ids > row_ids - local_window)
        p = jnp.where(mask, jnp.exp(s - lse_blk[:, None]), 0.0)

        dv = dv + _dot(p.astype(do_blk.dtype).T, do_blk)
        dp = _dot(do_blk, v_blk.T)
        ds = (p * (dp - delta_blk[:, None]) * sm_scale).astype(q_blk.dtype)
        dk = dk + _dot(ds.T, q_blk)
        return dk, dv

    zeros = jnp.zeros((block_k, k_ref.shape[2]), jnp.float32)
    dk, dv = jax.lax.fori_loop(qb_start, qb_end, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_banded_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dq_acc_ref, *, block_k: int,
                            sm_scale: float, kv_len: int, num_k_blocks: int,
                            window: int, band_blocks: int):
    q_block = q_ref.shape[1]
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    virtual = _band_first_k(qi, q_block, block_k, window) + j
    clipped = jnp.minimum(virtual, num_k_blocks - 1)

    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0][0]
    delta = delta_ref[0][0]
    k_blk = k_ref[0]
    v_blk = v_ref[0]
    s = _dot(q, k_blk.T) * sm_scale
    row_ids = qi * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, block_k), 0)
    col_ids = clipped * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, block_k), 1)
    mask = ((col_ids < kv_len) & (col_ids <= row_ids)
            & (col_ids > row_ids - window) & (virtual == clipped))
    p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
    dp = _dot(do, v_blk.T)
    ds = (p * (dp - delta[:, None]) * sm_scale).astype(k_blk.dtype)
    dq_acc_ref[...] = dq_acc_ref[...] + _dot(ds, k_blk)

    @pl.when(j == band_blocks - 1)
    def _finish():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _flash_banded_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                             dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *,
                             block_q: int, sm_scale: float, kv_len: int,
                             num_q_blocks: int, window: int,
                             band_blocks: int):
    block_k = k_ref.shape[1]
    kb = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    virtual = _band_first_q(kb, block_q, block_k) + j
    clipped = jnp.minimum(virtual, num_q_blocks - 1)

    k_blk = k_ref[0]
    v_blk = v_ref[0]
    q_blk = q_ref[0]
    do_blk = do_ref[0]
    lse_blk = lse_ref[0][0]
    delta_blk = delta_ref[0][0]

    s = _dot(q_blk, k_blk.T) * sm_scale
    row_ids = clipped * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    col_ids = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = ((col_ids < kv_len) & (col_ids <= row_ids)
            & (col_ids > row_ids - window) & (virtual == clipped))
    p = jnp.where(mask, jnp.exp(s - lse_blk[:, None]), 0.0)
    dv_acc_ref[...] = dv_acc_ref[...] + _dot(p.astype(do_blk.dtype).T, do_blk)
    dp = _dot(do_blk, v_blk.T)
    ds = (p * (dp - delta_blk[:, None]) * sm_scale).astype(q_blk.dtype)
    dk_acc_ref[...] = dk_acc_ref[...] + _dot(ds.T, q_blk)

    @pl.when(j == band_blocks - 1)
    def _finish():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _banded_backward(qp, kp, vp, gp, lse_p, delta, d_pad, seq_params,
                     sm_scale, window, interpret):
    """Streaming-banded dQ and dK/dV over padded (bh, …) inputs."""
    bh, t_pad, _ = qp.shape
    kv_pad = kp.shape[1]
    kv_len, block_q, block_k = seq_params
    num_k_blocks = kv_pad // block_k
    num_q_blocks = t_pad // block_q

    k_index = _band_k_index(block_q, block_k, window, num_k_blocks)

    band_k = _band_extent(window, block_q, block_k, num_k_blocks)
    dq_kernel = functools.partial(
        _flash_banded_dq_kernel, block_k=block_k, sm_scale=sm_scale,
        kv_len=kv_len, num_k_blocks=num_k_blocks, window=window,
        band_blocks=band_k)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, num_q_blocks, band_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_pad), k_index),
            pl.BlockSpec((1, block_k, d_pad), k_index),
            pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_pad, d_pad), qp.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d_pad), jnp.float32)],
        interpret=interpret,
        metadata={"kernel": KERNEL_DQ},
    )(qp, kp, vp, gp, lse_p, delta)

    def q_index(b, i, j):
        return (b, jnp.minimum(_band_first_q(i, block_q, block_k) + j,
                               num_q_blocks - 1), 0)

    def qrow_index(b, i, j):
        return (b, 0, jnp.minimum(_band_first_q(i, block_q, block_k) + j,
                                  num_q_blocks - 1))

    band_q = _band_extent(window, block_k, block_q, num_q_blocks)
    dkv_kernel = functools.partial(
        _flash_banded_dkv_kernel, block_q=block_q, sm_scale=sm_scale,
        kv_len=kv_len, num_q_blocks=num_q_blocks, window=window,
        band_blocks=band_q)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, num_k_blocks, band_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), q_index),
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, d_pad), q_index),
            pl.BlockSpec((1, 8, block_q), qrow_index),
            pl.BlockSpec((1, 8, block_q), qrow_index),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, kv_pad, d_pad), kp.dtype),
            jax.ShapeDtypeStruct((bh, kv_pad, d_pad), vp.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d_pad), jnp.float32),
            pltpu.VMEM((block_k, d_pad), jnp.float32),
        ],
        interpret=interpret,
        metadata={"kernel": KERNEL_DKDV},
    )(qp, kp, vp, gp, lse_p, delta)
    return dq, dk, dv


def _flash_backward(q, k, v, out, lse, g, causal, sm_scale, local_window,
                    interpret):
    batch, heads, seq_len, head_dim = q.shape
    kv_len = k.shape[2]

    qp, kp, vp, d_pad = _pad_inputs(q, k, v)
    bh, t_pad, _ = qp.shape
    kv_pad = kp.shape[1]
    gp = _pad_to(_pad_to(g, 2, BLOCK_Q), 3, LANE).reshape(bh, t_pad, d_pad)
    # δ = rowsum(dO ∘ O): cheap elementwise — plain XLA, padded with zeros so
    # padding query rows contribute nothing in the kernels.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = _pad_to(delta, 2, BLOCK_Q).reshape(bh, t_pad)
    lse_p = _pad_to(lse, 2, BLOCK_Q).reshape(bh, t_pad)
    # Sublane-broadcast to (bh, 8, t_pad): a flat (1, block_q) block over a
    # (bh, t_pad) array violates Mosaic's (8, 128) block-divisibility rule
    # whenever bh > 1 — the forward's lse output hit the same wall and stores
    # the broadcast layout; the backward reads row 0 back out.
    delta = jnp.broadcast_to(delta[:, None, :], (bh, 8, t_pad))
    lse_p = jnp.broadcast_to(lse_p[:, None, :], (bh, 8, t_pad))

    block_q, block_k = _block_size(t_pad), _block_size(kv_pad)

    if local_window is not None and kv_pad * d_pad > _STREAM_KV_ELEMS:
        dq, dk, dv = _banded_backward(
            qp, kp, vp, gp, lse_p, delta, d_pad,
            (kv_len, block_q, block_k), sm_scale, local_window, interpret)
        dq = dq.reshape(batch, heads, t_pad, d_pad)[:, :, :seq_len, :head_dim]
        dk = dk.reshape(batch, heads, kv_pad, d_pad)[:, :, :kv_len, :head_dim]
        dv = dv.reshape(batch, heads, kv_pad, d_pad)[:, :, :kv_len, :head_dim]
        return dq, dk, dv

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, block_k=block_k, causal=causal,
        sm_scale=sm_scale, kv_len=kv_len, kv_pad=kv_pad,
        local_window=local_window)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, t_pad // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, kv_pad, d_pad), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, kv_pad, d_pad), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d_pad), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d_pad), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_pad, d_pad), q.dtype),
        interpret=interpret,
        metadata={"kernel": KERNEL_DQ},
    )(qp, kp, vp, gp, lse_p, delta)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, block_q=block_q, causal=causal,
        sm_scale=sm_scale, kv_len=kv_len, t_pad=t_pad,
        local_window=local_window)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, kv_pad // block_k),
        in_specs=[
            pl.BlockSpec((1, t_pad, d_pad), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, t_pad, d_pad), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 8, t_pad), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 8, t_pad), lambda b, j: (b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, d_pad), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, j: (b, j, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, kv_pad, d_pad), k.dtype),
            jax.ShapeDtypeStruct((bh, kv_pad, d_pad), v.dtype),
        ),
        interpret=interpret,
        metadata={"kernel": KERNEL_DKDV},
    )(qp, kp, vp, gp, lse_p, delta)

    dq = dq.reshape(batch, heads, t_pad, d_pad)[:, :, :seq_len, :head_dim]
    dk = dk.reshape(batch, heads, kv_pad, d_pad)[:, :, :kv_len, :head_dim]
    dv = dv.reshape(batch, heads, kv_pad, d_pad)[:, :, :kv_len, :head_dim]
    return dq, dk, dv


def _per_device(fn, shard, in_ranks, out_ranks):
    """``fn`` itself, or ``fn`` run once per device of a mesh.

    Mosaic kernels cannot be partitioned automatically: a bare
    ``pallas_call`` inside a program jitted over several devices is refused
    ("wrap the call in a shard_map"). ``shard = (mesh, batch_axis)`` wraps
    the kernel call in a ``shard_map`` whose every operand splits its
    LEADING (batch) dim over ``batch_axis`` — attention rows are
    independent, so each device runs the kernel on its own rows with no
    collective — or, with ``batch_axis=None`` (the episode model's
    batch-of-one shared trunk), replicates: every device computes the same
    small call. The wrap sits INSIDE the custom_vjp, around the forward and
    backward kernel calls separately, so autodiff never transposes a
    shard_map and the replicated case needs no psum."""
    if shard is None:
        return fn
    mesh, batch_axis = shard

    def spec(rank):
        return P(batch_axis, *([None] * (rank - 1)))

    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(spec(r) for r in in_ranks),
        out_specs=tuple(spec(r) for r in out_ranks), check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, sm_scale, local_window, interpret,
                     shard=None):
    return _flash_fwd_rule(q, k, v, causal, sm_scale, local_window,
                           interpret, shard)[0]


def _flash_fwd_rule(q, k, v, causal, sm_scale, local_window, interpret,
                    shard):
    fwd = _per_device(
        lambda q, k, v: _flash_forward(q, k, v, causal, sm_scale,
                                       local_window, interpret),
        shard, (4, 4, 4), (4, 3))
    out, lse = fwd(q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, sm_scale, local_window, interpret, shard,
                    residuals, g):
    bwd = _per_device(
        lambda q, k, v, out, lse, g: _flash_backward(
            q, k, v, out, lse, g, causal, sm_scale, local_window, interpret),
        shard, (4, 4, 4, 4, 3, 4), (4, 4, 4))
    return bwd(*residuals, g)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: float | None = None,
                    local_window: int | None = None,
                    use_pallas: bool | None = None,
                    mesh=None, batch_axis: str | None = None):
    """Causal MHA over (batch, heads, seq, head_dim).

    ``local_window=W`` restricts each query to the W-key band ending at
    itself (sliding-window attention, Mistral-style), letting a sliding
    price window be attended inside ONE long sequence. Compute and the
    K-block loop skip everything outside the band, so cost is O(T·W)
    rather than O(T²).

    ``use_pallas=None`` auto-selects: the kernel on TPU, the XLA reference
    elsewhere (the unit suite runs the kernel through the Pallas interpreter
    separately — tests/test_ops.py — so both paths stay covered).

    ``mesh``: the mesh of the program this call is traced into, when that
    program is partitioned over more than one device. The kernel then runs
    under a ``shard_map`` with the batch dim split over ``batch_axis``
    (replicated when the batch does not divide it) — see
    :func:`_per_device`. Callers already inside a ``shard_map`` (the sp /
    ulysses / pipeline paths) pass no mesh: they are per-device already.
    """
    if q.ndim != 4:
        raise ConfigError(f"expected (batch, heads, seq, head_dim), got {q.shape}")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if local_window is not None:
        if not causal:
            raise ConfigError("local_window requires causal attention")
        if local_window < 1:
            raise ConfigError(f"local_window must be >= 1, got {local_window}")
        if local_window >= q.shape[2]:
            local_window = None    # band covers everything: plain causal
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return reference_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                   local_window=local_window)
    interpret = jax.default_backend() != "tpu"
    shard = None
    if mesh is not None and mesh.size > 1:
        if batch_axis is not None and q.shape[0] % mesh.shape[batch_axis]:
            batch_axis = None      # e.g. the batch-of-one trunk: replicate
        shard = (mesh, batch_axis)
    return _flash_attention(q, k, v, causal, sm_scale, local_window,
                            interpret, shard)
