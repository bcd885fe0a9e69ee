"""Expert parallelism: mixture-of-experts layers sharded over the ``ep`` axis.

Absent from the reference (SURVEY.md §2.2 lists EP as none). Two routing
schemes, both shape-static:

1. **Dense-mask top-1** (``moe_apply`` / ``moe_apply_sharded``): every device
   evaluates its resident experts on the full token batch under the routing
   mask; a ``psum`` combines the disjoint contributions. Exact (no token
   dropping) and load-balance-oblivious, but O(E·N) compute — the right
   choice for small expert counts and the numeric reference for the rest.
2. **Capacity-bucketed top-k dispatch** (``moe_apply_topk`` and its
   ``_sharded`` psum / ``_a2a`` all_to_all variants): GShard-style grouped
   routing into per-expert buffers of C = O(k·g/E) tokens, so each expert
   only computes its routed tokens; picks overflowing the static buffers are
   dropped. The ``_a2a`` variant additionally shards the tokens over ``ep``
   and moves only dispatched buffers across the ICI — the pattern that
   scales both E and N.

Which schemes drop tokens: scheme 1 never does (every expert sees every
token); scheme 2 DROPS whatever overflows an expert's capacity buffer, in
all three of its variants. A third scheme lives outside this module and
drops nothing either: the serve-only latent_moe trunk
(``models/latent_moe_episode.py``) routes by sigmoid scores + a selection
bias, top-k renormalised and scaled, with a shared expert and NO capacity
(a warm tick runs every held expert over every row under the routing
weights; the prefill sorts its tokens into per-expert blocks sized for the
worst case), and is told which experts this chip holds of an
expert-parallel deployment. It has no exchange across chips yet.

Every path returns an auxiliary load-balancing loss (mean-importance ·
mean-load, the standard switch-style regularizer) alongside the output;
models surface it via ``ModelOut.aux`` and learners weight it by
``LearnerConfig.aux_loss_coef`` — essential for the dropping schemes, where
a collapsed gate silently zeroes overflow tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from sharetrade_tpu.config import ConfigError


def init_moe_params(key: jax.Array, num_experts: int, in_dim: int,
                    hidden_dim: int, *, dtype=jnp.float32) -> dict:
    k_gate, k_in, k_out = jax.random.split(key, 3)
    s_in = jnp.sqrt(2.0 / in_dim).astype(dtype)
    s_hid = jnp.sqrt(2.0 / hidden_dim).astype(dtype)
    return {
        "gate": jax.random.normal(k_gate, (in_dim, num_experts), dtype) * 0.01,
        "w_in": jax.random.normal(
            k_in, (num_experts, in_dim, hidden_dim), dtype) * s_in,
        "w_out": jax.random.normal(
            k_out, (num_experts, hidden_dim, in_dim), dtype) * s_hid,
    }


def moe_apply(params: dict, tokens: jax.Array):
    """Single-device reference: top-1 MoE over (N, in_dim) tokens.

    Returns (output (N, in_dim), aux_loss scalar)."""
    logits = tokens @ params["gate"]                        # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    choice = jnp.argmax(logits, axis=-1)                    # (N,)
    num_experts = params["gate"].shape[-1]
    onehot = jax.nn.one_hot(choice, num_experts, dtype=tokens.dtype)
    weight = jnp.sum(probs * onehot, axis=-1)               # gate value of pick

    # Dense-mask evaluation: h[e] = relu(x @ w_in[e]) @ w_out[e], masked.
    h = jnp.einsum("ni,eih->enh", tokens, params["w_in"],
                   preferred_element_type=jnp.float32).astype(tokens.dtype)
    h = jax.nn.relu(h)
    y = jnp.einsum("enh,ehi->eni", h, params["w_out"],
                   preferred_element_type=jnp.float32).astype(tokens.dtype)
    out = jnp.einsum("eni,ne->ni", y, onehot) * weight[:, None]

    # Switch-style load-balance loss: E * sum_e importance_e * load_e.
    importance = jnp.mean(probs, axis=0)
    load = jnp.mean(onehot, axis=0)
    aux = num_experts * jnp.sum(importance * load)
    return out, aux


def _pad_groups(tokens: jax.Array, group_size: int | None):
    """Reshape (N, d) tokens into fixed-size routing groups, zero-padding the
    tail (GShard's group dimension): the one-hot dispatch/combine tensors
    stay O(g·E·C) per group instead of O(N·E·C) globally — without grouping
    they grow quadratically in N.

    Returns ``(grouped (G, g, d), valid (G, g) 0/1 mask)``; callers slice
    their output back to N rows.
    """
    n = tokens.shape[0]
    if group_size is None or n <= group_size:
        groups, g = 1, n
    else:
        g = group_size
        groups = -(-n // g)
    n_pad = groups * g
    toks = jnp.pad(tokens, ((0, n_pad - n), (0, 0)))
    valid = (jnp.arange(n_pad) < n).astype(jnp.float32).reshape(groups, g)
    return toks.reshape(groups, g, -1), valid


def _capacity(group_tokens: int, num_experts: int, top_k: int,
              capacity_factor: float) -> int:
    """Static per-expert buffer length per routing group, rounded up to a
    sublane multiple so the (E, C, d) dispatch buffers tile cleanly on TPU."""
    cap = -(-top_k * group_tokens * capacity_factor // num_experts)
    cap = max(int(cap), 1)
    return -(-cap // 8) * 8


def _topk_route(gate_logits: jax.Array, top_k: int, capacity: int, dtype,
                valid: jax.Array | None = None):
    """Shape-static top-k routing with per-expert capacity, per group.

    ``gate_logits`` is (G, g, E); ``valid`` is an optional (G, g) 0/1 mask —
    padding rows claim no buffer slots and are excluded from the balance
    statistics. Returns ``(dispatch (G, g, E, C), combine (G, g, E, C),
    (importance, load))``: ``dispatch`` is a 0/1 scatter of each surviving
    (token, pick) into its expert's buffer slot; ``combine`` additionally
    carries the gate weight; the final element is the per-expert balance
    statistics pair for :func:`_balance_loss`. Within a group, slots are
    claimed in pick-rank-major order (every token's top-1 pick beats any
    token's top-2 pick), the standard overflow priority; picks past capacity
    are dropped — the documented trade for static shapes.
    """
    groups, g, num_experts = gate_logits.shape
    probs = jax.nn.softmax(gate_logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)              # (G, g, k)
    sel = jax.nn.one_hot(top_i, num_experts, dtype=jnp.float32)  # (G, g, k, E)
    if valid is not None:
        sel = sel * valid[:, :, None, None]

    # Buffer position of each pick: cumulative count of earlier claims on the
    # same expert, counting rank-major (k outer, token inner) per group.
    sel_rank_major = sel.transpose(0, 2, 1, 3).reshape(
        groups, top_k * g, num_experts)
    pos = jnp.cumsum(sel_rank_major, axis=1) - sel_rank_major
    pos = pos.reshape(groups, top_k, g, num_experts).transpose(0, 2, 1, 3)
    pos_of_pick = jnp.sum(pos * sel, axis=-1).astype(jnp.int32)  # (G, g, k)

    keep = (pos_of_pick < capacity).astype(jnp.float32)     # (G, g, k)
    slot = jax.nn.one_hot(pos_of_pick, capacity, dtype=jnp.float32)
    dispatch = jnp.einsum("Gnk,Gnke,Gnkc->Gnec", keep, sel, slot)
    combine = jnp.einsum("Gnk,Gnke,Gnkc->Gnec", keep * top_p, sel, slot)

    if valid is None:
        importance = jnp.mean(probs, axis=(0, 1))
        load = jnp.mean(sel[:, :, 0, :], axis=(0, 1))       # top-1 routing share
    else:
        denom = jnp.maximum(jnp.sum(valid), 1.0)
        importance = jnp.sum(probs * valid[:, :, None], axis=(0, 1)) / denom
        load = jnp.sum(sel[:, :, 0, :], axis=(0, 1)) / denom
    return dispatch.astype(dtype), combine.astype(dtype), (importance, load)


def _balance_loss(importance: jax.Array, load: jax.Array) -> jax.Array:
    """Switch-style load-balance regularizer: E · Σ_e importance_e · load_e."""
    return importance.shape[-1] * jnp.sum(importance * load)


def _expert_ffn(w_in: jax.Array, w_out: jax.Array, xs: jax.Array) -> jax.Array:
    """relu FFN over per-expert buffers: (E, C, in) -> (E, C, in)."""
    h = jnp.einsum("eci,eih->ech", xs, w_in,
                   preferred_element_type=jnp.float32).astype(xs.dtype)
    h = jax.nn.relu(h)
    return jnp.einsum("ech,ehi->eci", h, w_out,
                      preferred_element_type=jnp.float32).astype(xs.dtype)


def _dispatch_gather(dispatch: jax.Array, toks: jax.Array) -> jax.Array:
    """(G, g, E, C) dispatch × (G, g, d) tokens -> (E, G·C, d) buffers."""
    groups, _, num_experts, cap = dispatch.shape
    xs = jnp.einsum("Gnec,Gni->Geci", dispatch, toks)
    return xs.transpose(1, 0, 2, 3).reshape(num_experts, groups * cap, -1)


def _combine_scatter(combine: jax.Array, ys: jax.Array) -> jax.Array:
    """(E, G·C, d) expert outputs × (G, g, E, C) combine -> (G·g, d)."""
    groups, g, num_experts, cap = combine.shape
    ys = ys.reshape(num_experts, groups, cap, -1).transpose(1, 0, 2, 3)
    out = jnp.einsum("Geci,Gnec->Gni", ys, combine)
    return out.reshape(groups * g, -1)


def moe_apply_topk(params: dict, tokens: jax.Array, *, top_k: int = 2,
                   capacity_factor: float = 1.25,
                   group_size: int | None = 1024):
    """Top-k MoE with capacity-bucketed dispatch (single-device reference).

    Unlike :func:`moe_apply`'s dense-mask scheme — exact but O(E·N), every
    expert runs every token — each expert here evaluates only its
    C = O(k·g/E) dispatched tokens per routing group, the compute profile
    that makes large expert counts affordable. Picks overflowing an expert's
    static per-group buffer are dropped (contribute zero), bounded by
    ``capacity_factor``.

    Returns (output (N, in_dim), aux_loss scalar).
    """
    n = tokens.shape[0]
    num_experts = params["gate"].shape[-1]
    toks, valid = _pad_groups(tokens, group_size)
    cap = _capacity(toks.shape[1], num_experts, top_k, capacity_factor)
    dispatch, combine, (importance, load) = _topk_route(
        jnp.einsum("Gni,ie->Gne", toks, params["gate"]), top_k, cap,
        tokens.dtype, valid)
    ys = _expert_ffn(params["w_in"], params["w_out"],
                     _dispatch_gather(dispatch, toks))
    return _combine_scatter(combine, ys)[:n], _balance_loss(importance, load)


def moe_apply_topk_sharded(params: dict, tokens: jax.Array, mesh: Mesh,
                           *, axis: str = "ep", top_k: int = 2,
                           capacity_factor: float = 1.25,
                           group_size: int | None = 1024,
                           batch_axis: str | None = None):
    """Expert-parallel top-k MoE: experts sharded over ``axis``, routing
    replicated, each device running only its resident experts' buffers.

    Per-device expert compute is E/ep buffers of G·C tokens — versus the
    dense-mask scheme's E/ep experts × ALL N tokens — with the same single
    psum combine. Numerically identical to :func:`moe_apply_topk` (same
    global buffer positions, so the same picks drop).
    """
    num_experts = params["gate"].shape[-1]
    ep = mesh.shape[axis]
    if num_experts % ep != 0:
        raise ConfigError(f"num_experts={num_experts} not divisible by "
                         f"{axis}={ep}")
    if batch_axis is not None and tokens.shape[0] % mesh.shape[batch_axis]:
        batch_axis = None   # odd token count: fall back to replication
    n = tokens.shape[0]
    local_e = num_experts // ep

    def local_fn(gate, w_in, w_out, toks):
        if batch_axis is not None:
            toks = jax.lax.all_gather(toks, batch_axis, axis=0, tiled=True)
        toks, valid = _pad_groups(toks, group_size)
        cap = _capacity(toks.shape[1], num_experts, top_k, capacity_factor)
        dispatch, combine, (importance, load) = _topk_route(
            jnp.einsum("Gni,ie->Gne", toks, gate), top_k, cap, toks.dtype,
            valid)
        aux = _balance_loss(importance, load)
        if batch_axis is not None:
            # Computed from the all_gathered batch, so already equal across
            # batch shards; the pmean marks the replication for shard_map's
            # out_specs check.
            aux = jax.lax.pmean(aux, batch_axis)
        lo = jax.lax.axis_index(axis) * local_e
        disp_l = jax.lax.dynamic_slice_in_dim(dispatch, lo, local_e, axis=2)
        comb_l = jax.lax.dynamic_slice_in_dim(combine, lo, local_e, axis=2)
        ys = _expert_ffn(w_in, w_out, _dispatch_gather(disp_l, toks))
        partial = _combine_scatter(comb_l, ys)[:n]
        out = jax.lax.psum(partial, axis)                   # disjoint -> exact
        if batch_axis is not None:
            shard = jax.lax.axis_index(batch_axis)
            nloc = n // mesh.shape[batch_axis]
            out = jax.lax.dynamic_slice_in_dim(out, shard * nloc, nloc, axis=0)
        return out, aux

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(batch_axis)),
        out_specs=(P(batch_axis), P()),
    )(params["gate"], params["w_in"], params["w_out"], tokens)


def moe_apply_topk_a2a(params: dict, tokens: jax.Array, mesh: Mesh,
                       *, axis: str = "ep", top_k: int = 2,
                       capacity_factor: float = 1.25,
                       group_size: int | None = 1024,
                       n_valid: int | None = None):
    """GShard-style all_to_all dispatch: tokens AND experts sharded over
    ``axis``; each shard routes its local tokens, an all_to_all carries the
    dispatched buffers to their expert-owner devices, and a second
    all_to_all returns expert outputs for the local combine.

    Communication is two all_to_alls of the (ep, E/ep, G·C, d) buffers —
    O(k·N·d·capacity_factor) bytes total, independent of E — versus the
    replicated schemes' psum of the full (N, d) activations on every device.
    This is the dispatch pattern that scales token counts: no device ever
    materializes the global batch. Routing groups are per source shard, so
    drop decisions are shard-local; in the no-drop regime the result equals
    :func:`moe_apply_topk` exactly.

    ``n_valid`` marks rows past it as padding (callers pad the token count
    up to a multiple of ep): they claim no buffer slots and are excluded
    from the balance statistics, exactly like group padding.
    """
    num_experts = params["gate"].shape[-1]
    ep = mesh.shape[axis]
    if num_experts % ep != 0:
        raise ConfigError(f"num_experts={num_experts} not divisible by "
                         f"{axis}={ep}")
    if tokens.shape[0] % ep != 0:
        raise ConfigError(f"token count {tokens.shape[0]} not divisible by "
                         f"{axis}={ep} (a2a dispatch shards tokens)")
    n_local = tokens.shape[0] // ep
    local_e = num_experts // ep

    def local_fn(gate, w_in, w_out, toks):
        # toks: (N/ep, d) — this shard's tokens only.
        gtoks, valid = _pad_groups(toks, group_size)
        if n_valid is not None:
            # Global row ids of this shard's rows, laid into the group grid.
            start = jax.lax.axis_index(axis) * n_local
            row_ok = (start + jnp.arange(n_local) < n_valid)
            row_ok = jnp.pad(row_ok, (0, valid.size - n_local))
            valid = valid * row_ok.reshape(valid.shape).astype(valid.dtype)
        groups = gtoks.shape[0]
        cap = _capacity(gtoks.shape[1], num_experts, top_k, capacity_factor)
        dispatch, combine, (importance, load) = _topk_route(
            jnp.einsum("Gni,ie->Gne", gtoks, gate), top_k, cap, toks.dtype,
            valid)
        # Global balance statistics BEFORE the product: averaging per-shard
        # importance·load products is not the global loss (nonlinear in the
        # means). Count-weighted: shards can hold unequal VALID counts (the
        # n_valid pad tail lives on the last shard), so per-shard means are
        # recombined as global-sum / global-count, not pmean'd.
        cnt = jnp.sum(valid)
        total = jnp.maximum(jax.lax.psum(cnt, axis), 1.0)
        imp_g = jax.lax.psum(importance * jnp.maximum(cnt, 1.0), axis) / total
        load_g = jax.lax.psum(load * jnp.maximum(cnt, 1.0), axis) / total
        aux = _balance_loss(imp_g, load_g)
        xs = _dispatch_gather(dispatch, gtoks)              # (E, G·C, d)
        d = xs.shape[-1]
        xs = xs.reshape(ep, local_e, groups * cap, d)
        # Non-tiled all_to_all: slice j of the leading (size-ep) axis goes to
        # device j; the received leading axis indexes the SOURCE shard, so
        # each owner holds (ep_src, E_local, G·C, d).
        xs = jax.lax.all_to_all(xs, axis, split_axis=0, concat_axis=0)
        ys = _expert_ffn(
            w_in, w_out,
            xs.transpose(1, 0, 2, 3).reshape(local_e, ep * groups * cap, d))
        ys = ys.reshape(local_e, ep, groups * cap, d).transpose(1, 0, 2, 3)
        ys = jax.lax.all_to_all(ys, axis, split_axis=0, concat_axis=0)
        out = _combine_scatter(
            combine, ys.reshape(num_experts, groups * cap, d))
        return out[:toks.shape[0]], aux

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P()),
    )(params["gate"], params["w_in"], params["w_out"], tokens)


def moe_apply_sharded(params: dict, tokens: jax.Array, mesh: Mesh,
                      *, axis: str = "ep", batch_axis: str | None = None):
    """Expert-parallel evaluation: experts sharded over ``axis``, tokens and
    gate replicated, contributions psum-combined. Numerically identical to
    :func:`moe_apply`. ``batch_axis`` names a mesh axis the token batch is
    already sharded over (e.g. "dp") so the shard_map keeps that layout
    instead of all-gathering the tokens."""
    num_experts = params["gate"].shape[-1]
    ep = mesh.shape[axis]
    if num_experts % ep != 0:
        raise ConfigError(f"num_experts={num_experts} not divisible by "
                         f"{axis}={ep}")
    if batch_axis is not None and tokens.shape[0] % mesh.shape[batch_axis]:
        batch_axis = None   # odd token count: fall back to replication

    def local_fn(gate, w_in, w_out, toks):
        logits = toks @ gate                                # replicated (N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        choice = jnp.argmax(logits, axis=-1)
        onehot = jax.nn.one_hot(choice, num_experts, dtype=toks.dtype)
        weight = jnp.sum(probs * onehot, axis=-1)

        # This device's expert slice: global ids [lo, lo + E/ep).
        local_e = num_experts // ep
        lo = jax.lax.axis_index(axis) * local_e
        local_mask = jax.lax.dynamic_slice_in_dim(onehot, lo, local_e, axis=1)

        h = jnp.einsum("ni,eih->enh", toks, w_in,
                       preferred_element_type=jnp.float32).astype(toks.dtype)
        h = jax.nn.relu(h)
        y = jnp.einsum("enh,ehi->eni", h, w_out,
                       preferred_element_type=jnp.float32).astype(toks.dtype)
        partial = jnp.einsum("eni,ne->ni", y, local_mask) * weight[:, None]
        out = jax.lax.psum(partial, axis)                   # disjoint -> exact

        importance = jnp.mean(probs, axis=0)
        load = jnp.mean(onehot, axis=0)
        aux = num_experts * jnp.sum(importance * load)
        if batch_axis is not None:
            aux = jax.lax.pmean(aux, batch_axis)
        return out, aux

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(batch_axis)),
        out_specs=(P(batch_axis), P()),
    )(params["gate"], params["w_in"], params["w_out"], tokens)
