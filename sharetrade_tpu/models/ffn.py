"""The transformer block's FFN half: dense MLP or mixture-of-experts.

One dispatch helper shared by BOTH transformer families (window mode,
models/transformer.py; episode mode, models/transformer_episode.py) so the
MoE routing variants — dense-mask top-1, capacity top-k, their ep-sharded
psum forms, and the token-sharded all_to_all dispatch (parallel/moe.py) —
cannot drift between them. Of these, dense-mask top-1 never drops a token
(every expert runs every token); the capacity top-k forms drop the picks
that overflow an expert's buffer. (The serve-only latent_moe trunk has an
expert layer of its own, models/latent_moe_episode.py: sigmoid-routed top-k
with a shared expert, no capacity and no drops.) The reference has a single dense 2-layer MLP and
no MoE at all (SURVEY.md §2.2 lists EP as absent); this is the forward-
looking expert-parallel capability.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sharetrade_tpu.models.core import dense


def ffn_apply(blk: dict, h: jax.Array, *, moe_experts: int = 0,
              ep_mesh=None, ep_axis: str = "ep", moe_top_k: int = 0,
              moe_capacity_factor: float = 1.25,
              moe_dispatch: str = "psum",
              batch_axis: str | None = None):
    """Apply the block's FFN to ``h`` (..., d) under the residual's LN2.

    Returns ``(y, aux)`` — ``y`` matches ``h``'s shape; ``aux`` is the MoE
    load-balance loss (0.0 for the dense path), which models surface via
    ``ModelOut.aux`` and learners weight by ``LearnerConfig.aux_loss_coef``
    (essential for the dropping schemes, where a collapsed gate silently
    zeroes overflow tokens).
    """
    if not moe_experts:
        return (dense(blk["mlp_out"], jax.nn.gelu(dense(blk["mlp_in"], h))),
                jnp.float32(0.0))
    from sharetrade_tpu.parallel import moe as moe_lib
    d_model = h.shape[-1]
    flat = h.reshape(-1, d_model)
    if moe_top_k:          # capacity-bucketed top-k dispatch
        if ep_mesh is not None and moe_dispatch == "a2a":
            # Token-sharded all_to_all dispatch: pad the token count to a
            # multiple of ep (pad rows are marked invalid — no buffer
            # slots, no balance-stat contribution), slice real rows back.
            ep = ep_mesh.shape[ep_axis]
            n = flat.shape[0]
            pad = (-n) % ep
            y, aux = moe_lib.moe_apply_topk_a2a(
                blk["moe"],
                jnp.pad(flat, ((0, pad), (0, 0))) if pad else flat,
                ep_mesh, axis=ep_axis, top_k=moe_top_k,
                capacity_factor=moe_capacity_factor,
                n_valid=n if pad else None)
            y = y[:n] if pad else y
        elif ep_mesh is not None:
            y, aux = moe_lib.moe_apply_topk_sharded(
                blk["moe"], flat, ep_mesh, axis=ep_axis,
                top_k=moe_top_k, capacity_factor=moe_capacity_factor,
                batch_axis=batch_axis)
        else:
            y, aux = moe_lib.moe_apply_topk(
                blk["moe"], flat, top_k=moe_top_k,
                capacity_factor=moe_capacity_factor)
    elif ep_mesh is not None:
        y, aux = moe_lib.moe_apply_sharded(
            blk["moe"], flat, ep_mesh, axis=ep_axis, batch_axis=batch_axis)
    else:
        y, aux = moe_lib.moe_apply(blk["moe"], flat)
    return y.reshape(h.shape), aux
