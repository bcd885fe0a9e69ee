"""Readers of kernel time from the device trace, each kernel's cost
function beside its reader."""


def banded_attention_cost(s: dict, seq: int, *, backward: bool,
                          itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) one banded flash-attention call needs for a
    batch-of-one ``seq``-token pass over all heads: QK^T and PV over the
    ``window`` keys each query sees (forward 4*S*W*d; backward recomputes
    the scores and forms dQ, dK, dV: 2.5x the forward's matmuls), and Q, K,
    V, O (and their gradients) each crossing HBM once."""
    d = s["heads"] * s["head_dim"]
    band = min(s["window"], seq)
    fwd = 4.0 * seq * band * d
    tensors = 4.0 * seq * d * itemsize
    if backward:
        return 2.5 * fwd, 2.0 * tensors + 3.0 * seq * d * itemsize
    return fwd, tensors


def attention_roofline(context, forward, backward):
    """The banded flash-attention kernels' share of their roofline: for
    every forward and backward kernel event in the trace, the least time
    the chip could take for that call (the larger of operations over the
    bf16 peak and bytes over the HBM peak, from ``banded_attention_cost``
    at the sequence length the model's replay runs at), over
    the kernels' measured device seconds, in percent. ``forward`` and
    ``backward`` are lists of regular expressions over the trace's
    operation names; the backward of one call is ``len(backward)`` kernels
    (dQ; dK and dV), so its least time is shared among them."""
    trace, sizes, peaks = context["trace"], context["sizes"], context["peaks"]
    seq = context["model"].replay_seq_len(sizes)
    least = 0.0
    measured = 0.0
    for patterns, is_bwd, share in ((forward, False, 1.0),
                                    (backward, True, 1.0 / len(backward))):
        seconds, events = trace.matching(patterns)
        ops, nbytes = banded_attention_cost(sizes, seq, backward=is_bwd)
        call = max(ops / peaks["bf16_flops"],
                   nbytes / peaks["hbm_bytes_per_s"])
        least += call * share * events
        measured += seconds
    if measured <= 0:
        return None
    return 100.0 * least / measured
