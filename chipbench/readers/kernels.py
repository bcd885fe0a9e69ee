"""Readers of kernel time from the device trace."""

from chipbench.harness import flops


def attention_roofline(context, forward, backward):
    """The banded flash-attention kernels' share of their roofline: for
    every forward and backward kernel event in the trace, the least time
    the chip could take for that call (the larger of operations over the
    bf16 peak and bytes over the HBM peak, from
    ``flops.banded_attention_cost`` at the replay's sequence length), over
    the kernels' measured device seconds, in percent. ``forward`` and
    ``backward`` are lists of regular expressions over the trace's
    operation names; the backward of one call is ``len(backward)`` kernels
    (dQ; dK and dV), so its least time is shared among them."""
    trace, sizes, peaks = context["trace"], context["sizes"], context["peaks"]
    seq = flops.replay_seq_len(sizes)
    least = 0.0
    measured = 0.0
    for patterns, is_bwd, share in ((forward, False, 1.0),
                                    (backward, True, 1.0 / len(backward))):
        seconds, events = trace.matching(patterns)
        ops, nbytes = flops.banded_attention_cost(sizes, seq, backward=is_bwd)
        call = max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
        least += call * share * events
        measured += seconds
    if measured <= 0:
        return None
    return 100.0 * least / measured
