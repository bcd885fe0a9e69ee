"""Readers of the routed-expert layer: the program's ``serve_moe_*``
counters (one small vector of picks a warm tick, reduced over the tick's
real rows), and the expert layer's share of its roofline with its cost
function beside it. The sizes are the configuration's own model's
(``context["sizes"]``), read by the names ``expert_layers`` and
``expert_sizes`` give them here."""


def expert_sizes(s: dict) -> dict:
    """What the cost function needs of a family's sizes: the model width
    and one expert's hidden width, the experts held here, the shared
    experts, and the layers that have experts."""
    return {"width": s["width"], "ffn": s["expert_ffn"],
            "held": s["held_n"], "shared": s["shared"],
            "layers": s["blocks"] - s["dense_blocks"]}


def _per_tick(context):
    """(picks on held experts, held experts hit, rows) a warm tick, the
    means over the window; None where the program counted nothing."""
    counters = context.get("counters") or {}
    ticks = counters.get("serve_moe_ticks_total")
    picks = counters.get("serve_moe_picks_total")
    if not ticks or not picks:
        return None
    e = expert_sizes(context["sizes"])
    top_k = context["sizes"]["picks"]
    return (counters.get("serve_moe_local_picks_total", 0.0) / ticks,
            counters.get("serve_moe_experts_hit_total", 0.0) / ticks,
            picks / ticks / (top_k * e["layers"]))


def local_pick_share(context):
    """Picks that fell on an expert held here, of all picks, in percent."""
    counters = context.get("counters") or {}
    picks = counters.get("serve_moe_picks_total")
    if not picks:
        return None
    return 100.0 * counters.get("serve_moe_local_picks_total", 0.0) / picks


def experts_hit_share(context):
    """Held experts that at least one of a tick's rows picked, of the held
    experts of all expert layers, the mean over the window's ticks, in
    percent."""
    per_tick = _per_tick(context)
    if per_tick is None:
        return None
    e = expert_sizes(context["sizes"])
    return 100.0 * per_tick[1] / (e["held"] * e["layers"])


def expert_layer_cost(e: dict, local_picks: float, experts_hit: float,
                      rows: float, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) the held routed experts of ONE tick need,
    whatever computes them: each held expert that a row picked is read once
    (three width x ffn matrices); every pick on a held expert costs 2 x 3 x
    width x ffn operations; the rows' activations come in and go out once a
    layer. Nothing padded or masked is counted. The shared expert is on
    neither side of the share: its small matrices are prefetched beside
    other work and leave no event of their own to find."""
    one = 3.0 * e["width"] * e["ffn"]
    ops = 2.0 * one * local_picks
    nbytes = itemsize * (one * experts_hit
                         + 2.0 * rows * e["layers"] * e["width"])
    return ops, nbytes


def experts_roofline(context, events, programs):
    """The held routed experts' share of their roofline: the least time the
    chip could take for a tick's necessary work (the larger of operations over
    the bf16 peak and bytes over the HBM peak, from ``expert_layer_cost`` at
    the window's mean picks, experts hit and rows a tick) over the measured
    device seconds a tick of the expert bank's events (``events``: regular
    expressions over the trace's operation names, which carry the names of
    the weights an operation reads; ``programs``: the tick
    program's name, whose runs in the traced stretch are the ticks), in
    percent."""
    per_tick = _per_tick(context)
    trace = context.get("trace")
    if per_tick is None or trace is None:
        return None
    ticks = sum(n for name, n in trace.module_counts.items()
                if any(p in name for p in programs))
    seconds, _ = trace.matching(events)
    if not ticks or seconds <= 0:
        return None
    ops, nbytes = expert_layer_cost(expert_sizes(context["sizes"]), *per_tick)
    peaks = context["peaks"]
    least = max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / ticks)
