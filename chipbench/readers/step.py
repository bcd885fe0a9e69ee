"""Readers of whole-step quantities."""

from chipbench.harness import flops


def train_mfu(context):
    """The training step's share of the chip's bf16 peak: the model's
    operations per agent-step (chipbench/harness/flops.py) times the
    measured agent-steps per second, over the peak, in percent."""
    rate = context["values"].get("agent_steps_per_s")
    if not rate or rate != rate:
        return None
    per_step = flops.episode_train_flops_per_agent_step(context["sizes"])
    return 100.0 * per_step * rate / context["peaks"]["bf16_flops"]


def rows_per_tick(context):
    """Requests answered per batch between the window's first instant and
    the last answer: the engine's own two counters, so set-up's cold
    prefills are not in it."""
    counters = context.get("counters") or {}
    batches = counters.get("serve_batches_total")
    if not batches:
        return None
    return counters.get("serve_responses_total", 0.0) / batches


def serve_tick_mfu(context, patterns):
    """The warm tick's share of the chip's bf16 peak: the model's
    operations for the rows a tick carries (``rows_per_tick``) over (the
    device seconds of one run of the tick program, the mean over its runs in
    the traced stretch, x the peak), in percent. Rows and seconds are both
    per tick, so how long the profiler took to start and stop is not in
    it."""
    trace = context["trace"]
    names = [n for n in trace.module_seconds if any(p in n for p in patterns)]
    seconds = sum(trace.module_seconds[n] for n in names)
    runs = sum(trace.module_counts[n] for n in names)
    rows = rows_per_tick(context)
    if not seconds or not runs or not rows:
        return None
    ops = flops.serve_warm_step_flops(context["sizes"]) * rows
    return 100.0 * ops / (seconds / runs * context["peaks"]["bf16_flops"])
