"""Readers of whole-step quantities. The operation counts are the
configuration's own model's (``context["model"]``, a module under
``chipbench/models/``); the peak is that of all the chips the cell holds."""


def peak_flops(context) -> float:
    return context["chips"] * context["peaks"]["bf16_flops"]


def train_mfu(context):
    """The training step's share of the cell's chips' bf16 peak: the
    model's operations per agent-step times the measured agent-steps per
    second, over (chips x the chip's peak), in percent."""
    rate = context["values"].get("agent_steps_per_s")
    if not rate or rate != rate:
        return None
    per_step = context["model"].train_flops_per_agent_step(context["sizes"])
    return 100.0 * per_step * rate / peak_flops(context)


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
    """The warm tick's share of the cell's chips' bf16 peak: the model's
    operations for the rows a tick carries (``rows_per_tick``) over (the
    device seconds of one run of the tick program, the mean over its runs in
    the traced stretch, x chips x the chip's peak), in percent. Rows and
    seconds are both per tick, so how long the profiler took to start and
    stop is not in it."""
    trace = context["trace"]
    names = [n for n in trace.module_seconds if any(p in n for p in patterns)]
    seconds = sum(trace.module_seconds[n] for n in names)
    runs = sum(trace.module_counts[n] for n in names)
    rows = rows_per_tick(context)
    if not seconds or not runs or not rows:
        return None
    ops = context["model"].serve_warm_step_flops(context["sizes"]) * rows
    return 100.0 * ops / (seconds / runs * peak_flops(context))
