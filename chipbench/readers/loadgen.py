"""Readers of the benchmark's own load generator and of the engine's
counters."""

from chipbench.harness import common
from chipbench.readers.step import rows_per_tick


def late_p95_ms(context):
    late = context.get("late_ms")
    return common.percentile(late, 95) if late else None


def batch_occupancy(context):
    """Rows per tick over ``max_batch`` in the window (set-up's cold
    prefills left out), in percent."""
    rows = rows_per_tick(context)
    return 100.0 * rows / context["max_batch"] if rows else None
