"""Readers of the device's own numbers."""


def idle_share(context):
    trace = context["trace"]
    if not trace.window_s:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)


def peak_hbm_gb(context):
    peak = context.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
