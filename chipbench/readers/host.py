"""Readers over the program's host-process histograms: the interpreter's
garbage-collection pauses (``host/gc``, PERF.md section 3)."""


def total(context, histogram: str):
    """The window's sum of the histogram, in its own unit. A histogram the
    program attached with no sample in the window reads 0.0 (no collection
    is a reading); a program without it gives nothing to read."""
    snap = context["histograms"].get(histogram)
    if snap is None:
        return None
    return float(snap.get("sum", 0.0)) if snap.get("count") else 0.0
