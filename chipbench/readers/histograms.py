"""Readers over the program's fixed-bucket histograms (the window's share
of a snapshot: ``{"bounds": [...], "counts": [...], "sum": s, "count":
n}``, counts per bucket with one overflow slot at the end)."""


def percentile(context, histogram: str, q: float):
    """Nearest-rank percentile, interpolated within the bucket the rank
    falls in, so it resolves no finer than that bucket. Nothing to read (no
    such histogram, no sample, or the rank in the overflow bucket) returns
    None."""
    snap = context["histograms"].get(histogram)
    if not snap or sum(snap["counts"]) <= 0:
        return None
    rank = max(1, -(-sum(snap["counts"]) * q // 100))
    seen, low = 0, 0.0
    for bound, count in zip(snap["bounds"], snap["counts"]):
        if count and seen + count >= rank:
            return float(low + (rank - seen) / count * (bound - low))
        seen += count
        low = bound
    return None


def mean(context, histogram: str):
    """The mean of the window's samples, from the histogram's own sum and
    count: it moves with every sample, where a percentile waits for a bucket
    edge."""
    snap = context["histograms"].get(histogram)
    if not snap or not snap.get("count"):
        return None
    return float(snap["sum"] / snap["count"])
