"""Readers over the program's host-span histograms: the stage histograms
observed from the same two stamps as the host spans (PERF.md, section 3)."""


def share(context, numerators, denominator, scale=1.0):
    """100 x (the summed ``sum`` of the ``numerators`` histograms, brought
    to the denominator's unit by ``scale``) over the ``sum`` of the
    ``denominator`` histogram: the share of the denominator's time that the
    numerators' stages took, exact where a bucketed percentile is not. A
    program without one of the histograms, or a window without a sample,
    gives nothing to read."""
    snaps = context["histograms"]
    if any(name not in snaps for name in (*numerators, denominator)):
        return None
    total = snaps[denominator].get("sum", 0.0)
    if not snaps[denominator].get("count") or total <= 0:
        return None
    return 100.0 * scale * sum(snaps[n].get("sum", 0.0)
                               for n in numerators) / total
