"""The host layer's readers: the window's garbage-collection pauses, read
from the program's two process-wide histograms through the manifest."""

import pytest

from chipbench.harness import common
from chipbench.readers import host

#: The metrics and the histograms the program attaches for them
#: (``sharetrade_tpu/obs/trace.py``).
METRICS = {"host.gc_pause_ms": "host_gc_pause_ms",
           "host.gc_full_pause_ms": "host_gc_full_pause_ms"}


def snap(total, count):
    return {"bounds": [1.0, 10.0], "counts": [count, 0, 0], "sum": total,
            "count": count}


@pytest.mark.parametrize("histograms, expected", [
    ({}, None),                                         # the parent commit
    ({"host_gc_pause_ms": snap(0.0, 0)}, 0.0),          # no collection
    ({"host_gc_pause_ms": snap(112.7, 3)}, 112.7),
], ids=["absent", "empty", "filled"])
def test_total_is_the_windows_sum_zero_without_a_sample_none_without_one(
        histograms, expected):
    assert host.total({"histograms": histograms},
                      histogram="host_gc_pause_ms") == expected


@pytest.mark.parametrize("name", sorted(METRICS))
def test_each_metric_reads_the_histogram_the_program_attaches(name):
    manifest = common.Manifest()
    fn, args = manifest.reader(name)
    assert args == {"histogram": METRICS[name]}
    window = common.histogram_delta(
        {METRICS[name]: snap(40.0, 7)},
        {METRICS[name]: snap(152.7, 9), "serve_tick_host_ms": snap(1.0, 1)})
    assert fn({"histograms": window}, **args) == pytest.approx(112.7)
    assert fn({"histograms": {}}, **args) is None
    entry = next(m for m in manifest.doc["per_layer"] if m["name"] == name)
    assert entry["layer"] == "host" and entry["moves"] == "serve_p95_ms"
    assert entry["workloads"] == ["serve_d1024_steady", "serve_xing4_steady"]
