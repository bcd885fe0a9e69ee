"""The per-layer metrics PR 24 added: the span-share reader, the fused-update
reader (hand-made events, then a slice recorded on the chip: train_d1024,
PR 24, one AdaGrad update's 56 kernel events with what feeds and takes
them), and every new metric read through the manifest on a temporary
copy."""

import json
import os
import shutil

import pytest

from chipbench.harness import common, trace_reduce
from chipbench.harness.peaks import peaks_for
from chipbench.readers import fused_update, spans

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "train_d1024_update_slice.json")
PEAKS = peaks_for("TPU v5 lite")
NEW_METRICS = {
    "train.dispatch_call_mean_ms": "ms", "train.host_busy_share": "%",
    "train.pipeline_stall_share": "%", "fused_update_roofline": "%",
    "serve.batch_wait_mean_ms": "ms", "serve.device_stage_mean_ms": "ms",
    "serve.inflight_ticks_mean": "ticks", "serve.tick_host_mean_ms": "ms",
    "serve.done_wait_mean_ms": "ms", "serve.complete_host_mean_ms": "ms"}


def snap(total, count):
    return {"bounds": [1.0], "counts": [count, 0], "sum": total,
            "count": count}


# A window of 20 chunks of 0.45 s: 3 ms in the dispatch call and 2 ms in
# host_process a chunk, 440 ms blocked on the pipeline.
TRAIN_HISTOGRAMS = {
    "train_chunk_seconds": snap(9.0, 20),
    "train_dispatch_call_ms": snap(60.0, 20),
    "train_host_process_ms": snap(40.0, 20),
    "train_pipeline_stall_ms": snap(8800.0, 20),
    "train_dispatch_gap_ms": snap(8900.0, 20)}
SERVE_HISTOGRAMS = {
    "serve_batch_wait_ms": snap(300.0, 100),
    "serve_device_ms": snap(16000.0, 100),
    "serve_inflight_ticks": snap(58.0, 10),
    "serve_tick_host_ms": snap(25.0, 10),
    "serve_done_wait_ms": snap(240.0, 10),
    "serve_complete_host_ms": snap(15.0, 10)}


def test_share_is_the_ratio_of_sums_in_the_denominators_unit():
    ctx = {"histograms": TRAIN_HISTOGRAMS}
    busy = spans.share(ctx, ["train_dispatch_call_ms",
                             "train_host_process_ms"],
                       "train_chunk_seconds", scale=0.001)
    assert busy == pytest.approx(100.0 * 0.100 / 9.0)
    stall = spans.share(ctx, ["train_pipeline_stall_ms"],
                        "train_chunk_seconds", scale=0.001)
    assert stall == pytest.approx(100.0 * 8.8 / 9.0)
    assert spans.share(ctx, ["train_dispatch_call_ms"],
                       "train_dispatch_call_ms") == pytest.approx(100.0)


@pytest.mark.parametrize("histograms", [
    {},                                                 # the parent commit
    {"train_chunk_seconds": snap(9.0, 20)},             # no numerator
    {"train_dispatch_call_ms": snap(60.0, 20)},         # no denominator
    {"train_dispatch_call_ms": snap(60.0, 20),
     "train_chunk_seconds": snap(0.0, 0)},              # an empty window
], ids=["none", "no_numerator", "no_denominator", "empty_window"])
def test_share_finds_nothing_to_read_and_does_not_raise(histograms):
    assert spans.share({"histograms": histograms},
                       ["train_dispatch_call_ms"], "train_chunk_seconds",
                       scale=0.001) is None


# -- the fused update --------------------------------------------------------

KERNEL = ('%update.7 = (f32[512,128]{1,0:T(8,128)S(1)}, f32[512,128]{1,0:T('
          '8,128)}) custom-call(f32[512,128]{1,0:T(8,128)S(1)} %reshape.1, '
          'bf16[512,128]{1,0:T(8,128)(2,1)S(1)} %reshape.2, f32[512,128]{1,0'
          ':T(8,128)S(1)} %reshape.3), custom_call_target="tpu_custom_call",'
          ' frontend_attributes={kernel_metadata={\n"kernel":"fused_update"'
          '\n}}')
OTHER_KERNEL = KERNEL.replace("%update.7", "%jvp__.2").replace(
    "fused_update", "flash_fwd")
IDENTITY = common.load_json(os.path.join(
    common.BENCH_DIR, "metrics", "fused_update_roofline.json"))["reader"]


def hand_made_rows():
    """One update of one 65,536-element leaf: three relayouts in (0.5 us
    each), the kernel (1 us), a relayout out (1.5 us); beside them another
    kernel, its own feeder and an unrelated fusion."""
    op = "XLA Ops"
    dev = "/device:TPU:0"
    feed = "%reshape.{} = f32[512,128]{{1,0:T(8,128)S(1)}} reshape(f32[64," \
           "1024]{{1,0:T(8,128)S(1)}} %custom-call.{})"
    return [
        [dev, op, feed.format(1, 11), 0, 500],
        [dev, op, feed.format(2, 12), 500, 500],
        [dev, op, feed.format(3, 13), 1000, 500],
        [dev, op, KERNEL, 1500, 1000],
        [dev, op, "%reshape_reshape.4 = f32[64,1024]{1,0:T(8,128)} reshape("
                  "f32[512,128]{1,0:T(8,128)S(1)} %pallas_call.9)", 2500,
         1500],
        [dev, op, feed.format(5, 15), 4000, 500],
        [dev, op, OTHER_KERNEL.replace("%reshape.1", "%reshape.5"), 4500,
         1000],
        [dev, op, "%fusion.6 = f32[64,1024]{1,0:T(8,128)} fusion(f32[64,1024"
                  "]{1,0:T(8,128)} %get-tuple-element.3), kind=kLoop", 5500,
         700]]


def test_fused_update_counts_the_kernel_with_what_feeds_and_takes_it():
    summary = trace_reduce.reduce_events(hand_made_rows())
    share = fused_update.fused_update_roofline(
        {"trace": summary, "peaks": PEAKS}, **IDENTITY["args"])
    least = 512 * 128 * 18 / PEAKS["hbm_bytes_per_s"]
    assert share == pytest.approx(100.0 * least / 4000e-9)
    # No event carries the identity (the parent commit): nothing to read.
    rows = [r for r in hand_made_rows() if "fused_update" not in r[2]]
    assert fused_update.fused_update_roofline(
        {"trace": trace_reduce.reduce_events(rows), "peaks": PEAKS},
        **IDENTITY["args"]) is None


def test_fused_update_on_a_slice_recorded_on_the_chip():
    with open(FIXTURE) as fh:
        rows = json.load(fh)
    summary = trace_reduce.reduce_events(rows)
    seconds, events = summary.matching([IDENTITY["args"]["kernel"]])
    assert events == 56                  # 14 leaves x 4 layers, one update
    share = fused_update.fused_update_roofline(
        {"trace": summary, "peaks": PEAKS}, **IDENTITY["args"])
    kernels_alone = 100.0 * (
        sum(fused_update._elements(fused_update._FIRST_RESULT.match(
            n).group(1)) * c for n, c in summary.op_counts.items()
            if '"fused_update"' in n)
        * 18 / PEAKS["hbm_bytes_per_s"]) / seconds
    # The kernel events alone leave out the side of the update that crosses
    # HBM (their operands are staged in VMEM) and read far above 100%; with
    # the relayouts that feed and take them the share is one.
    assert kernels_alone > 200 > 100 > share > 30


# -- through the manifest ----------------------------------------------------

def test_every_new_metric_is_read_through_the_manifest_on_a_copy(tmp_path):
    """The new metrics are data: an entry, a file naming a reader, nothing
    else. Read each through ``Manifest.reader`` from a temporary copy of the
    data directories, on contexts made by hand."""
    data = tmp_path / "chipbench"
    shutil.copytree(os.path.join(common.BENCH_DIR, "metrics"),
                    data / "metrics")
    manifest = common.Manifest(data_dir=str(data))
    declared = {m["name"]: m for m in manifest.doc["per_layer"]}
    context = {"histograms": {**TRAIN_HISTOGRAMS, **SERVE_HISTOGRAMS},
               "trace": trace_reduce.reduce_events(hand_made_rows()),
               "peaks": PEAKS}
    expect = {
        "train.dispatch_call_mean_ms": 3.0,
        "train.host_busy_share": 100.0 * 0.100 / 9.0,
        "train.pipeline_stall_share": 100.0 * 8.8 / 9.0,
        "serve.batch_wait_mean_ms": 3.0,
        "serve.device_stage_mean_ms": 160.0,
        "serve.inflight_ticks_mean": 5.8,
        "serve.tick_host_mean_ms": 2.5,
        "serve.done_wait_mean_ms": 24.0,
        "serve.complete_host_mean_ms": 1.5}
    for name, unit in NEW_METRICS.items():
        assert declared[name]["unit"] == unit
        fn, args = manifest.reader(name)
        value = fn(context, **args)
        if name in expect:
            assert value == pytest.approx(expect[name]), name
        else:
            assert 0 < value < 100, name          # fused_update_roofline
        # ... and on the parent commit's context: nothing, and no raise.
        bare = {"histograms": {}, "peaks": PEAKS,
                "trace": trace_reduce.reduce_events(
                    [r for r in hand_made_rows()
                     if "fused_update" not in r[2]])}
        assert fn(bare, **args) is None, name
    cells = {m["name"]: m["workloads"] for m in manifest.doc["per_layer"]}
    for cell in ("train_d1024", "train_d256"):
        names = [m["name"] for m in manifest.metrics_for(cell, "per_layer")]
        assert {n for n in NEW_METRICS if cell in cells[n]} <= set(names)
