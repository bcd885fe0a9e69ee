"""The one host-span path (obs/trace.py ``host_span``) and the stage
histograms observed beside it.

One call opens a ``jax.profiler.TraceAnnotation`` (on the ``/host:CPU``
plane of whatever profiler session runs) and, with an enabled tracer, also
writes the ``trace.jsonl`` event. The orchestrator, the async pipeline and
the serve engine open every span through it, per chunk or per tick, under
fixed names with the serial as an identifier. The interpreter's garbage
collector is one more host phase: one ``gc.callbacks`` entry a process opens
``host/gc`` through the same entry and observes each pause into two
process-wide histograms, without taking a lock.
"""

from __future__ import annotations

import gc
import glob
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sharetrade_tpu.config import FrameworkConfig, ModelConfig, ServeConfig
from sharetrade_tpu.models import build_model
from sharetrade_tpu.obs import SpanTracer, read_trace
from sharetrade_tpu.obs import trace as obs_trace
from sharetrade_tpu.obs.trace import (GC_FULL_PAUSE_HISTOGRAM,
                                      GC_PAUSE_HISTOGRAM, attach_gc_pauses,
                                      clock_pair, host_span, span)
from sharetrade_tpu.runtime import Orchestrator, ReplyState
from sharetrade_tpu.serve import ServeEngine
from sharetrade_tpu.utils.metrics import MetricsRegistry
from sharetrade_tpu.utils.profiling import Tracer

WINDOW = 8
PRICES = np.linspace(10.0, 20.0, 72, dtype=np.float32)  # 64-step episode
DONE_DEPTH = 1

TRAIN_SPANS = {"train/dispatch": {"chunk", "k"},
               "train/pipeline_stall": {"chunk"},
               "train/queue_wait": set(),
               "train/readback": {"chunk"},
               "train/host_process": {"chunk"}}
GC_SPANS = {"host/gc": {"gen", "collected", "uncollectable"}}
SERVE_SPANS = {"serve/slot_wait": {"tick"},
               "serve/collect_batch": {"tick"},
               "serve/dispatch_tick": {"tick", "rows", "cold"},
               "serve/done_wait": {"tick"},
               "serve/complete_batch": {"tick", "rows"},
               "serve/readback": {"tick"}}


def host_plane_events(trace_dir) -> list[tuple[str, dict]]:
    """(name, identifiers) of every event on the trace's host plane."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, f"no xplane trace under {trace_dir}"
    data = ProfileData.from_file(sorted(paths)[-1])
    return [(ev.name, dict(ev.stats)) for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def start_profiler(trace_dir) -> None:
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # host TraceMe spans only
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)


def toy_train_cfg(tmp_path, *, obs: bool) -> FrameworkConfig:
    cfg = FrameworkConfig()
    cfg.learner.algo = "qlearn"
    cfg.env.window = WINDOW
    cfg.model.hidden_dim = 8
    cfg.parallel.num_workers = 4
    cfg.runtime.chunk_steps = 16
    cfg.runtime.metrics_every_chunks = 1
    cfg.runtime.pipeline_depth = 1
    cfg.runtime.checkpoint_every_updates = 0
    cfg.runtime.checkpoint_dir = str(tmp_path / "ckpts")
    cfg.obs.enabled = obs
    cfg.obs.dir = str(tmp_path / "obs")
    cfg.obs.export_interval_s = 3600
    return cfg


def run_toy_training(cfg, *, slow_consumer_s: float = 0.0) -> Orchestrator:
    """One 4-chunk episode; a slowed consumer fills the depth-1 pipeline,
    so the dispatcher blocks in ``pl.put`` as it does on the chip."""
    orch = Orchestrator(cfg)
    orch.send_training_data(PRICES)
    if slow_consumer_s:
        consume = orch._host_process

        def slowed(boundary):
            time.sleep(slow_consumer_s)
            return consume(boundary)
        orch._host_process = slowed
    orch.start_training(background=False)
    assert orch.is_everything_done().state is ReplyState.COMPLETED
    return orch


def toy_engine(*, done_depth: int = DONE_DEPTH) -> ServeEngine:
    model = build_model(ModelConfig(kind="mlp", hidden_dim=16), WINDOW + 2,
                        head="ac")
    engine = ServeEngine(
        model, ServeConfig(max_batch=1, slots=8, batch_timeout_ms=0.0),
        model.init(jax.random.PRNGKey(1)), done_depth=done_depth)
    engine.warmup()
    return engine


def serve_ticks(engine, ticks: int, *, slow_consumer_s: float = 0.0,
                linger_s: float = 0.0) -> None:
    """``ticks`` one-row ticks. A slowed consumer holds two ticks in
    flight, so the dispatcher waits for a slot; one that also lingers after
    each tick, the next still in the depth-1 done queue, makes the
    dispatcher block in ``_done_q.put`` as well."""
    if slow_consumer_s:
        complete = engine._complete_batch

        def slowed(done):
            time.sleep(slow_consumer_s)
            complete(done)
        engine._complete_batch = slowed
    if linger_s:
        drain = engine._drain_spill_ops

        def lingering():
            time.sleep(linger_s)
            drain()
        engine._drain_spill_ops = lingering
    obs = np.concatenate([PRICES[:WINDOW], [2400.0, 0.0]]).astype(np.float32)
    handles = [engine.submit(f"s{i}", obs) for i in range(ticks)]
    assert all(h.wait(30.0) is not None for h in handles)


# -- the entry itself --------------------------------------------------------

@pytest.mark.parametrize("case", ["no_tracer_no_session",
                                  "disabled_tracer_no_session",
                                  "profile_dir_session"])
def test_host_span_cases(tmp_path, case):
    """The two ``Tracer.span`` cases of old (no profiler: a no-op; under
    ``runtime.profile_dir``: the span is in the device trace), on the one
    span path."""
    if case == "profile_dir_session":
        tracer = Tracer(str(tmp_path))
        with tracer.trace():
            with span("matmul", chunk=7):
                x = jnp.ones((64, 64))
                jax.block_until_ready(x @ x)
        assert ("matmul", {"chunk": 7}) in host_plane_events(tmp_path)
        return
    with Tracer(None).trace():                 # no profiler started
        if case == "no_tracer_no_session":
            ctx = span("x", chunk=1)
        else:
            ctx = host_span("x", SpanTracer(None), chunk=1)
        assert type(ctx) is jax.profiler.TraceAnnotation
        with ctx:
            pass
    assert list(tmp_path.iterdir()) == []


def test_one_call_lands_in_the_profiler_trace_and_in_trace_jsonl(tmp_path):
    tracer = SpanTracer(str(tmp_path / "trace.jsonl"))
    start_profiler(tmp_path / "prof")
    with tracer.span("serve/dispatch_tick", tick=5, rows=3) as sp:
        sp.set_metadata(cold=1)
    jax.profiler.stop_trace()
    tracer.close()
    ids = {"tick": 5, "rows": 3, "cold": 1}
    assert ("serve/dispatch_tick", ids) in host_plane_events(tmp_path / "prof")
    events = read_trace(str(tmp_path / "trace.jsonl"))
    assert [(e["name"], e.get("args")) for e in events if e["ph"] == "X"] \
        == [("serve/dispatch_tick", ids)]


def test_clock_event_lays_trace_jsonl_over_the_epoch(tmp_path):
    epoch, mono = clock_pair()
    assert abs(epoch - time.time()) < 5
    assert abs(mono - time.perf_counter()) < 5
    tracer = SpanTracer(str(tmp_path / "trace.jsonl"))
    before = time.time_ns()
    with tracer.span("a"):
        pass
    after = time.time_ns()
    tracer.close()
    clock, ev = read_trace(str(tmp_path / "trace.jsonl"))
    assert clock["name"] == "clock" and clock["ts"] == 0.0
    start_ns = clock["args"]["epoch_ns"] + ev["ts"] * 1e3
    assert before - 5e6 <= start_ns <= after + 5e6


# -- every span of the table, on the host plane ------------------------------

def test_profiler_session_holds_every_training_and_serving_span(tmp_path):
    start_profiler(tmp_path / "prof")
    orch = run_toy_training(toy_train_cfg(tmp_path, obs=True),
                            slow_consumer_s=0.05)
    engine = toy_engine()
    serve_ticks(engine, 4, slow_consumer_s=0.05, linger_s=0.1)
    gc.collect(2)
    jax.profiler.stop_trace()
    orch.stop()
    engine.stop(drain=False)
    on_host: dict[str, list[dict]] = {}
    for name, ids in host_plane_events(tmp_path / "prof"):
        on_host.setdefault(name, []).append(ids)
    for name, wanted in {**TRAIN_SPANS, **SERVE_SPANS, **GC_SPANS}.items():
        assert name in on_host, f"{name} is not on the host plane"
        assert all(wanted <= set(ids) for ids in on_host[name]), name
    # One dispatch span a chunk, the chunk's serial as its identifier.
    assert sorted(ids["chunk"] for ids in on_host["train/dispatch"]) \
        == [0, 1, 2, 3]
    assert sorted(ids["tick"] for ids in on_host["serve/dispatch_tick"]) \
        == [1, 2, 3, 4]
    assert not [n for n in on_host if n.startswith("train_chunk_")]
    assert 2 in {ids["gen"] for ids in on_host["host/gc"]}
    # The same spans, from the same calls, in the run's trace.jsonl; the
    # collections' too, the obs-enabled orchestrator's tracer being on.
    events = [e for e in read_trace(
        os.path.join(orch.cfg.obs.dir, "trace.jsonl")) if e["ph"] == "X"]
    assert set(TRAIN_SPANS) <= {e["name"] for e in events}
    collections = [e["args"] for e in events if e["name"] == "host/gc"]
    assert all(GC_SPANS["host/gc"] <= set(ids) for ids in collections)
    assert 2 in {ids["gen"] for ids in collections}


# -- the stage histograms ----------------------------------------------------

def test_training_histograms_observe_once_per_boundary(tmp_path):
    orch = run_toy_training(toy_train_cfg(tmp_path, obs=True),
                            slow_consumer_s=0.05)
    orch.stop()
    snaps = orch.metrics.histograms()
    boundaries = 4
    for name in ("train_dispatch_call_ms", "train_pipeline_stall_ms",
                 "train_host_process_ms"):
        assert snaps[name]["count"] == boundaries, name
    assert snaps["train_chunk_seconds"]["count"] == boundaries
    # The consumer sleeps 50 ms a boundary behind a depth-1 queue: the
    # dispatcher spends that time blocked in put (the first dispatch call
    # holds the step's compile, so the two are not compared).
    assert snaps["train_pipeline_stall_ms"]["sum"] > 50.0
    assert snaps["train_dispatch_call_ms"]["sum"] > 0
    assert snaps["train_host_process_ms"]["sum"] > 0
    assert orch.metrics.counters()["pipeline_stalls_total"] >= 1


def test_training_histograms_are_absent_with_obs_off(tmp_path):
    orch = run_toy_training(toy_train_cfg(tmp_path, obs=False))
    orch.stop()
    assert not [n for n in orch.metrics.histograms()
                if n.startswith(("train_", "host_gc_"))]
    assert not os.path.exists(orch.cfg.obs.dir)


@pytest.mark.parametrize("slow_consumer_s", [0.0, 0.03])
def test_serving_histograms_observe_once_per_tick(slow_consumer_s):
    engine = toy_engine()
    ticks = 6
    try:
        serve_ticks(engine, ticks, slow_consumer_s=slow_consumer_s)
    finally:
        engine.stop(drain=False)
    snaps = engine.registry.histograms()
    for name in ("serve_slot_wait_ms", "serve_tick_host_ms",
                 "serve_done_wait_ms", "serve_complete_host_ms",
                 "serve_inflight_ticks"):
        assert snaps[name]["count"] == ticks, name
    inflight = snaps["serve_inflight_ticks"]
    assert inflight["bounds"] == [float(n) for n in range(1, 17)]
    worst = max(b for b, c in zip(inflight["bounds"], inflight["counts"])
                if c)
    assert 1 <= worst <= 2 and inflight["counts"][-1] == 0
    if slow_consumer_s:
        # Two ticks in flight behind the consumer: the dispatcher waits
        # for a slot before it collects the next.
        assert worst == 2
        assert snaps["serve_slot_wait_ms"]["sum"] > 10.0
    else:
        assert snaps["serve_done_wait_ms"]["sum"] < \
            snaps["serve_tick_host_ms"]["sum"] + 50.0
    assert snaps["serve_complete_host_ms"]["sum"] > 0
    assert engine.registry.counters().get(
        "serve_trace_decomposition_error_total", 0.0) == 0.0


def test_requests_arriving_behind_two_ticks_ride_the_next_one():
    """Requests submitted while two ticks are in flight wait in the ingress
    queue, not in ticks of their own: once a slot frees they all ride the
    third tick, though each came after the last one's 1 ms coalescing
    window had closed."""
    model = build_model(ModelConfig(kind="mlp", hidden_dim=16), WINDOW + 2,
                        head="ac")
    engine = ServeEngine(
        model, ServeConfig(max_batch=8, slots=16, batch_timeout_ms=1.0),
        model.init(jax.random.PRNGKey(1)))
    engine.warmup()
    release = threading.Event()
    complete = engine._complete_batch

    def held(done):
        release.wait(30.0)
        complete(done)
    engine._complete_batch = held

    def dispatched(n):
        deadline = time.monotonic() + 30.0
        while engine._ticks_dispatched < n and time.monotonic() < deadline:
            time.sleep(0.001)
        return engine._ticks_dispatched

    obs = np.concatenate([PRICES[:WINDOW], [2400.0, 0.0]]).astype(np.float32)
    try:
        first = engine.submit("a", obs)
        assert dispatched(1) == 1
        second = engine.submit("b", obs)
        assert dispatched(2) == 2
        late = []
        for i in range(4):
            late.append(engine.submit(f"c{i}", obs))
            time.sleep(0.005)
        time.sleep(0.05)
        assert engine._ticks_dispatched == 2, "a tick launched behind two"
        release.set()
        assert all(h.wait(30.0) is not None for h in [first, second, *late])
    finally:
        release.set()
        engine.stop(drain=False)
    assert [h.trace.batch for h in (first, second)] == [1, 2]
    assert {h.trace.batch for h in late} == {3}
    assert engine.registry.histograms()["serve_tick_host_ms"]["count"] == 3


# -- the garbage collector's pauses ------------------------------------------

@pytest.mark.parametrize("generation", [0, 1, 2])
def test_a_forced_collection_is_one_pause_sample(generation):
    registry = MetricsRegistry()
    attach_gc_pauses(registry)
    was_enabled = gc.isenabled()
    gc.disable()                    # no collection but the forced one
    try:
        before = registry.histograms()
        gc.collect(generation)
        after = registry.histograms()
    finally:
        if was_enabled:
            gc.enable()
    added = {name: after[name]["count"] - before[name]["count"]
             for name in (GC_PAUSE_HISTOGRAM, GC_FULL_PAUSE_HISTOGRAM)}
    # The names the benchmark's host.gc_pause_ms / host.gc_full_pause_ms
    # read.
    assert added == {"host_gc_pause_ms": 1,
                     "host_gc_full_pause_ms": int(generation == 2)}
    assert after[GC_PAUSE_HISTOGRAM]["sum"] > before[GC_PAUSE_HISTOGRAM]["sum"]
    for snap in after.values():
        assert snap["count"] == sum(snap["counts"])


def test_engines_and_an_orchestrator_share_one_callback(tmp_path):
    engines = [toy_engine(), toy_engine()]
    orch = Orchestrator(toy_train_cfg(tmp_path, obs=True))
    try:
        ours = [cb for cb in gc.callbacks
                if isinstance(cb, obs_trace._GcHook)]
        assert len(ours) == 1
        registries = [e.registry for e in engines] + [orch.metrics]
        for name in (GC_PAUSE_HISTOGRAM, GC_FULL_PAUSE_HISTOGRAM):
            hists = [r.histogram(name) for r in registries]
            assert hists[0] is not None
            assert all(h is hists[0] for h in hists), name
    finally:
        orch.stop()
        for engine in engines:
            engine.stop(drain=False)


def test_reading_the_histograms_while_collections_run_cannot_deadlock():
    """The callback runs on whichever thread allocated, inside a snapshot
    too: the reader keeps what it reads, so under a low threshold its own
    copies set off collections, while more threads than cores force a
    thousand more. A short switch interval stops a writer in the middle of
    a sample; no snapshot may be torn and no sample lost."""
    registry = MetricsRegistry()
    attach_gc_pauses(registry)
    reads = []
    n_collectors = (os.cpu_count() or 1) + 1

    def reader():
        for _ in range(2000):
            reads.append(registry.histograms())

    def collector(n):
        for _ in range(n):
            gc.collect(0)

    share = [1000 // n_collectors + (i < 1000 % n_collectors)
             for i in range(n_collectors)]
    threads = [threading.Thread(target=reader, daemon=True)] + [
        threading.Thread(target=collector, args=(n,), daemon=True)
        for n in share]

    def counted():
        """(collections the interpreter ran, pause samples, full ones)."""
        gc.disable()
        try:
            snaps = registry.histograms()
            return (sum(st["collections"] for st in gc.get_stats()),
                    snaps[GC_PAUSE_HISTOGRAM]["count"],
                    snaps[GC_FULL_PAUSE_HISTOGRAM]["count"],
                    gc.get_stats()[2]["collections"])
        finally:
            gc.enable()

    threshold, interval = gc.get_threshold(), sys.getswitchinterval()
    before = counted()
    gc.set_threshold(10)
    sys.setswitchinterval(1e-5)
    try:
        deadline = time.monotonic() + 10.0
        for t in threads:
            t.start()
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
    finally:
        gc.set_threshold(*threshold)
        sys.setswitchinterval(interval)
    assert not [t for t in threads if t.is_alive()]
    after = counted()
    assert len(reads) == 2000
    assert all(snap["count"] == sum(snap["counts"])
               for snaps in reads for snap in snaps.values())
    ran, samples, full, full_ran = (b - a for a, b in zip(before, after))
    # A forced collection that meets another one running does nothing.
    assert ran > 0 and samples == ran and full == full_ran


def test_the_callback_queues_its_event_while_the_tracer_flushes(tmp_path):
    """A collection inside a ``trace.jsonl`` flush on its own thread: the
    event waits for the next flush instead of for the tracer's lock."""
    tracer = SpanTracer(str(tmp_path / "trace.jsonl"))
    attach_gc_pauses(MetricsRegistry(), tracer)

    def flush_then_collect():
        with tracer._lock:
            gc.collect(1)

    t = threading.Thread(target=flush_then_collect, daemon=True)
    t.start()
    t.join(10.0)
    assert not t.is_alive()
    tracer.close()
    assert 1 in {e["args"]["gen"] for e in read_trace(
        str(tmp_path / "trace.jsonl")) if e["name"] == "host/gc"}
