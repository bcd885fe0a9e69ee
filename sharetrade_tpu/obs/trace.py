"""Host spans: ONE entry, on the profiler's clock and in ``trace.jsonl``.

:func:`host_span` is the single way this package opens a host span. It
always opens a ``jax.profiler.TraceAnnotation(name, **ids)``: while a
profiler session runs (``runtime.profile_dir``, or whoever called
``jax.profiler.start_trace``) the span lands in the trace's ``/host:CPU``
plane, on that session's clock, beside the device operations; with no
session the annotation is inert (no name is encoded, nothing is stored).
Given an enabled :class:`SpanTracer` it ALSO writes the Chrome trace event
to ``trace.jsonl`` (Perfetto / chrome://tracing load it directly, no
profiler runtime required). ``SpanTracer.span`` / ``Obs.span`` are that
entry with the run's tracer; :func:`span` is it with none, for a caller
that holds no ``Obs`` bundle (``ServeEngine(obs=None)``). Names are fixed
strings; a chunk or tick serial is an argument, never part of the name, so
every consumer can aggregate by name. ``tools/lint_hot_loop.py`` (check 20)
keeps ``TraceAnnotation`` out of every other module, so no per-request or
per-agent-step annotation can slip in beside this helper.

``trace.jsonl``: the JSON Array Format of the Trace Event spec — an
opening ``[`` then one ``{event},`` per line. The spec makes the closing
``]`` optional precisely so crashed writers still leave a loadable trace,
which is also what makes the file greppable/tail-able like JSONL: every
event is one self-contained line. Events are buffered and flushed every
``flush_every`` records (and on close), so the hot loop pays a dict+append,
not a syscall, per span. Timestamps are microseconds on ``perf_counter``
from tracer construction; the file's FIRST event (``clock``) pairs that
origin with the epoch (:func:`clock_pair`): event start = ``epoch_ns + ts
* 1000``. An ``.xplane.pb`` read through ``ProfileData`` counts nanoseconds
from its session's start instead; every span here is in both files, so any
one of them gives the offset between the two.

``SpanTracer(None)`` is the disabled instance: nothing is ever opened or
written (the obs.enabled=false contract — zero files); its ``span()``
still hands back the bare annotation.

The interpreter's garbage collector is a host phase too: a collection holds
the GIL, so it stops every thread wherever it stands. :func:`attach_gc_pauses`
installs ONE ``gc.callbacks`` entry per process (the first time a registry
asks; never removed) that opens ``host/gc`` (identifier ``gen``; ``collected``
and ``uncollectable`` added at its end) through the same entry, and observes
each pause in ms into two process-wide histograms: ``host_gc_pause_ms``
(every generation) and ``host_gc_full_pause_ms`` (generation 2). The
callback runs on whichever thread allocated, possibly inside a histogram's
``snapshot()`` or a tracer's flush, so it takes no lock: its histograms have
the callback as their one writer and are read under a sequence number, and
its ``trace.jsonl`` event is queued for the tracer's next flush.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import threading
import time
from bisect import bisect_left
from collections import deque
from typing import Any

from sharetrade_tpu.obs.hist import Histogram

_TraceAnnotation = None


def _annotation_class():
    # Imported on first use: the fleet's router and supervisor processes
    # import this module and must stay off JAX.
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation


def span(name: str, **ids: Any):
    """:func:`host_span` with no tracer: the profiler annotation alone."""
    return _annotation_class()(name, **ids)


def clock_pair(samples: int = 5) -> tuple[float, float]:
    """``(time.time(), time.perf_counter())`` read together: the tightest
    of several samples, so the pairing error is bounded by the narrowest
    observed sampling window."""
    best = None
    for _ in range(samples):
        a = time.perf_counter()
        epoch = time.time()
        b = time.perf_counter()
        if best is None or (b - a) < best[2]:
            best = (epoch, (a + b) / 2.0, b - a)
    return best[0], best[1]


class _Span:
    """One in-flight span of an enabled tracer: the profiler annotation,
    and a complete ("ph": "X") ``trace.jsonl`` event on exit."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann", "_flush")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict, *,
                 flush: bool = True):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._ann = span(name, **args)
        self._flush = flush

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = self._tracer._now_us()
        return self

    def set_metadata(self, **ids: Any) -> None:
        """Identifiers known only once the phase is under way (the bare
        annotation has the same method)."""
        self._ann.set_metadata(**ids)
        self._args = {**self._args, **ids}

    def __exit__(self, *exc) -> None:
        t1 = self._tracer._now_us()
        self._ann.__exit__(*exc)
        self._tracer._emit({
            "name": self._name, "ph": "X", "ts": self._t0,
            "dur": t1 - self._t0, "pid": self._tracer._pid,
            "tid": threading.get_ident(),
            **({"args": self._args} if self._args else {}),
        }, flush=self._flush)


def host_span(name: str, tracer: "SpanTracer | None" = None, **ids: Any):
    """Context manager for one named host phase (module docstring)."""
    if tracer is None or tracer._fh is None:
        return span(name, **ids)
    return _Span(tracer, name, ids)


class SpanTracer:
    def __init__(self, path: str | None, *, flush_every: int = 64):
        self._path = path
        self._flush_every = max(1, flush_every)
        # Serialized events wait here for the next flush. Appending takes no
        # lock (a deque's append is atomic), so the garbage collector's
        # callback can queue its event from inside a flush on its own thread;
        # the lock only orders the flushes.
        # trace-buffer-ok: drained whole by every flush (each flush_every)
        self._pending: deque[str] = deque()
        self._lock = threading.Lock()
        self._pid = os.getpid()
        # Trace timestamps are microseconds on the perf_counter clock from
        # tracer construction; the leading clock event anchors ts=0 to the
        # epoch (a profiler trace's clock).
        epoch, self._t0 = clock_pair()
        self._fh = None
        if path:
            self._fh = open(path, "w", encoding="utf-8")
            self._fh.write("[\n" + json.dumps({
                "name": "clock", "ph": "i", "ts": 0.0, "s": "p",
                "pid": self._pid, "tid": threading.get_ident(),
                "args": {"epoch_ns": int(epoch * 1e9),
                         "perf_counter": self._t0}}) + ",\n")

    @property
    def enabled(self) -> bool:
        return self._fh is not None

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def span(self, name: str, **args: Any):
        """:func:`host_span` with this tracer."""
        return host_span(name, self, **args)

    def instant(self, name: str, **args: Any) -> None:
        """Zero-duration marker (lifecycle transitions, dumps, restarts)."""
        if self._fh is None:
            return
        self._emit({
            "name": name, "ph": "i", "ts": self._now_us(), "s": "p",
            "pid": self._pid, "tid": threading.get_ident(),
            **({"args": args} if args else {}),
        })

    def to_us(self, t_perf: float) -> float:
        """A raw ``time.perf_counter()`` stamp on this tracer's timeline —
        for RETROSPECTIVE emission (the serve engine stamps request edges
        as floats and emits the whole lifecycle at completion)."""
        return (t_perf - self._t0) * 1e6

    @property
    def pid(self) -> int:
        return self._pid

    def emit_lines(self, lines: list[str]) -> None:
        """Bulk-append PRE-SERIALIZED event lines (no trailing comma/
        newline) — the per-request hot path. The serve engine formats its
        request-lifecycle events with f-strings instead of per-event
        ``json.dumps`` (measured ~10x cheaper at 5 events/request on the
        completion thread); callers own the validity of what they hand in
        (tests round-trip it through :func:`read_trace`)."""
        if self._fh is None:
            return
        self._pending.extend(lines)
        self._flush_if_due()

    def _emit(self, event: dict, *, flush: bool = True) -> None:
        """Queue one event; ``flush=False`` (the garbage collector's
        callback) leaves the write, and its lock, to the next flush."""
        if self._fh is None:
            return
        self._pending.append(json.dumps(event))
        if flush:
            self._flush_if_due()

    def _flush_if_due(self) -> None:
        if len(self._pending) >= self._flush_every:
            with self._lock:
                self._flush_locked()

    def _flush_locked(self) -> None:
        n = len(self._pending)
        if n and self._fh is not None:
            pop = self._pending.popleft
            self._fh.write("".join(pop() + ",\n" for _ in range(n)))
            self._fh.flush()

    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    def close(self) -> None:
        with self._lock:
            self._flush_locked()
            if self._fh is not None:
                self._fh.close()
                self._fh = None


# ---------------------------------------------------------------------------
# The garbage collector's pauses (module docstring): one callback a process.

#: The two process-wide histograms the callback observes into, by the names
#: every registry that asks exports them under.
GC_PAUSE_HISTOGRAM = "host_gc_pause_ms"
GC_FULL_PAUSE_HISTOGRAM = "host_gc_full_pause_ms"


class _CallbackHistogram(Histogram):
    """A :class:`Histogram` whose one writer is the gc callback. The
    interpreter runs one collection at a time, so ``observe`` needs no lock;
    it bumps ``_seq`` to odd before its writes and back to even after, and
    ``snapshot`` copies until it reads one even ``_seq`` on both sides of the
    copy. A collection that lands inside the copy, on any thread, finishes
    its sample first and the copy runs again: nothing here can wait on the
    thread it interrupted."""

    __slots__ = ("_seq",)

    def __init__(self):
        super().__init__()
        self._seq = 0

    def observe(self, value: float) -> None:
        value = float(value)
        idx = bisect_left(self.bounds, value)
        self._seq += 1
        self.counts[idx] += 1
        self.sum += value
        self.count += 1
        self._seq += 1

    def snapshot(self) -> dict:
        while True:
            seq = self._seq
            snap = {"bounds": list(self.bounds), "counts": list(self.counts),
                    "sum": self.sum, "count": self.count}
            if not seq & 1 and seq == self._seq:
                return snap
            time.sleep(0)       # a writer on another thread: let it finish


class _GcHook:
    """The ``gc.callbacks`` entry: ``host/gc`` around each collection, and
    its pause observed into the two histograms. Every field is written by the
    callback alone (one collection at a time), except ``tracer``, which
    :func:`attach_gc_pauses` replaces by one assignment."""

    __slots__ = ("pauses", "full_pauses", "tracer", "_t0", "_span", "_gen")

    def __init__(self):
        self.pauses = _CallbackHistogram()
        self.full_pauses = _CallbackHistogram()
        self.tracer: SpanTracer | None = None
        self._t0 = 0.0
        self._span = None
        self._gen = 0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            gen = self._gen = info["generation"]
            tracer = self.tracer
            if tracer is not None and tracer._fh is not None:
                sp = _Span(tracer, "host/gc", {"gen": gen}, flush=False)
            else:
                sp = _TraceAnnotation("host/gc", gen=gen)
            sp.__enter__()
            self._span = sp
            return
        sp = self._span
        if sp is None:          # installed between a start and its stop
            return
        ms = (time.perf_counter() - self._t0) * 1e3
        self._span = None
        self.pauses.observe(ms)
        if self._gen == 2:
            self.full_pauses.observe(ms)
        sp.set_metadata(collected=info["collected"],
                        uncollectable=info["uncollectable"])
        sp.__exit__(None, None, None)


_GC_HOOK: _GcHook | None = None
_GC_INSTALL_LOCK = threading.Lock()


def attach_gc_pauses(registry: Any, tracer: "SpanTracer | None" = None
                     ) -> None:
    """Attach the process's two gc-pause histograms to ``registry``,
    installing the one ``gc.callbacks`` entry on the first call. An enabled
    ``tracer`` becomes the one the callback writes ``trace.jsonl`` events
    to; a call without one leaves the callback's tracer as it was."""
    global _GC_HOOK
    _annotation_class()         # imported now: a callback must not import
    with _GC_INSTALL_LOCK:
        if _GC_HOOK is None:
            _GC_HOOK = _GcHook()
        hook = _GC_HOOK
        if hook not in gc.callbacks:
            gc.callbacks.append(hook)
    if tracer is not None and tracer.enabled:
        hook.tracer = tracer
    registry.attach_histogram(GC_PAUSE_HISTOGRAM, hook.pauses)
    registry.attach_histogram(GC_FULL_PAUSE_HISTOGRAM, hook.full_pauses)


# ---------------------------------------------------------------------------
# Cross-process wire spans (ISSUE 17): every fleet process journals its
# finished spans to a bounded per-process CRC-framed file; obs/collect.py
# stitches them by trace id into one Perfetto trace. Timestamps are raw
# ``time.perf_counter()`` floats — processes do NOT share that clock, so
# each journal records a monotonic→epoch anchor the collector aligns with.


def new_trace_id() -> str:
    """A fresh 16-hex-char trace id (random, collision-free in practice);
    minted once per inbound request at whichever hop first finds no
    ``X-Trace-Id`` header (the client when traced, else the frontend)."""
    return os.urandom(8).hex()


class SpanJournal:
    """Bounded per-process span journal: CRC-framed batches, segment
    rotation, oldest-first pruning — the data/journal.py frame (ONE
    framing definition; every file replays through
    ``iter_framed_records``) without its writer-lock/fsync weight: span
    files are keyed by (process label, pid) so two writers can never
    share one, and spans are telemetry — a torn tail loses at most the
    last unflushed batch, never correctness.

    Clock contract (the correctness core the collector leans on): at
    open, ONE ``(epoch=time.time(), mono=time.perf_counter())`` pair is
    captured — the tightest of several samples, so the pairing error is
    bounded by the narrowest observed sampling window — and a clock line
    carrying it leads EVERY flushed batch payload. Each record is
    therefore self-describing: segment pruning or a torn tail can never
    orphan spans from their alignment offset."""

    def __init__(self, directory: str, proc: str, *,
                 max_records: int = 4096, max_segments: int = 8):
        self.dir = directory
        self.proc = proc
        self.pid = os.getpid()
        os.makedirs(directory, exist_ok=True)
        self.path = os.path.join(directory,
                                 f"spans-{proc}-{self.pid}.journal")
        self.epoch, self.mono = clock_pair()
        self._clock_line = json.dumps(
            {"clock": 1, "proc": proc, "pid": self.pid,
             "epoch": self.epoch, "mono": self.mono},
            separators=(",", ":")).encode()
        self._max_records = max(1, int(max_records))
        self._max_segments = max(1, int(max_segments))
        self._records = 0
        self._lock = threading.Lock()
        self._fh = open(self.path, "ab")

    def append_batch(self, lines: list[bytes]) -> None:
        """Append ONE framed record: the clock line plus ``lines``
        (newline-joined pre-serialized span events). Flushed to the OS
        immediately — the page cache survives a SIGKILLed writer, which
        is what lets a dead engine's ingress spans reach the stitched
        trace of a migrated request."""
        from sharetrade_tpu.data.journal import frame_record
        payload = b"\n".join([self._clock_line, *lines])
        record = frame_record(payload)
        with self._lock:
            if self._fh is None:
                return
            self._fh.write(record)
            self._fh.flush()
            self._records += 1
            if self._records >= self._max_records:
                self._rotate_locked()

    def _rotate_locked(self) -> None:
        from sharetrade_tpu.data.journal import segment_paths
        self._fh.close()
        existing = segment_paths(self.path)
        last = int(existing[-1].rsplit(".seg", 1)[1]) if existing else 0
        os.rename(self.path, f"{self.path}.seg{last + 1:08d}")
        for stale in segment_paths(self.path)[:-self._max_segments]:
            try:
                os.unlink(stale)
            except OSError:
                pass
        self._fh = open(self.path, "ab")
        self._records = 0

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


class SpanSink:
    """Hot-path wire-span buffer: one tuple append per finished span into
    a BOUNDED ring, serialization deferred to the batched flush (one
    ``json.dumps`` per span at flush cadence, one framed journal append
    per batch) — the emission discipline tools/lint_hot_loop.py check 16
    pins for the evloop runner and router relay closures. Overflow drops
    the oldest spans (counted in ``dropped``) instead of growing."""

    def __init__(self, journal: SpanJournal, *, capacity: int = 8192,
                 flush_every: int = 128):
        self._journal = journal
        self._flush_every = max(1, int(flush_every))
        # trace-buffer-ok: bounded ring (maxlen); overflow counted, not grown
        self._buf: deque = deque(maxlen=max(self._flush_every,
                                            int(capacity)))
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._id_prefix = f"{journal.pid:x}"
        self.proc = journal.proc
        self.dropped = 0

    def new_span_id(self) -> str:
        """Pid-prefixed counter hex — unique across processes without
        per-span entropy syscalls."""
        return f"{self._id_prefix}.{next(self._ids):x}"

    def span(self, trace_id: str, span_id: str, parent: str, name: str,
             t0: float, t1: float | None, note: str = "") -> None:
        """Record one finished span (``t0``/``t1`` on this process's
        ``perf_counter`` clock; ``t1=None`` = instant event)."""
        with self._lock:
            buf = self._buf
            if len(buf) == buf.maxlen:
                self.dropped += 1
            buf.append((trace_id, span_id, parent, name, t0, t1, note))
            if len(buf) >= self._flush_every:
                self._flush_locked()

    def instant(self, trace_id: str, span_id: str, parent: str, name: str,
                note: str = "", *, flush: bool = False) -> None:
        """Zero-duration marker at now; ``flush=True`` makes it DURABLE
        before returning (the engine-ingress eager flush: a SIGKILLed
        engine must still leave trace evidence for in-flight requests)."""
        self.span(trace_id, span_id, parent, name,
                  time.perf_counter(), None, note)
        if flush:
            self.flush()

    def _flush_locked(self) -> None:
        if not self._buf:
            return
        lines = []
        for trace_id, span_id, parent, name, t0, t1, note in self._buf:
            ev: dict = {"trace": trace_id, "span": span_id,
                        "parent": parent, "name": name, "t0": t0}
            if t1 is not None:
                ev["t1"] = t1
            if note:
                ev["note"] = note
            lines.append(json.dumps(ev, separators=(",", ":")).encode())
        self._buf.clear()
        self._journal.append_batch(lines)

    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    def close(self) -> None:
        self.flush()
        self._journal.close()


def read_trace(path: str) -> list[dict]:
    """Parse a (possibly unterminated) JSON-Array-Format trace back into
    event dicts — the reader the `cli obs` summary and tests share."""
    with open(path, encoding="utf-8") as f:
        content = f.read()
    content = content.strip()
    if not content or content == "[":
        return []
    if not content.endswith("]"):
        content = content.rstrip(",") + "]"
    return json.loads(content)
