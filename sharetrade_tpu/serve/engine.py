"""Continuous-batching inference engine: one device program per tick.

The training side of this repo compiles everything; nothing served. This
module is ROADMAP item 2's serving tier: a policy-inference engine that
coalesces per-user ``(window, portfolio)`` queries into padded device
batches under a deadline (``serve.max_batch`` / ``serve.batch_timeout_ms``)
and keeps a fixed-capacity device-resident SESSION SLOT POOL — a
``(slots + max_batch, ...)`` arena of per-session recurrent carries, the
episode transformer's incremental K/V cache repurposed as a per-session
serving cache — so steady-state serving is ONE jitted batched program per
tick instead of a dispatch per request. That is the TF-Agents
batched-simulation thesis (arxiv 1709.02878) applied to inference, and
RLAX's TPU inference/learner decoupling (arxiv 2512.06392): throughput
comes from keeping one big batched program resident, not from many small
calls.

Structure (mirrors ``runtime/pipeline.py``'s dispatcher/consumer split):

- **submit** (any thread): enqueue a request; returns a waitable handle.
- **dispatcher thread** (``_serve_loop``): coalesce a batch (first request
  waits at most ``batch_timeout_ms``; a full batch never waits), admit
  sessions into the slot pool (LRU eviction; evicted sessions restart COLD
  through the batched prefill), and dispatch the jitted program(s) for the
  tick — asynchronously, so collection of tick k+1 overlaps device compute
  of tick k. No blocking host work happens here (tools/lint_hot_loop.py
  check 8).
- **consumer thread** (``_complete_batch``): device readback, request
  completion (events + callbacks), latency accounting, SLO gauge
  publication through ``MetricsRegistry`` (→ ``metrics.prom`` when obs
  export is on). The dispatcher collects a tick only while fewer than
  two are launched and unread (one running on the device, one staged
  behind it), so a request rides the tick after the running one instead
  of queueing behind a pile of launched ticks, and in-flight device
  buffers stay bounded.

Weight swaps are ATOMIC between batches: :meth:`ServeEngine.swap_params`
replaces one ``(params, step)`` reference; the dispatcher reads it exactly
once per tick, so every response is attributable to exactly one checkpoint
step and no batch ever sees mixed weights (serve/swap.py is the
``tag_best`` watcher that calls it through the verified restore path).

Model contract: models providing ``apply_prefill``/``apply_serve_batch``
(the episode transformer) get the two-program cold/warm split — per-row
episode clocks, heterogeneous sessions in one batch. Everything else is
served through ``apply_batched`` in one program with an in-program cold-row
carry reset (stateless models like the MLP carry ``()`` and the pool is
structurally empty).

Parity contract (tests/test_serve.py): under fp32 the batched engine
returns BIT-IDENTICAL logits/actions to threading each session one at a
time through ``model.apply`` — batching is a scheduling optimization,
never a numerics change. bf16_mixed serving inherits the PR-7 tolerance
contract instead.

Overload & failure semantics (ISSUE 10; tools/serve_chaos.py pins them):

- **Admission control**: the ingress queue is bounded at
  ``serve.max_queue``; a submit past the bound never blocks and never
  grows host memory — the new request is refused
  (``shed_policy="reject"``) or the oldest queued request is shed
  (``"oldest"``), the loser completing immediately with
  :class:`ServeRejected`. Counters ``serve_queue_rejected_total`` /
  ``serve_shed_total``, gauge ``serve_overload``.
- **Deadlines**: ``submit(..., deadline_ms=)`` (default
  ``serve.default_deadline_ms``) expires un-dispatched requests with
  :class:`ServeDeadlineExceeded` at batch-collection time, before they
  can occupy a padded device row; coalescing waits are clamped to the
  earliest surviving deadline. Counter ``serve_deadline_expired_total``.
- **Supervision** (``serve.max_restarts > 0``): a dispatch/consumer
  fault fails its batch, then the engine itself is retried — fresh
  jitted programs + fresh slot arena under seeded exponential backoff
  (``serve.restart_backoff_s``); sessions re-enter cold through the
  batched prefill (bitwise-equivalent to a fresh session, the PR-8
  eviction contract). More than ``max_restarts`` CONSECUTIVE faults trip
  a terminal failed state that fails all queued work loudly
  (:class:`ServeEngineFailed`) instead of wedging. Counter
  ``serve_restarts_total``, gauge ``serve_failed``.

Every submitted request reaches exactly one terminal outcome — result,
rejection, deadline error, batch failure, or engine failure — the chaos
soak's core invariant.

Observability (ISSUE 11). Every request carries a :class:`RequestTrace`
stamped at each lifecycle edge (submitted → collected → dispatched →
device-complete → callback-complete, plus the shed/expired/failed
terminal edges and deferral counts). From the stamps the engine derives,
ALWAYS (they are the SLO gauges' source):

- **per-stage histograms** (obs/hist.py; fixed log buckets, exact
  merge): ``serve_queue_wait_ms`` / ``serve_batch_wait_ms`` /
  ``serve_device_ms`` / ``serve_readback_ms`` / ``serve_request_ms`` —
  and the ``serve_p50_ms``/``serve_p99_ms`` gauges are now quantiles of
  the end-to-end histogram's per-window bucket DELTA (cumulative counts
  subtract exactly), replacing the old sample-ring percentiles;
- **stage decomposition invariant**: for every completed request
  queue_wait + batch_wait + device == latency_ms by construction
  (telescoping perf_counter stamps); a violation increments
  ``serve_trace_decomposition_error_total``, which the soaks assert
  stays 0 (``ServeResult.stages`` carries the breakdown per response);
- **exemplars**: a bounded ring of the K slowest requests per stats
  window with their full stage breakdown (``obs.exemplar_k``), written
  to ``serve_exemplars.json`` when obs is on and recorded into the
  flight ring on overload onset / SLO burn / supervised restart /
  terminal failure;
- **SLO burn rates** (``obs.slo_*``): rolling error-budget burn gauges
  ``serve_slo_availability_burn`` (sheds/expiries/failures against the
  availability objective) and ``serve_slo_latency_burn`` (fraction over
  the target p99 against the 1% allowance), with a flight-recorder
  event on threshold crossing — the per-engine signal a fleet router
  aggregates.

Session tiers (ISSUE 18; ``serve.warm_bytes``). The slot pool is the
HOT tier of a hot/warm/cold hierarchy that lets one engine serve a
session POPULATION far larger than its device arena:

- **hot**: a device slot — the carry lives in the arena, steady-state
  requests run the warm program (unchanged).
- **warm**: a PARKED carry in :class:`WarmStore`, a bounded
  byte-budgeted host-RAM LRU. On eviction the victim's arena row is
  batch-gathered on the dispatch thread (async device op, never a
  readback), and the CONSUMER thread pages it out (``device_get`` —
  blocking host work belongs there, lint check 17) into the dispatcher's
  park inbox; the dispatcher commits it to the store, dropping entries
  whose session already re-entered (stale). A returning session's
  parked carry is reinstalled through the batched scatter path
  (``device_put`` + one jitted donated scatter) and the session
  continues BITWISE-identically to one that was never evicted — the
  round trip is an exact byte copy, the tier's acceptance oracle.
- **spill** (ISSUE 20; ``serve.spill_dir``): the warm store's overflow
  — and every live/parked carry at drain — seals into a
  crash-consistent on-disk parked-carry arena (serve/spill.py: CRC +
  step stamp + atomic rename), so RAM stops being the warm bound and a
  carry survives its writer's SIGKILL. The arena directory is SHARED
  across a fleet (fleet/pool.py): after an engine dies or drains, the
  engine the router reassigns a session to ADOPTS its carry — paged in
  iff the record's step stamp equals the session's expected clock (the
  router-forwarded completed-response count; an engine-local take with
  no clock accepts only its own incarnation's records). A stale, torn,
  or CRC-bad record demotes to cold — injected corruption can change
  latency, never bytes. Spill disk I/O rides the CONSUMER thread like
  page-out readback does (the dispatcher enqueues put/take/delete ops
  and only ever pays one ``os.stat`` probe); an adopted carry lands in
  the warm store and re-enters through the same batched scatter path,
  so an adopted session is bitwise an uninterrupted one.
- **cold**: everything else — the pre-existing
  restart-through-batched-prefill path, unchanged, and still what a
  warm-tier overflow demotes to (stalest parked carry first) when the
  spill tier is off or refuses the record.

``warm_bytes=0`` (default) disables the tier: every eviction is a cold
restart, bitwise-identical to the PR-8 contract. Eviction economics is
a live gauge: ``serve_warm_econ_ms_per_mb`` — prefill-recompute
milliseconds avoided by warm hits this stats window, per MB of carry
bytes held (EWMA'd cold device time × window hits / held MB). A spill
adoption flows through the warm store and counts as a warm hit at
admission, so the econ gauge prices spill hits too.

With obs enabled (``obs.request_trace``), the lifecycle additionally
emits through obs/trace.py as nested ASYNC spans keyed by
request/batch/session ids, so Perfetto renders request flows through the
batches the dispatcher coalesced them into; off by default, zero
artifacts, and the stamps themselves are a few ``perf_counter`` calls
per request (<2% measured — ``bench_obs_overhead`` serve arm).
"""

from __future__ import annotations

import itertools
import json
import math
import os
import queue
import random
import re
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from sharetrade_tpu.config import ConfigError, ServeConfig
from sharetrade_tpu.models.core import apply_batched
from sharetrade_tpu.obs import SERVE_STAGES
from sharetrade_tpu.obs.hist import Histogram
from sharetrade_tpu.obs.trace import attach_gc_pauses, span as trace_span
from sharetrade_tpu.precision import FP32, PrecisionPolicy
from sharetrade_tpu.serve.spill import SpillArena
from sharetrade_tpu.utils.logging import get_logger
from sharetrade_tpu.utils.metrics import MetricsRegistry

log = get_logger("serve")

_SHUTDOWN = object()
#: Done-queue nudge: the dispatcher enqueues spill ops for the consumer
#: and pokes this sentinel (put_nowait — best-effort; a full queue means
#: the consumer is already awake) so an IDLE consumer executes the disk
#: ops now instead of after its 200 ms poll.
_SPILL_TICK = object()
#: Ticks launched and not yet consumed before the dispatcher collects the
#: next: one running on the device and one staged behind it. The least
#: that keeps the device fed while the host closes and stages a tick in
#: less than a tick's device time; where it needs more, the count never
#: reaches the bound and the wait never engages.
_MAX_INFLIGHT_TICKS = 2

#: Session ids made only of these characters embed into trace JSON
#: without escaping (the fast path — harness/CLI ids are all of this
#: shape); anything else routes through json.dumps.
_SID_SAFE = re.compile(r"[A-Za-z0-9_\-#.:]*\Z").match


def _gather_rows(pool, idx):
    """Rows ``idx`` of every leaf of the arena ``pool``: bit for bit
    ``jax.tree.map(lambda x: x[idx], pool)`` for in-bounds indices, unique
    or repeated, over any carry pytree (leaves of any rank >= 1, any
    dtype), reading ``len(idx)`` rows of each leaf and nothing else.

    Not ``x[idx]``: that is an XLA gather, and where a trailing axis does
    not fill its tile the TPU compiler relayouts the WHOLE leaf first (a
    window of 201 became a 128-wide and a 73-wide copy of every row of the
    arena, 7 GB read and written for the 211 MB asked for: three quarters
    of a warm tick on the v5e — PERF.md, PR 29). A row is contiguous in
    any layout, so one ``dynamic_slice`` a row is a plain copy, the
    read-side twin of the per-row update ``.at[idx].set`` lowers to."""
    def copy_row(i, rows):
        return jax.tree.map(
            lambda x, out: lax.dynamic_update_slice_in_dim(
                out, lax.dynamic_slice_in_dim(x, idx[i], 1, axis=0), i,
                axis=0),
            pool, rows)

    batch = idx.shape[0]
    return lax.fori_loop(0, batch, copy_row, jax.tree.map(
        lambda x: jnp.empty((batch,) + x.shape[1:], x.dtype), pool))


class ServeRejected(RuntimeError):
    """The request was refused admission (ingress queue at
    ``serve.max_queue`` under ``shed_policy="reject"``) or shed from the
    queue under overload (``shed_policy="oldest"``). Always delivered as a
    completed handle (``wait()`` returns None, :attr:`_Request.error`
    carries this), never as a silent block of the caller's thread.
    ``reason`` is ``"queue_full"`` / ``"shed_oldest"`` /
    ``"deferred_overflow"``."""

    def __init__(self, message: str, *, reason: str):
        super().__init__(message)
        self.reason = reason


class ServeDeadlineExceeded(RuntimeError):
    """The request's deadline (``submit(..., deadline_ms=)`` or
    ``serve.default_deadline_ms``) expired before it reached a device
    batch; it was completed with this error instead of occupying a padded
    device row."""


class ServeEngineFailed(RuntimeError):
    """The engine tripped its terminal failed state: more than
    ``serve.max_restarts`` consecutive dispatch/consumer faults. All
    queued and future work fails loudly with this error (wrapping the
    last underlying fault) instead of wedging."""


def latency_percentiles(values) -> dict[str, float]:
    """p50/p99/mean over a latency sample, ONE quantile convention for the
    whole serving tier (the SLO gauges here, the load harnesses in
    serve/driver.py, and the histogram quantiles in obs/hist.py —
    BASELINE.md compares them directly, so the percentile math must never
    diverge).

    Convention: NEAREST-RANK, rank = ceil(q·n), 1-indexed. The old
    ``int(q * (n - 1))`` floored the rank and systematically UNDERSTATED
    the tail at small n (with n=10 its "p99" was the 9th value — really
    p90); ceil(q·n) is the standard nearest-rank estimator whose reported
    p99 is a value at least 99% of the sample does not exceed."""
    if not len(values):
        return {"p50_ms": 0.0, "p99_ms": 0.0, "mean_ms": 0.0}
    arr = np.sort(np.asarray(values, np.float64))
    n = len(arr)

    def nearest_rank(q: float) -> float:
        return float(arr[min(max(math.ceil(q * n), 1), n) - 1])

    return {
        "p50_ms": nearest_rank(0.50),
        "p99_ms": nearest_rank(0.99),
        "mean_ms": float(arr.mean()),
    }


class RequestTrace:
    """Lifecycle stamps of one request, on the ``perf_counter`` clock.

    Stamps telescope, so the stage decomposition of a completed request
    sums EXACTLY to its end-to-end latency:

    ``queue_wait`` (t_enq→t_collected) + ``batch_wait``
    (t_collected→t_dispatched) + ``device`` (t_dispatched→t_device,
    device compute + readback of its group) == ``latency_ms``
    (t_device - t_enq); ``readback`` (t_device→t_done) is the
    completion/callback wait on top — the trace span shows that
    client-observable wall wait, while the ``serve_readback_ms``
    histogram charges each request only its OWN completion slice (the
    consumer serializes a batch's callbacks). Unstamped edges stay
    None (a shed request never collected; an expired one never
    dispatched)."""

    __slots__ = ("rid", "t_enq", "t_collected", "t_dispatched", "t_device",
                 "t_done", "deferrals", "cold", "batch", "outcome",
                 "trace_id", "parent_span")

    def __init__(self, rid: int, t_enq: float):
        self.rid = rid
        self.t_enq = t_enq
        self.t_collected: float | None = None
        self.t_dispatched: float | None = None
        self.t_device: float | None = None
        self.t_done: float | None = None
        self.deferrals = 0          # same-session ticks waited out
        self.cold = False           # served through the batched prefill
        self.batch: int | None = None   # dispatch tick serial
        self.outcome: str | None = None
        #: Fleet-wide trace identity (ISSUE 17): set by the wire backend
        #: (fleet/frontend.py) when the request arrived with trace
        #: headers, None for local/untraced submits — stitches this
        #: engine's chrome-trace spans to the cross-process trace.
        self.trace_id: str | None = None
        self.parent_span: str | None = None


class ServeResult(NamedTuple):
    """One completed inference: the action plus enough provenance to audit
    it (``params_step`` names the exact checkpoint that produced it — the
    hot-swap atomicity observable). ``stages`` is the request's latency
    decomposition (``queue_wait_ms``/``batch_wait_ms``/``device_ms``,
    summing exactly to ``latency_ms`` — the invariant the soaks assert);
    None only from servers that don't stage-stamp (BatchOneServer)."""

    session_id: Any
    action: int
    logits: np.ndarray
    value: float
    params_step: int
    latency_ms: float
    stages: dict | None = None


class _Live(NamedTuple):
    """The serving weights as ONE immutable reference: swapped atomically
    (a single attribute store), read exactly once per dispatch tick."""

    params: Any
    step: int


class _LiveKnobs(NamedTuple):
    """The engine's RUNTIME-TUNABLE knobs as one immutable reference —
    the same atomicity pattern as :class:`_Live` weights: swapped by
    :meth:`ServeEngine.set_knobs` (the online controller's actuator,
    serve/controller.py), read once per decision site, so a tick never
    sees a half-applied knob vector. The CONFIGURED values are the
    ceilings: the controller only ever tightens below them (config is
    the operator's safety rail, never something the controller can
    exceed)."""

    batch_timeout_ms: float
    max_queue: int


class _Request:
    """A submitted query; completed by the consumer thread (or, for
    rejected/expired work, by the thread that discovered the terminal
    outcome)."""

    __slots__ = ("session_id", "obs", "t_enq", "t_deadline", "callback",
                 "_event", "result", "error", "trace", "clock")

    def __init__(self, session_id: Any, obs: np.ndarray,
                 callback: Callable[[ServeResult | None], None] | None,
                 deadline_ms: float = 0.0, rid: int = 0,
                 clock: int | None = None):
        self.session_id = session_id
        self.obs = obs
        #: The session's EXPECTED step clock (ISSUE 20): the router's
        #: completed-response count, forwarded over the wire on
        #: migration so the adopting engine accepts a spilled carry iff
        #: its step stamp matches. None = local submit, no fleet clock —
        #: adoption falls back to the engine's own incarnation check.
        self.clock = clock
        self.t_enq = time.perf_counter()
        #: Lifecycle stamps (always kept — the per-stage histograms' and
        #: SLO gauges' source; the async trace spans ride them when obs
        #: request tracing is on).
        self.trace = RequestTrace(rid, self.t_enq)
        #: Absolute expiry on the perf_counter clock; None = no deadline.
        #: A NEGATIVE deadline_ms (a client whose latency budget already
        #: ran out before submit) means already-expired — clamped to the
        #: enqueue instant, NOT silently promoted to "no deadline".
        self.t_deadline = (self.t_enq + max(deadline_ms, 0.0) / 1e3
                           if deadline_ms else None)
        self.callback = callback
        self._event = threading.Event()
        self.result: ServeResult | None = None
        #: Set when the request failed terminally without a result —
        #: ServeRejected (admission/shedding), ServeDeadlineExceeded,
        #: ServeEngineFailed, or the dispatch fault that failed its batch
        #: — so callers can distinguish failure from a wait() timeout.
        self.error: BaseException | None = None

    def wait(self, timeout: float | None = None) -> ServeResult | None:
        """Block until the response is ready; None on timeout or when the
        request failed (then :attr:`error` carries the cause)."""
        self._event.wait(timeout)
        return self.result


class _DoneBatch(NamedTuple):
    """One dispatched tick handed dispatcher→consumer: per-program request
    groups with their (still device-resident) outputs."""

    #: (reqs, act, log, val, stats): ``stats`` the model step's
    #: ``ModelOut.stats`` of a warm tick, None where the model has none
    groups: list[tuple[list[_Request], Any, Any, Any, Any]]
    step: int
    n: int                 # real rows in the tick
    cold: int              # rows served through the prefill
    evicted: int           # sessions evicted to admit this tick's rows
    #: Supervision fault epoch at dispatch time: only a batch dispatched
    #: AFTER the latest fault may reset the consecutive-fault streak —
    #: pre-fault batches draining out of the done queue during a backoff
    #: attest nothing about post-fault engine health.
    epoch: int = 0
    #: Page-out payload (warm tier on, this tick evicted someone): the
    #: victims' session ids and their still-device-resident carry rows
    #: (stacked at the max_batch shape; only the first len(parked_sids)
    #: rows are real). The CONSUMER device_gets the rows and hands the
    #: host copies back through the dispatcher's park inbox.
    parked_sids: tuple = ()
    parked_rows: Any = None
    #: The victims' dispatched-step stamps (parallel to parked_sids):
    #: popped by the dispatcher at eviction time and carried through the
    #:  readback so the committed warm entry — and any spill record it
    #: later demotes into — is sealed with the right adoption clock.
    parked_steps: tuple = ()
    #: The dispatcher's batch serial: the identifier the tick's host spans
    #: share across the two threads.
    tick: int = 0


class SlotPool:
    """Host-side session→slot map with LRU eviction.

    The carries themselves live on DEVICE in the engine's arena; this class
    owns only the mapping and the recency order. ``admit`` never evicts a
    session pinned by the current batch (its slot is about to be read or
    written) — with ``capacity >= max_batch`` an unpinned victim or a free
    slot always exists."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._lru: OrderedDict[Any, int] = OrderedDict()  # oldest first
        self._free = list(range(capacity))
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._lru)

    def lookup(self, session_id: Any) -> int | None:
        """Slot of a WARM session (refreshes its recency); None when the
        session is absent (never admitted, or evicted — cold either way)."""
        slot = self._lru.get(session_id)
        if slot is not None:
            self._lru.move_to_end(session_id)
        return slot

    def contains(self, session_id: Any) -> bool:
        """Membership WITHOUT a recency refresh — the park-inbox
        staleness check (a session that re-entered the pool before its
        page-out committed makes that parked carry stale)."""
        return session_id in self._lru

    def drop(self, session_id: Any) -> None:
        """Forget a session (its slot returns to the free list) — the
        dispatch-fault path, where an admitted slot may never have
        received its prefilled carry."""
        slot = self._lru.pop(session_id, None)
        if slot is not None:
            self._free.append(slot)

    def admit(self, session_id: Any, pinned: set) -> tuple[int, Any | None]:
        """Assign a slot to a NEW session; returns ``(slot, evicted_sid)``
        (``evicted_sid`` None when a free slot absorbed the admission)."""
        if self._free:
            slot = self._free.pop()
            self._lru[session_id] = slot
            return slot, None
        for victim in self._lru:                       # oldest first
            if victim not in pinned:
                slot = self._lru.pop(victim)
                self._lru[session_id] = slot
                self.evictions += 1
                return slot, victim
        raise RuntimeError(
            "slot pool exhausted by pinned sessions (capacity < max_batch "
            "should have been rejected at construction)")


class WarmStore:
    """The WARM session tier: a bounded, byte-budgeted LRU of PARKED
    carries (host numpy trees read back by the consumer thread's
    page-out). Owned by ONE thread — the dispatcher commits, hits, and
    demotes; no lock guards the map. The stats other threads publish
    (``bytes``/``len``) read single references, atomic under the GIL.

    Bounded by construction (lint check 17): every ``put`` demotes
    stalest-first until BOTH the byte budget and the session bound hold
    again, and a single carry larger than the whole budget is refused
    outright (that session pages straight to cold)."""

    def __init__(self, max_bytes: int, max_sessions: int):
        self.max_bytes = int(max_bytes)
        self.max_sessions = max(1, int(max_sessions))
        #: session -> (rows, nbytes, steps): the carry, its footprint,
        #: and the session's dispatched-step stamp at park time (ISSUE
        #: 20 — the stamp travels with the carry so a demotion to the
        #: spill tier seals the right adoption clock into the record).
        self._lru: OrderedDict[Any, tuple[Any, int, int]] = OrderedDict()
        self.bytes = 0
        # Event totals (dispatcher-thread writes; readers see ints).
        self.demotions = 0
        self.refusals = 0
        self.stale_drops = 0

    def __len__(self) -> int:
        return len(self._lru)

    def contains(self, session_id: Any) -> bool:
        """Membership WITHOUT a recency refresh or removal — the
        dispatcher's spill-probe gate (a RAM-parked session never needs
        a disk take)."""
        return session_id in self._lru

    def pop(self, session_id: Any) -> tuple[Any, int] | None:
        """Remove and return a parked ``(carry, steps)`` (the warm HIT —
        unpark); None on a miss (never parked, demoted, or page-out
        still in flight — cold either way)."""
        entry = self._lru.pop(session_id, None)
        if entry is None:
            return None
        rows, nbytes, steps = entry
        self.bytes -= nbytes
        return rows, steps

    def discard(self, session_id: Any) -> None:
        """Forget a parked carry without returning it (poisoned/dropped
        sessions must not resurrect an old episode state)."""
        self.pop(session_id)

    def put(self, session_id: Any, rows: Any, nbytes: int,
            steps: int = 0) -> list:
        """Park one carry; returns the ENTRIES demoted to make room
        (stalest first, as ``(session, rows, nbytes, steps)`` tuples —
        the caller spills them to disk when the spill tier is on, or
        lets them fall to cold). A carry that cannot fit the budget at
        all is refused — the caller's session simply stays cold."""
        nbytes = int(nbytes)
        if nbytes <= 0 or nbytes > self.max_bytes:
            self.refusals += 1
            return []
        old = self._lru.pop(session_id, None)
        if old is not None:
            self.bytes -= old[1]
        self._lru[session_id] = (rows, nbytes, int(steps))
        self.bytes += nbytes
        demoted = []
        # The boundedness contract: demote stalest-first until both the
        # byte budget and the session bound hold (terminates — the entry
        # just parked fits the budget on its own).
        while (self.bytes > self.max_bytes
               or len(self._lru) > self.max_sessions):
            victim, (vrows, vbytes, vsteps) = self._lru.popitem(last=False)
            self.bytes -= vbytes
            self.demotions += 1
            demoted.append((victim, vrows, vbytes, vsteps))
        return demoted


class ServeEngine:
    """See the module docstring. Construct, :meth:`warmup` (optional but
    recommended — compiles the serving programs before traffic), submit
    from any thread, :meth:`stop` when done."""

    def __init__(self, model: Any, cfg: ServeConfig, params: Any, *,
                 params_step: int = 0,
                 precision: PrecisionPolicy = FP32,
                 registry: MetricsRegistry | None = None,
                 obs: Any = None,
                 obs_cfg: Any = None,
                 done_depth: int = 4,
                 restart_seed: int | None = None):
        if cfg.max_batch < 1:
            raise ConfigError(
                f"serve.max_batch must be >= 1, got {cfg.max_batch}")
        if cfg.slots < cfg.max_batch:
            raise ConfigError(
                f"serve.slots ({cfg.slots}) must be >= serve.max_batch "
                f"({cfg.max_batch}): every session of a full batch needs a "
                "live slot")
        if cfg.batch_timeout_ms < 0:
            raise ConfigError(
                f"serve.batch_timeout_ms must be >= 0, got "
                f"{cfg.batch_timeout_ms}")
        if cfg.max_queue < 1:
            raise ConfigError(
                f"serve.max_queue must be >= 1 (an unbounded ingress queue "
                f"turns a request flood into unbounded host memory), got "
                f"{cfg.max_queue}")
        if cfg.shed_policy not in ("reject", "oldest"):
            raise ConfigError(
                f"serve.shed_policy must be 'reject' or 'oldest', got "
                f"{cfg.shed_policy!r}")
        if cfg.default_deadline_ms < 0:
            raise ConfigError(
                f"serve.default_deadline_ms must be >= 0 (0 = none), got "
                f"{cfg.default_deadline_ms}")
        if cfg.max_restarts < 0:
            raise ConfigError(
                f"serve.max_restarts must be >= 0 (0 = no engine rebuild), "
                f"got {cfg.max_restarts}")
        if cfg.restart_backoff_s <= 0 or cfg.restart_backoff_max_s <= 0:
            raise ConfigError(
                "serve.restart_backoff_s / restart_backoff_max_s must be "
                f"> 0, got {cfg.restart_backoff_s}/"
                f"{cfg.restart_backoff_max_s}")
        if cfg.warm_bytes < 0:
            raise ConfigError(
                f"serve.warm_bytes must be >= 0 (0 disables the warm "
                f"tier), got {cfg.warm_bytes}")
        if cfg.warm_max_sessions < 1:
            raise ConfigError(
                f"serve.warm_max_sessions must be >= 1, got "
                f"{cfg.warm_max_sessions}")
        if cfg.spill_bytes < 0:
            raise ConfigError(
                f"serve.spill_bytes must be >= 0 (the spill tier is "
                f"byte-bounded like warm_bytes), got {cfg.spill_bytes}")
        if cfg.spill_dir and cfg.warm_bytes <= 0:
            raise ConfigError(
                "serve.spill_dir requires the warm tier "
                "(serve.warm_bytes > 0): the spill arena is the warm "
                "store's overflow and an adopted carry re-enters through "
                "it")
        self.model = model
        self.cfg = cfg
        self._precision = precision
        self._registry = registry if registry is not None else MetricsRegistry()
        self._obs = obs
        # The one host-span entry (obs/trace.py): the run's when an Obs
        # bundle came along, else the bare profiler annotation. Per tick,
        # never per request.
        self._span = getattr(obs, "span", None) or trace_span
        self._episode = (model.apply_prefill is not None
                         and model.apply_serve_batch is not None)
        self._live = _Live(jax.device_put(precision.cast_compute(params)),
                           int(params_step))
        self._carry0 = precision.cast_carry(model.init_carry(), model)
        #: One session's carry footprint in bytes — the warm tier's
        #: accounting unit (static per model/precision) and the
        #: numerator of the eviction-economics gauge.
        self._carry_nbytes = sum(
            int(leaf.size) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(self._carry0))
        #: Warm tier on only when budgeted AND the model has a carry to
        #: park (a stateless MLP's pool is structurally empty — there is
        #: nothing a warm tier could preserve).
        self._warm_enabled = (cfg.warm_bytes > 0
                              and self._carry_nbytes > 0)
        #: Spill tier on only with a configured arena directory AND a
        #: live warm tier to overflow from / adopt into.
        self._spill_enabled = bool(cfg.spill_dir) and self._warm_enabled
        # What a tick's gather must move, from the carry's shapes: the
        # compiled programs are held to it (tests/test_chip_compile.py).
        self._registry.record("serve_tick_gather_bytes",
                              cfg.max_batch * self._carry_nbytes)
        self._registry.record("serve_arena_row_bytes", self._carry_nbytes)
        self._build_arena_and_programs()

        # Live tunable knobs (tuned-knob-ok: seeded from config — the
        # ceiling — then adjusted only DOWNWARD by the online controller
        # through set_knobs). Read via self._knobs at each decision site.
        self._knobs = _LiveKnobs(
            batch_timeout_ms=float(cfg.batch_timeout_ms),
            max_queue=int(cfg.max_queue))
        # Current-knob gauges: every adjustment is VISIBLE (the ISSUE-14
        # contract — the controller may never move a knob silently).
        self._registry.record_many({
            "serve_knob_batch_timeout_ms": self._knobs.batch_timeout_ms,
            "serve_knob_max_queue": float(self._knobs.max_queue)})
        # Bounded ingress: depth caps at the live max_queue knob (seeded
        # from serve.max_queue, the hard ceiling), the overload surface
        # (submit sheds/rejects instead of growing host memory).
        # set_knobs() retargets the bound in place under the queue mutex.
        self._q: queue.Queue = queue.Queue(maxsize=cfg.max_queue)
        # trace-buffer-ok: bounded by logic, not maxlen — _collect_batch
        # sheds/rejects past cfg.max_queue (the deferred-overflow branch)
        self._deferred: deque[_Request] = deque()
        self._done_q: queue.Queue = queue.Queue(maxsize=done_depth)
        #: Sessions whose slot carry is suspect after a CONSUMER fault
        #: (the device program advanced their carries, the readback
        #: failed): appended by the consumer, drained — and dropped from
        #: the pool — by the DISPATCHER, which owns the SlotPool (a
        #: cross-thread drop would race admit()'s LRU iteration).
        self._poisoned: deque = deque()  # trace-buffer-ok: drained to empty
        # by the dispatcher every tick; growth is bounded by in-flight
        # batches (_MAX_INFLIGHT_TICKS * max_batch)
        self._stop_event = threading.Event()
        self._pending = 0
        self._pending_lock = threading.Lock()

        # Supervision state (serve.max_restarts > 0): consecutive-fault
        # streak (guarded by _sup_lock — the dispatcher increments, the
        # consumer resets), the fault epoch gating those resets, a
        # consumer-side restart request, and the terminal fault.
        self._restart_streak = 0
        self._sup_lock = threading.Lock()
        self._fault_epoch = 0
        # Backoff jitter seed: None (the production default — cli serve
        # never passes one) draws per-process OS entropy, so a fleet of
        # replicas does NOT share a jitter sequence and restart in
        # lockstep; tests/the chaos soak pass an int for replayability.
        self._restart_rng = random.Random(restart_seed)
        self._restart_requested = threading.Event()
        self._consumer_fault: BaseException | None = None
        #: Fault epoch of the batch whose completion faulted: a fault
        #: from a batch dispatched BEFORE the latest restart is stale —
        #: the rebuild already cured it — and must not burn another
        #: restart from the streak.
        self._consumer_fault_epoch = 0
        self._failed: BaseException | None = None
        # Overload events since the last stats publication (guarded by
        # _pending_lock; feeds the serve_overload gauge).
        self._overload_events = 0

        # SLO accounting (consumer-thread-owned).
        self._stats_t = time.perf_counter()
        self._stats_completed = 0
        self._stats_occupancy: list[float] = []
        # Eviction-economics inputs (survive a supervised rebuild — they
        # are measurements, not session state): EWMA cold-re-entry cost
        # and the warm-hit counter base of the last stats window.
        self._ewma_prefill_ms = 0.0
        self._prev_warm_hits = 0.0
        #: Serializes _publish_stats: the consumer thread publishes after
        #: every batch, but terminal FAILURES (shed/reject/expiry/engine-
        #: failed) also publish from their own threads — during a total
        #: outage nothing completes, and the availability burn gauge must
        #: climb DURING the incident, not after the first post-recovery
        #: batch. Non-force callers skip instead of blocking.
        self._stats_lock = threading.Lock()

        # ---- request-level observability (ISSUE 11) ------------------
        # obs_cfg carries the obs.request_trace / exemplar_k / slo_*
        # knobs; None (library users without an ObsConfig) = tracing off,
        # default exemplars, SLO disabled. The stage stamps + histograms
        # below are ALWAYS on: they are the serve_p50/p99 gauges' source.
        self._obs_cfg = obs_cfg
        slo_avail = float(getattr(obs_cfg, "slo_availability", 0.0) or 0.0)
        slo_p99 = float(getattr(obs_cfg, "slo_target_p99_ms", 0.0) or 0.0)
        slo_window = float(getattr(obs_cfg, "slo_window_s", 60.0))
        slo_burn_thr = float(getattr(obs_cfg, "slo_burn_threshold", 2.0))
        if not 0.0 <= slo_avail < 1.0:
            raise ConfigError(
                f"obs.slo_availability must be in [0, 1) (0 disables), "
                f"got {slo_avail}")
        if slo_p99 < 0 or slo_window <= 0 or slo_burn_thr <= 0:
            raise ConfigError(
                "obs.slo_target_p99_ms must be >= 0 and slo_window_s / "
                f"slo_burn_threshold > 0, got {slo_p99}/{slo_window}/"
                f"{slo_burn_thr}")
        self._slo = (slo_avail, slo_p99, slo_window, slo_burn_thr)
        self._slo_on = slo_avail > 0 or slo_p99 > 0
        #: Terminal-outcome totals (cumulative; guarded by _pending_lock,
        #: which both terminal paths already hold): the burn-rate window
        #: diffs these.
        self._term_total = 0
        self._term_bad = 0
        self._term_completed = 0
        self._term_slow = 0
        #: Rolling window of cumulative snapshots, one per stats publish,
        #: SEEDED with an all-zero snapshot at construction: without it
        #: the first publish's own append is the delta base (d == 0), so
        #: a run — or an incident — that terminates entirely within the
        #: first stats interval would never publish a burn rate at all.
        # trace-buffer-ok: bounded ring (maxlen) of per-window snapshots
        self._slo_win: deque[tuple] = deque(maxlen=4096)
        self._slo_win.append((self._stats_t, 0, 0, 0, 0))
        self._burn_alarm = False
        # Request/batch serials: itertools.count.__next__ is atomic under
        # CPython, so submit stays lock-free for the id.
        self._rid = itertools.count(1)
        self._batch_serial = 0          # dispatcher-thread-owned
        # Per-stage histograms (obs/hist.py; the default fixed ms-bucket
        # layout, so every engine's export merges exactly): attached to
        # the registry for metrics.prom export, observed via these direct
        # references off the registry lock.
        self._hists = {
            name: self._registry.attach_histogram(name, Histogram())
            for name in ("serve_request_ms",
                         *(f"serve_{s}_ms" for s in SERVE_STAGES))}
        self._h_e2e = self._hists["serve_request_ms"]
        # Per-TICK histograms, where the work waits between the two
        # threads: how long the dispatcher waited for a device slot before
        # collecting the tick (0 with one free), its host time in the tick,
        # how long it then blocks handing the tick to the consumer (0 when
        # the done queue has room), the consumer's host time per tick less
        # its readback, and the ticks dispatched and not yet completed at
        # each dispatch.
        self._h_slot_wait = self._registry.attach_histogram(
            "serve_slot_wait_ms", Histogram())
        self._h_tick_host = self._registry.attach_histogram(
            "serve_tick_host_ms", Histogram())
        self._h_done_wait = self._registry.attach_histogram(
            "serve_done_wait_ms", Histogram())
        self._h_complete_host = self._registry.attach_histogram(
            "serve_complete_host_ms", Histogram())
        self._h_inflight = self._registry.attach_histogram(
            "serve_inflight_ticks",
            Histogram(bounds=tuple(float(n) for n in range(1, 17))))
        # The process's garbage-collection pauses (obs/trace.py), which
        # stop both threads wherever they stand.
        attach_gc_pauses(self._registry, getattr(obs, "tracer", None))
        self._ticks_dispatched = 0      # dispatcher-thread-owned
        self._ticks_completed = 0       # consumer-thread-owned
        #: Read both counters under it; the consumer notifies it each time
        #: it consumes a tick, waking a dispatcher waiting for a slot.
        self._slot_cv = threading.Condition()
        #: End-to-end bucket counts at the last stats publish — the
        #: per-window delta the p50/p99 gauges are quantiled over.
        self._p50_prev_counts = self._h_e2e.snapshot()["counts"]
        # Exemplars: top-K slowest of the current window (consumer-thread
        # list, trimmed to K), folded per publish into a bounded ring.
        self._exemplar_k = max(0, int(getattr(obs_cfg, "exemplar_k", 8)
                                      if obs_cfg is not None else 8))
        self._window_slowest: list[dict] = []
        # trace-buffer-ok: bounded exemplar ring (maxlen = 4 windows of K)
        self._exemplars: deque[dict] = deque(
            maxlen=max(1, 4 * self._exemplar_k))
        #: Guards _window_slowest/_exemplars: the consumer appends while
        #: failure-path publishes fold the window from their own threads
        #: and _supervise/cli snapshot the ring — an unlocked deque
        #: iteration concurrent with extend() raises and would kill the
        #: reading thread. Ordering: _stats_lock may take _ex_lock,
        #: never the reverse.
        self._ex_lock = threading.Lock()
        #: Ring changed since the last serve_exemplars.json write (folds
        #: with io_ok=False — failure-path publishes — defer the file IO
        #: to the next consumer/stop publish).
        self._ex_dirty = False
        self._overload_flagged = False
        # Per-request trace emission: cached tracer reference, None unless
        # obs is enabled with the span trace + request_trace knob on — the
        # zero-artifact default costs one attribute check per request.
        tracer = getattr(obs, "tracer", None)
        self._req_tracer = (
            tracer if (obs is not None and getattr(obs, "enabled", False)
                       and tracer is not None and tracer.enabled
                       and (obs_cfg is None
                            or getattr(obs_cfg, "request_trace", True)))
            else None)

        self._dispatcher = threading.Thread(
            target=self._serve_loop, name="serve-dispatcher", daemon=True)
        self._consumer = threading.Thread(
            target=self._complete_loop, name="serve-consumer", daemon=True)
        self._dispatcher.start()
        self._consumer.start()

    def _build_arena_and_programs(self) -> None:
        """Fresh slot pool, fresh device arena, fresh jitted programs —
        construction AND the supervised-restart rebuild path (a restart
        discards every compiled program and every slot carry; sessions
        re-enter cold through the batched prefill, which PR 8 pinned as
        bitwise-equivalent to a fresh session suffix).

        Device arena: one carry row per slot, plus max_batch SCRATCH rows
        (indices >= cfg.slots) that padding rows read/write so a partial
        batch can never touch a live session's slot.

        The arena is DONATED on every backend: scatter into an aliased
        buffer updates in place, a non-donated pool round-trips a full
        arena copy per tick (measured 5.5x tick cost at the soak shape).
        The PR-4 CPU donation carve-out (runtime/orchestrator.py) does
        not apply here: its segfault was a consumer device_get racing a
        dispatch that donated the very state the readback came from; the
        pool never leaves the device, and the consumer reads only the
        action/logit/value outputs, which are never donated."""
        cfg = self.cfg
        self._slots = SlotPool(cfg.slots)
        # Fresh warm tier too: the restart contract is ALL sessions cold
        # (a parked carry would survive the rebuild bit-exactly, but the
        # documented supervision semantics — and the soak's assertions —
        # say a rebuilt engine serves only cold re-entries).
        self._warm = WarmStore(cfg.warm_bytes, cfg.warm_max_sessions)
        # Page-outs the consumer has read back but the dispatcher has
        # not yet committed to the store (single-owner handoff: the
        # consumer appends host carries, the dispatcher — who owns ALL
        # admission state — drains at the top of each tick and drops
        # entries whose session already re-entered).
        # trace-buffer-ok: bounded by in-flight batches
        # (_MAX_INFLIGHT_TICKS * max_batch entries at most)
        self._park_inbox: deque = deque()
        # ---- spill tier (ISSUE 20) ----------------------------------
        #: Per-session dispatched-step counts for HOT sessions (the
        #: adoption-clock source; travels into WarmStore entries and
        #: spill records at park time). Dispatcher-owned; bounded by
        #: the slot-pool capacity — entries are popped at eviction.
        self._steps: dict[Any, int] = {}
        #: Disk-op FIFO dispatcher -> consumer ("put"/"del"/"take"
        #: tuples): the dispatcher NEVER touches the arena files beyond
        #: an os.stat probe — all real I/O rides the consumer, like
        #: page-out readback (lint checks 8/17/19).
        # Puts are warm-store demotions (bounded by the park inbox);
        # takes are capped by _spill_inflight — one per distinct
        # deferred session, itself capped by the ingress bound.
        # trace-buffer-ok: bounded by park inbox + _spill_inflight
        self._spill_ops: deque = deque()
        #: Completed takes consumer -> dispatcher: (sid, rows|None,
        #: steps, reason) — drained at the top of batch collection.
        # trace-buffer-ok: bounded by _spill_inflight
        self._spill_inbox: deque = deque()
        #: Sessions with a take in flight: their requests DEFER (the
        #: carry is coming — admitting them cold would fork the
        #: episode). Dispatcher-owned.
        self._spill_inflight: set = set()
        if self._spill_enabled:
            # A fresh incarnation per (re)build: an engine-local take
            # with no fleet clock accepts only same-incarnation records,
            # so the supervised-restart contract (a rebuilt engine
            # serves only cold re-entries) survives the spill tier —
            # every pre-fault record reads as stale to the rebuilt
            # engine, while a CLOCKED fleet take can still adopt it.
            self._incarnation = os.urandom(8).hex()
            self._arena: SpillArena | None = SpillArena(
                cfg.spill_dir, max_bytes=cfg.spill_bytes,
                record_nbytes=self._carry_nbytes,
                incarnation=self._incarnation)
        else:
            self._arena = None
        #: Last spill-gauge re-anchor (perf_counter): shared cadence
        #: between the consumer's stats publish and the health-probe
        #: refresh, so the two never double-scan one window.
        self._spill_scan_t = 0.0
        n_arena = cfg.slots + cfg.max_batch

        def rows_of(x, n):
            # One broadcast, one buffer: an eager ``jnp.repeat`` broadcasts
            # and then reshapes, two copies of a leaf alive at once (2.3 GB
            # more at set-up's peak for a 2.3 GB leaf: PERF.md, PR 33).
            x = jnp.asarray(x)
            return jnp.broadcast_to(x[None], (n,) + x.shape)

        self._pool = jax.tree.map(lambda x: rows_of(x, n_arena),
                                  self._carry0)
        # Per-row init carries for the generic path's in-program cold reset.
        self._carry0_rows = jax.tree.map(
            lambda x: rows_of(x, cfg.max_batch), self._carry0)
        donate = (1,)
        if self._episode:
            self._warm_fn = jax.jit(self._warm_program, donate_argnums=donate)
            self._cold_fn = jax.jit(self._cold_program, donate_argnums=donate)
        else:
            self._step_fn = jax.jit(self._generic_program,
                                    donate_argnums=donate)
        if self._warm_enabled:
            # Paging programs, both at the static max_batch shape (one
            # compile each). The park gather does NOT donate — the arena
            # must survive it for the tick's programs; the unpark
            # install donates like every other arena writer.
            self._park_fn = jax.jit(self._park_program)
            self._install_fn = jax.jit(self._install_program,
                                       donate_argnums=(0,))

    # -- device programs --------------------------------------------------

    def _warm_program(self, params, pool, obs, idx):
        """One incremental step for a warm batch: gather slot carries,
        per-row-clock serve step, scatter back. THE steady-state program.
        A model step that hands back ``ModelOut.stats`` (small, a row a
        request) adds them as a fifth result, read back with the tick's
        answers; every other model's program returns the four it always
        did."""
        with jax.named_scope("gather"):
            rows = _gather_rows(pool, idx)
        with jax.named_scope("model"):
            out, new_rows = self.model.apply_serve_batch(params, obs, rows)
        with jax.named_scope("scatter"):
            new_pool = jax.tree.map(lambda p, r: p.at[idx].set(r), pool,
                                    new_rows)
        actions = jnp.argmax(out.logits, axis=-1).astype(jnp.int32)
        stats = () if out.stats is None else (out.stats,)
        return (actions, out.logits, out.value, new_pool, *stats)

    def _cold_program(self, params, pool, obs, idx):
        """Batched re-prefill: cold sessions (fresh or evicted) compute
        their episode-start pass and land their carries in their slots."""
        with jax.named_scope("model"):
            out, new_rows = self.model.apply_prefill(params, obs)
        with jax.named_scope("scatter"):
            new_pool = jax.tree.map(lambda p, r: p.at[idx].set(r), pool,
                                    new_rows)
        actions = jnp.argmax(out.logits, axis=-1).astype(jnp.int32)
        return actions, out.logits, out.value, new_pool

    def _park_program(self, pool, idx):
        """Batch-gather the tick's eviction victims' carry rows (page-out
        step 1). Async device compute, never a readback — legal on the
        dispatch thread; the CONSUMER device_gets the result."""
        return _gather_rows(pool, idx)

    def _install_program(self, pool, rows, idx):
        """Scatter parked carries back into their (re-)admitted slots
        (unpark): the same ``.at[idx].set`` path every program writes
        through, so a warm re-entry is bitwise a never-evicted session."""
        return jax.tree.map(lambda p, r: p.at[idx].set(r), pool, rows)

    def _generic_program(self, params, pool, obs, idx, cold):
        """Single program for models without a prefill/incremental split:
        cold rows take a fresh init carry in-program, everything else runs
        ``apply_batched`` (no cross-row constraint to honor)."""
        rows = _gather_rows(pool, idx)

        def reset_cold(init_row, row):
            mask = cold.reshape((-1,) + (1,) * (row.ndim - 1))
            return jnp.where(mask, init_row, row)

        rows = jax.tree.map(reset_cold, self._carry0_rows, rows)
        out, new_rows = apply_batched(self.model, params, obs, rows)
        new_pool = jax.tree.map(lambda p, r: p.at[idx].set(r), pool,
                                new_rows)
        actions = jnp.argmax(out.logits, axis=-1).astype(jnp.int32)
        return actions, out.logits, out.value, new_pool

    # -- public surface ---------------------------------------------------

    def submit(self, session_id: Any, obs: Any,
               callback: Callable[[ServeResult], None] | None = None,
               *, deadline_ms: float | None = None,
               session_clock: int | None = None) -> _Request:
        """Enqueue one ``(window, portfolio)`` query; thread-safe. Returns
        a handle whose :meth:`_Request.wait` blocks for the response;
        ``callback(result)`` additionally fires on the consumer thread.

        ``deadline_ms`` bounds how long the request may wait before it is
        completed with a :class:`ServeDeadlineExceeded` error instead of
        being served (None = ``serve.default_deadline_ms``; 0 = none).

        ``session_clock`` (ISSUE 20) is the session's expected
        completed-response count, forwarded by the fleet router on
        migration: a spilled carry is adopted warm iff its step stamp
        matches this; None (local submits) restricts adoption to records
        this engine incarnation wrote.

        NEVER blocks on a full queue: past ``serve.max_queue`` the
        request is refused (``shed_policy="reject"``) or the oldest
        queued request is shed to make room (``"oldest"``) — either way
        the loser's handle completes immediately with
        :class:`ServeRejected` (its callback fires with None on the
        CALLER's thread, the one place completion doesn't ride the
        consumer)."""
        if self._stop_event.is_set():
            raise RuntimeError("serve engine is stopped")
        if self._failed is not None:
            raise ServeEngineFailed(
                "serve engine is in the terminal failed state "
                f"(last fault: {self._failed!r}); rebuild it") \
                from self._failed
        if deadline_ms is None:
            deadline_ms = self.cfg.default_deadline_ms
        req = _Request(session_id, np.asarray(obs, np.float32), callback,
                       deadline_ms=deadline_ms, rid=next(self._rid),
                       clock=(int(session_clock)
                              if session_clock is not None else None))
        with self._pending_lock:
            self._pending += 1
        self._registry.inc("serve_requests_total")
        while True:
            try:
                self._q.put_nowait(req)
                if (self._stop_event.is_set()
                        and not self._dispatcher.is_alive()):
                    # TOCTOU: stop() completed between our gate check at
                    # the top and this put — nobody will ever read the
                    # queue again, so sweep it ourselves (pop-ownership
                    # makes this race-safe against other sweepers).
                    self._fail_leftovers()
                return req
            except queue.Full:
                pass
            with self._pending_lock:
                self._overload_events += 1
            if self.cfg.shed_policy == "reject":
                self._registry.inc("serve_queue_rejected_total")
                self._registry.record("serve_overload", 1.0)
                self._finish_failed(req, ServeRejected(
                    f"ingress queue full ({self._knobs.max_queue}); "
                    "request rejected under shed_policy='reject'",
                    reason="queue_full"))
                return req
            # shed_policy == "oldest": drop the oldest queued request and
            # retry the admission (the dispatcher may race us for it —
            # an Empty get just means the queue drained; retry the put).
            try:
                victim = self._q.get_nowait()
            except queue.Empty:
                continue
            self._registry.inc("serve_shed_total")
            self._registry.record("serve_overload", 1.0)
            self._finish_failed(victim, ServeRejected(
                f"shed from the ingress queue under overload "
                f"(shed_policy='oldest', "
                f"max_queue={self._knobs.max_queue})",
                reason="shed_oldest"))

    def _finish_failed(self, req: _Request, exc: BaseException) -> None:
        """Complete a request with a terminal error outcome (rejection,
        shed, deadline expiry, engine failure): release its waiter, fire
        its callback with None, and un-count it from the drain-pending
        total — a failed request must never strand :meth:`drain`."""
        with self._pending_lock:
            self._pending -= 1
            self._term_total += 1
            self._term_bad += 1
        req.error = exc
        req._event.set()
        if req.callback is not None:
            try:
                req.callback(None)
            except Exception:   # noqa: BLE001
                log.exception("serve failure callback failed")
        if isinstance(exc, ServeRejected):
            outcome = exc.reason            # queue_full / shed_oldest / ...
        elif isinstance(exc, ServeDeadlineExceeded):
            outcome = "expired"
        elif isinstance(exc, ServeEngineFailed):
            outcome = "engine_failed"
        else:
            outcome = "failed"
        self._trace_request(req, outcome, time.perf_counter())
        # Terminal failures drive the stats cadence too: under a total
        # outage (restart storm, flood of sheds) no batch ever completes,
        # and the availability-burn gauge/alert must fire mid-incident.
        # io_ok=False: this runs on the submit caller's or dispatcher's
        # thread — the exemplar file write must not ride either.
        self._publish_stats(io_ok=False)

    #: Request-flow lanes: request spans render on synthetic tids (one of
    #: 64 lanes by request id) so overlapping lifecycles draw as parallel
    #: tracks in Perfetto, with the envelope span time-containing its
    #: stage children (track-local nesting). Base offset keeps lanes away
    #: from real thread ids.
    _TRACE_LANE_BASE = 1_000_000
    _TRACE_LANES = 64

    def _trace_request(self, req: _Request, outcome: str,
                       t_end: float, lines: list[str] | None = None
                       ) -> None:
        """Emit the request's whole lifecycle — one ``serve_request``
        envelope plus one child span per stamped stage, keyed by
        request/batch/session ids in the args — called exactly once per
        terminal outcome, from whichever thread discovered it. The events
        are PRE-SERIALIZED f-string lines (per-event ``json.dumps`` on
        the completion thread measured ~40 µs/request — a 3x throughput
        tax at CPU-MLP request costs); ``lines`` (the batch-completion
        path) accumulates them for ONE bulk tracer append per batch.
        No-op (one attribute check) when request tracing is off."""
        tracer = self._req_tracer
        if tracer is None:
            return
        tr = req.trace
        tr.outcome = outcome
        to_us = tracer.to_us
        pid = tracer.pid
        lane = self._TRACE_LANE_BASE + tr.rid % self._TRACE_LANES
        ts0 = to_us(tr.t_enq)
        sid = req.session_id
        session = (f'"{sid}"' if type(sid) is str and _SID_SAFE(sid)
                   else json.dumps(str(sid)))
        own = lines is None
        if own:
            lines = []
        # The fleet trace id rides along when the wire set one, so a
        # per-engine chrome trace cross-references the stitched
        # cross-process trace (obs/collect.py) by id.
        fleet = (f',"trace":"{tr.trace_id}"'
                 if tr.trace_id is not None else "")
        lines.append(
            f'{{"name":"serve_request","cat":"serve","ph":"X",'
            f'"ts":{ts0:.3f},"dur":{to_us(t_end) - ts0:.3f},'
            f'"pid":{pid},"tid":{lane},"args":{{"request":{tr.rid},'
            f'"session":{session},"outcome":"{outcome}",'
            f'"batch":{tr.batch if tr.batch is not None else 0},'
            f'"cold":{"true" if tr.cold else "false"},'
            f'"deferrals":{tr.deferrals}{fleet}}}}}')
        for name, t0, t1 in (("queue_wait", tr.t_enq, tr.t_collected),
                             ("batch_wait", tr.t_collected,
                              tr.t_dispatched),
                             ("device", tr.t_dispatched, tr.t_device),
                             ("readback", tr.t_device, tr.t_done)):
            if t0 is not None and t1 is not None:
                za = to_us(t0)
                lines.append(
                    f'{{"name":"{name}","cat":"serve","ph":"X",'
                    f'"ts":{za:.3f},"dur":{to_us(t1) - za:.3f},'
                    f'"pid":{pid},"tid":{lane},'
                    f'"args":{{"request":{tr.rid}}}}}')
        if own:
            tracer.emit_lines(lines)

    @property
    def params_step(self) -> int:
        """Checkpoint step of the CURRENT serving weights."""
        return self._live.step

    @property
    def failed(self) -> BaseException | None:
        """The terminal fault, when the engine tripped its failed state
        (None while healthy). Terminal = submits raise ServeEngineFailed
        and all queued work has been failed loudly."""
        return self._failed

    def queue_depth(self) -> int:
        """Current ingress-queue depth (bounded by ``serve.max_queue`` —
        the chaos soak's queue invariant reads this)."""
        return self._q.qsize()

    @property
    def registry(self) -> MetricsRegistry:
        """The engine's metrics registry (counters + SLO gauges)."""
        return self._registry

    @property
    def knobs(self) -> _LiveKnobs:
        """The CURRENT live knob vector (one immutable reference — the
        controller's read side)."""
        return self._knobs

    @property
    def latency_histogram(self):
        """The end-to-end request-latency histogram (obs/hist.py): the
        online controller windows its p99 objective off snapshot deltas
        of this — the same bucket math as the ``serve_p99_ms`` gauge."""
        return self._h_e2e

    def set_knobs(self, *, batch_timeout_ms: float | None = None,
                  max_queue: int | None = None) -> _LiveKnobs:
        """Atomically install new runtime knob values (the online
        controller's actuator; also usable by hand). Both knobs are
        clamped to the CONFIGURED values as ceilings — ``serve.
        batch_timeout_ms`` / ``serve.max_queue`` are the operator's
        safety rails, and a controller that could raise the queue bound
        above config would re-open the unbounded-ingress memory hole
        admission control closed. Values are validated loudly; the new
        vector is returned and published as gauges."""
        cur = self._knobs
        if batch_timeout_ms is None:
            batch_timeout_ms = cur.batch_timeout_ms
        if max_queue is None:
            max_queue = cur.max_queue
        batch_timeout_ms = float(batch_timeout_ms)
        max_queue = int(max_queue)
        if batch_timeout_ms < 0:
            raise ConfigError(
                f"batch_timeout_ms must be >= 0, got {batch_timeout_ms}")
        if max_queue < 1:
            raise ConfigError(f"max_queue must be >= 1, got {max_queue}")
        batch_timeout_ms = min(batch_timeout_ms, self.cfg.batch_timeout_ms)
        max_queue = min(max_queue, self.cfg.max_queue)
        new = _LiveKnobs(batch_timeout_ms=batch_timeout_ms,
                         max_queue=max_queue)
        self._knobs = new
        if max_queue != cur.max_queue:
            # Retarget the physical ingress bound in place: put_nowait
            # checks maxsize under this mutex, so the new bound applies
            # to the very next admission. Shrinking below the current
            # depth is safe — admissions fail (shed/reject) until the
            # dispatcher drains back under the bound, which is exactly
            # the brownout behavior the shrink asked for.
            with self._q.mutex:
                self._q.maxsize = max_queue
                self._q.not_full.notify_all()
        self._registry.record_many({
            "serve_knob_batch_timeout_ms": new.batch_timeout_ms,
            "serve_knob_max_queue": float(new.max_queue)})
        return new

    def swap_params(self, master_params: Any, step: int) -> None:
        """Atomically install new serving weights between batches. The
        dispatcher reads the live reference once per tick, so a batch
        computes entirely under one step's weights — in-flight ticks keep
        the old params alive until their buffers are read back."""
        params = jax.device_put(self._precision.cast_compute(master_params))
        self._live = _Live(params, int(step))
        self._registry.inc("serve_swaps_total")
        log.info("serving params swapped to step %d", int(step))

    def warmup(self) -> None:
        """Compile every serving program with a scratch-only batch (live
        slots untouched). Call before traffic so the first real request
        doesn't pay the compile. Must run before concurrent submits."""
        cfg = self.cfg
        obs_dim = getattr(self.model, "obs_dim", 0) or 3
        obs = np.full((cfg.max_batch, obs_dim), 10.0, np.float32)
        idx = np.arange(cfg.slots, cfg.slots + cfg.max_batch, dtype=np.int32)
        if self._episode:
            _, _, _, pool = self._cold_fn(self._live.params, self._pool,
                                          obs, idx)
            self._pool = pool
            self._pool = self._warm_fn(self._live.params, self._pool,
                                       obs, idx)[3]
        else:
            cold = np.ones((cfg.max_batch,), bool)
            _, _, _, pool = self._step_fn(self._live.params, self._pool,
                                          obs, idx, cold)
            self._pool = pool
        if self._warm_enabled:
            # Compile the paging programs too — a first-eviction compile
            # on the dispatch thread would stall every queued deadline.
            # Scratch-only, like everything else here: the gather pads
            # to scratch row 0, the install writes only scratch rows.
            pidx = np.full((cfg.max_batch,), cfg.slots, np.int32)
            self._park_fn(self._pool, pidx)
            row0 = jax.tree.map(np.asarray, self._carry0)
            self._pool = self._install_parked([row0], [cfg.slots])

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Block until every submitted request has been answered (the
        SIGTERM drain of ``cli serve``); False on timeout."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._pending_lock:
                if self._pending == 0:
                    return True
            time.sleep(0.002)   # serve-block-ok: drain's bounded poll runs
            # on the CALLER's thread (cli shutdown), never the dispatch path.
        with self._pending_lock:
            return self._pending == 0

    def stop(self, *, drain: bool = True, timeout_s: float = 30.0) -> bool:
        """Drain (optionally), stop both threads, publish final gauges.

        Returns False — loudly — when either thread is still alive after
        its join timeout: a hung dispatcher/consumer means in-flight work
        may never complete, and the caller (``cli serve``'s SIGTERM path)
        must exit nonzero instead of reporting a clean shutdown."""
        if drain:
            self.drain(timeout_s)
        self._stop_event.set()
        self._dispatcher.join(timeout_s)
        if not self._dispatcher.is_alive():
            # The dispatcher failed its leftovers in its own exit path;
            # this sweep catches requests that raced in between that
            # sweep and its death (safe now — the owner is gone).
            self._fail_leftovers()
        try:
            # Bounded put: with the consumer hung behind a full done
            # queue, an unbounded put would hang stop() itself.
            self._done_q.put(_SHUTDOWN, timeout=timeout_s)
        except queue.Full:
            pass
        self._consumer.join(timeout_s)
        ok = True
        for thread in (self._dispatcher, self._consumer):
            if thread.is_alive():
                log.error(
                    "serve %s thread still alive %.1fs after stop(): "
                    "shutdown is NOT clean (in-flight requests may never "
                    "complete)", thread.name, timeout_s)
                ok = False
        self._publish_stats(force=True)
        return ok

    def page_out_all(self) -> dict[str, int]:
        """Drain-time warm handoff (ISSUE 20): seal EVERY surviving
        carry — RAM-parked, hot slot rows, and in-flight page-outs/
        adoptions — into the spill arena, so the engines this one's
        sessions are reassigned to adopt them warm instead of paying the
        cold-restart prefill for the whole population.

        ORDERING CONTRACT (the drain test asserts it): drain →
        ``stop()`` → ``page_out_all()`` → exit 75. This method REFUSES
        while either worker thread is alive — a live dispatcher still
        mutates the stores and a live consumer still owes page-out
        readbacks; only after ``stop()`` does the caller's thread own
        every structure (and may block on device readback freely).

        Returns ``{"written", "refused", "skipped_takes"}`` for the cli
        shutdown summary; all-zero without a spill arena."""
        if self._dispatcher.is_alive() or self._consumer.is_alive():
            raise RuntimeError(
                "page_out_all() before stop(): the dispatcher/consumer "
                "threads still own the session stores — the drain "
                "ordering is drain -> stop() -> page_out_all() -> exit")
        counts = {"written": 0, "refused": 0, "skipped_takes": 0}
        arena = self._arena
        if arena is None:
            return counts
        counts["skipped_takes"] = sum(
            1 for op in self._spill_ops if op[0] == "take")
        # Settle queued ops first: puts seal, deletes tombstone, takes
        # skip (stop_event is set — the records stay for adopters).
        self._drain_spill_ops()

        def _seal(sid: Any, rows: Any, steps: int) -> None:
            if arena.put(sid, jax.tree.leaves(rows), steps):
                counts["written"] += 1
                self._registry.inc("serve_spill_puts_total")
            else:
                counts["refused"] += 1
                self._registry.inc("serve_spill_put_refusals_total")

        # Page-outs the consumer read back that never committed, and
        # adopted takes that never reached a batch: their state exists
        # ONLY in these inboxes now — seal or the carry dies here.
        while self._park_inbox:
            sid, rows, steps = self._park_inbox.popleft()
            if not self._slots.contains(sid):
                _seal(sid, rows, steps)
        while self._spill_inbox:
            sid, rows, steps, _reason = self._spill_inbox.popleft()
            if rows is not None and not self._slots.contains(sid):
                _seal(sid, rows, steps)
        # The RAM-warm population (single-owner map — the dispatcher
        # that owned it is provably dead).
        for sid, (rows, _nbytes, steps) in list(self._warm._lru.items()):
            _seal(sid, rows, steps)
        # The hot population: ONE bulk arena readback, then per-session
        # row copies. serve-host-ok: post-stop, the caller's thread.
        if len(self._slots):
            host_pool = jax.device_get(self._pool)
            for sid, slot in self._slots._lru.items():
                rows = jax.tree.map(
                    lambda x: np.asarray(x[slot]).copy(), host_pool)
                _seal(sid, rows, self._steps.get(sid, 0))
        log.info(
            "drain page-out sealed %d carr%s to the spill arena "
            "(%d refused, %d takes left for adopters)",
            counts["written"], "y" if counts["written"] == 1 else "ies",
            counts["refused"], counts["skipped_takes"])
        self._publish_stats(force=True)
        return counts

    # -- dispatcher thread ------------------------------------------------

    def _serve_loop(self) -> None:
        while not self._stop_event.is_set():
            if self._failed is not None:
                # Terminal failed state: never wedge — every request that
                # raced past the submit-side gate still gets a loud
                # terminal outcome.
                self._drain_failed()
                continue
            # Sessions a consumer fault poisoned (their slot carries
            # advanced but the responses were lost): drop them so their
            # next request re-enters cold instead of double-stepping a
            # warm carry. Best-effort — a same-session request already
            # in flight this tick may still read the advanced carry; the
            # supervision rebuild (max_restarts > 0) resets even that.
            while self._poisoned:
                sid = self._poisoned.popleft()
                self._slots.drop(sid)
                self._steps.pop(sid, None)
            if self._restart_requested.is_set():
                self._restart_requested.clear()
                # Epoch-gate: a fault from a batch dispatched before the
                # latest restart was already cured by that rebuild; only
                # a current-epoch fault earns another restart.
                if self._consumer_fault_epoch >= self._fault_epoch:
                    self._supervise(self._consumer_fault
                                    or RuntimeError("serve consumer fault"))
                continue
            tick = self._batch_serial + 1
            slot_wait_ms = self._wait_for_slot(tick)
            if slot_wait_ms is None:
                continue        # stopped, or a consumer fault to supervise
            with self._span("serve/collect_batch", tick=tick):
                batch = self._collect_batch()
            if not batch:
                continue
            live = self._live       # ONE read per tick: the atomicity seam
            t_tick = time.perf_counter()
            try:
                with self._span("serve/dispatch_tick", tick=tick,
                                rows=len(batch)) as tick_span:
                    done = self._dispatch_batch(batch, live)
                    tick_span.set_metadata(cold=done.cold)
            except Exception as exc:    # noqa: BLE001 — one malformed
                # request (bad obs shape) must fail ITS batch, not wedge
                # the dispatcher and hang every later session.
                self._fail_batch(batch, exc)
                # ... and with supervision on, retry the ENGINE: rebuild
                # programs + arena under seeded backoff (no-op at the
                # default max_restarts=0, the PR-8 contract).
                self._supervise(exc)
                continue
            t_put = time.perf_counter()
            self._h_slot_wait.observe(slot_wait_ms)
            self._h_tick_host.observe((t_put - t_tick) * 1e3)
            self._ticks_dispatched += 1
            self._h_inflight.observe(
                self._ticks_dispatched - self._ticks_completed)
            # Bounded handoff; with the slot bound above it blocks only
            # behind a spill nudge or a shallow done queue.
            try:
                self._done_q.put_nowait(done)
                self._h_done_wait.observe(0.0)
            except queue.Full:
                with self._span("serve/done_wait", tick=done.tick):
                    self._done_q.put(done)
                self._h_done_wait.observe(
                    (time.perf_counter() - t_put) * 1e3)
        # Dispatcher exit: whatever is still queued/deferred can never be
        # dispatched — fail it terminally HERE, on the thread that owns
        # these structures (stop() and submit() re-sweep only for racers,
        # and only once this thread is provably dead).
        self._fail_leftovers()

    def _wait_for_slot(self, tick: int) -> float | None:
        """Block until fewer than ``_MAX_INFLIGHT_TICKS`` ticks are
        launched and unconsumed, so the tick collected next starts right
        after the one the device runs; requests arriving meanwhile stay in
        the ingress queue for that tick. Returns the milliseconds waited
        (0.0 with a slot free), or None when stop() or a consumer fault's
        restart request ended the wait first."""
        cv = self._slot_cv
        with cv:
            if (self._ticks_dispatched - self._ticks_completed
                    < _MAX_INFLIGHT_TICKS):
                return 0.0
        t0 = time.perf_counter()
        with self._span("serve/slot_wait", tick=tick), cv:
            while (self._ticks_dispatched - self._ticks_completed
                   >= _MAX_INFLIGHT_TICKS):
                if (self._stop_event.is_set()
                        or self._restart_requested.is_set()):
                    return None
                # Bounded as the idle poll is: stop() sets only its event.
                cv.wait(0.05)
        return (time.perf_counter() - t0) * 1e3

    def _fail_leftovers(self) -> None:
        """Fail every request still in the ingress/deferred queues with a
        terminal stopped error. Safe concurrently: items transfer to the
        caller one pop at a time, so each request is completed exactly
        once even when stop()/submit() racers sweep alongside the
        dispatcher's own exit sweep."""
        leftover = RuntimeError(
            "serve engine stopped before this request was dispatched")
        while True:
            try:
                req = self._deferred.popleft()
            except IndexError:
                break
            self._finish_failed(req, leftover)
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            self._finish_failed(req, leftover)

    def _fail_batch(self, batch: list[_Request], exc: Exception) -> None:
        """Dispatch-fault path (off the lint-guarded closure): release the
        batch's waiters with no result and keep serving."""
        log.exception("serve dispatch failed for a %d-request batch: %s",
                      len(batch), exc)
        for req in batch:
            # An admitted slot may hold a stale/garbage carry (the prefill
            # may never have run): drop the session so its next request
            # re-enters cold instead of reading a poisoned slot. Callback-
            # driven clients (the load harnesses, a network front-end) see
            # the failure as a None result, or the session silently leaks
            # out of their bookkeeping.
            self._slots.drop(req.session_id)
            self._steps.pop(req.session_id, None)
            self._finish_failed(req, exc)

    # -- dispatch supervision (serve.max_restarts > 0) --------------------

    def _supervise(self, exc: BaseException) -> None:
        """Training-loop restart contract applied to serving: after a
        fault fails its batch, rebuild the engine (fresh jitted programs +
        fresh slot arena — sessions re-enter cold through the batched
        prefill) under seeded exponential backoff. A streak of more than
        ``max_restarts`` consecutive faults (reset by any completed batch)
        trips the terminal failed state instead of retrying forever."""
        if self.cfg.max_restarts <= 0:
            return                      # PR-8 behavior: no engine rebuild
        with self._sup_lock:
            # Bump under the SAME lock as the consumer's compare-and-
            # reset: either the consumer resets first (pre-fault streak,
            # harmless) or it sees the new epoch and leaves the streak
            # alone — a pre-fault completion can never erase this fault.
            self._fault_epoch += 1
        while not self._stop_event.is_set():
            with self._sup_lock:
                self._restart_streak += 1
                streak = self._restart_streak
            if streak > self.cfg.max_restarts:
                self._enter_failed(exc)
                return
            self._registry.inc("serve_restarts_total")
            if self._obs is not None:
                # Forensics for the eventual bundle: which restart, why,
                # and what the tail looked like going in (flight-ring
                # append — gated off internally when the recorder is off).
                self._obs.record("serve_restart", streak=streak,
                                 error=repr(exc),
                                 exemplars=self.exemplars()[:4])
            self._backoff_sleep(streak)
            try:
                self._build_arena_and_programs()
                # Recompile NOW, on scratch rows, not on the first real
                # post-restart batch (seconds of XLA compile on the
                # dispatch path would blow every queued deadline and
                # shed at max rate); a compile failure folds into the
                # restart streak instead of failing an innocent batch.
                self.warmup()
                log.warning(
                    "serve engine rebuilt after fault (restart %d/%d): "
                    "fresh programs + slot arena, all sessions cold",
                    streak, self.cfg.max_restarts)
                return
            except Exception as rebuild_exc:    # noqa: BLE001 — a failed
                # rebuild is just the next fault in the streak.
                log.exception("serve engine rebuild failed")
                exc = rebuild_exc

    def _backoff_sleep(self, attempt: int) -> None:
        """Seeded exponential backoff between engine rebuilds:
        initial * 2^(attempt-1), capped, with seeded multiplicative jitter
        so a fleet of engines doesn't restart in lockstep. Deliberately
        NOT a ``time.sleep`` (which lint check 10 bans throughout serve/):
        waiting on the stop event keeps shutdown from blocking behind a
        backoff."""
        cfg = self.cfg
        delay = min(cfg.restart_backoff_s * (2.0 ** (attempt - 1)),
                    cfg.restart_backoff_max_s)
        delay *= 0.5 + self._restart_rng.random()
        self._stop_event.wait(delay)

    def _enter_failed(self, exc: BaseException) -> None:
        """Trip the terminal failed state: fail ALL queued work loudly and
        refuse future submits — a restart storm must end in a diagnosable
        corpse, never a silent wedge."""
        self._failed = exc
        self._registry.record("serve_failed", 1.0)
        log.error(
            "serve engine TERMINALLY FAILED: %d consecutive faults "
            "exceeded serve.max_restarts=%d (last: %r); failing all "
            "queued work", self._restart_streak, self.cfg.max_restarts,
            exc)
        if self._obs is not None and getattr(self._obs, "enabled", False):
            # The serve-side black box: the terminal corpse dumps the
            # flight ring (restart trail, overload exemplars, WARNING+
            # logs) plus the current slowest-request exemplars.
            self._obs.record("serve_exemplars",
                             exemplars=self.exemplars()[:8])
            self._obs.dump_flight(reason="serve_failed", error=repr(exc),
                                  restart_streak=self._restart_streak)
        self._drain_failed()

    def _drain_failed(self) -> None:
        """Fail everything queued/deferred with ServeEngineFailed (bounded
        wait on the empty queue so the loop stays responsive to stop)."""
        failure = ServeEngineFailed(
            f"serve engine is terminally failed (last fault: "
            f"{self._failed!r})")
        failure.__cause__ = self._failed
        while self._deferred:
            self._finish_failed(self._deferred.popleft(), failure)
        try:
            while True:
                self._finish_failed(self._q.get(timeout=0.05), failure)
        except queue.Empty:
            pass

    # -- batch collection -------------------------------------------------

    def _expire_if_dead(self, req: _Request, now: float) -> bool:
        """Deadline gate at collection time: a request whose deadline
        passed is completed with ServeDeadlineExceeded BEFORE it can
        occupy a padded device row. Returns True when the request was
        expired (caller must skip it)."""
        if req.t_deadline is None or now < req.t_deadline:
            return False
        self._registry.inc("serve_deadline_expired_total")
        self._finish_failed(req, ServeDeadlineExceeded(
            f"deadline expired {1e3 * (now - req.t_deadline):.1f} ms ago "
            "before the request reached a batch"))
        return True

    def _collect_batch(self) -> list[_Request]:
        """Coalesce one tick's batch: deferred same-session requests first
        (sequential consistency per session — a session's second in-flight
        request must see its first one's carry), then drain the queue until
        ``max_batch`` or the coalescing deadline — anchored at the FIRST
        request and clamped to the earliest surviving request's
        per-request deadline, so waiting for batch-mates never expires
        work the tick could have served. Expired requests are completed
        with a deadline error at pop time and never join the batch."""
        cfg = self.cfg
        # ONE knob read per tick (the _Live atomicity pattern): a
        # mid-collection set_knobs never hands this tick a mixed vector.
        knobs = self._knobs
        # Commit parked rows BEFORE adopted disk takes: both land in the
        # WarmStore, and when the warm budget overflows the store demotes
        # its stalest entry — a carry adopted this tick must be the
        # freshest so the park-inbox commit can never demote it back to
        # disk before its deferred request re-collects.
        self._drain_park_inbox()
        # Commit any completed disk takes next: their sessions' deferred
        # requests un-defer this very tick (and the drain below must see
        # an up-to-date _spill_inflight).
        self._drain_spill_inbox()
        batch: list[_Request] = []
        seen: set = set()
        kept: deque[_Request] = deque()  # trace-buffer-ok: re-queued subset
        # of _deferred, which _collect_batch bounds at the max_queue knob
        now = time.perf_counter()
        while self._deferred:
            req = self._deferred.popleft()
            if self._expire_if_dead(req, now):
                continue
            if (req.session_id in seen or len(batch) >= cfg.max_batch
                    or self._maybe_begin_spill_take(req)):
                req.trace.deferrals += 1
                kept.append(req)
            else:
                req.trace.t_collected = now
                batch.append(req)
                seen.add(req.session_id)
        self._deferred = kept
        if not batch:
            # Idle poll — EXCEPT while a disk take is in flight: the
            # consumer resolves one in µs, and sleeping the full idle
            # interval would bill that 50ms to the adopting session's
            # first response (the spill soak's recovery p99 would eat
            # it whole). _spill_inflight is dispatcher-owned state, so
            # this read races nothing.
            timeout = 0.002 if self._spill_inflight else 0.05
            try:
                req = self._q.get(timeout=timeout)
            except queue.Empty:
                return []
            if self._expire_if_dead(req, time.perf_counter()):
                return []
            if self._maybe_begin_spill_take(req):
                req.trace.deferrals += 1
                self._deferred.append(req)
                return []
            req.trace.t_collected = time.perf_counter()
            batch.append(req)
            seen.add(req.session_id)
        deadline = time.perf_counter() + knobs.batch_timeout_ms / 1e3
        for req in batch:           # anchor to the earliest survivor
            if req.t_deadline is not None:
                deadline = min(deadline, req.t_deadline)
        while len(batch) < cfg.max_batch:
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                break
            try:
                req = self._q.get(timeout=remaining)
            except queue.Empty:
                break
            if self._expire_if_dead(req, time.perf_counter()):
                continue
            if req.session_id in seen:
                if len(self._deferred) >= knobs.max_queue:
                    # The deferred side-queue is bounded too: a single-
                    # session flood must not re-grow the memory the
                    # ingress bound just capped. The loser follows the
                    # configured policy: "oldest" sheds the STALEST
                    # deferred request and admits the new one (the
                    # brownout contract), "reject" refuses the arrival.
                    with self._pending_lock:
                        self._overload_events += 1
                    if cfg.shed_policy == "oldest":
                        victim = self._deferred.popleft()
                        self._registry.inc("serve_shed_total")
                        self._finish_failed(victim, ServeRejected(
                            "shed from the same-session backlog under "
                            "overload (shed_policy='oldest')",
                            reason="shed_oldest"))
                        req.trace.deferrals += 1
                        self._deferred.append(req)
                    else:
                        self._registry.inc("serve_queue_rejected_total")
                        self._finish_failed(req, ServeRejected(
                            "same-session backlog exceeded "
                            "serve.max_queue", reason="deferred_overflow"))
                    continue
                req.trace.deferrals += 1
                self._deferred.append(req)
            else:
                if self._maybe_begin_spill_take(req):
                    req.trace.deferrals += 1
                    self._deferred.append(req)
                    continue
                req.trace.t_collected = time.perf_counter()
                batch.append(req)
                seen.add(req.session_id)
                if (req.t_deadline is not None
                        and req.t_deadline < deadline):
                    deadline = req.t_deadline
        return batch

    def _dispatch_batch(self, batch: list[_Request],
                        live: _Live) -> _DoneBatch:
        """Admit, partition cold/warm, dispatch the tick's program(s).
        Runs on the dispatch critical path: NO blocking host ops here
        (tools/lint_hot_loop.py check 8) — jit calls return asynchronously
        and readback belongs to ``_complete_batch``.  Park-inbox rows are
        committed twice per tick: by ``_collect_batch`` BEFORE the
        spill-inbox drain (so a carry adopted from disk lands freshest in
        the WarmStore and cannot be demoted by an older park), and again
        here for any readback that completed during the collection wait —
        otherwise a session evicted last tick could miss its own parked
        carry at admission and restart cold.  The order keeps both
        invariants: every pre-admission park is committed, and adopted
        takes (committed between the two park drains) stay ahead of every
        park that was pending when they landed."""
        pinned = {r.session_id for r in batch}
        # Batch-pinned carries this drain's commits pushed out of the
        # warm budget come back here — admission consumes them below in
        # place of a warm pop (see _drain_park_inbox).
        rescued = self._drain_park_inbox(pinned=pinned)
        cold_reqs: list[_Request] = []
        cold_idx: list[int] = []
        warm_reqs: list[_Request] = []
        warm_idx: list[int] = []
        evicted = 0
        park_sids: list[Any] = []       # this tick's eviction victims …
        park_slots: list[int] = []      # … and the arena rows they held
        park_steps: list[int] = []      # … and their step stamps
        unpark_slots: list[int] = []    # slots taking a parked carry back
        unpark_rows: list[Any] = []     # the parked host carries
        warm_on = self._warm_enabled
        for req in batch:
            sid = req.session_id
            slot = self._slots.lookup(sid)
            if slot is not None:
                if warm_on:
                    # Dispatched-step clock of a hot session: +1 per
                    # dispatch, so a later park stamps the record with
                    # exactly the completed-response count the router
                    # tracks for the session (the adoption rendezvous).
                    self._steps[sid] = self._steps.get(sid, 0) + 1
                warm_reqs.append(req)
                warm_idx.append(slot)
                continue
            parked = rescued.pop(sid, None) if warm_on else None
            if parked is None and warm_on:
                parked = self._warm.pop(sid)
            if (parked is not None and req.clock is not None
                    and parked[1] != req.clock):
                # RAM-parked carry from an earlier stint of this session
                # on THIS engine, superseded while the session lived
                # elsewhere (the router's clock outran the stamp):
                # serving it warm would change bytes — drop it and
                # restart cold, the same stale demotion disk records get.
                self._warm.stale_drops += 1
                self._registry.inc("serve_warm_stale_drops_total")
                parked = None
            slot, victim = self._slots.admit(sid, pinned)
            if victim is not None:
                evicted += 1
                if warm_on:
                    # The victim's carry still sits in the arena row the
                    # admission just reassigned: remember it for the
                    # batched park gather below (which runs BEFORE any
                    # program or install writes the row).
                    park_sids.append(victim)
                    park_slots.append(slot)
                    park_steps.append(self._steps.pop(victim, 0))
            if parked is not None:
                # Warm HIT: the parked carry reinstalls into the new
                # slot and the session continues through the warm path,
                # bitwise as if never evicted. (A spill-adopted carry
                # landed in the warm store first, so it arrives here —
                # the econ gauge prices spill hits for free.)
                rows, psteps = parked
                self._registry.inc("serve_warm_hits_total")
                self._steps[sid] = psteps + 1
                unpark_slots.append(slot)
                unpark_rows.append(rows)
                warm_reqs.append(req)
                warm_idx.append(slot)
            else:
                if warm_on:
                    self._registry.inc("serve_warm_misses_total")
                    # Cold (re)start: re-anchor the step clock to the
                    # router's view when one was forwarded — the carry
                    # built from here on corresponds to clock+1 completed
                    # responses, so later spills stamp adoptably even
                    # after a mid-life cold restart.
                    self._steps[sid] = (req.clock + 1
                                        if req.clock is not None else 1)
                    if req.clock:
                        # A session the fleet believes has history is
                        # restarting through prefill: a COLD adoption
                        # (counted against warm ones per migration).
                        self._registry.inc("serve_adopt_cold_total")
                    if self._spill_enabled:
                        # Unconditional tombstone: a cold (re)start
                        # invalidates any record the arena still holds
                        # for this session (e.g. one sealed by a racing
                        # put after our probe missed) — stale episode
                        # state must never outlive the restart.
                        self._spill_ops.append(("del", sid))
                        self._kick_consumer()
                cold_reqs.append(req)
                cold_idx.append(slot)
        for sid, (rows, psteps) in rescued.items():
            # Defensive: a rescued carry whose session somehow took the
            # hot path (slots and warm store are disjoint, so this
            # should be unreachable) re-parks instead of silently dying.
            self._commit_warm(sid, rows, psteps)
        parked_rows = None
        if park_sids:
            # Page-out step 1 (dispatch side): ONE batched gather of the
            # victims' rows at the static max_batch shape — async device
            # compute; the consumer does the host readback (check 17).
            pidx = np.full((self.cfg.max_batch,), self.cfg.slots,
                           np.int32)
            pidx[:len(park_slots)] = park_slots
            parked_rows = self._park_fn(self._pool, pidx)
        if unpark_rows:
            self._pool = self._install_parked(unpark_rows, unpark_slots)
        # self._pool is reassigned IMMEDIATELY after each program call:
        # the calls donate the arena, so holding the old reference across
        # a later failure (the warm group's _pad raising after the cold
        # program already consumed the buffer) would leave the field
        # pointing at a deleted array and wedge every future tick.
        self._batch_serial += 1         # dispatcher-thread-owned serial
        bid = self._batch_serial

        def _stamp(reqs: list[_Request], cold: bool) -> None:
            # Dispatch edge: the jit call below returns asynchronously, so
            # this stamp marks "handed to the device", and the device
            # stage absorbs compute + queueing behind earlier programs.
            t = time.perf_counter()
            for req in reqs:
                req.trace.t_dispatched = t
                req.trace.batch = bid
                req.trace.cold = cold

        groups: list[tuple[list[_Request], Any, Any, Any, Any]] = []
        if self._episode:
            if cold_reqs:
                obs, idx = self._pad(cold_reqs, cold_idx)
                _stamp(cold_reqs, True)
                act, logit, val, self._pool = self._cold_fn(
                    live.params, self._pool, obs, idx)
                groups.append((cold_reqs, act, logit, val, None))
            if warm_reqs:
                obs, idx = self._pad(warm_reqs, warm_idx)
                _stamp(warm_reqs, False)
                act, logit, val, self._pool, *stats = self._warm_fn(
                    live.params, self._pool, obs, idx)
                groups.append((warm_reqs, act, logit, val,
                               stats[0] if stats else None))
        else:
            reqs = cold_reqs + warm_reqs
            cold_mask = np.zeros((self.cfg.max_batch,), bool)
            cold_mask[:len(cold_reqs)] = True
            obs, idx = self._pad(reqs, cold_idx + warm_idx)
            _stamp(reqs, False)
            for req in cold_reqs:
                req.trace.cold = True
            act, logit, val, self._pool = self._step_fn(
                live.params, self._pool, obs, idx, cold_mask)
            groups.append((reqs, act, logit, val, None))
        return _DoneBatch(groups=groups, step=live.step, n=len(batch),
                          cold=len(cold_reqs), evicted=evicted,
                          epoch=self._fault_epoch,
                          parked_sids=tuple(park_sids),
                          parked_rows=parked_rows,
                          parked_steps=tuple(park_steps), tick=bid)

    def _pad(self, reqs: list[_Request],
             idx: list[int]) -> tuple[np.ndarray, np.ndarray]:
        """Pad a group to the static ``max_batch`` shape: padding rows
        repeat the first real observation (finite by construction) and
        index SCRATCH arena rows, never a live slot."""
        cfg = self.cfg
        obs = np.empty((cfg.max_batch, reqs[0].obs.shape[-1]), np.float32)
        out_idx = np.empty((cfg.max_batch,), np.int32)
        for i, req in enumerate(reqs):
            obs[i] = req.obs
            out_idx[i] = idx[i]
        for i in range(len(reqs), cfg.max_batch):
            obs[i] = reqs[0].obs
            out_idx[i] = cfg.slots + i
        return obs, out_idx

    # -- session paging (dispatch side) -----------------------------------

    def _drain_park_inbox(self, pinned: set | None = None
                          ) -> dict[Any, tuple[Any, int]]:
        """Commit consumer-read-back page-outs into the warm store.
        Dispatcher-only, so ALL admission state (slot pool + warm store)
        has one owner and no insert can race an unpark. An entry whose
        session re-entered the slot pool before its page-out committed
        is STALE — that session already restarted cold and its old
        episode state must never resurrect — and is dropped.

        ``pinned`` is the pre-admission call's batch membership: a
        commit here may overflow the warm budget and demote a carry
        whose session is about to be admitted THIS tick (with a 1-carry
        budget, any park between a spill-take commit and its deferred
        request's admission would bounce the adopted carry straight
        back out). Such victims are RESCUED — returned as
        ``{sid: (rows, steps)}`` for admission to consume directly —
        instead of spilled/dropped; everyone else demotes normally."""
        rescued: dict[Any, tuple[Any, int]] = {}
        while self._park_inbox:
            sid, rows, steps = self._park_inbox.popleft()
            if self._slots.contains(sid):
                self._warm.stale_drops += 1
                self._registry.inc("serve_warm_stale_drops_total")
                continue
            self._commit_warm(sid, rows, steps, pinned=pinned,
                              rescued=rescued)
        return rescued

    def _commit_warm(self, sid: Any, rows: Any, steps: int, *,
                     pinned: set | None = None,
                     rescued: dict | None = None) -> None:
        """Park one host carry in the warm store; overflow demotes to
        the spill arena (tier on) or to cold (off — the ISSUE-18
        contract, unchanged), except batch-pinned victims, which land
        in ``rescued`` for this tick's admission. Dispatcher-only."""
        demoted = self._warm.put(sid, rows, self._carry_nbytes, steps)
        if demoted and pinned:
            kept = []
            for victim, vrows, _vnbytes, vsteps in demoted:
                if victim in pinned and rescued is not None:
                    rescued[victim] = (vrows, vsteps)
                    # Not a real demotion — admission consumes it in a
                    # moment, exactly as a warm pop would have.
                    self._warm.demotions -= 1
                else:
                    kept.append((victim, vrows, _vnbytes, vsteps))
            demoted = kept
        if demoted:
            self._registry.inc("serve_warm_demotions_total",
                               len(demoted))
            self._spill_demoted(demoted)

    def _spill_demoted(self, demoted: list) -> None:
        """Route warm-store overflow toward the disk arena: enqueue one
        put op per demoted entry for the CONSUMER to seal (dispatch
        never touches the files). With the spill tier off the entries
        simply fall to cold."""
        if not self._spill_enabled:
            return
        for sid, rows, _nbytes, steps in demoted:
            self._spill_ops.append(("put", sid, rows, steps))
        self._kick_consumer()

    def _kick_consumer(self) -> None:
        """Nudge an idle consumer to run the queued spill ops now
        (best-effort: a full done queue means it is already awake and
        drains the op FIFO after its current batch)."""
        try:
            self._done_q.put_nowait(_SPILL_TICK)
        except queue.Full:
            pass

    def _drain_spill_inbox(self) -> None:
        """Commit completed disk takes into the warm store and release
        their sessions from the deferral set. Dispatcher-only (the
        admission-state single-owner rule); the consumer only appends.
        A hit whose session somehow re-entered the pool meanwhile is
        dropped like a stale page-out — never overwrite a live episode."""
        while self._spill_inbox:
            sid, rows, steps, _reason = self._spill_inbox.popleft()
            self._spill_inflight.discard(sid)
            if rows is None:
                continue        # miss/stale/corrupt: the session lands cold
            if self._slots.contains(sid):
                self._warm.stale_drops += 1
                self._registry.inc("serve_warm_stale_drops_total")
                continue
            self._commit_warm(sid, rows, steps)

    def _maybe_begin_spill_take(self, req: _Request) -> bool:
        """Collection-time spill gate: True when the request must DEFER
        (the caller re-queues it) behind a disk take — either one
        already in flight for its session, or the one this call just
        enqueued. The only dispatch-side arena touch is probe()'s
        ``os.stat`` (µs — the read itself rides the consumer, lint
        checks 8/19); sessions with no sealed record admit cold on this
        very tick and pay nothing."""
        if not self._spill_enabled:
            return False
        sid = req.session_id
        if sid in self._spill_inflight:
            return True
        if self._slots.contains(sid) or self._warm.contains(sid):
            return False        # hot or RAM-warm: no disk involved
        if not self._arena.probe(sid):
            return False
        self._spill_ops.append(("take", sid, req.clock))
        self._spill_inflight.add(sid)
        self._kick_consumer()
        return True

    def _install_parked(self, rows: list[Any], slots: list[int]) -> Any:
        """Unpark: stack the tick's parked host carries, pad to the
        static ``max_batch`` shape (padding rows repeat row 0 and write
        SCRATCH arena rows, mirroring :meth:`_pad`), and scatter-install
        into the (re-)admitted slots. ``device_put`` of host rows is an
        async H2D enqueue — legal on the dispatch thread; no readback
        happens here."""
        cfg = self.cfg
        n = len(rows)
        idx = np.empty((cfg.max_batch,), np.int32)
        idx[:n] = slots
        for i in range(n, cfg.max_batch):
            idx[i] = cfg.slots + i
        pad = cfg.max_batch - n
        stacked = jax.tree.map(
            lambda *leaves: np.stack(leaves + (leaves[0],) * pad),
            *rows)
        return self._install_fn(self._pool, stacked, idx)

    # -- consumer thread --------------------------------------------------

    def _complete_loop(self) -> None:
        while True:
            try:
                item = self._done_q.get(timeout=0.2)
            except queue.Empty:
                # Normally the _SHUTDOWN sentinel ends this loop; the
                # timed poll covers the sentinel stop() had to DROP on a
                # full queue (consumer stalled past the put timeout) — a
                # later-recovering consumer drains what remains and then
                # exits here instead of parking forever on a sentinel
                # that will never arrive. Exit ONLY once the dispatcher
                # is gone too, and even then drain once more first: the
                # dispatcher may have put its final batch between our
                # empty get and its exit, and those waiters must still
                # reach a terminal outcome.
                if (self._stop_event.is_set()
                        and not self._dispatcher.is_alive()):
                    while True:
                        try:
                            item = self._done_q.get_nowait()
                        except queue.Empty:
                            # Exit debt: queued spill PUTS still seal
                            # (demoted carries must not die with the
                            # process); takes skip — their requesters
                            # were failed, and a consumed record would
                            # be lost to the adopting engine.
                            self._drain_spill_ops()
                            return
                        if (item is not _SHUTDOWN
                                and item is not _SPILL_TICK):
                            self._consume_done(item)
                continue
            if item is _SHUTDOWN:
                self._drain_spill_ops()
                return
            if item is _SPILL_TICK:
                self._drain_spill_ops()
                continue
            self._consume_done(item)
            # Safety net behind the best-effort _kick_consumer: ops
            # enqueued while the done queue was full drain here.
            self._drain_spill_ops()

    def _consume_done(self, item: _DoneBatch) -> None:
        try:
            with self._span("serve/complete_batch", tick=item.tick,
                            rows=item.n):
                self._complete_batch(item)
        except Exception as exc:  # noqa: BLE001 — a completion fault
            # (readback error, device fault) must neither wedge the
            # dispatcher behind a full done queue NOR leak the batch's
            # waiters: release every request not already completed,
            # mirroring the dispatcher's _fail_batch contract.
            log.exception("serve consumer failed completing a batch")
            for reqs, *_ in item.groups:
                for req in reqs:
                    # The dispatched program already ADVANCED these
                    # sessions' slot carries; hand them to the
                    # dispatcher to drop (it owns the SlotPool) so a
                    # client retry doesn't double-step a warm carry.
                    self._poisoned.append(req.session_id)
                    if req._event.is_set():
                        continue
                    req.error = exc
                    req._event.set()
                    with self._pending_lock:
                        # Pending was already decremented by the batch-
                        # level finally; only the SLO outcome accounting
                        # is per-request here.
                        self._term_total += 1
                        self._term_bad += 1
                    if req.callback is not None:
                        try:
                            req.callback(None)
                        except Exception:   # noqa: BLE001
                            log.exception("serve failure callback failed")
                    self._trace_request(req, "failed",
                                        time.perf_counter())
            # A consumer fault is an ENGINE fault for the supervisor:
            # the readback path may hold poisoned device buffers, so ask
            # the dispatcher to run the restart/backoff contract (no-op
            # at the default max_restarts=0), stamped with the faulting
            # batch's epoch so a pre-restart batch draining out of the
            # done queue can't re-trip a restart the rebuild already
            # delivered.
            self._consumer_fault = exc
            self._consumer_fault_epoch = item.epoch
            self._restart_requested.set()
        finally:
            # Frees the tick's slot, faulted or not.
            with self._slot_cv:
                self._ticks_completed += 1
                self._slot_cv.notify()

    #: Arena take verdicts -> registry counters (the fleet router folds
    #: these per engine into fleet_spill_* — ISSUE 20 observability).
    _SPILL_REASON_COUNTERS = {
        "hit": "serve_spill_hits_total",
        "miss": "serve_spill_misses_total",
        "stale": "serve_spill_stale_total",
        "corrupt": "serve_spill_corrupt_total",
    }

    def _drain_spill_ops(self) -> None:
        """Execute queued arena ops — the ONLY place spill disk I/O
        happens while the engine runs (consumer thread; dispatch only
        enqueues, lint checks 8/17/19). Once the stop event is set,
        takes are SKIPPED instead of executed: their requesters are
        being failed, and consuming the record here would steal the
        carry from whichever engine adopts the session next."""
        arena = self._arena
        if arena is None:
            return
        reg = self._registry
        skip_takes = self._stop_event.is_set()
        while self._spill_ops:
            op = self._spill_ops.popleft()
            kind = op[0]
            if kind == "put":
                _, sid, rows, steps = op
                ok = arena.put(sid, jax.tree.leaves(rows), steps)
                reg.inc("serve_spill_puts_total" if ok
                        else "serve_spill_put_refusals_total")
            elif kind == "del":
                arena.delete(op[1])
            elif skip_takes:
                self._spill_inbox.append((op[1], None, 0, "skipped"))
            else:
                _, sid, clock = op
                payload, steps, reason, foreign = arena.take(sid, clock)
                reg.inc(self._SPILL_REASON_COUNTERS[reason])
                if reason == "hit" and clock is not None and foreign:
                    # A clocked hit on ANOTHER incarnation's record is
                    # a cross-engine warm ADOPTION (this engine's own
                    # re-reads — spill thrash — deliberately don't
                    # count; the soak reconciles this exactly).
                    reg.inc("serve_adopt_warm_total")
                rows = (self._rows_from_payload(payload)
                        if payload is not None else None)
                self._spill_inbox.append((sid, rows, steps, reason))

    def _rows_from_payload(self, payload: bytes) -> Any:
        """Rebuild a carry tree from a spill record's raw payload: split
        against this engine's carry template in ``jax.tree`` order (the
        order the writer concatenated; the arena already validated the
        total byte length, so a foreign-model record never reaches
        here)."""
        leaves, treedef = jax.tree.flatten(self._carry0)
        out, off = [], 0
        for leaf in leaves:
            n = int(leaf.size)
            arr = np.frombuffer(payload, dtype=leaf.dtype, count=n,
                                offset=off)
            out.append(arr.reshape(leaf.shape).copy())
            off += n * leaf.dtype.itemsize
        return jax.tree.unflatten(treedef, out)

    def _complete_batch(self, done: _DoneBatch) -> None:
        """Readback + request completion + SLO accounting — the consumer
        side of the split; blocking host work is EXPECTED here. The
        pending count decrements in a finally so a mid-completion fault
        (handled by :meth:`_complete_loop`) can never strand
        :meth:`drain`."""
        n_done = slow = 0
        slo_target = self._slo[1]
        hists = self._hists
        t_begin = time.perf_counter()
        readback_s = 0.0
        if done.parked_sids:
            # Page-out step 2: the host readback of the victims' carry
            # rows rides HERE, on the consumer — the dispatch loop never
            # blocks on a device_get (lint check 17). The copies detach
            # each session's rows from the stacked transfer buffer so a
            # later partial demotion frees real memory.
            t_rb = time.perf_counter()
            with self._span("serve/readback", tick=done.tick):
                # serve-host-ok: consumer-side page-out readback.
                host_rows = jax.device_get(done.parked_rows)
            readback_s += time.perf_counter() - t_rb
            for i, sid in enumerate(done.parked_sids):
                row = jax.tree.map(lambda x: np.asarray(x[i]).copy(),
                                   host_rows)
                self._park_inbox.append((sid, row, done.parked_steps[i]))
            self._registry.inc("serve_warm_parks_total",
                               len(done.parked_sids))
        # Batch-level trace buffer: one bulk tracer append per completed
        # batch instead of one lock round-trip per request.
        trace_lines: list[str] | None = (
            [] if self._req_tracer is not None else None)
        try:
            for reqs, act_dev, logit_dev, val_dev, stats_dev in done.groups:
                t_rb = time.perf_counter()
                with self._span("serve/readback", tick=done.tick):
                    # serve-host-ok: consumer-side readback — the
                    # dispatcher never blocks on these buffers.
                    actions, logits, values, stats = jax.device_get(
                        (act_dev, logit_dev, val_dev, stats_dev))
                now = time.perf_counter()
                readback_s += now - t_rb
                # The consumer serializes a batch's completions, so the
                # readback HISTOGRAM charges each request only its own
                # completion slice (t_prev→t_done): billing t_done minus
                # the group readback stamp would blame every request for
                # its earlier batch-mates' callbacks and regress the
                # serve_readback_p99_ms gate row as occupancy rises. The
                # trace's readback child span keeps the client-observable
                # t_device→t_done wait.
                t_prev = now
                for i, req in enumerate(reqs):
                    tr = req.trace
                    tr.t_device = now
                    # Telescoping stage decomposition: the three stages
                    # share their interior stamps, so their sum IS the
                    # end-to-end latency (the soak-asserted invariant).
                    # The None-guards are defensive only — every request
                    # that reaches here was collected and dispatched — a
                    # missing stamp must degrade one request's breakdown,
                    # never fail the whole batch on this thread.
                    t_coll = tr.t_collected or tr.t_enq
                    t_disp = tr.t_dispatched or t_coll
                    latency_ms = (now - req.t_enq) * 1e3
                    stages = {
                        "queue_wait_ms": (t_coll - tr.t_enq) * 1e3,
                        "batch_wait_ms": (t_disp - t_coll) * 1e3,
                        "device_ms": (now - t_disp) * 1e3,
                    }
                    if tr.cold:
                        # EWMA of what a cold re-entry COSTS (device
                        # time incl. queueing behind the tick's other
                        # programs — the amortized, honest figure): the
                        # recompute side of the eviction-economics
                        # gauge.
                        prev_ewma = self._ewma_prefill_ms
                        self._ewma_prefill_ms = (
                            stages["device_ms"] if prev_ewma == 0.0
                            else 0.9 * prev_ewma
                            + 0.1 * stages["device_ms"])
                    result = ServeResult(
                        session_id=req.session_id,
                        action=int(actions[i]),
                        logits=logits[i],
                        value=float(values[i]),
                        params_step=done.step,
                        latency_ms=latency_ms,
                        stages=stages)
                    req.result = result
                    req._event.set()
                    if req.callback is not None:
                        try:
                            req.callback(result)
                        except Exception:   # noqa: BLE001
                            log.exception("serve result callback failed")
                    tr.t_done = time.perf_counter()
                    hists["serve_queue_wait_ms"].observe(
                        stages["queue_wait_ms"])
                    hists["serve_batch_wait_ms"].observe(
                        stages["batch_wait_ms"])
                    hists["serve_device_ms"].observe(stages["device_ms"])
                    hists["serve_readback_ms"].observe(
                        (tr.t_done - t_prev) * 1e3)
                    t_prev = tr.t_done
                    self._h_e2e.observe(latency_ms)
                    if abs(sum(stages.values()) - latency_ms) > 1e-6:
                        # Structural self-check: the decomposition is
                        # exact by construction, so any drift means a
                        # refactor broke a stamp — the soaks assert this
                        # counter stays 0.
                        self._registry.inc(
                            "serve_trace_decomposition_error_total")
                    if slo_target and latency_ms > slo_target:
                        slow += 1
                    n_done += 1
                    if self._exemplar_k:
                        self._note_exemplar(req, latency_ms, stages,
                                            done.step)
                    self._trace_request(req, "completed", tr.t_done,
                                        lines=trace_lines)
                if stats is not None:
                    # The model's own counters over the tick's REAL rows
                    # (padding rows routed too, and are left out), once the
                    # tick's answers have gone out.
                    counts, samples = self.model.serve_stats(
                        stats[:len(reqs)])
                    for name, amount in counts.items():
                        self._registry.inc(name, amount)
                    for name, value in samples.items():
                        self._stat_hist(name).observe(value)
        finally:
            if trace_lines:
                self._req_tracer.emit_lines(trace_lines)
            with self._pending_lock:
                self._pending -= done.n
                self._term_total += n_done
                self._term_completed += n_done
                self._term_slow += slow
        # A completed batch heals the supervisor's consecutive-fault
        # streak (mirrors the training loop's restart accounting) — but
        # ONLY a batch dispatched after the latest fault: pre-fault
        # batches draining out of the done queue during a backoff say
        # nothing about the rebuilt engine.
        with self._sup_lock:
            if done.epoch == self._fault_epoch:
                self._restart_streak = 0
        with self._pending_lock:
            # Locked: failure-path publishes snapshot-and-reset these
            # from other threads (the qps/occupancy window).
            self._stats_completed += done.n
            self._stats_occupancy.append(done.n / self.cfg.max_batch)
        reg = self._registry
        reg.inc("serve_responses_total", done.n)
        reg.inc("serve_batches_total")
        if done.cold:
            reg.inc("serve_prefills_total", done.cold)
        if done.evicted:
            reg.inc("serve_evictions_total", done.evicted)
        self._publish_stats()
        self._h_complete_host.observe(
            (time.perf_counter() - t_begin - readback_s) * 1e3)

    def _stat_hist(self, name: str) -> Histogram:
        """The registry's histogram of one of the model's ``serve_stats``
        samples, attached on first use (consumer thread only)."""
        hist = self._hists.get(name)
        if hist is None:
            hist = self._hists[name] = self._registry.attach_histogram(
                name, Histogram())
        return hist

    def _note_exemplar(self, req: _Request, latency_ms: float,
                       stages: dict, step: int) -> None:
        """Track the window's K slowest completed requests with their full
        stage breakdown (consumer thread; K is small, so the min-replace
        scan is a handful of comparisons)."""
        tr = req.trace
        with self._ex_lock:
            w = self._window_slowest
            if len(w) >= self._exemplar_k:
                m = min(range(len(w)), key=lambda j: w[j]["latency_ms"])
                if latency_ms <= w[m]["latency_ms"]:
                    return
                del w[m]
            w.append({
                "session": str(req.session_id),
                "latency_ms": round(latency_ms, 3),
                "stages": {k: round(v, 3) for k, v in stages.items()},
                "batch": tr.batch,
                "cold": tr.cold,
                "deferrals": tr.deferrals,
                "params_step": step,
            })

    def exemplars(self) -> list[dict]:
        """The slowest-request exemplar ring (recent windows' top-K plus
        the in-progress window), slowest first — the ``cli serve`` summary
        and flight-recorder payload. Safe from any thread."""
        with self._ex_lock:
            merged = list(self._exemplars) + list(self._window_slowest)
        return sorted(merged, key=lambda e: -e["latency_ms"])

    def refresh_spill_gauges(self) -> None:
        """Health-probe hook (the fleet scrape path calls this): re-
        anchor and republish the spill-arena census gauges even while
        no batch is completing. The stats cadence rides batch
        completions, so an idle engine's last in-traffic publish would
        otherwise freeze ``serve_spill_bytes/sessions`` exactly when a
        drain or kill decision wants them (the population quiesces,
        THEN someone reads the fleet sums). One bounded scandir at the
        stats cadence, callable from any scrape thread — the same
        budget class as the dispatcher's admission-time ``probe``."""
        arena = self._arena
        if arena is None:
            return
        now = time.perf_counter()
        if now - self._spill_scan_t < self.cfg.stats_interval_s:
            return
        self._spill_scan_t = now
        arena.scan_usage()
        self._registry.record_many({
            "serve_spill_bytes": float(arena.bytes),
            "serve_spill_sessions": float(arena.sessions)})

    def _publish_stats(self, *, force: bool = False,
                       io_ok: bool = True) -> None:
        """SLO gauges at ``stats_interval_s`` cadence. Callers: the
        consumer thread (every completed batch), terminal-failure paths
        (any thread — see ``_stats_lock``; they pass ``io_ok=False`` so
        the never-blocks submit/dispatcher contract survives the exemplar
        file write), and ``stop`` (force). A non-force caller that loses
        the lock race simply skips: someone else is publishing this
        window."""
        now = time.perf_counter()
        if not force and now - self._stats_t < self.cfg.stats_interval_s:
            return
        if not self._stats_lock.acquire(blocking=force):
            return
        try:
            if force:
                # Re-anchor past any publish that won the lock while we
                # blocked: a stale `now` would read as interval <= 0 and
                # silently skip the FINAL gauges (and any deferred
                # exemplar-file write) stop() exists to flush.
                now = time.perf_counter()
            self._publish_stats_locked(now, force, io_ok)
        finally:
            self._stats_lock.release()

    def _publish_stats_locked(self, now: float, force: bool,
                              io_ok: bool) -> None:
        interval = now - self._stats_t
        if not force and interval < self.cfg.stats_interval_s:
            return
        if interval <= 0:
            return
        with self._pending_lock:
            overload_events = self._overload_events
            self._overload_events = 0
            term = (self._term_total, self._term_bad,
                    self._term_completed, self._term_slow)
            completed = self._stats_completed
            occupancy = self._stats_occupancy
            self._stats_completed = 0
            self._stats_occupancy = []
        depth = self._q.qsize()
        overloaded = (overload_events > 0
                      or depth >= self._knobs.max_queue)
        row: dict[str, float] = {
            "serve_qps": completed / interval,
            "serve_queue_depth": float(depth),
            # Overload gauge: 1 while the engine is shedding/rejecting or
            # the ingress queue is pinned at its bound, else 0.
            "serve_overload": float(overloaded),
        }
        # p50/p99 from the end-to-end histogram's per-window bucket DELTA
        # (cumulative counts subtract exactly — the same bucket math a
        # fleet router uses to merge engines): every completed request in
        # the window counts, where the old bounded sample ring silently
        # forgot overflow under load.
        snap = self._h_e2e.snapshot()
        delta = [a - b for a, b in zip(snap["counts"],
                                       self._p50_prev_counts)]
        self._p50_prev_counts = snap["counts"]
        if sum(delta) > 0:
            row["serve_p50_ms"] = self._h_e2e.quantile(0.50, counts=delta)
            row["serve_p99_ms"] = self._h_e2e.quantile(0.99, counts=delta)
        if occupancy:
            row["serve_batch_occupancy"] = (
                sum(occupancy) / len(occupancy))
        # Session-tier populations + warm accounting. Reading the
        # dispatcher-owned structures from here is a couple of int/len
        # loads (GIL-atomic references; approximate by a tick at worst —
        # gauges, not invariants).
        row["serve_sessions_hot"] = float(len(self._slots))
        if self._warm_enabled:
            warm = self._warm
            row["serve_warm_sessions"] = float(len(warm))
            row["serve_warm_bytes"] = float(warm.bytes)
            row["serve_warm_budget_bytes"] = float(warm.max_bytes)
            # Eviction economics, live: prefill-recompute ms AVOIDED by
            # this window's warm hits, per MB of carry bytes held — the
            # "is the RAM paying for itself" gauge (≫0: keep paging;
            # ~0: the budget is dead weight).
            hits = self._registry.counters().get(
                "serve_warm_hits_total", 0.0)
            d_hits = max(0.0, hits - self._prev_warm_hits)
            self._prev_warm_hits = hits
            held_mb = warm.bytes / 2**20
            # serve_warm_hits_total counts SPILL hits too (an adopted
            # carry re-enters through the warm store), so the econ
            # gauge prices the whole warm+spill tier per RAM MB held.
            row["serve_warm_econ_ms_per_mb"] = (
                d_hits * self._ewma_prefill_ms / held_mb
                if held_mb > 0 else 0.0)
        if self._arena is not None:
            arena = self._arena
            if io_ok:
                # Re-anchor the approximate usage counters with one
                # bounded scandir — consumer/stop threads only (io_ok
                # keeps the failure-path publishes, which run on submit/
                # dispatcher threads, off the filesystem).
                arena.scan_usage()
                self._spill_scan_t = now
            row["serve_spill_bytes"] = float(arena.bytes)
            row["serve_spill_sessions"] = float(arena.sessions)
            row["serve_spill_budget_bytes"] = float(arena.max_bytes)
        row.update(self._slo_burn(now, term))
        self._registry.record_many(row)
        self._fold_exemplars(overloaded, io_ok)
        self._stats_t = now

    def _slo_burn(self, now: float, term: tuple) -> dict[str, float]:
        """Rolling error-budget burn rates over ``obs.slo_window_s``: the
        window is the difference of cumulative terminal-outcome counts
        between now and the oldest in-window publish snapshot. Burn 1.0 =
        spending exactly the SLO's error budget; crossing
        ``obs.slo_burn_threshold`` records a flight event (with the
        current exemplars) and a trace instant, re-arming only after the
        burn halves (hysteresis)."""
        if not self._slo_on:
            return {}
        avail, target_p99, window_s, threshold = self._slo
        win = self._slo_win
        win.append((now, *term))
        # Prune to the NEWEST snapshot at-or-before the window edge: that
        # snapshot is the delta base, so popping it whenever it merely
        # predates the edge would (a) silently exclude every event between
        # the edge and the next snapshot and (b) collapse the delta to
        # zero outright whenever the publish interval reaches window_s
        # (base == the just-appended snapshot). When publishes are sparser
        # than the window, the window degrades to one publish interval —
        # the honest reading, never a frozen gauge.
        while len(win) > 1 and win[1][0] <= now - window_s:
            win.popleft()
        base = win[0]
        d_total = term[0] - base[1]
        d_bad = term[1] - base[2]
        d_completed = term[2] - base[3]
        d_slow = term[3] - base[4]
        out: dict[str, float] = {}
        burns: dict[str, float] = {}
        if avail > 0 and d_total > 0:
            burns["availability"] = (d_bad / d_total) / (1.0 - avail)
            out["serve_slo_availability_burn"] = burns["availability"]
        if target_p99 > 0 and d_completed > 0:
            burns["latency"] = (d_slow / d_completed) / 0.01
            out["serve_slo_latency_burn"] = burns["latency"]
        worst = max(burns.values(), default=0.0)
        if worst >= threshold and not self._burn_alarm:
            self._burn_alarm = True
            self._registry.inc("serve_slo_burn_alerts_total")
            log.warning(
                "SLO burn rate %.2f crossed threshold %.2f "
                "(window %ds: %d/%d bad, %d/%d slow)", worst, threshold,
                int(window_s), d_bad, d_total, d_slow, d_completed)
            if self._obs is not None:
                self._obs.record(
                    "slo_burn", burns=burns, threshold=threshold,
                    window_s=window_s, bad=d_bad, total=d_total,
                    slow=d_slow, completed=d_completed,
                    exemplars=self.exemplars()[:4])
                self._obs.tracer.instant("serve_slo_burn", **burns)
        elif self._burn_alarm and worst < 0.5 * threshold:
            self._burn_alarm = False
        return out

    def _fold_exemplars(self, overloaded: bool, io_ok: bool) -> None:
        """End of a stats window: fold the window's top-K slowest into the
        bounded exemplar ring; on overload ONSET record them into the
        flight ring (the forensic payload for "why was the tail slow when
        shedding started"); write the ring to ``serve_exemplars.json`` in
        the obs run dir when obs is on. ``io_ok=False`` (failure-path
        publishes on submit/dispatcher threads) defers the file write —
        the fold still happens and ``_ex_dirty`` carries the debt to the
        next consumer/stop publish."""
        with self._ex_lock:
            if self._window_slowest:
                self._exemplars.extend(
                    sorted(self._window_slowest,
                           key=lambda e: -e["latency_ms"]))
                self._window_slowest = []
                self._ex_dirty = True
        obs = self._obs
        if obs is None or not getattr(obs, "enabled", False):
            self._overload_flagged = overloaded
            return
        if overloaded and not self._overload_flagged:
            obs.record("serve_overload_exemplars",
                       exemplars=self.exemplars()[:8])
        self._overload_flagged = overloaded
        run_dir = getattr(obs, "run_dir", None)
        # Rewrite the file only when the ring actually changed: a publish
        # with no new window exemplars (idle engine, outage-driven stats
        # ticks) must not pay write+rename on a request-path thread.
        if run_dir and io_ok and self._ex_dirty:
            try:
                path = os.path.join(run_dir, "serve_exemplars.json")
                tmp = f"{path}.tmp-{os.getpid()}"
                with open(tmp, "w", encoding="utf-8") as f:
                    json.dump({"exemplars": self.exemplars()}, f)
                os.replace(tmp, path)
                self._ex_dirty = False
            except OSError:
                log.exception("serve exemplar export failed")
