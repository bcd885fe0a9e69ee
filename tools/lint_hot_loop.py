#!/usr/bin/env python
"""Grep-lint for the orchestrator's training hot loop and the device code.

Four checks, all run by ``make check``/``make lint`` and the tier-1 guard
in tests/test_megachunk.py:

1. **Hot-loop syncs** — the megachunk refactor (runtime/orchestrator.py
   _run_supervised) replaced the per-chunk scalar device round-trips —
   ``jax.device_get(ts.updates)``, ``float(np.asarray(v))`` per metric key
   — with ONE batched readback per (mega)chunk sample; each stray scalar
   sync costs a full device round-trip that serializes the dispatch
   pipeline (per-dispatch host cost, not yet measured on an attached
   chip). FAILS when a bare ``device_get(`` /
   ``float(np.asarray`` / ``block_until_ready(`` reappears inside the
   hot-loop functions without the explicit ``hot-loop-sync-ok`` marker
   naming why that sync is off the per-chunk path.

2. **Bare device_put in the parallel layer** (the shard-audit PR's guard) —
   inside ``sharetrade_tpu/parallel/`` a ``jax.device_put(x)`` WITHOUT an
   explicit sharding lands the array wherever the default device is, and
   the first partitioned program that consumes it pays an involuntary
   reshard to pull it onto its canonical spec — exactly the class of
   silent data movement the shard audit (tools/shard_audit.py) gates out
   of the compiled step. FAILS on any ``device_put`` call in the parallel
   package that passes neither a second positional argument nor a
   ``device=`` keyword, unless the line carries ``device-put-ok`` naming
   why placement is intentionally unspecified.

3. **Host calls in traced step code** (the obs PR's guard) — inside the
   device packages (agents/env/models/ops) the traced step bodies are
   NESTED functions (closures handed to ``jax.jit``/``lax.scan``). A
   ``time.time()`` / ``time.perf_counter()`` / ``log.*()`` / ``print()``
   there does not do what it reads as doing: it runs ONCE at trace time,
   freezing its value into the compiled program (a timestamp constant, a
   once-per-retrace log line) — never the per-step signal the author
   expected, and a retrace-cadence host side effect besides. Telemetry
   belongs on the host side of the chunk boundary (obs/), keyed off the
   batched readback. FAILS on such calls inside any nested function of
   those packages unless the line carries ``jit-host-call-ok`` naming why
   it is trace-time-only on purpose (``jax.debug.print`` is exempt — the
   dotted call never matches).

4. **Blocking host work in the DISPATCHER** (the async-pipeline PR's
   guard) — with ``runtime.async_pipeline`` the orchestrator's dispatch
   loop (``_run_supervised``) and its boundary-decision block
   (``_boundary_actions``) must never block on a device readback or host
   IO: that work belongs to the pipeline's consumer thread
   (``_host_process`` / ``_journal_transitions``), where the same calls
   are expected and carry the ``hot-loop-sync-ok`` marker naming the
   consumer-side exemption. FAILS when ``jax.device_get`` /
   ``np.asarray`` / ``os.fsync`` / ``block_until_ready`` appears unmarked
   in a dispatcher-section function, and when the consumer-side functions
   this split relies on disappear (a rename must update this lint, not
   silently un-guard the seam).

5. **fsync before publishing a durable rename** (the crash-safety PR's
   guard) — in the checkpoint/journal write paths
   (``checkpoint/manager.py``, ``data/journal.py``) an ``os.replace``
   publishes a payload atomically, but WITHOUT a preceding fsync the
   published name can outlive its bytes across a power loss (the rename
   is ordered in the directory, the data blocks are not). FAILS on any
   function in those files that calls ``os.replace`` without fsync
   evidence in the same function — an actual CALL, matched in the AST, to
   ``os.fsync``/``_fsync_dir`` or to one of the fsynced write helpers
   (``write_framed*`` / ``_write_payload_tmp`` / ``_publish`` /
   ``_write_checkpoint_dir``) — unless the replace line carries
   ``replace-fsync-ok`` naming why durability is not needed there (e.g.
   quarantining bytes that are already known-corrupt).

6. **Roofline capture stays at compile time** (the roofline PR's guard) —
   ``cost_analysis()`` / ``memory_analysis()`` / ``RooflineCapture
   .capture()`` AOT-lower and compile a program, seconds of work that
   must happen ONCE at build time (the ``cost_hook`` seam in
   ``parallel/sharding.py``, the orchestrator's fallback capture), never
   per chunk. FAILS when such a call site appears in the dispatcher
   section (``_run_supervised``/``_boundary_actions``) or inside a
   nested (traced) function of the device packages — the run-time half
   of the roofline (gauge math on already-captured static costs) rides
   the pipeline consumer and never needs these calls. Escape hatch:
   ``roofline-capture-ok`` naming why a capture is intentionally there.

7. **Params/grads casts go through the precision policy** (the
   mixed-precision PR's guard) — a bare ``.astype(`` touching params or
   gradients inside ``_run_supervised`` or a traced step closure
   sidesteps the precision policy (precision.py): under fp32 it breaks
   the default mode's bit-identity contract, and under bf16_mixed a
   stray cast either re-creates the whole-model-cast failure mode
   (optimizer state silently following the compute dtype) or flips a
   scan carry's dtype mid-program. Casts on params/grads must route
   through ``PrecisionPolicy.cast_compute`` / ``grads_to_master`` /
   ``cast_carry``. FAILS on a line that both mentions params/grads and
   calls ``.astype(`` in those regions, unless it carries
   ``precision-cast-ok`` naming why the cast is policy-sanctioned
   (activation casts — a dot output that merely MENTIONS params on the
   same line — use the same marker).

8. **No blocking host ops in the serve batch-dispatch closure** (the
   serving PR's guard) — the continuous-batching engine's dispatcher
   (``sharetrade_tpu/serve/engine.py`` ``_serve_loop`` / ``_collect_batch``
   / ``_dispatch_batch`` / ``_pad``) sits on the per-tick critical path:
   a ``jax.device_get`` / ``os.fsync`` / ``time.sleep`` / ``log.*()`` /
   ``print()`` there stalls EVERY queued session's latency behind one
   host call (check 4's dispatcher/consumer inversion, applied to
   serving). Readback, completion, and telemetry belong to the consumer
   side (``_complete_batch`` / ``_complete_loop``), whose existence the
   check also enforces. Escape hatch: ``serve-host-ok`` naming why a host
   op intentionally rides the dispatch path.

9. **No host work in the traced replay sample/priority-update path** (the
   replay-data-plane PR's guard) — the PER sum-tree ops
   (``sharetrade_tpu/ops/sum_tree.py``) and the DQN step closure
   (``agents/dqn.py`` ``one_step``) run INSIDE the jitted (mega)chunk:
   journal IO (``journal`` / ``append_bytes`` / ``open``), ``os.*``
   calls, or host RNG (``np.random`` / stdlib ``random`` — anything but
   ``jax.random``) there either freezes into the trace or adds a host
   sync to the chunk path, exactly what keeping replay device-resident
   exists to avoid. The host half of the data plane — journaling,
   segment rotation/retirement, warm starts — belongs to the consumer
   side (``_journal_transitions`` / ``_warm_start_replay`` in the
   orchestrator), whose existence the check also enforces. Escape hatch:
   ``replay-host-ok`` naming why a host call is trace-safe there.

10. **Serving stays overload-safe** (the serve-robustness PR's guard) —
    inside ``sharetrade_tpu/serve/`` an UNBOUNDED ``queue.Queue()`` (no
    ``maxsize``, or the literal ``maxsize=0``, which ALSO means
    unbounded) is exactly the admission-control hole ISSUE 10 closed: a
    request flood grows host memory without bound before any shedding
    can happen. And a bare ``time.sleep`` anywhere in the package is
    either a dispatch-path stall (check 8's territory) or an unkillable
    wait a stop() can't interrupt — NO sleep is sanctioned: even the
    supervised-restart backoff (``_backoff_sleep``) waits on the stop
    event instead, precisely so shutdown can interrupt it. FAILS on any
    unbounded ``queue.Queue(...)`` call and any ``time.sleep`` call in
    the package — unless the line carries ``serve-block-ok`` naming why
    the block is off the serving path (e.g. a drain poll on the
    caller's thread, a load generator's pacing sleep).

11. **No unbounded exemplar/trace accumulation** (the request-tracing
    PR's guard) — per-request observability (stage stamps, exemplars,
    trace buffers, SLO windows) accumulates at REQUEST rate: an
    unbounded collection there is a slow memory leak that tracks
    offered load, exactly the class of growth admission control (check
    10) exists to prevent on the request side. Inside
    ``sharetrade_tpu/serve/`` and ``sharetrade_tpu/obs/`` every
    ``deque(...)`` construction must pass a bounded ``maxlen`` (not the
    literal ``None``/``0``) — unless the construction, or a comment
    within the two preceding lines, carries ``trace-buffer-ok`` naming
    the logical bound (e.g. "drained every tick", "bounded by
    max_queue shedding").

12. **Process spawning stays in the actor-pool supervisor** (the
    disaggregation PR's guard) — ``subprocess.Popen`` / ``os.fork`` /
    ``os.spawn*`` / ``os.exec*`` inside ``sharetrade_tpu/`` creates a
    child process whose lifecycle SOMEBODY must own: unsupervised spawns
    are exactly the zombie/leak class the :class:`ActorPool` contract
    (reap, seeded backoff, terminal-failed state, drain-on-stop) exists
    to prevent. The only sanctioned spawn site is the supervisor module
    itself (``distrib/pool.py``); anywhere else FAILS unless the line
    carries ``actor-spawn-ok`` naming who supervises that child.
    Blocking helpers (``subprocess.run`` — e.g. the manifest's git-rev
    probe) are deliberately out of scope: they cannot outlive the call.
    The supervisor's consumer-side functions (``_reap``,
    ``_heartbeat_ages``) must keep existing — a rename must update this
    lint, not silently un-guard the reap seam.

13. **Registered knobs have no hard-coded shadows** (the self-tuning
    PR's guard) — a knob in the tuning registry
    (``sharetrade_tpu/tuning.py`` ``KNOBS``) is read through the
    profile/controller layer: config seeds it, the tuned profile may
    override the default, and the online controllers adjust it within
    config ceilings. A fresh ASSIGNMENT of a NUMERIC LITERAL to a name
    or attribute matching a registered knob's leaf inside
    ``sharetrade_tpu/serve/`` or ``sharetrade_tpu/runtime/`` re-creates
    the hand-set constant the registry exists to retire — the value
    silently stops following the profile and the controller gauges lie.
    FAILS on such an assignment unless the line (or the two preceding
    lines) carries ``tuned-knob-ok`` naming why a literal is correct
    there; also fails when a registered dotted path disappears from
    tuning.py (the registry and this lint must move together).
"""

from __future__ import annotations

import ast
import pathlib
import re
import sys

TARGET = (pathlib.Path(__file__).resolve().parent.parent
          / "sharetrade_tpu" / "runtime" / "orchestrator.py")
#: Functions whose bodies are the per-chunk hot path.
HOT_FUNCS = ("_run_supervised",)
#: Host-sync constructs that serialize the dispatch pipeline.
PATTERN = re.compile(
    r"device_get\(|float\(np\.asarray|block_until_ready\(")
#: Escape hatch: a line carrying this marker declares (and should name) why
#: its sync is not a per-chunk cost.
MARKER = "hot-loop-sync-ok"

#: Device-code packages whose NESTED functions are the jit/scan-traced step
#: bodies (closures built by module-level factories).
DEVICE_PACKAGES = ("agents", "env", "models", "ops")
#: Host side effects that silently become trace-time constants inside a
#: compiled program. ``jax.debug.print(`` stays legal: the dotted call
#: never matches the lookbehind-guarded bare ``print(``.
JIT_PATTERN = re.compile(
    r"time\.time\(|time\.perf_counter\(|\blog\.\w+\s*\(|(?<![\w.])print\s*\(")
#: Escape hatch for intentionally-trace-time host calls in device code.
JIT_MARKER = "jit-host-call-ok"

#: Escape hatch for a parallel-layer device_put that intentionally leaves
#: placement to jax.
PUT_MARKER = "device-put-ok"

#: Dispatcher-section functions: with runtime.async_pipeline these run on
#: the dispatch critical path and must not block on readback or host IO.
DISPATCHER_FUNCS = ("_run_supervised", "_boundary_actions")
#: Consumer-side functions the dispatcher/consumer split moves the blocking
#: work INTO — they must exist, or the split silently un-guarded itself.
CONSUMER_FUNCS = ("_host_process", "_journal_transitions")
#: Blocking host calls that stall the dispatch pipeline when they appear in
#: dispatcher-section code (consumer-side occurrences carry MARKER).
DISPATCH_BLOCK_PATTERN = re.compile(
    r"device_get\(|np\.asarray\(|os\.fsync\(|block_until_ready\(")

#: Files whose os.replace calls publish DURABLE payloads (checkpoints,
#: journal compactions) and therefore need fsync evidence in-function.
DURABLE_WRITE_FILES = ("checkpoint/manager.py", "data/journal.py",
                       "serve/spill.py")
#: Evidence that a function fsyncs what its os.replace publishes: an ACTUAL
#: CALL (matched in the AST, not a substring — a comment or an `if
#: self.fsync:` gate with the real os.fsync deleted must not satisfy the
#: check) to fsync itself or to one of the fsynced write helpers.
FSYNC_EVIDENCE_CALLS = {
    "fsync", "_fsync_dir",
    "write_framed", "write_framed_bytes",
    "_write_checkpoint_dir", "_write_payload_tmp", "_publish",
}
#: Escape hatch for a durable-path os.replace that intentionally skips
#: fsync (must name why — e.g. the payload is already known-corrupt).
REPLACE_MARKER = "replace-fsync-ok"

#: Compile-time-only roofline capture calls (check 6): each one lowers and
#: compiles a whole program — never a per-chunk cost, never traced-code
#: behavior. ``.capture(`` is matched as the RooflineCapture entry point.
ROOFLINE_PATTERN = re.compile(
    r"cost_analysis\(|memory_analysis\(|compiled_costs\(|\.capture\(")
#: Escape hatch for an intentional capture site in guarded code.
ROOFLINE_MARKER = "roofline-capture-ok"

#: Check 7: a ``.astype(`` whose RECEIVER is a params/grads expression
#: (``ts.params.astype(``, ``grads.astype(``, ``params["w"].astype(`` —
#: ``\w*params`` catches new_params/target_params too), or a tree.map'd
#: cast applied to a params/grads tree on the same line. Activation casts
#: that merely mention params elsewhere on the line (head outputs,
#: ``dense(params[...], h).astype(f32)``) deliberately do NOT match: they
#: cast dot outputs, not the weight/grad trees the policy owns.
PRECISION_PATTERN = re.compile(
    r"(?:\w*params\b|\bgrads?\b)(?:\.\w+|\[[^]]*\])*\s*\.astype\("
    r"|(?=.*tree\.map)(?=.*\.astype\()(?=.*(?:\w*params\b|\bgrads?\b))")
#: Escape hatch: the policy's own cast sites (precision.py helpers, model
#: cast_carry hooks) and activation casts that merely mention params.
PRECISION_MARKER = "precision-cast-ok"


def lint_parallel_device_put() -> list[tuple[str, int, str]]:
    """Flag ``device_put`` calls without an explicit sharding inside
    ``sharetrade_tpu/parallel/``; returns (relpath, line, text) hits."""
    root = TARGET.parent.parent / "parallel"
    bad: list[tuple[str, int, str]] = []
    for path in sorted(root.glob("*.py")):
        src = path.read_text()
        lines = src.splitlines()
        for node in ast.walk(ast.parse(src)):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = (fn.attr if isinstance(fn, ast.Attribute)
                    else getattr(fn, "id", None))
            if name != "device_put":
                continue
            explicit = (len(node.args) >= 2
                        or any(kw.arg == "device" for kw in node.keywords))
            if explicit or PUT_MARKER in lines[node.lineno - 1]:
                continue
            bad.append((f"parallel/{path.name}", node.lineno,
                        lines[node.lineno - 1].strip()))
    return bad


def _scan_named_funcs(names, pattern, marker, *, also_find=(),
                      target: pathlib.Path | None = None
                      ) -> tuple[list[tuple[str, int, str]], set[str]]:
    """Shared traversal for the named-function checks: pattern hits on
    non-comment lines inside the named functions of ``target`` (default
    TARGET — comment-only lines can't dispatch anything, so prose ABOUT
    device_get never trips a check). Returns (hits, found-function-names
    over ``names`` + ``also_find`` — existence checks ride the same
    walk)."""
    src = (target or TARGET).read_text()
    lines = src.splitlines()
    bad: list[tuple[str, int, str]] = []
    found: set[str] = set()
    watch = set(names) | set(also_find)
    for node in ast.walk(ast.parse(src)):
        if (not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                or node.name not in watch):
            continue
        found.add(node.name)
        if node.name not in names:
            continue
        for ln in range(node.lineno, node.end_lineno + 1):
            text = lines[ln - 1]
            if text.lstrip().startswith("#"):
                continue
            if pattern.search(text) and marker not in text:
                bad.append((node.name, ln, text.strip()))
    return bad, found


def _scan_nested_funcs(pattern, marker) -> list[tuple[str, int, str, str]]:
    """Shared traversal for the traced-closure checks: pattern hits on
    non-comment lines inside NESTED functions of the device packages (the
    closures handed to jit/scan); returns (relpath, line, function, text)
    hits."""
    root = TARGET.parent.parent     # sharetrade_tpu/
    bad: list[tuple[str, int, str, str]] = []
    for pkg in DEVICE_PACKAGES:
        for path in sorted((root / pkg).glob("*.py")):
            src = path.read_text()
            lines = src.splitlines()
            seen: set[tuple[int, int]] = set()
            for node in ast.walk(ast.parse(src)):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                for child in ast.walk(node):
                    if (child is node
                            or not isinstance(child, (ast.FunctionDef,
                                                      ast.AsyncFunctionDef))):
                        continue
                    span = (child.lineno, child.end_lineno)
                    if span in seen:
                        continue
                    seen.add(span)
                    for ln in range(child.lineno, child.end_lineno + 1):
                        text = lines[ln - 1]
                        if text.lstrip().startswith("#"):
                            continue
                        if pattern.search(text) and marker not in text:
                            bad.append((f"{pkg}/{path.name}", ln,
                                        child.name, text.strip()))
    return bad


#: Check 8 (the serving PR): the serve engine's BATCH-DISPATCH closure —
#: batch collection + program dispatch on the tick critical path — must
#: never block on a device readback or host IO: a device_get / fsync /
#: sleep / log call there serializes every session's latency behind one
#: host stall (the same inversion as check 4, applied to serving). That
#: work belongs to the engine's consumer side (``_complete_batch`` /
#: ``_complete_loop``), which must keep existing.
SERVE_TARGET = (pathlib.Path(__file__).resolve().parent.parent
                / "sharetrade_tpu" / "serve" / "engine.py")
SERVE_DISPATCH_FUNCS = ("_serve_loop", "_wait_for_slot", "_collect_batch",
                        "_dispatch_batch", "_pad")
SERVE_CONSUMER_FUNCS = ("_complete_batch", "_complete_loop")
SERVE_BLOCK_PATTERN = re.compile(
    r"device_get\(|os\.fsync\(|time\.sleep\(|\blog\.\w+\s*\(|"
    r"block_until_ready\(|(?<![\w.])print\s*\(")
#: Escape hatch for an intentional host op on the serve dispatch path.
SERVE_MARKER = "serve-host-ok"

#: Check 9 (the replay-data-plane PR): the traced replay sample /
#: priority-update path. The sum-tree module's functions run inside the
#: jitted chunk wholesale; in agents/dqn.py the traced closure is
#: ``one_step`` (td_loss nests inside it).
REPLAY_TREE_TARGET = (pathlib.Path(__file__).resolve().parent.parent
                      / "sharetrade_tpu" / "ops" / "sum_tree.py")
REPLAY_DQN_TARGET = (pathlib.Path(__file__).resolve().parent.parent
                     / "sharetrade_tpu" / "agents" / "dqn.py")
#: Sum-tree ops that ARE the device-side sample/priority-update path —
#: a rename must update this lint, not silently un-guard it.
REPLAY_TREE_FUNCS = ("set_priorities", "sample_stratified", "is_weights",
                     "from_leaves")
REPLAY_DQN_FUNCS = ("one_step",)
#: Consumer-side functions (runtime/orchestrator.py) the device/host split
#: moves journal IO INTO — they must keep existing.
REPLAY_CONSUMER_FUNCS = ("_journal_transitions", "_warm_start_replay")
#: Journal IO, os.* calls, and host RNG (np.random / stdlib random —
#: jax.random stays legal via the dotted-receiver exclusion).
REPLAY_BLOCK_PATTERN = re.compile(
    r"\bos\.\w+\s*\(|(?<![\w.])(?:np|numpy)\.random\.|"
    r"(?<!\.)\brandom\.\w+\s*\(|\bjournal\b|append_bytes\(|"
    r"(?<![\w.])open\s*\(")
#: Escape hatch for an intentionally trace-safe host call there.
REPLAY_MARKER = "replay-host-ok"

#: Check 10 (the serve-robustness PR): the serve package stays overload-
#: safe — no unbounded ingress queues, and the ONLY bare sleep is the
#: supervised-restart backoff helper (everything else marks itself).
SERVE_PKG = (pathlib.Path(__file__).resolve().parent.parent
             / "sharetrade_tpu" / "serve")
#: Escape hatch naming why a block is off the serving path. There is NO
#: function allowlist: the engine's restart backoff waits on the stop
#: event, so no serve/ code needs an unmarked time.sleep.
SERVE_PKG_MARKER = "serve-block-ok"

#: Check 12 (the disaggregation PR): the ONLY module allowed to spawn
#: worker processes — the ActorPool supervisor owns every child's
#: lifecycle (reap/backoff/terminal-failed/drain).
ACTOR_SPAWN_MODULE = "distrib/pool.py"
#: Supervisor consumer-side functions that must keep existing.
ACTOR_POOL_FUNCS = ("_reap", "_heartbeat_ages")
#: Process-creating calls: Popen detaches a child; fork/spawn*/exec*
#: likewise. subprocess.run/check_* block until the child exits and are
#: deliberately NOT matched (they cannot leak an unsupervised process).
ACTOR_SPAWN_PATTERN = re.compile(
    r"subprocess\.Popen\(|\bos\.fork\(|\bos\.spawn\w*\(|\bos\.exec\w*\(")
#: Escape hatch naming who supervises the spawned child.
ACTOR_SPAWN_MARKER = "actor-spawn-ok"

#: Check 11 (the request-tracing PR): packages whose deque buffers hold
#: per-request observability state and must be bounded rings.
TRACE_BUFFER_DIRS = ("serve", "obs")
#: Escape hatch naming the LOGICAL bound of a maxlen-less deque (on the
#: construction line or within the two preceding comment lines).
TRACE_BUFFER_MARKER = "trace-buffer-ok"

#: Check 13 (the self-tuning PR): the knob registry file — every dotted
#: path below must stay registered there — and the packages where a
#: registered knob must be read through the profile/controller layer,
#: never re-hard-coded.
TUNING_REGISTRY_FILE = (pathlib.Path(__file__).resolve().parent.parent
                        / "sharetrade_tpu" / "tuning.py")
TUNED_KNOB_PATHS = (
    "runtime.megachunk_factor", "runtime.pipeline_depth",
    "serve.max_batch", "serve.batch_timeout_ms", "serve.max_queue",
    "distrib.ingest_every_updates", "distrib.ingest_max_rows",
)
TUNED_KNOB_DIRS = ("serve", "runtime")
#: Escape hatch naming why a literal assignment of a registered knob is
#: correct (construction line or the two preceding lines).
TUNED_KNOB_MARKER = "tuned-knob-ok"

#: Check 14 (the fleet PR): network LISTENERS live in fleet/ and nowhere
#: else inside sharetrade_tpu/ — a socket server in the serve/obs/data
#: layers would be an unsupervised second front door around the fleet's
#: drain/status-code/telemetry contract. Matches listener construction
#: (socket.socket / socketserver.* / http.server / *HTTPServer), never
#: clients (urlopen, HTTPConnection — data/service.py's price fetch is
#: legal); fleet/ itself is exempt wholesale.
FLEET_NET_DIR = "fleet"
FLEET_NET_PATTERN = re.compile(
    r"socket\.socket\s*\(|\bsocketserver\.\w|\bhttp\.server\b|"
    r"\w*HTTPServer\s*\(")
#: ...and the serve engine's dispatch closures must not grow BLOCKING
#: network I/O either: a wire call on the batch-collection path stalls
#: every queued session behind one peer's RTT (the check-8 inversion,
#: network edition). Scans SERVE_DISPATCH_FUNCS for client calls too.
SERVE_NET_PATTERN = re.compile(
    r"urlopen\s*\(|HTTPConnection\s*\(|FleetClient\s*\(|"
    r"\.recv\s*\(|\.sendall\s*\(|\.accept\s*\(|\.connect\s*\(")
FLEET_NET_MARKER = "fleet-net-ok"

#: Check 15 (the evloop PR): the event-loop wire path stays
#: non-blocking and the protocol core stays sans-IO. One blocking call
#: on the loop thread stalls EVERY connection the process is proxying —
#: so fleet/evloop.py + fleet/proto.py must not grow blocking socket
#: idioms (sendall / settimeout / create_connection /
#: setblocking(True) / time.sleep) or per-connection threads
#: (threading.Thread — the single loop-runner thread carries the
#: marker). And proto.py must not import I/O modules AT ALL: the
#: parser's whole value is that the same state machine frames bytes
#: for the client, the front-end, and the router without touching a
#: socket (that is what makes torn-read/pipelining tests exhaustive).
EVLOOP_FILES = ("fleet/evloop.py", "fleet/proto.py")
EVLOOP_BLOCK_PATTERN = re.compile(
    r"\.sendall\s*\(|time\.sleep\s*\(|socket\.create_connection\s*\(|"
    r"\.settimeout\s*\(|\.setblocking\s*\(\s*True|"
    r"threading\.Thread\s*\(|\.makefile\s*\(")
#: Escape hatch naming why a blocking idiom is correct (on the line or
#: the two preceding lines) — e.g. the one loop-runner thread.
EVLOOP_BLOCK_MARKER = "evloop-block-ok"
#: Modules the sans-IO core must never import.
SANSIO_FORBIDDEN_IMPORTS = ("socket", "select", "selectors", "ssl",
                            "http", "socketserver", "asyncio")
SANSIO_FILE = "fleet/proto.py"

#: Check 16 (the distributed-tracing PR): span emission in the evloop
#: loop-runner and the router relay path stays a bounded buffered
#: append. These two files run per-event/per-hop at wire rate; the
#: SpanSink contract (obs/trace.py) is ONE tuple append into a bounded
#: ring now, serialization deferred to the batched flush — so (a) no
#: ``json.dumps`` may appear on a line that also touches span/trace
#: context (per-event serialization on the hot path), and (b) no
#: span/trace-named name may be assigned an UNBOUNDED accumulator (a
#: list literal, ``list()``, or a maxlen-less ``deque``) — span volume
#: tracks offered load, exactly check 11's leak class on the wire
#: path. Escape hatch: ``trace-buffer-ok`` (shared with check 11) on
#: the line or the two above, naming the bound / why serialization is
#: off the hot path.
SPAN_EMIT_FILES = ("fleet/evloop.py", "fleet/router.py")
SPAN_EMIT_DUMPS_PATTERN = re.compile(r"json\.dumps?\s*\(")
SPAN_EMIT_CTX_PATTERN = re.compile(r"span|tctx|trace", re.IGNORECASE)
SPAN_NAME_PATTERN = re.compile(r"span|trace", re.IGNORECASE)

#: Check 20 (the host-span PR): ``jax.profiler.TraceAnnotation`` is
#: constructed in ONE module, obs/trace.py (``host_span`` / ``span``), whose
#: callers open spans per chunk or per tick under fixed names. A bare
#: annotation anywhere else in the package is how a span per request or
#: per agent-step (a TraceMe each, in every profiled run, at request rate)
#: slips in beside the helper, under a name nothing aggregates. Matched in
#: the AST (a call to a name or attribute ``TraceAnnotation``), so prose
#: and imports do not count. Escape hatch: ``trace-annotation-ok`` on the
#: line or the two above, naming why the helper cannot serve.
ANNOTATION_MODULE = "obs/trace.py"
ANNOTATION_NAME = "TraceAnnotation"
ANNOTATION_MARKER = "trace-annotation-ok"

#: Check 17 (the session-paging PR): the warm session tier stays a
#: BOUNDED host-RAM cache and the paging seam keeps the serve engine's
#: dispatcher/consumer split. (a) The ``WarmStore`` class must carry its
#: own eviction evidence IN CODE — an actual ``popitem`` call inside a
#: ``while`` loop whose condition references the byte/session budget —
#: because a warm tier that only *documents* its bound is check 11's
#: leak class at carry-tree size: each parked session holds a whole
#: per-session carry, so unbounded growth tracks the SESSION population,
#: not the request rate. (b) The paging functions that run on the
#: dispatch thread (``_drain_park_inbox`` — the park-inbox commit at the
#: top of ``_dispatch_batch`` — and ``_install_parked`` — the batched
#: scatter re-install) inherit check 8's host-op ban wholesale: the
#: whole point of parking on the consumer thread is that dispatch never
#: blocks on a device_get/fsync/log for paging, and both functions must
#: keep existing (a rename must update this lint, not un-guard the
#: seam). Escape hatch: ``warm-tier-ok`` on the class line (or the two
#: above) naming where the bound actually lives; the dispatch half uses
#: check 8's ``serve-host-ok``.
SERVE_WARM_CLASS = "WarmStore"
SERVE_PAGE_FUNCS = ("_drain_park_inbox", "_install_parked")
WARM_TIER_MARKER = "warm-tier-ok"
WARM_BOUND_PATTERN = re.compile(r"max_bytes|max_sessions")

#: Check 18 (the native-wire PR): the C parse/render extension
#: (native/wire.cc → stwire.so) stays confined behind ONE seam.
#: (a) No Python file in ``sharetrade_tpu/`` outside
#: ``fleet/proto.py`` may touch the binding surface (the ``stwire``
#: module or an ``ExtensionFileLoader``) — every wire party reaches
#: the native path through proto.py's backend dispatch, which is what
#: lets the Python oracle swap in (graceful degrade, differential
#: fuzzing) without any caller changing. Escape: ``native-wire-ok`` on
#: the line or the two above, naming why a second site must exist.
#: (b) ``native/wire.cc`` must RELEASE the GIL around its parse/render
#: cores — at least one ``Py_BEGIN_ALLOW_THREADS``, and the BEGIN/END
#: pairing balanced — or the "native hot path" serializes against
#: engine callbacks and loadgen threads exactly like the Python parser
#: it replaces. (c) ``fleet/proto.py`` stays I/O-import-free under
#: BOTH backends: the loader runs at import time, so check 15's
#: sans-IO import scan is re-asserted here.
NATIVE_WIRE_MODULE = "fleet/proto.py"
NATIVE_WIRE_BINDING_PATTERN = re.compile(
    r"\bstwire\b|ExtensionFileLoader")
NATIVE_WIRE_MARKER = "native-wire-ok"
NATIVE_WIRE_CC = TARGET.parent.parent.parent / "native" / "wire.cc"
GIL_BEGIN = "Py_BEGIN_ALLOW_THREADS"
GIL_END = "Py_END_ALLOW_THREADS"

#: Check 19: the crash-consistent spill arena (serve/spill.py). (a)
#: Arena record file I/O — the ``.spill`` suffix / ``SPILL_SUFFIX`` /
#: ``record_name(`` — appears nowhere in ``sharetrade_tpu/`` outside
#: SPILL_MODULE: a second reader/writer forks the record format away
#: from the CRC/seal/consume-on-take contract the adoption tests pin;
#: marker-exempt on the line or the two above (``spill-io-ok``). (b)
#: Every SpillArena method that PUBLISHES a record (calls os.replace)
#: must also CALL crc32 in the same method (AST call scan — a comment
#: or a dead ``if self.checksum:`` gate cannot satisfy it); the seal
#: half (fsync before the rename) rides check 5 via
#: DURABLE_WRITE_FILES. (c) ``SpillArena.__init__`` builds no
#: container: the record census lives on disk (os.scandir re-anchor),
#: so an in-memory dict/set/list index would drift across engine
#: incarnations sharing one arena and grow with session population;
#: marker-exempt (``spill-index-ok``).
SPILL_MODULE = "serve/spill.py"
SPILL_IO_PATTERN = re.compile(
    r"""['"]\.spill['"]|\bSPILL_SUFFIX\b|\brecord_name\s*\(""")
SPILL_IO_MARKER = "spill-io-ok"
SPILL_INDEX_MARKER = "spill-index-ok"
SPILL_CLASS = "SpillArena"
#: Container constructors that would anchor an arena census in memory.
SPILL_CONTAINER_CALLS = {"dict", "set", "list", "OrderedDict",
                        "defaultdict", "deque", "Counter"}


def lint_hot_loop_syncs() -> tuple[list[tuple[str, int, str]], set[str]]:
    return _scan_named_funcs(HOT_FUNCS, PATTERN, MARKER)


def lint_serve_dispatch() -> tuple[list[tuple[str, int, str]], set[str]]:
    """Check 8: no blocking host ops (device_get / os.fsync / time.sleep /
    logging / print) in the serve engine's batch-dispatch closure; the
    consumer-side functions must still exist. Returns (hits, found
    function names over SERVE_DISPATCH_FUNCS + SERVE_CONSUMER_FUNCS)."""
    return _scan_named_funcs(SERVE_DISPATCH_FUNCS, SERVE_BLOCK_PATTERN,
                             SERVE_MARKER, also_find=SERVE_CONSUMER_FUNCS,
                             target=SERVE_TARGET)


def lint_replay_device_path() -> tuple[list[tuple[str, int, str]], set[str]]:
    """Check 9: no journal IO / os.* / host RNG in the traced replay
    sample + priority-update path (ops/sum_tree.py functions, the DQN
    ``one_step`` closure); the orchestrator's consumer-side journal
    functions must still exist. Returns (hits, found names over all
    three watch sets)."""
    tree_bad, tree_found = _scan_named_funcs(
        REPLAY_TREE_FUNCS, REPLAY_BLOCK_PATTERN, REPLAY_MARKER,
        target=REPLAY_TREE_TARGET)
    dqn_bad, dqn_found = _scan_named_funcs(
        REPLAY_DQN_FUNCS, REPLAY_BLOCK_PATTERN, REPLAY_MARKER,
        target=REPLAY_DQN_TARGET)
    _none, orch_found = _scan_named_funcs(
        (), REPLAY_BLOCK_PATTERN, REPLAY_MARKER,
        also_find=REPLAY_CONSUMER_FUNCS)
    return tree_bad + dqn_bad, tree_found | dqn_found | orch_found


def lint_serve_overload_safety(
        root: pathlib.Path | None = None) -> list[tuple[str, int, str]]:
    """Check 10: inside ``sharetrade_tpu/serve/`` every ``queue.Queue``
    construction must be BOUNDED (a non-zero ``maxsize``) and no
    ``time.sleep`` may appear at all (the restart backoff waits on the
    stop event instead); a line carrying ``serve-block-ok`` is exempt.
    Returns (relpath, line, text) hits. ``root`` overrides the scanned
    directory (tests exercise the pattern semantics on fixtures)."""
    root = root or SERVE_PKG
    bad: list[tuple[str, int, str]] = []
    for path in sorted(root.glob("*.py")):
        src = path.read_text()
        lines = src.splitlines()
        tree = ast.parse(src)

        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = (fn.attr if isinstance(fn, ast.Attribute)
                    else getattr(fn, "id", None))
            text = lines[node.lineno - 1]
            if SERVE_PKG_MARKER in text:
                continue
            if name == "Queue":
                # Bounded = a maxsize argument that is not the literal 0
                # (maxsize=0 IS unbounded in queue.Queue — passing it
                # would green-light exactly the hole this check guards).
                bound_expr = (node.args[0] if node.args else next(
                    (kw.value for kw in node.keywords
                     if kw.arg == "maxsize"), None))
                bounded = bound_expr is not None and not (
                    isinstance(bound_expr, ast.Constant)
                    and bound_expr.value == 0)
                if not bounded:
                    bad.append((f"serve/{path.name}", node.lineno,
                                text.strip()))
            elif name == "sleep":
                # Both forms: ``time.sleep(...)`` and a bare
                # ``sleep(...)`` from ``from time import sleep`` (other
                # dotted receivers — somemodule.sleep — stay legal).
                time_sleep = (isinstance(fn, ast.Name)
                              or (isinstance(fn, ast.Attribute)
                                  and isinstance(fn.value, ast.Name)
                                  and fn.value.id == "time"))
                if time_sleep:
                    bad.append((f"serve/{path.name}", node.lineno,
                                text.strip()))
    return bad


def lint_bounded_trace_buffers(
        roots: list | None = None) -> list[tuple[str, int, str]]:
    """Check 11: every ``deque(...)`` constructed inside ``serve/`` and
    ``obs/`` must be a bounded ring — a ``maxlen`` argument that is not
    the literal ``None``/``0`` — or carry ``trace-buffer-ok`` (on the
    call line or within the two preceding lines) naming its logical
    bound. Returns (relpath, line, text) hits. ``roots`` overrides the
    scanned directories (tests exercise the pattern on fixtures)."""
    targets = (roots if roots is not None
               else [TARGET.parent.parent / d for d in TRACE_BUFFER_DIRS])
    bad: list[tuple[str, int, str]] = []
    for root in targets:
        for path in sorted(pathlib.Path(root).glob("*.py")):
            src = path.read_text()
            lines = src.splitlines()
            for node in ast.walk(ast.parse(src)):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                name = (fn.attr if isinstance(fn, ast.Attribute)
                        else getattr(fn, "id", None))
                if name != "deque":
                    continue
                bound_expr = (node.args[1] if len(node.args) >= 2
                              else next((kw.value for kw in node.keywords
                                         if kw.arg == "maxlen"), None))
                bounded = bound_expr is not None and not (
                    isinstance(bound_expr, ast.Constant)
                    and bound_expr.value in (None, 0))
                if bounded:
                    continue
                window = lines[max(0, node.lineno - 3):node.lineno]
                if any(TRACE_BUFFER_MARKER in ln for ln in window):
                    continue
                bad.append((f"{pathlib.Path(root).name}/{path.name}",
                            node.lineno, lines[node.lineno - 1].strip()))
    return bad


def lint_actor_spawn(
        root: pathlib.Path | None = None) -> tuple[
            list[tuple[str, int, str]], set[str]]:
    """Check 12: no process-creating call (``subprocess.Popen`` /
    ``os.fork`` / ``os.spawn*`` / ``os.exec*``) anywhere in
    ``sharetrade_tpu/`` outside the ActorPool supervisor module, unless
    the line carries ``actor-spawn-ok``; the supervisor's ``_reap`` /
    ``_heartbeat_ages`` must exist. Returns (hits, found supervisor
    function names). ``root`` overrides the scanned package (tests
    exercise the pattern semantics on fixtures)."""
    root = root or TARGET.parent.parent     # sharetrade_tpu/
    bad: list[tuple[str, int, str]] = []
    found: set[str] = set()
    for path in sorted(pathlib.Path(root).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        src = path.read_text()
        if rel == ACTOR_SPAWN_MODULE:
            for node in ast.walk(ast.parse(src)):
                if (isinstance(node, (ast.FunctionDef,
                                      ast.AsyncFunctionDef))
                        and node.name in ACTOR_POOL_FUNCS):
                    found.add(node.name)
            continue
        for ln, text in enumerate(src.splitlines(), 1):
            if text.lstrip().startswith("#"):
                continue
            if (ACTOR_SPAWN_PATTERN.search(text)
                    and ACTOR_SPAWN_MARKER not in text):
                bad.append((rel, ln, text.strip()))
    return bad, found


def lint_tuned_knob_shadows(
        roots: list | None = None,
        registry: pathlib.Path | None = None
        ) -> tuple[list[tuple[str, int, str]], set[str]]:
    """Check 13: no numeric-literal ASSIGNMENT to a name/attribute whose
    leaf matches a registered tuning knob inside ``serve/``/``runtime/``
    (marker-exempt on the line or the two above); the registry file must
    still name every dotted path. Returns (hits, registered-paths found
    in the registry file). ``roots``/``registry`` override the scanned
    locations (tests exercise the semantics on fixtures)."""
    targets = (roots if roots is not None
               else [TARGET.parent.parent / d for d in TUNED_KNOB_DIRS])
    registry = registry or TUNING_REGISTRY_FILE
    leaves = {p.split(".")[-1] for p in TUNED_KNOB_PATHS}
    found: set[str] = set()
    reg_src = registry.read_text()
    for node in ast.walk(ast.parse(reg_src)):
        if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                and node.value in TUNED_KNOB_PATHS):
            found.add(node.value)
    bad: list[tuple[str, int, str]] = []
    for root in targets:
        for path in sorted(pathlib.Path(root).glob("*.py")):
            src = path.read_text()
            lines = src.splitlines()
            for node in ast.walk(ast.parse(src)):
                if isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)):
                    targets_ = (node.targets
                                if isinstance(node, ast.Assign)
                                else [node.target])
                    value = node.value
                else:
                    continue
                if value is None or not (
                        isinstance(value, ast.Constant)
                        and type(value.value) in (int, float)):
                    continue
                names = set()
                for tgt in targets_:
                    if isinstance(tgt, ast.Name):
                        names.add(tgt.id)
                    elif isinstance(tgt, ast.Attribute):
                        names.add(tgt.attr)
                if not names & leaves:
                    continue
                window = lines[max(0, node.lineno - 3):node.lineno]
                if any(TUNED_KNOB_MARKER in ln for ln in window):
                    continue
                bad.append((f"{pathlib.Path(root).name}/{path.name}",
                            node.lineno, lines[node.lineno - 1].strip()))
    return bad, found


def lint_fleet_net(
        root: pathlib.Path | None = None) -> tuple[
            list[tuple[str, int, str]], list[tuple[str, int, str]]]:
    """Check 14: (a) no network-listener construction anywhere in
    ``sharetrade_tpu/`` outside ``fleet/`` without ``fleet-net-ok`` on
    the line; (b) no blocking network I/O (client calls included) in the
    serve engine's dispatch closures. Returns ``(listener_hits,
    dispatch_hits)``. ``root`` overrides the scanned package (tests
    exercise the semantics on fixtures)."""
    root = root or TARGET.parent.parent     # sharetrade_tpu/
    listener_bad: list[tuple[str, int, str]] = []
    for path in sorted(pathlib.Path(root).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if rel.split("/")[0] == FLEET_NET_DIR:
            continue
        for ln, text in enumerate(path.read_text().splitlines(), 1):
            if text.lstrip().startswith("#"):
                continue
            if (FLEET_NET_PATTERN.search(text)
                    and FLEET_NET_MARKER not in text):
                listener_bad.append((rel, ln, text.strip()))
    dispatch_bad, _ = _scan_named_funcs(
        SERVE_DISPATCH_FUNCS, SERVE_NET_PATTERN, FLEET_NET_MARKER,
        target=SERVE_TARGET)
    return listener_bad, [(SERVE_TARGET.name, ln, text)
                          for _, ln, text in dispatch_bad]


def lint_evloop_sansio(
        root: pathlib.Path | None = None) -> tuple[
            list[tuple[str, int, str]], list[tuple[str, int, str]]]:
    """Check 15: (a) no blocking socket idioms or per-connection
    threads in the event-loop wire path (EVLOOP_FILES), marker-exempt
    on the line or the two above (``evloop-block-ok`` — the one
    loop-runner thread); (b) the sans-IO core (SANSIO_FILE) imports no
    I/O module at all. Returns ``(blocking_hits, import_hits)``.
    ``root`` overrides the scanned package root (tests exercise the
    semantics on fixtures)."""
    root = root or TARGET.parent.parent     # sharetrade_tpu/
    blocking_bad: list[tuple[str, int, str]] = []
    for rel in EVLOOP_FILES:
        path = pathlib.Path(root) / rel
        if not path.exists():
            continue
        lines = path.read_text().splitlines()
        for ln, text in enumerate(lines, 1):
            if text.lstrip().startswith("#"):
                continue
            if not EVLOOP_BLOCK_PATTERN.search(text):
                continue
            window = lines[max(0, ln - 3):ln]
            if any(EVLOOP_BLOCK_MARKER in w for w in window):
                continue
            blocking_bad.append((rel, ln, text.strip()))
    import_bad: list[tuple[str, int, str]] = []
    sansio = pathlib.Path(root) / SANSIO_FILE
    if sansio.exists():
        src = sansio.read_text()
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mods = [node.module or ""]
            else:
                continue
            for mod in mods:
                if mod.split(".")[0] in SANSIO_FORBIDDEN_IMPORTS:
                    import_bad.append(
                        (SANSIO_FILE, node.lineno,
                         src.splitlines()[node.lineno - 1].strip()))
    return blocking_bad, import_bad


def lint_span_emission(
        root: pathlib.Path | None = None) -> list[tuple[str, int, str]]:
    """Check 16: in the evloop/router wire path (SPAN_EMIT_FILES), span
    emission must be a bounded buffered append — no per-event
    ``json.dumps`` on a span/trace-context line, no unbounded
    span/trace-named accumulator construction — unless the line (or the
    two above) carries ``trace-buffer-ok`` naming the bound. Returns
    (relpath, line, text) hits. ``root`` overrides the scanned package
    root (tests exercise the semantics on fixtures)."""
    root = root or TARGET.parent.parent     # sharetrade_tpu/
    bad: list[tuple[str, int, str]] = []
    for rel in SPAN_EMIT_FILES:
        path = pathlib.Path(root) / rel
        if not path.exists():
            continue
        src = path.read_text()
        lines = src.splitlines()

        def exempt(ln: int) -> bool:
            return any(TRACE_BUFFER_MARKER in w
                       for w in lines[max(0, ln - 3):ln])

        for ln, text in enumerate(lines, 1):
            if text.lstrip().startswith("#"):
                continue
            if (SPAN_EMIT_DUMPS_PATTERN.search(text)
                    and SPAN_EMIT_CTX_PATTERN.search(text)
                    and not exempt(ln)):
                bad.append((rel, ln, text.strip()))
        for node in ast.walk(ast.parse(src)):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            tgts = (node.targets if isinstance(node, ast.Assign)
                    else [node.target])
            names = set()
            for tgt in tgts:
                if isinstance(tgt, ast.Name):
                    names.add(tgt.id)
                elif isinstance(tgt, ast.Attribute):
                    names.add(tgt.attr)
            if not any(SPAN_NAME_PATTERN.search(n) for n in names):
                continue
            value = node.value
            unbounded = isinstance(value, ast.List)
            if isinstance(value, ast.Call):
                fn = value.func
                fname = (fn.attr if isinstance(fn, ast.Attribute)
                         else getattr(fn, "id", None))
                if fname == "list":
                    unbounded = True
                elif fname == "deque":
                    bound_expr = (
                        value.args[1] if len(value.args) >= 2
                        else next((kw.value for kw in value.keywords
                                   if kw.arg == "maxlen"), None))
                    unbounded = bound_expr is None or (
                        isinstance(bound_expr, ast.Constant)
                        and bound_expr.value in (None, 0))
            if unbounded and not exempt(node.lineno):
                bad.append((rel, node.lineno,
                            lines[node.lineno - 1].strip()))
    return sorted(bad, key=lambda hit: (hit[0], hit[1]))


def lint_profiler_annotations(
        root: pathlib.Path | None = None) -> list[tuple[str, int, str]]:
    """Check 20: no ``TraceAnnotation(...)`` call in ``sharetrade_tpu/``
    outside ANNOTATION_MODULE unless the line (or the two above) carries
    ``trace-annotation-ok``. Returns (relpath, line, text) hits. ``root``
    overrides the scanned package root (tests exercise the semantics on
    fixtures)."""
    root = root or TARGET.parent.parent     # sharetrade_tpu/
    bad: list[tuple[str, int, str]] = []
    for path in sorted(pathlib.Path(root).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if rel == ANNOTATION_MODULE:
            continue
        src = path.read_text()
        if ANNOTATION_NAME not in src:
            continue
        lines = src.splitlines()
        for node in ast.walk(ast.parse(src)):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            fname = (fn.attr if isinstance(fn, ast.Attribute)
                     else getattr(fn, "id", None))
            if fname != ANNOTATION_NAME:
                continue
            window = lines[max(0, node.lineno - 3):node.lineno]
            if not any(ANNOTATION_MARKER in w for w in window):
                bad.append((rel, node.lineno,
                            lines[node.lineno - 1].strip()))
    return sorted(bad)


def lint_warm_tier(target: pathlib.Path | None = None
                   ) -> tuple[list[tuple[str, int, str]], set[str]]:
    """Check 17: (a) the ``WarmStore`` class carries in-code eviction
    evidence — a ``popitem`` call plus a ``while`` loop conditioned on
    the byte/session budget — unless the class line (or the two above)
    carries ``warm-tier-ok`` naming where the bound lives; (b) the
    dispatch-thread paging functions (SERVE_PAGE_FUNCS) inherit check
    8's blocking-host-op ban (``serve-host-ok`` escape). Returns (hits,
    found names over the class + paging functions). ``target``
    overrides the scanned file (tests exercise the semantics on
    fixtures)."""
    target = target or SERVE_TARGET
    src = target.read_text()
    lines = src.splitlines()
    bad: list[tuple[str, int, str]] = []
    found: set[str] = set()
    for node in ast.walk(ast.parse(src)):
        if not (isinstance(node, ast.ClassDef)
                and node.name == SERVE_WARM_CLASS):
            continue
        found.add(node.name)
        window = lines[max(0, node.lineno - 3):node.lineno]
        if any(WARM_TIER_MARKER in w for w in window):
            continue
        called: set = set()
        for child in ast.walk(node):
            if isinstance(child, ast.Call):
                f = child.func
                called.add(f.attr if isinstance(f, ast.Attribute)
                           else getattr(f, "id", None))
        bounded_loop = any(
            isinstance(child, ast.While)
            and WARM_BOUND_PATTERN.search(
                ast.get_source_segment(src, child.test) or "")
            for child in ast.walk(node))
        if "popitem" not in called or not bounded_loop:
            bad.append((node.name, node.lineno,
                        lines[node.lineno - 1].strip()))
    page_bad, page_found = _scan_named_funcs(
        SERVE_PAGE_FUNCS, SERVE_BLOCK_PATTERN, SERVE_MARKER, target=target)
    return (sorted(bad + page_bad, key=lambda hit: hit[1]),
            found | page_found)


def lint_native_wire(
        root: pathlib.Path | None = None,
        wire_cc: pathlib.Path | None = None) -> tuple[
            list[tuple[str, int, str]], list[tuple[str, int, str]],
            list[tuple[str, int, str]]]:
    """Check 18: (a) the native wire binding surface (the ``stwire``
    extension / an ``ExtensionFileLoader``) appears nowhere in
    ``sharetrade_tpu/`` outside NATIVE_WIRE_MODULE, marker-exempt on
    the line or the two above (``native-wire-ok``); (b) native/wire.cc
    exists and releases the GIL around parse/render (at least one
    ``Py_BEGIN_ALLOW_THREADS``, BEGIN/END balanced, comment lines
    excluded); (c) the sans-IO core stays I/O-import-free under both
    backends (check 15's import scan, re-run). Returns
    ``(binding_hits, gil_hits, import_hits)``. ``root``/``wire_cc``
    override the scanned tree (tests exercise the semantics on
    fixtures)."""
    root = root or TARGET.parent.parent     # sharetrade_tpu/
    wire_cc = pathlib.Path(wire_cc) if wire_cc is not None \
        else NATIVE_WIRE_CC
    binding_bad: list[tuple[str, int, str]] = []
    for path in sorted(pathlib.Path(root).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if rel == NATIVE_WIRE_MODULE:
            continue
        lines = path.read_text().splitlines()
        for ln, text in enumerate(lines, 1):
            if text.lstrip().startswith("#"):
                continue
            if not NATIVE_WIRE_BINDING_PATTERN.search(text):
                continue
            window = lines[max(0, ln - 3):ln]
            if any(NATIVE_WIRE_MARKER in w for w in window):
                continue
            binding_bad.append((rel, ln, text.strip()))
    gil_bad: list[tuple[str, int, str]] = []
    if not wire_cc.exists():
        gil_bad.append((wire_cc.name, 0, "native/wire.cc is missing"))
    else:
        begins = ends = 0
        for line in wire_cc.read_text().splitlines():
            code = line.split("//", 1)[0]    # prose mentions don't count
            begins += code.count(GIL_BEGIN)
            ends += code.count(GIL_END)
        if begins == 0:
            gil_bad.append((wire_cc.name, 0,
                            f"no {GIL_BEGIN} — parse/render hold the GIL"))
        elif begins != ends:
            gil_bad.append(
                (wire_cc.name, 0,
                 f"{GIL_BEGIN} x{begins} vs {GIL_END} x{ends} — "
                 "unbalanced pairing"))
    _, import_bad = lint_evloop_sansio(root)
    return binding_bad, gil_bad, import_bad


def _is_spill_container(val: ast.AST) -> bool:
    """True for an expression that constructs a dict/set/list-family
    container (literal, comprehension, or a bare constructor call)."""
    if isinstance(val, (ast.Dict, ast.DictComp, ast.Set, ast.SetComp,
                        ast.List, ast.ListComp)):
        return True
    if isinstance(val, ast.Call):
        f = val.func
        name = f.attr if isinstance(f, ast.Attribute) \
            else getattr(f, "id", "")
        return name in SPILL_CONTAINER_CALLS
    return False


def lint_spill_arena(
        root: pathlib.Path | None = None,
        spill_py: pathlib.Path | None = None) -> tuple[
            list[tuple[str, int, str]], list[tuple[str, int, str]],
            list[tuple[str, int, str]], set[str]]:
    """Check 19: (a) arena record file I/O confined to SPILL_MODULE
    (``spill-io-ok`` escape on the line or the two above); (b) every
    SpillArena method publishing a record via os.replace also calls
    crc32 — the fsync-before-rename seal itself is enforced by check 5
    (SPILL_MODULE sits in DURABLE_WRITE_FILES); (c) SpillArena.__init__
    keeps no in-memory container over arena records (``spill-index-ok``
    escape). Returns ``(io_hits, crc_hits, index_hits, found class
    names)``. ``root``/``spill_py`` override the scanned tree (tests
    exercise the semantics on fixtures)."""
    root = pathlib.Path(root) if root is not None else TARGET.parent.parent
    spill_py = pathlib.Path(spill_py) if spill_py is not None \
        else root / SPILL_MODULE
    io_bad: list[tuple[str, int, str]] = []
    for path in sorted(pathlib.Path(root).rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if rel == SPILL_MODULE or path == spill_py:
            continue
        lines = path.read_text().splitlines()
        for ln, text in enumerate(lines, 1):
            if text.lstrip().startswith("#"):
                continue
            if not SPILL_IO_PATTERN.search(text):
                continue
            window = lines[max(0, ln - 3):ln]
            if any(SPILL_IO_MARKER in w for w in window):
                continue
            io_bad.append((rel, ln, text.strip()))
    crc_bad: list[tuple[str, int, str]] = []
    index_bad: list[tuple[str, int, str]] = []
    found: set[str] = set()
    if not spill_py.exists():
        crc_bad.append((SPILL_MODULE, 0, "spill module is missing"))
        return io_bad, crc_bad, index_bad, found
    src = spill_py.read_text()
    lines = src.splitlines()
    publishers = 0
    for cls in ast.walk(ast.parse(src)):
        if not (isinstance(cls, ast.ClassDef) and cls.name == SPILL_CLASS):
            continue
        found.add(cls.name)
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            called: set[str] = set()
            replaces = False
            for child in ast.walk(fn):
                if not isinstance(child, ast.Call):
                    continue
                f = child.func
                called.add(f.attr if isinstance(f, ast.Attribute)
                           else getattr(f, "id", None))
                if (isinstance(f, ast.Attribute) and f.attr == "replace"
                        and isinstance(f.value, ast.Name)
                        and f.value.id == "os"):
                    replaces = True
            if replaces:
                publishers += 1
                if "crc32" not in called:
                    crc_bad.append(
                        (SPILL_MODULE, fn.lineno,
                         f"{fn.name}() publishes via os.replace without "
                         "calling crc32"))
            if fn.name != "__init__":
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign):
                    val = node.value
                elif isinstance(node, ast.AnnAssign) \
                        and node.value is not None:
                    val = node.value
                else:
                    continue
                if not _is_spill_container(val):
                    continue
                window = lines[max(0, node.lineno - 3):node.lineno]
                if any(SPILL_INDEX_MARKER in w for w in window):
                    continue
                index_bad.append((SPILL_MODULE, node.lineno,
                                  lines[node.lineno - 1].strip()))
        if publishers == 0:
            crc_bad.append(
                (SPILL_MODULE, cls.lineno,
                 f"{SPILL_CLASS} has no os.replace publish — record "
                 "writes are not atomically sealed"))
    return io_bad, crc_bad, index_bad, found


def lint_dispatcher_blocking() -> tuple[list[tuple[str, int, str]], set[str]]:
    """Check 4: no unmarked blocking host calls in the dispatcher section;
    the consumer-side functions must still exist. Returns (hits, found
    function names over DISPATCHER_FUNCS + CONSUMER_FUNCS)."""
    return _scan_named_funcs(DISPATCHER_FUNCS, DISPATCH_BLOCK_PATTERN,
                             MARKER, also_find=CONSUMER_FUNCS)


def lint_durable_replace() -> list[tuple[str, int, str, str]]:
    """Check 5: every function in the durable write paths that calls
    ``os.replace`` must carry fsync evidence (or a justifying marker on the
    replace line); returns (relpath, line, function, text) hits."""
    root = TARGET.parent.parent     # sharetrade_tpu/
    bad: list[tuple[str, int, str, str]] = []
    for rel in DURABLE_WRITE_FILES:
        path = root / rel
        src = path.read_text()
        lines = src.splitlines()
        tree = ast.parse(src)
        # Innermost enclosing function per os.replace call site.
        funcs = [n for n in ast.walk(tree)
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "replace"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "os"):
                continue
            if REPLACE_MARKER in lines[node.lineno - 1]:
                continue
            enclosing = [f for f in funcs
                         if f.lineno <= node.lineno <= f.end_lineno]
            if not enclosing:
                continue    # module-level replace: out of scope
            fn = min(enclosing, key=lambda f: f.end_lineno - f.lineno)
            called = set()
            for child in ast.walk(fn):
                if isinstance(child, ast.Call):
                    f = child.func
                    called.add(f.attr if isinstance(f, ast.Attribute)
                               else getattr(f, "id", None))
            if not (called & FSYNC_EVIDENCE_CALLS):
                bad.append((rel, node.lineno, fn.name,
                            lines[node.lineno - 1].strip()))
    return bad


def lint_roofline_capture() -> list[tuple[str, int, str, str]]:
    """Check 6: no compiled-cost capture (cost_analysis / memory_analysis /
    RooflineCapture.capture) in the dispatcher section or inside nested
    (traced) device-package functions; returns (where, line, function,
    text) hits."""
    disp, _ = _scan_named_funcs(DISPATCHER_FUNCS, ROOFLINE_PATTERN,
                                ROOFLINE_MARKER)
    return ([(TARGET.name, ln, fn, text) for fn, ln, text in disp]
            + _scan_nested_funcs(ROOFLINE_PATTERN, ROOFLINE_MARKER))


def lint_device_host_calls() -> list[tuple[str, int, str, str]]:
    """Flag time/log/print host calls inside nested (= traced) functions of
    the device packages; returns (relpath, line, function, text) hits."""
    return _scan_nested_funcs(JIT_PATTERN, JIT_MARKER)


def lint_precision_casts() -> list[tuple[str, int, str, str]]:
    """Check 7: no bare ``.astype(`` on params/grads in ``_run_supervised``
    or nested (traced) device-package functions — casts route through the
    precision policy helpers; returns (where, line, function, text) hits."""
    disp, _ = _scan_named_funcs(HOT_FUNCS, PRECISION_PATTERN,
                                PRECISION_MARKER)
    return ([(TARGET.name, ln, fn, text) for fn, ln, text in disp]
            + _scan_nested_funcs(PRECISION_PATTERN, PRECISION_MARKER))


def main() -> int:
    bad, found = lint_hot_loop_syncs()
    missing = set(HOT_FUNCS) - found
    if missing:
        # A rename must update this lint, not silently un-guard the loop.
        print(f"hot-loop lint: function(s) {sorted(missing)} not found in "
              f"{TARGET} — update tools/lint_hot_loop.py HOT_FUNCS")
        return 1
    if bad:
        print(f"hot-loop sync lint FAILED ({TARGET.name}):")
        for fn, ln, text in bad:
            print(f"  {fn}:{ln}: {text}")
        print("per-chunk host syncs serialize the dispatch pipeline; route "
              "reads through the batched megachunk readback, or tag the "
              f"line '# {MARKER}: <why this is not a per-chunk cost>'")
        return 1
    put_bad = lint_parallel_device_put()
    if put_bad:
        print("parallel-layer device_put lint FAILED:")
        for rel, ln, text in put_bad:
            print(f"  {rel}:{ln}: {text}")
        print("a bare device_put in the parallel layer places data off its "
              "canonical sharding and the next partitioned program pays an "
              "involuntary reshard; pass the NamedSharding (see "
              "sharding.canonical_sharding), or tag the line "
              f"'# {PUT_MARKER}: <why placement is intentionally default>'")
        return 1
    jit_bad = lint_device_host_calls()
    if jit_bad:
        print("device-code host-call lint FAILED:")
        for rel, ln, fn, text in jit_bad:
            print(f"  {rel}:{ln} (in {fn}): {text}")
        print("time/log/print inside a traced step body runs ONCE at trace "
              "time, not per step; move telemetry to the host side of the "
              "chunk boundary (obs/), or tag the line "
              f"'# {JIT_MARKER}: <why trace-time-only is intended>'")
        return 1
    disp_bad, disp_found = lint_dispatcher_blocking()
    disp_missing = (set(DISPATCHER_FUNCS) | set(CONSUMER_FUNCS)) - disp_found
    if disp_missing:
        print(f"dispatcher lint: function(s) {sorted(disp_missing)} not "
              f"found in {TARGET} — the async-pipeline dispatcher/consumer "
              "split was renamed; update tools/lint_hot_loop.py "
              "DISPATCHER_FUNCS/CONSUMER_FUNCS")
        return 1
    if disp_bad:
        print(f"dispatcher blocking-call lint FAILED ({TARGET.name}):")
        for fn, ln, text in disp_bad:
            print(f"  {fn}:{ln}: {text}")
        print("a blocking device_get/np.asarray/os.fsync in the dispatcher "
              "section stalls the dispatch pipeline; move it to the "
              "readback consumer (_host_process), or tag the line "
              f"'# {MARKER}: <why this blocks the dispatcher on purpose>'")
        return 1
    roof_bad = lint_roofline_capture()
    if roof_bad:
        print("roofline compile-time capture lint FAILED:")
        for rel, ln, fn, text in roof_bad:
            print(f"  {rel}:{ln} (in {fn}): {text}")
        print("cost_analysis/memory_analysis/RooflineCapture.capture lower "
              "and compile a whole program — compile-time-only work that "
              "must never ride the dispatcher or a traced step body; move "
              "it to the build path (jit_parallel_step cost_hook), or tag "
              f"the line '# {ROOFLINE_MARKER}: <why capture here>'")
        return 1
    prec_bad = lint_precision_casts()
    if prec_bad:
        print("precision-policy cast lint FAILED:")
        for rel, ln, fn, text in prec_bad:
            print(f"  {rel}:{ln} (in {fn}): {text}")
        print("a bare .astype( on params/grads in the hot paths bypasses "
              "the precision policy (fp32 bit-identity, bf16 master-weight "
              "contract); route it through PrecisionPolicy.cast_compute/"
              "grads_to_master/cast_carry (precision.py), or tag the line "
              f"'# {PRECISION_MARKER}: <why this cast is policy-"
              "sanctioned>'")
        return 1
    serve_bad, serve_found = lint_serve_dispatch()
    serve_missing = (set(SERVE_DISPATCH_FUNCS)
                     | set(SERVE_CONSUMER_FUNCS)) - serve_found
    if serve_missing:
        print(f"serve dispatch lint: function(s) {sorted(serve_missing)} "
              f"not found in {SERVE_TARGET} — the serve engine's "
              "dispatcher/consumer split was renamed; update "
              "tools/lint_hot_loop.py SERVE_DISPATCH_FUNCS/"
              "SERVE_CONSUMER_FUNCS")
        return 1
    if serve_bad:
        print(f"serve batch-dispatch lint FAILED ({SERVE_TARGET.name}):")
        for fn, ln, text in serve_bad:
            print(f"  {fn}:{ln}: {text}")
        print("a blocking device_get/fsync/sleep/log in the serve "
              "dispatch closure stalls every queued session's latency; "
              "move it to the consumer side (_complete_batch), or tag the "
              f"line '# {SERVE_MARKER}: <why this host op is on the "
              "dispatch path on purpose>'")
        return 1
    replay_bad, replay_found = lint_replay_device_path()
    replay_missing = (set(REPLAY_TREE_FUNCS) | set(REPLAY_DQN_FUNCS)
                      | set(REPLAY_CONSUMER_FUNCS)) - replay_found
    if replay_missing:
        print(f"replay device-path lint: function(s) "
              f"{sorted(replay_missing)} not found — the replay data "
              "plane's device/host split was renamed; update "
              "tools/lint_hot_loop.py REPLAY_TREE_FUNCS/REPLAY_DQN_FUNCS/"
              "REPLAY_CONSUMER_FUNCS")
        return 1
    if replay_bad:
        print("replay device-path lint FAILED:")
        for fn, ln, text in replay_bad:
            print(f"  {fn}:{ln}: {text}")
        print("journal IO / os.* / host RNG in the traced replay sample "
              "or priority-update path either freezes at trace time or "
              "adds a host sync to the chunk; move it to the consumer "
              "side (_journal_transitions / _warm_start_replay), or tag "
              f"the line '# {REPLAY_MARKER}: <why this is trace-safe>'")
        return 1
    serve_pkg_bad = lint_serve_overload_safety()
    if serve_pkg_bad:
        print("serve overload-safety lint FAILED:")
        for rel, ln, text in serve_pkg_bad:
            print(f"  {rel}:{ln}: {text}")
        print("an unbounded queue.Queue() in serve/ re-opens the "
              "request-flood memory hole admission control closed, and a "
              "bare time.sleep there is an uninterruptible stall; bound "
              "the queue (non-zero maxsize=) / route the wait through "
              "the stop event (see ServeEngine._backoff_sleep), or tag "
              f"the line '# {SERVE_PKG_MARKER}: <why this block is off "
              "the serving path>'")
        return 1
    buf_bad = lint_bounded_trace_buffers()
    if buf_bad:
        print("trace-buffer bound lint FAILED:")
        for rel, ln, text in buf_bad:
            print(f"  {rel}:{ln}: {text}")
        print("an unbounded deque in serve/ or obs/ accumulates per-"
              "request observability state at request rate — a slow "
              "memory leak that tracks offered load; give it a maxlen "
              "ring bound, or tag it (call line or the two lines above) "
              f"'# {TRACE_BUFFER_MARKER}: <the logical bound>'")
        return 1
    spawn_bad, spawn_found = lint_actor_spawn()
    spawn_missing = set(ACTOR_POOL_FUNCS) - spawn_found
    if spawn_missing:
        print(f"actor-spawn lint: function(s) {sorted(spawn_missing)} not "
              f"found in sharetrade_tpu/{ACTOR_SPAWN_MODULE} — the actor "
              "pool's reap/heartbeat seam was renamed; update "
              "tools/lint_hot_loop.py ACTOR_POOL_FUNCS")
        return 1
    if spawn_bad:
        print("actor-spawn lint FAILED:")
        for rel, ln, text in spawn_bad:
            print(f"  sharetrade_tpu/{rel}:{ln}: {text}")
        print("a process spawned outside the ActorPool supervisor has no "
              "reap/backoff/terminal-failure owner (zombie and leak "
              "territory); route it through distrib/pool.py, or tag the "
              f"line '# {ACTOR_SPAWN_MARKER}: <who supervises this "
              "child>'")
        return 1
    knob_bad, knob_found = lint_tuned_knob_shadows()
    knob_missing = set(TUNED_KNOB_PATHS) - knob_found
    if knob_missing:
        print(f"tuned-knob lint: knob path(s) {sorted(knob_missing)} not "
              f"found in {TUNING_REGISTRY_FILE} — the tuning registry "
              "and tools/lint_hot_loop.py TUNED_KNOB_PATHS must move "
              "together")
        return 1
    if knob_bad:
        print("tuned-knob shadow lint FAILED:")
        for rel, ln, text in knob_bad:
            print(f"  sharetrade_tpu/{rel}:{ln}: {text}")
        print("a numeric-literal assignment to a registered tuning knob "
              "in serve//runtime/ re-creates the hand-set constant the "
              "registry retired (the profile/controller layer silently "
              "stops owning it); read it through config/set_knobs, or "
              f"tag the line '# {TUNED_KNOB_MARKER}: <why a literal is "
              "correct here>'")
        return 1
    net_listener_bad, net_dispatch_bad = lint_fleet_net()
    if net_listener_bad:
        print("fleet net-listener lint FAILED:")
        for rel, ln, text in net_listener_bad:
            print(f"  sharetrade_tpu/{rel}:{ln}: {text}")
        print("a socket/HTTP listener outside fleet/ is an unsupervised "
              "second front door around the fleet's drain/status-code/"
              "telemetry contract; serve it through fleet/frontend.py, "
              f"or tag the line '# {FLEET_NET_MARKER}: <why this "
              "listener lives here>'")
        return 1
    if net_dispatch_bad:
        print("serve dispatch network-I/O lint FAILED:")
        for rel, ln, text in net_dispatch_bad:
            print(f"  {rel}:{ln}: {text}")
        print("a blocking network call in the serve dispatch closure "
              "stalls every queued session behind one peer's RTT; wire "
              "work belongs to the fleet front-end/router threads, or "
              f"tag the line '# {FLEET_NET_MARKER}: <why the dispatch "
              "path blocks on the network on purpose>'")
        return 1
    ev_block_bad, ev_import_bad = lint_evloop_sansio()
    if ev_block_bad:
        print("evloop blocking-idiom lint FAILED:")
        for rel, ln, text in ev_block_bad:
            print(f"  sharetrade_tpu/{rel}:{ln}: {text}")
        print("one blocking call on the event-loop thread stalls every "
              "connection the process is proxying; use the loop's "
              "non-blocking write/timer paths, or tag the line (or a "
              f"comment just above) '# {EVLOOP_BLOCK_MARKER}: <why "
              "this may block>'")
        return 1
    if ev_import_bad:
        print("sans-IO protocol-core import lint FAILED:")
        for rel, ln, text in ev_import_bad:
            print(f"  sharetrade_tpu/{rel}:{ln}: {text}")
        print("fleet/proto.py is the SANS-IO core: bytes in, events "
              "out — an I/O import there couples the parser to a "
              "transport and breaks the exhaustive torn-read/"
              "pipelining tests; keep I/O in fleet/evloop.py and "
              "fleet/wire.py")
        return 1
    span_bad = lint_span_emission()
    if span_bad:
        print("span-emission hot-path lint FAILED:")
        for rel, ln, text in span_bad:
            print(f"  sharetrade_tpu/{rel}:{ln}: {text}")
        print("span emission on the evloop/router wire path must be a "
              "bounded buffered append: one tuple into the SpanSink "
              "ring now, json.dumps only at the batched flush "
              "(obs/trace.py), and never an unbounded span list; route "
              "emission through SpanSink.span/instant, or tag the line "
              f"(or the two above) '# {TRACE_BUFFER_MARKER}: <the "
              "bound / why serialization is off the hot path>'")
        return 1
    ann_bad = lint_profiler_annotations()
    if ann_bad:
        print("profiler-annotation lint FAILED:")
        for rel, ln, text in ann_bad:
            print(f"  sharetrade_tpu/{rel}:{ln}: {text}")
        print("host spans open through ONE entry, obs/trace.py host_span "
              "(Obs.span / SpanTracer.span / span): per chunk or per tick, "
              "a fixed name, the serial as an identifier, on the "
              "profiler's clock and in trace.jsonl from one call; a bare "
              "TraceAnnotation elsewhere is how a per-request or "
              "per-agent-step span slips in. Use the helper, or tag the "
              f"line (or the two above) '# {ANNOTATION_MARKER}: <why the "
              "helper cannot serve>'")
        return 1
    warm_bad, warm_found = lint_warm_tier()
    warm_missing = ({SERVE_WARM_CLASS} | set(SERVE_PAGE_FUNCS)) - warm_found
    if warm_missing:
        print(f"warm-tier lint: name(s) {sorted(warm_missing)} not found "
              f"in {SERVE_TARGET} — the session-paging tier was renamed; "
              "update tools/lint_hot_loop.py SERVE_WARM_CLASS/"
              "SERVE_PAGE_FUNCS")
        return 1
    if warm_bad:
        print(f"warm-tier lint FAILED ({SERVE_TARGET.name}):")
        for fn, ln, text in warm_bad:
            print(f"  {fn}:{ln}: {text}")
        print("the warm session tier must evict IN CODE (a popitem loop "
              "conditioned on max_bytes/max_sessions — each parked "
              "session holds a whole carry tree, so an unbounded store "
              "leaks at session-population rate), and the dispatch-"
              "thread paging functions must not block on host ops "
              "(device_get belongs to the consumer's park readback); "
              f"tag the class '# {WARM_TIER_MARKER}: <where the bound "
              f"lives>' or the line '# {SERVE_MARKER}: <why this host "
              "op rides dispatch>'")
        return 1
    nw_binding_bad, nw_gil_bad, nw_import_bad = lint_native_wire()
    if nw_binding_bad:
        print("native-wire binding confinement lint FAILED:")
        for rel, ln, text in nw_binding_bad:
            print(f"  sharetrade_tpu/{rel}:{ln}: {text}")
        print("the stwire extension is loaded through fleet/proto.py's "
              "backend dispatch ONLY — a second binding site forks the "
              "wire semantics away from the differential oracle; go "
              "through proto.set_backend()/proto.RequestParser, or tag "
              f"the line (or the two above) '# {NATIVE_WIRE_MARKER}: "
              "<why this binding site must exist>'")
        return 1
    if nw_gil_bad:
        print("native-wire GIL-release lint FAILED:")
        for rel, ln, text in nw_gil_bad:
            print(f"  native/{rel}:{ln}: {text}")
        print("native/wire.cc must frame bytes with the GIL released "
              f"({GIL_BEGIN}/{GIL_END} pairs around the C parse/render "
              "cores) — a native parser that holds the GIL serializes "
              "against engine callbacks exactly like the Python one it "
              "replaces, which is the whole regression the check "
              "guards")
        return 1
    if nw_import_bad:
        print("native-wire sans-IO import lint FAILED:")
        for rel, ln, text in nw_import_bad:
            print(f"  sharetrade_tpu/{rel}:{ln}: {text}")
        print("fleet/proto.py must stay I/O-import-free under BOTH "
              "backends — the native loader runs at proto import time, "
              "so an I/O import there couples every parser (C and "
              "Python alike) to a transport")
        return 1
    dur_bad = lint_durable_replace()
    if dur_bad:
        print("durable-rename fsync lint FAILED:")
        for rel, ln, fn, text in dur_bad:
            print(f"  {rel}:{ln} (in {fn}): {text}")
        print("an os.replace in a checkpoint/journal write path publishes a "
              "name whose bytes are not yet durable; fsync the payload (and "
              "directory) first — see _write_checkpoint_dir / "
              "write_framed_bytes — or tag the line "
              f"'# {REPLACE_MARKER}: <why durability is not needed here>'")
        return 1
    sp_io_bad, sp_crc_bad, sp_index_bad, sp_found = lint_spill_arena()
    if SPILL_CLASS not in sp_found:
        print(f"spill-arena lint: class {SPILL_CLASS} not found in "
              f"sharetrade_tpu/{SPILL_MODULE} — the disk spill tier was "
              "renamed; update tools/lint_hot_loop.py SPILL_CLASS/"
              "SPILL_MODULE")
        return 1
    if sp_io_bad:
        print("spill-arena record-I/O confinement lint FAILED:")
        for rel, ln, text in sp_io_bad:
            print(f"  sharetrade_tpu/{rel}:{ln}: {text}")
        print("arena record files are read and written through "
              f"sharetrade_tpu/{SPILL_MODULE} ONLY — a second site "
              "touching .spill records forks the record format away "
              "from the CRC/seal/consume-on-take contract the bitwise "
              "adoption tests pin; go through SpillArena/sweep_debris, "
              f"or tag the line (or the two above) '# {SPILL_IO_MARKER}: "
              "<why this site must touch records directly>'")
        return 1
    if sp_crc_bad:
        print("spill-arena record-integrity lint FAILED:")
        for rel, ln, text in sp_crc_bad:
            print(f"  sharetrade_tpu/{rel}:{ln}: {text}")
        print("every spill record publish must stamp a crc32 over the "
              "payload before the atomic os.replace seal — an adopting "
              "engine decides warm-vs-cold from that checksum, and a "
              "torn or bit-flipped record without one would replay "
              "WRONG session state instead of demoting to a cold "
              "restart (fsync-before-rename itself is check 5)")
        return 1
    if sp_index_bad:
        print("spill-arena in-memory index lint FAILED:")
        for rel, ln, text in sp_index_bad:
            print(f"  sharetrade_tpu/{rel}:{ln}: {text}")
        print("the arena keeps NO in-memory record index: the census "
              "lives on disk (os.scandir re-anchor in scan_usage) so "
              "that engine incarnations sharing one arena cannot drift "
              "and memory cannot grow with session population; if the "
              "container is not a record index, tag the line (or the "
              f"two above) '# {SPILL_INDEX_MARKER}: <what bounds it>'")
        return 1
    print(f"hot-loop sync lint OK ({', '.join(sorted(found))}); "
          f"parallel device_put lint OK; "
          f"device-code host-call lint OK ({', '.join(DEVICE_PACKAGES)}); "
          f"dispatcher blocking-call lint OK "
          f"({', '.join(DISPATCHER_FUNCS)}); "
          f"roofline capture lint OK; "
          f"precision-cast lint OK; "
          f"serve batch-dispatch lint OK ({', '.join(SERVE_DISPATCH_FUNCS)}); "
          f"replay device-path lint OK ({', '.join(REPLAY_TREE_FUNCS + REPLAY_DQN_FUNCS)}); "
          f"serve overload-safety lint OK; "
          f"trace-buffer bound lint OK ({', '.join(TRACE_BUFFER_DIRS)}); "
          f"actor-spawn lint OK ({ACTOR_SPAWN_MODULE}); "
          f"tuned-knob shadow lint OK ({len(TUNED_KNOB_PATHS)} knobs, "
          f"{', '.join(TUNED_KNOB_DIRS)}); "
          f"fleet net-listener lint OK (listeners confined to "
          f"sharetrade_tpu/{FLEET_NET_DIR}/); "
          f"evloop non-blocking lint OK ({', '.join(EVLOOP_FILES)}); "
          f"sans-IO import lint OK ({SANSIO_FILE}); "
          f"span-emission lint OK ({', '.join(SPAN_EMIT_FILES)}); "
          f"profiler-annotation lint OK (confined to {ANNOTATION_MODULE}); "
          f"warm-tier lint OK ({SERVE_WARM_CLASS}, "
          f"{', '.join(SERVE_PAGE_FUNCS)}); "
          f"native-wire lint OK ({NATIVE_WIRE_MODULE} seam, "
          f"GIL released in wire.cc); "
          f"durable-rename fsync lint OK ({', '.join(DURABLE_WRITE_FILES)}); "
          f"spill-arena lint OK ({SPILL_MODULE} confinement, CRC'd + "
          f"sealed records, disk-anchored census)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
