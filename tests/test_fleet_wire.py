"""Sans-IO wire core + evloop backend (fleet/proto.py, fleet/evloop.py
— ISSUE 16).

The load-bearing contracts:

- **Torn reads are invisible**: a parser fed the SAME byte stream split
  at EVERY offset (including one byte at a time) emits the same message
  sequence — framing is a pure state machine, never "hope recv returned
  a whole request".
- **Pipelining**: N messages in one chunk come back as N events in
  order; partial tails stay buffered across feeds.
- **Bounded buffering**: an oversized head or declared body raises
  :class:`ProtocolError` (status 400) instead of buffering unboundedly;
  malformed framing is refused with the same class.
- **Differential oracle**: the threaded and evloop wire backends answer
  the SAME request stream with BYTE-IDENTICAL response streams — the
  blocking stdlib path is retained exactly so the event-loop rewrite
  can be diffed against it.
- **Non-blocking discipline is linted**: check 15 keeps blocking socket
  idioms and per-connection threads out of the evloop path, and keeps
  fleet/proto.py free of I/O imports entirely.
- **Trace headers ride the same frame** (ISSUE 17): ``X-Trace-Id``/
  ``X-Parent-Span`` canonicalize identically through torn reads, a bad
  id is dropped rather than relayed, replies NEVER echo trace headers —
  so the differential oracle holds byte-identically with tracing on AND
  off — and check 16 keeps span emission on the evloop/router hot path
  a bounded buffered append.
- **The native parser is indistinguishable** (ISSUE 19): the C
  extension behind ``proto.set_backend("native")`` replays seeded
  byte-split/pipelined/malformed corpora with event streams and
  ``ProtocolError`` status+detail EXACTLY equal to the Python oracle's,
  renders byte-identically, degrades loudly to "py" when the extension
  is missing, and check 18 confines the binding surface to
  fleet/proto.py with the GIL released in wire.cc.
"""

from __future__ import annotations

import json
import os
import socket
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from sharetrade_tpu.fleet import ServeFrontend
from sharetrade_tpu.fleet import proto, wire
from sharetrade_tpu.fleet.evloop import EvloopFrontend
from sharetrade_tpu.fleet.frontend import ThreadedServeFrontend
from sharetrade_tpu.utils.metrics import MetricsRegistry


# ---- corpus ---------------------------------------------------------

def _request_corpus() -> list[bytes]:
    submit = json.dumps({"session": "s-1", "obs": [1.0, 2.0, 3.0]})
    return [
        proto.render_request("GET", wire.HEALTH_PATH, "h:1"),
        proto.render_request("POST", wire.SUBMIT_PATH, "h:1",
                             submit.encode(),
                             headers={wire.DEADLINE_HEADER: "250"}),
        proto.render_request("POST", wire.SUBMIT_PATH, "h:1",
                             b"\x00binary body\xff",
                             headers={"Connection": "close"}),
        proto.render_request("POST", wire.SUBMIT_PATH, "h:1",
                             submit.encode(),
                             headers={proto.TRACE_HEADER: "ab12cd34ef56ab78",
                                      proto.PARENT_HEADER: "1f.2"}),
        proto.render_request("GET", wire.METRICS_PATH, "h:1"),
    ]


def _response_corpus() -> list[bytes]:
    return [
        proto.render_response(200, b'{"ok": true}'),
        proto.render_response(503, b'{"error": "engine_failed"}',
                              keep_alive=False),
        proto.render_response(200, b"metrics text",
                              content_type="text/plain; version=0.0.4",
                              extra_headers={"X-Probe": "1"}),
        proto.render_response(400, b""),
    ]


def _req_key(r: proto.Request) -> tuple:
    return (r.method, r.target, sorted(r.headers.items()), r.body,
            r.keep_alive)


def _resp_key(r: proto.Response) -> tuple:
    return (r.status, sorted(r.headers.items()), r.body)


class TestSansIOParsers:
    def test_request_stream_torn_at_every_offset(self):
        blob = b"".join(_request_corpus())
        reference = [_req_key(r)
                     for r in proto.RequestParser().feed(blob)]
        assert len(reference) == len(_request_corpus())
        for split in range(1, len(blob)):
            p = proto.RequestParser()
            got = p.feed(blob[:split]) + p.feed(blob[split:])
            assert [_req_key(r) for r in got] == reference, split
            assert not p.pending_bytes()

    def test_response_stream_torn_at_every_offset(self):
        blob = b"".join(_response_corpus())
        reference = [_resp_key(r)
                     for r in proto.ResponseParser().feed(blob)]
        assert len(reference) == len(_response_corpus())
        for split in range(1, len(blob)):
            p = proto.ResponseParser()
            got = p.feed(blob[:split]) + p.feed(blob[split:])
            assert [_resp_key(r) for r in got] == reference, split

    def test_one_byte_at_a_time(self):
        blob = b"".join(_request_corpus())
        reference = [_req_key(r)
                     for r in proto.RequestParser().feed(blob)]
        p = proto.RequestParser()
        got = []
        for i in range(len(blob)):
            got.extend(p.feed(blob[i:i + 1]))
        assert [_req_key(r) for r in got] == reference
        assert not p.pending_bytes()

    def test_pending_bytes_mid_message(self):
        blob = _request_corpus()[1]
        p = proto.RequestParser()
        assert not p.pending_bytes()
        assert p.feed(blob[:len(blob) - 1]) == []
        assert p.pending_bytes()        # mid-body: not pool-reusable
        assert len(p.feed(blob[len(blob) - 1:])) == 1
        assert not p.pending_bytes()

    def test_keep_alive_folding(self):
        def parse(version, connection=None):
            head = [f"GET / {version}", "Host: h"]
            if connection:
                head.append(f"Connection: {connection}")
            raw = ("\r\n".join(head) + "\r\n\r\n").encode()
            return proto.RequestParser().feed(raw)[0].keep_alive

        assert parse("HTTP/1.1") is True
        assert parse("HTTP/1.1", "close") is False
        assert parse("HTTP/1.0") is False
        assert parse("HTTP/1.0", "keep-alive") is True

    @pytest.mark.parametrize("raw", [
        b"GARBAGE\r\n\r\n",                       # no 3-part line
        b"GET /x HTTP/2\r\n\r\n",                 # unsupported version
        b"GET /x HTTP/1.1\r\nNoColonHere\r\n\r\n",
        b"GET /x HTTP/1.1\r\nContent-Length: xyz\r\n\r\n",
        b"GET /x HTTP/1.1\r\nContent-Length: -5\r\n\r\n",
    ])
    def test_malformed_requests_raise_400(self, raw):
        with pytest.raises(proto.ProtocolError) as exc:
            proto.RequestParser().feed(raw)
        assert exc.value.status == 400

    def test_oversized_head_refused_before_terminator(self):
        p = proto.RequestParser()
        with pytest.raises(proto.ProtocolError):
            p.feed(b"GET /x HTTP/1.1\r\nX: "
                   + b"a" * (proto.MAX_HEAD_BYTES + 8))

    def test_oversized_declared_body_refused(self):
        raw = (f"POST /x HTTP/1.1\r\nContent-Length: "
               f"{proto.MAX_BODY_BYTES + 1}\r\n\r\n").encode()
        with pytest.raises(proto.ProtocolError):
            proto.RequestParser().feed(raw)

    def test_response_requires_content_length(self):
        with pytest.raises(proto.ProtocolError) as exc:
            proto.ResponseParser().feed(b"HTTP/1.1 200 OK\r\n\r\n")
        assert "Content-Length" in exc.value.detail

    def test_render_request_is_the_fleet_client_frame(self):
        raw = proto.render_request("POST", "/v1/submit", "10.0.0.1:80",
                                   b"{}", headers={"X-Deadline-Ms": "9"})
        assert raw == (b"POST /v1/submit HTTP/1.1\r\n"
                       b"Host: 10.0.0.1:80\r\n"
                       b"Content-Length: 2\r\n"
                       b"X-Deadline-Ms: 9\r\n\r\n{}")


class TestTraceHeaders:
    """X-Trace-Id / X-Parent-Span canonicalization (ISSUE 17): ONE
    framing definition, bad ids dropped rather than relayed."""

    def test_roundtrip_through_the_parser(self):
        raw = proto.render_request(
            "POST", wire.SUBMIT_PATH, "h:1", b"{}",
            headers={proto.TRACE_HEADER: "DEADbeef00112233",
                     proto.PARENT_HEADER: "abc.1f"})
        req = proto.RequestParser().feed(raw)[0]
        assert proto.trace_context(req.headers) == \
            ("DEADbeef00112233", "abc.1f")
        # Torn at every offset: the context survives identically.
        for split in range(1, len(raw)):
            p = proto.RequestParser()
            got = p.feed(raw[:split]) + p.feed(raw[split:])
            assert proto.trace_context(got[0].headers) == \
                ("DEADbeef00112233", "abc.1f"), split

    def test_absent_context_is_none(self):
        req = proto.RequestParser().feed(
            proto.render_request("GET", wire.HEALTH_PATH, "h:1"))[0]
        assert proto.trace_context(req.headers) is None

    @pytest.mark.parametrize("trace_id", [
        "", "zz99", "a" * 65, "ab cd", "ab\tcd", "<script>"])
    def test_invalid_trace_id_never_relayed(self, trace_id):
        assert proto.trace_context({"x-trace-id": trace_id}) is None

    def test_invalid_parent_dropped_trace_kept(self):
        assert proto.trace_context(
            {"x-trace-id": "ab12", "x-parent-span": "not~valid"}) \
            == ("ab12", "")
        assert proto.trace_context(
            {"x-trace-id": "ab12", "x-parent-span": "f" * 65}) \
            == ("ab12", "")

    def test_stdlib_message_headers_resolve_case_insensitively(self):
        # The threaded front-end hands trace_context an
        # email.message.Message (BaseHTTPRequestHandler.headers) whose
        # .get is case-insensitive — same answer as the parsed dict.
        from email.message import Message
        msg = Message()
        msg["X-Trace-Id"] = "ab12cd34"
        msg["X-Parent-Span"] = "3.c"
        assert proto.trace_context(msg) == ("ab12cd34", "3.c")

    def test_replies_never_carry_trace_headers(self):
        raw = proto.render_response(
            200, b"{}", extra_headers={"X-Probe": "1"})
        resp = proto.ResponseParser().feed(raw)[0]
        assert "x-trace-id" not in resp.headers
        assert "x-parent-span" not in resp.headers


# ---- the differential oracle ---------------------------------------


class StubBackend:
    """Deterministic inline backend: replies are a pure function of the
    request, so the two wire backends' response streams must be
    byte-identical."""

    def serve_request(self, session, obs, deadline_ms):
        vals = [float(x) for x in obs]
        return {"session": session, "action": len(vals) % 3,
                "logits": vals[:3], "value": sum(vals),
                "params_step": 7, "latency_ms": 0.25,
                "stages": {"queue_ms": 0.1}}

    def health(self):
        return {"ok": True, "failed": False, "queue_depth": 0,
                "overload": 0.0, "params_step": 7, "swaps_total": 0}


def _scripted_stream() -> tuple[bytes, int]:
    """One connection's worth of requests covering every front-end
    reply path that is deterministic across backends; returns
    ``(payload, expected_response_count)``."""
    ok = json.dumps({"session": "d-1", "obs": [1.0, 2.0, 3.0]}).encode()
    reqs = [
        proto.render_request("GET", wire.HEALTH_PATH, "h:1"),
        proto.render_request("POST", wire.SUBMIT_PATH, "h:1", ok),
        proto.render_request("POST", wire.SUBMIT_PATH, "h:1",
                             b"not json at all"),
        proto.render_request("POST", wire.SUBMIT_PATH, "h:1",
                             b'{"obs": [1.0]}'),      # missing session
        proto.render_request("POST", wire.SUBMIT_PATH, "h:1", ok,
                             headers={wire.DEADLINE_HEADER: "soon"}),
        proto.render_request("GET", "/nope", "h:1"),
        proto.render_request("POST", "/nope", "h:1", b"ignored body"),
        # pipelined burst: three submits in one segment
        proto.render_request("POST", wire.SUBMIT_PATH, "h:1", ok) * 3,
    ]
    return b"".join(reqs), 10


def _drive(host: str, port: int, payload: bytes, n_responses: int,
           chunk: int | None = None) -> bytes:
    sock = socket.create_connection((host, port), timeout=30.0)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.settimeout(30.0)
    if chunk is None:
        sock.sendall(payload)
    else:
        for i in range(0, len(payload), chunk):
            sock.sendall(payload[i:i + chunk])
    parser = proto.ResponseParser()
    raw = bytearray()
    got = 0
    while got < n_responses:
        data = sock.recv(1 << 16)
        if not data:
            break
        raw += data
        got += len(parser.feed(data))
    sock.close()
    assert got == n_responses
    return bytes(raw)


class TestDifferentialOracle:
    def test_threaded_and_evloop_answer_byte_identically(self):
        payload, n = _scripted_stream()
        streams = {}
        for backend in ("threaded", "evloop"):
            fe = ServeFrontend(StubBackend(), MetricsRegistry(),
                               wire_backend=backend).start()
            try:
                streams[backend] = _drive(fe.host, fe.port, payload, n)
                # ...and torn delivery must not change a byte either.
                torn = _drive(fe.host, fe.port, payload, n, chunk=7)
                assert torn == streams[backend]
            finally:
                fe.stop()
        assert streams["threaded"] == streams["evloop"]

    def test_byte_identity_holds_with_tracing_on_and_off(self, tmp_path):
        """ISSUE 17 acceptance: replies never echo trace headers, so
        turning tracing ON (frontend mints + journals spans, requests
        may carry inbound context) changes ZERO reply bytes on either
        backend — all four (backend x tracing) streams are identical.
        StubBackend has no ``wire_traced`` attr, so the front-ends must
        also never hand it a tctx kwarg (that inversion would 500)."""
        from sharetrade_tpu.fleet.wire import WireTracer
        from sharetrade_tpu.obs import collect
        from sharetrade_tpu.obs.trace import SpanJournal, SpanSink

        payload, n = _scripted_stream()
        traced_req = proto.render_request(
            "POST", wire.SUBMIT_PATH, "h:1",
            json.dumps({"session": "d-1", "obs": [1.0, 2.0, 3.0]}).encode(),
            headers={proto.TRACE_HEADER: "ab12cd34ef56ab78",
                     proto.PARENT_HEADER: "1f.2"})
        payload = traced_req + payload
        n += 1
        streams: dict = {}
        for mode in ("off", "on"):
            for backend in ("threaded", "evloop"):
                sink = tracer = None
                if mode == "on":
                    sink = SpanSink(SpanJournal(
                        str(tmp_path / f"spans-{backend}"), "fleet"))
                    tracer = WireTracer(sink, mint=True)
                fe = ServeFrontend(StubBackend(), MetricsRegistry(),
                                   wire_backend=backend,
                                   tracer=tracer).start()
                try:
                    streams[(mode, backend)] = _drive(
                        fe.host, fe.port, payload, n)
                finally:
                    fe.stop()
                    if sink is not None:
                        sink.close()
        assert len(set(streams.values())) == 1
        # ...and tracing-on actually journaled: every POST got a
        # frontend hop span on both backends (the evloop additionally
        # traces GETs); the inbound context threads through intact
        # while untraced requests were minted fresh unique ids.
        posts = sum(1 for r in proto.RequestParser().feed(payload)
                    if r.method == "POST"
                    and r.target == wire.SUBMIT_PATH)
        for backend in ("threaded", "evloop"):
            spans = collect.read_span_dir(
                str(tmp_path / f"spans-{backend}"))
            fronts = [s for s in spans if s["name"] == "frontend"]
            assert len(fronts) >= posts
            assert len({s["span"] for s in fronts}) == len(fronts)
            assert len({s["trace"] for s in fronts}) == len(fronts)
            inbound = [s for s in fronts
                       if s["trace"] == "ab12cd34ef56ab78"]
            assert len(inbound) == 1 and inbound[0]["parent"] == "1f.2"

    def test_tracing_off_emits_zero_headers_and_files(self, tmp_path):
        """obs.enabled=false default: no tracer → the backend sees no
        trace context even when the CLIENT sends headers, and nothing
        span-shaped is ever written."""
        seen: list = []

        class Recorder(StubBackend):
            wire_traced = True

            def serve_request(self, session, obs, deadline_ms,
                              tctx=None):
                seen.append(tctx)
                return super().serve_request(session, obs, deadline_ms)

        payload = proto.render_request(
            "POST", wire.SUBMIT_PATH, "h:1",
            json.dumps({"session": "d-1", "obs": [1.0]}).encode(),
            headers={proto.TRACE_HEADER: "ab12cd34ef56ab78"})
        for backend in ("threaded", "evloop"):
            fe = ServeFrontend(Recorder(), MetricsRegistry(),
                               wire_backend=backend).start()
            try:
                _drive(fe.host, fe.port, payload, 1)
            finally:
                fe.stop()
        assert seen == [None, None]
        assert list(tmp_path.iterdir()) == []

    def test_wire_backend_knob(self):
        reg = MetricsRegistry()
        fe = ServeFrontend(StubBackend(), reg, wire_backend="threaded")
        assert isinstance(fe, ThreadedServeFrontend)
        fe2 = ServeFrontend(StubBackend(), reg)     # default: evloop
        assert isinstance(fe2, EvloopFrontend)
        with pytest.raises(ValueError):
            ServeFrontend(StubBackend(), reg, wire_backend="carrier")


class TestEvloopSocketEdges:
    def test_oversized_head_gets_400_and_close(self):
        fe = ServeFrontend(StubBackend(), MetricsRegistry(),
                           wire_backend="evloop").start()
        try:
            sock = socket.create_connection((fe.host, fe.port),
                                            timeout=30.0)
            sock.settimeout(30.0)
            sock.sendall(b"GET /healthz HTTP/1.1\r\nX-Pad: "
                         + b"a" * (proto.MAX_HEAD_BYTES + 64))
            raw = bytearray()
            while True:
                data = sock.recv(1 << 16)
                if not data:        # server closed after the refusal
                    break
                raw += data
            sock.close()
            resp = proto.ResponseParser().feed(bytes(raw))[0]
            assert resp.status == 400
            assert resp.headers.get("connection") == "close"
        finally:
            fe.stop()

    def test_draining_refusal_matches_threaded_wording(self):
        payload = proto.render_request(
            "POST", wire.SUBMIT_PATH, "h:1",
            json.dumps({"session": "x", "obs": [1.0, 2.0]}).encode())
        bodies = {}
        for backend in ("threaded", "evloop"):
            fe = ServeFrontend(StubBackend(), MetricsRegistry(),
                               wire_backend=backend).start()
            try:
                sock = socket.create_connection((fe.host, fe.port),
                                                timeout=30.0)
                sock.settimeout(30.0)
                parser = proto.ResponseParser()

                def roundtrip() -> proto.Response:
                    sock.sendall(payload)
                    resps: list = []
                    while not resps:
                        data = sock.recv(1 << 16)
                        if not data:
                            break
                        resps.extend(parser.feed(data))
                    return resps[0]

                # One served request FIRST: the connection is then
                # accepted and keep-alive before the listener closes.
                assert roundtrip().status == wire.STATUS_OK
                assert fe.drain(timeout_s=5.0)
                resp = roundtrip()
                sock.close()
                bodies[backend] = (resp.status, resp.body)
            finally:
                fe.stop()
        assert bodies["threaded"] == bodies["evloop"]
        assert bodies["evloop"][0] == wire.STATUS_UNAVAILABLE


class TestEvloopLint:
    def test_lint_evloop_sansio_semantics(self, tmp_path):
        import lint_hot_loop
        pkg = tmp_path / "pkg"
        (pkg / "fleet").mkdir(parents=True)
        (pkg / "fleet" / "evloop.py").write_text(
            "import socket, threading, time\n"
            "def bad(s):\n"
            "    s.sendall(b'x')\n"
            "    time.sleep(1)\n"
            "def ok(s):\n"
            "    # evloop-block-ok: test probe\n"
            "    s.sendall(b'x')\n"
            "    t = threading.Thread()  # evloop-block-ok: runner\n")
        (pkg / "fleet" / "proto.py").write_text(
            "import socket\n"
            "from selectors import DefaultSelector\n")
        block, imports = lint_hot_loop.lint_evloop_sansio(root=pkg)
        assert [(r, ln) for r, ln, _ in block] \
            == [("fleet/evloop.py", 3), ("fleet/evloop.py", 4)]
        assert [(r, ln) for r, ln, _ in imports] \
            == [("fleet/proto.py", 1), ("fleet/proto.py", 2)]
        # The real tree is clean (the repo-level invariant).
        real_block, real_imports = lint_hot_loop.lint_evloop_sansio()
        assert real_block == [] and real_imports == []


class TestSpanEmissionLint:
    def test_lint_span_emission_semantics(self, tmp_path):
        import lint_hot_loop
        pkg = tmp_path / "pkg"
        (pkg / "fleet").mkdir(parents=True)
        (pkg / "fleet" / "evloop.py").write_text(
            "import json\n"
            "from collections import deque\n"
            "def emit(tctx, out):\n"
            "    line = json.dumps({'span': tctx})\n"   # per-event dumps
            "    out.append(line)\n"
            "def build():\n"
            "    span_buf = []\n"                       # unbounded list
            "    trace_ring = deque()\n"                # maxlen-less
            "    other_ring = deque()\n"                # not span-named
            "    # trace-buffer-ok: drained every flush\n"
            "    span_ok = []\n"                        # marker-exempt
            "    spans2 = deque([], 128)\n"             # bounded
            "    return span_buf, trace_ring, span_ok, spans2\n")
        (pkg / "fleet" / "router.py").write_text(
            "import json\n"
            "def fine(status):\n"
            "    return json.dumps({'gauges': status})\n")  # no span ctx
        hits = lint_hot_loop.lint_span_emission(root=pkg)
        assert [(rel, ln) for rel, ln, _ in hits] == [
            ("fleet/evloop.py", 4), ("fleet/evloop.py", 7),
            ("fleet/evloop.py", 8)]
        # The real tree is clean (the repo-level invariant).
        assert lint_hot_loop.lint_span_emission() == []


class TestProfilerAnnotationLint:
    def test_lint_profiler_annotations_semantics(self, tmp_path):
        import lint_hot_loop
        pkg = tmp_path / "pkg"
        (pkg / "obs").mkdir(parents=True)
        (pkg / "serve").mkdir()
        (pkg / "obs" / "trace.py").write_text(       # the one module
            "from jax.profiler import TraceAnnotation\n"
            "def span(name, **ids):\n"
            "    return TraceAnnotation(name, **ids)\n")
        (pkg / "serve" / "engine.py").write_text(
            "import jax\n"
            "from jax.profiler import TraceAnnotation\n"   # import: fine
            "def per_request(req):\n"
            "    with jax.profiler.TraceAnnotation('req'):\n"    # line 4
            "        pass\n"
            "    with TraceAnnotation(f'req_{req}'):\n"          # line 6
            "        pass\n"
            "    # trace-annotation-ok: once, at start-up\n"
            "    with TraceAnnotation('boot'):\n"          # marker-exempt
            "        pass\n"
            "    return 'TraceAnnotation(x)'\n")           # prose: fine
        hits = lint_hot_loop.lint_profiler_annotations(root=pkg)
        assert [(rel, ln) for rel, ln, _ in hits] == [
            ("serve/engine.py", 4), ("serve/engine.py", 6)]
        # The real tree is clean (the repo-level invariant).
        assert lint_hot_loop.lint_profiler_annotations() == []


# ---- the native wire backend (ISSUE 19) ----------------------------


needs_native = pytest.mark.skipif(
    not proto.native_available(),
    reason="native wire extension not built (make -C native)")


def _drive_chunks(parser_factory, chunks, key):
    """Feed ``chunks`` into a fresh parser; returns (event keys before
    any error, (status, detail) of the ProtocolError or None). Events
    completed in the same feed() call as an error are discarded by
    BOTH implementations — the driver mirrors that by catching per
    call."""
    p = parser_factory()
    events, err = [], None
    for chunk in chunks:
        try:
            events.extend(p.feed(chunk))
        except proto.ProtocolError as exc:
            err = (exc.status, exc.detail)
            break
    return [key(ev) for ev in events], err


def _random_splits(rng, blob, n_cuts):
    cuts = sorted(rng.sample(range(1, len(blob)), min(n_cuts,
                                                      len(blob) - 1)))
    chunks, prev = [], 0
    for cut in cuts + [len(blob)]:
        chunks.append(blob[prev:cut])
        prev = cut
    return chunks


def _fuzz_request_corpus(rng) -> list[bytes]:
    """Valid, malformed, oversized, and trace-header request blobs —
    the satellite's four corpus classes, seeded."""
    blobs = []
    methods = ["GET", "POST", "PUT", "PATCH"]
    for _ in range(30):
        n = rng.randrange(1, 4)     # pipelined burst of n messages
        parts = []
        for _ in range(n):
            body = bytes(rng.randrange(256)
                         for _ in range(rng.randrange(0, 64)))
            headers = {}
            if rng.random() < 0.5:
                headers[proto.TRACE_HEADER] = rng.choice(
                    ["ab12cd34ef56ab78", "DEADbeef", "1a2f.3c",
                     "not~a~trace", "z" * 70])
            if rng.random() < 0.3:
                headers[proto.PARENT_HEADER] = rng.choice(
                    ["1f.2", "zz", "a" * 65])
            if rng.random() < 0.3:
                headers["X-Deadline-Ms"] = str(rng.randrange(1, 5000))
            if rng.random() < 0.2:
                headers["Connection"] = rng.choice(
                    ["close", "keep-alive", "Keep-Alive", "CLOSE"])
            parts.append(proto.py_render_request(
                rng.choice(methods), f"/p/{rng.randrange(100)}",
                "h:1", body, headers=headers or None))
        blobs.append(b"".join(parts))
    # hand-built heads: HTTP/1.0 folding, duplicate headers
    # (last-wins), padded values, underscored and signed
    # Content-Lengths, a µ header name (lowers OUTSIDE latin-1)
    blobs += [
        b"GET / HTTP/1.0\r\nHost: h\r\n\r\n",
        b"GET / HTTP/1.0\r\nConnection: Keep-Alive\r\n\r\n",
        b"POST /d HTTP/1.1\r\nX-N: 1\r\nX-N: 2\r\n"
        b"Content-Length: 2\r\n\r\nhi",
        b"POST /d HTTP/1.1\r\nContent-Length:   2  \r\n\r\nhi",
        b"POST /d HTTP/1.1\r\nContent-Length: +1_0\r\n\r\n" + b"a" * 10,
        b"GET /u HTTP/1.1\r\n\xb5Name: micro\r\nX-\xc0: caps\r\n\r\n",
    ]
    # malformed: bad request lines, versions, header lines, lengths
    blobs += [
        b"GARBAGE\r\n\r\n",
        b"ONE TWO THREE FOUR\r\n\r\n",
        b"GET /x HTTP/2\r\n\r\n",
        b"GET /x HTTP/1.1\r\nNoColonHere\r\n\r\n",
        b"GET /x HTTP/1.1\r\n  : empty-name\r\n\r\n",
        b"GET /x HTTP/1.1\r\nContent-Length: xyz\r\n\r\n",
        b"GET /x HTTP/1.1\r\nContent-Length: -5\r\n\r\n",
        b"GET /x HTTP/1.1\r\nContent-Length: 1__0\r\n\r\n",
        b"GET /x HTTP/1.1\r\nContent-Length: 5_\r\n\r\n",
        b"GET /x HTTP/1.1\r\nContent-Length: \xa07\r\n\r\n",
        (f"POST /x HTTP/1.1\r\nContent-Length: "
         f"{proto.MAX_BODY_BYTES + 1}\r\n\r\n").encode(),
        b"GET /x HTTP/1.1\r\nX: " + b"a" * (proto.MAX_HEAD_BYTES + 8),
        b"\r\nGET / HTTP/1.1\r\n\r\n",
    ]
    # mutations: valid frames with one random head byte flipped
    for _ in range(40):
        raw = bytearray(proto.py_render_request(
            rng.choice(methods), "/m", "h:1", b"xyz",
            headers={"X-K": "v"}))
        pos = rng.randrange(0, min(len(raw), 40))
        raw[pos] = rng.randrange(256)
        blobs.append(bytes(raw))
    return blobs


def _fuzz_response_corpus(rng) -> list[bytes]:
    blobs = []
    for _ in range(20):
        n = rng.randrange(1, 4)
        parts = []
        for _ in range(n):
            body = bytes(rng.randrange(256)
                         for _ in range(rng.randrange(0, 64)))
            parts.append(proto.py_render_response(
                rng.choice([200, 400, 404, 429, 500, 503, 504, 299]),
                body,
                keep_alive=rng.random() < 0.8,
                extra_headers=({"X-Probe": str(rng.randrange(10))}
                               if rng.random() < 0.4 else None)))
        blobs.append(b"".join(parts))
    blobs += [
        b"HTTP/1.1 200 OK\r\n\r\n",                 # no Content-Length
        b"NOPE 200 OK\r\nContent-Length: 0\r\n\r\n",
        b"HTTP/1.1 2x0 OK\r\nContent-Length: 0\r\n\r\n",
        b"HTTP/1.1\r\nContent-Length: 0\r\n\r\n",
        b"HTTP/1.1 2_0 OK\r\nContent-Length: 0\r\n\r\n",
        b"HTTP/1.1 -1 Odd\r\nContent-Length: 0\r\n\r\n",
        b"HTTP/1.1 200 OK with spaced reason\r\nContent-Length: 0\r\n\r\n",
        b"HTTP/1.1 200 OK\r\nContent-Length: bad\r\n\r\n",
    ]
    for _ in range(30):
        raw = bytearray(proto.py_render_response(200, b"body"))
        pos = rng.randrange(0, min(len(raw), 30))
        raw[pos] = rng.randrange(256)
        blobs.append(bytes(raw))
    return blobs


@needs_native
class TestNativeDifferentialFuzz:
    """Satellite 2: seeded random byte-split + pipelined burst corpora
    through BOTH parsers — event streams exactly equal, ProtocolError
    status AND detail exactly equal."""

    def _native(self):
        return proto._NATIVE      # skipif guarantees it loaded

    def test_request_parsers_agree_on_fuzzed_streams(self):
        import random
        rng = random.Random(0x57_17e)
        stw = self._native()
        for blob in _fuzz_request_corpus(rng):
            for _ in range(4):
                chunks = _random_splits(rng, blob, rng.randrange(0, 9))
                got_py = _drive_chunks(proto.PyRequestParser, chunks,
                                       _req_key)
                got_c = _drive_chunks(stw.RequestParser, chunks,
                                      _req_key)
                assert got_c == got_py, blob

    def test_response_parsers_agree_on_fuzzed_streams(self):
        import random
        rng = random.Random(0xbeef)
        stw = self._native()
        for blob in _fuzz_response_corpus(rng):
            for _ in range(4):
                chunks = _random_splits(rng, blob, rng.randrange(0, 9))
                got_py = _drive_chunks(proto.PyResponseParser, chunks,
                                       _resp_key)
                got_c = _drive_chunks(stw.ResponseParser, chunks,
                                      _resp_key)
                assert got_c == got_py, blob

    def test_renderers_agree_byte_for_byte(self):
        import random
        rng = random.Random(0x12e7de2)
        stw = self._native()
        for _ in range(60):
            method = rng.choice(["GET", "POST", "DELETE"])
            target = f"/t/{rng.randrange(1000)}"
            body = bytes(rng.randrange(256)
                         for _ in range(rng.randrange(0, 50)))
            headers = ({f"X-H{rng.randrange(5)}": f"v{rng.randrange(9)}",
                        "X-Trace-Id": "ab12"}
                       if rng.random() < 0.7 else None)
            assert stw.render_request(method, target, "h:1", body,
                                      headers=headers) \
                == proto.py_render_request(method, target, "h:1", body,
                                           headers=headers)
        for _ in range(60):
            status = rng.choice([200, 400, 404, 429, 500, 503, 504,
                                 299, 101])
            body = bytes(rng.randrange(256)
                         for _ in range(rng.randrange(0, 50)))
            ct = rng.choice(["application/json",
                             "text/plain; version=0.0.4"])
            ka = rng.random() < 0.7
            extra = ({"X-Probe": "1"} if rng.random() < 0.4 else None)
            assert stw.render_response(status, body, ct,
                                       keep_alive=ka,
                                       extra_headers=extra) \
                == proto.py_render_response(status, body, ct,
                                            keep_alive=ka,
                                            extra_headers=extra)

    def test_empty_headers_dict_and_bytearray_feed(self):
        stw = self._native()
        assert stw.render_request("GET", "/", "h:1", b"", headers={}) \
            == proto.py_render_request("GET", "/", "h:1", b"",
                                       headers={})
        raw = bytearray(proto.py_render_request("GET", "/", "h:1"))
        assert len(stw.RequestParser().feed(raw)) == 1


class TestNativeBackendDispatch:
    """Satellite 1: the proto_backend seam — native default when
    built, loud Python fallback when not, live-backend gauge."""

    def _pin(self, monkeypatch):
        # set_backend rebinds module globals outside monkeypatch's
        # sight; no-op patches record the originals for teardown.
        for name in ("RequestParser", "ResponseParser",
                     "render_request", "render_response",
                     "proto_backend", "_NATIVE", "_NATIVE_ERROR",
                     "_FALLBACK_LOGGED"):
            monkeypatch.setattr(proto, name, getattr(proto, name))

    @needs_native
    def test_native_is_the_default_when_built(self):
        assert proto.proto_backend == "native"
        assert proto.RequestParser is proto._NATIVE.RequestParser
        assert proto.render_response is proto._NATIVE.render_response
        assert proto.native_load_error() == ""

    def test_set_backend_py_and_back(self, monkeypatch):
        self._pin(monkeypatch)
        assert proto.set_backend("py") == "py"
        assert proto.proto_backend == "py"
        assert proto.RequestParser is proto.PyRequestParser
        assert proto.render_request is proto.py_render_request

    def test_unknown_backend_refused(self):
        with pytest.raises(ValueError, match="proto_backend"):
            proto.set_backend("carrier")

    def test_missing_extension_degrades_loudly_once(self, monkeypatch):
        import logging
        self._pin(monkeypatch)
        monkeypatch.setattr(proto, "_NATIVE", None)
        monkeypatch.setattr(proto, "_NATIVE_ERROR", "forced by test")
        monkeypatch.setattr(proto, "_FALLBACK_LOGGED", False)
        # The repo's "sharetrade" root logger is propagate=False, so
        # caplog's root handler never sees it — attach directly.
        records: list[logging.LogRecord] = []

        class _Sink(logging.Handler):
            def emit(self, record):
                records.append(record)

        logger = logging.getLogger("sharetrade.fleet.proto")
        sink = _Sink(level=logging.WARNING)
        logger.addHandler(sink)
        try:
            assert proto.set_backend("native") == "py"
            assert proto.proto_backend == "py"
            assert proto.RequestParser is proto.PyRequestParser
            assert proto.native_available() is False
            assert proto.native_load_error() == "forced by test"
            assert len(records) == 1
            msg = records[0].getMessage()
            assert "falling back" in msg
            assert "forced by test" in msg
            # ONE loud line per process, not one per request/frontend.
            assert proto.set_backend("native") == "py"
            assert len(records) == 1
        finally:
            logger.removeHandler(sink)

    @pytest.mark.parametrize("backend", ["threaded", "evloop"])
    def test_live_backend_gauge_recorded(self, backend):
        reg = MetricsRegistry()
        fe = ServeFrontend(StubBackend(), reg,
                           wire_backend=backend).start()
        try:
            want = 1.0 if proto.proto_backend == "native" else 0.0
            assert reg.latest("fleet_proto_backend_native") == want
        finally:
            fe.stop()

    @needs_native
    def test_evloop_py_and_native_answer_byte_identically(self,
                                                          monkeypatch):
        self._pin(monkeypatch)
        payload, n = _scripted_stream()
        streams = {}
        for pb in ("py", "native"):
            proto.set_backend(pb)
            fe = ServeFrontend(StubBackend(), MetricsRegistry(),
                               wire_backend="evloop").start()
            try:
                streams[pb] = _drive(fe.host, fe.port, payload, n)
            finally:
                fe.stop()
        proto.set_backend("native")
        assert streams["py"] == streams["native"]


class TestEvloopInternalsMetrics:
    """Satellite 3: the selector thread's internals land in the shared
    registry (→ /metrics and fleet_status.json)."""

    def test_open_conns_gauge_tracks_the_connection(self):
        import time
        reg = MetricsRegistry()
        fe = ServeFrontend(StubBackend(), reg,
                           wire_backend="evloop").start()
        try:
            assert reg.latest("fleet_evloop_open_conns") == 0.0
            payload, n = _scripted_stream()
            _drive(fe.host, fe.port, payload, n)
            deadline = time.monotonic() + 5.0
            while (reg.latest("fleet_evloop_open_conns") != 0.0
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            # it went up on accept and back to zero on close
            series = [v for _, v in
                      (reg.snapshot_series("fleet_evloop_open_conns")
                       if hasattr(reg, "snapshot_series") else [])]
            assert reg.latest("fleet_evloop_open_conns") == 0.0
        finally:
            fe.stop()

    def test_deadline_expiry_counter_fires_on_engine_timeout(self):
        class WedgedBackend(StubBackend):
            request_timeout_s = 0.05

            def submit_async(self, session, obs, deadline_ms, done):
                class Handle:
                    result = None
                    error = None
                return Handle()     # never signals: the wheel must fire

        reg = MetricsRegistry()
        fe = ServeFrontend(WedgedBackend(), reg,
                           wire_backend="evloop").start()
        try:
            body = json.dumps({"session": "w", "obs": [1.0]}).encode()
            raw = _drive(fe.host, fe.port,
                         proto.render_request("POST", wire.SUBMIT_PATH,
                                              "h:1", body), 1)
            resp = proto.ResponseParser().feed(raw)[0]
            assert resp.status == wire.STATUS_UNAVAILABLE
            assert reg.counters().get(
                "fleet_evloop_deadline_expiries_total") == 1.0
        finally:
            fe.stop()


class TestNativeWireLint:
    def test_lint_native_wire_semantics(self, tmp_path):
        import lint_hot_loop
        pkg = tmp_path / "pkg"
        (pkg / "fleet").mkdir(parents=True)
        (pkg / "fleet" / "proto.py").write_text(
            "import stwire\n")      # the ONE sanctioned seam: exempt
        (pkg / "fleet" / "evloop.py").write_text(
            "import stwire\n"
            "def load(path):\n"
            "    from importlib.machinery import ExtensionFileLoader\n"
            "    # native-wire-ok: test probe\n"
            "    import stwire as sw\n"
            "    return sw\n"
            "# stwire in a comment is prose, not a binding\n")
        wire_cc = tmp_path / "wire.cc"
        wire_cc.write_text(
            "// Py_BEGIN_ALLOW_THREADS in prose does not count\n"
            "static int core() {\n"
            "  Py_BEGIN_ALLOW_THREADS\n"
            "  Py_END_ALLOW_THREADS\n"
            "  return 0;\n"
            "}\n")
        binding, gil, imports = lint_hot_loop.lint_native_wire(
            root=pkg, wire_cc=wire_cc)
        assert [(r, ln) for r, ln, _ in binding] \
            == [("fleet/evloop.py", 1), ("fleet/evloop.py", 3)]
        assert gil == [] and imports == []
        # no GIL release at all
        wire_cc.write_text("static int core() { return 0; }\n")
        _, gil, _ = lint_hot_loop.lint_native_wire(root=pkg,
                                                   wire_cc=wire_cc)
        assert len(gil) == 1 and "Py_BEGIN_ALLOW_THREADS" in gil[0][2]
        # unbalanced pairing
        wire_cc.write_text("Py_BEGIN_ALLOW_THREADS\n"
                           "Py_BEGIN_ALLOW_THREADS\n"
                           "Py_END_ALLOW_THREADS\n")
        _, gil, _ = lint_hot_loop.lint_native_wire(root=pkg,
                                                   wire_cc=wire_cc)
        assert len(gil) == 1 and "unbalanced" in gil[0][2]
        # missing wire.cc is itself a failure
        _, gil, _ = lint_hot_loop.lint_native_wire(
            root=pkg, wire_cc=tmp_path / "absent.cc")
        assert len(gil) == 1 and "missing" in gil[0][2]
        # The real tree is clean (the repo-level invariant).
        rb, rg, ri = lint_hot_loop.lint_native_wire()
        assert rb == [] and rg == [] and ri == []
