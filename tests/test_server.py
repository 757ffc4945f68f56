"""Network front door (repro.serving.server): SSE/JSON-lines streaming,
backpressure, tenant quotas, wire-level cancel, graceful drain.

The contract that makes the server safe to put in front of the engine:

  1. the SSE delta stream is byte-identical to ``RequestHandle.stream()``
     on a twin engine — same chunk boundaries, same tokens, same final
     payload — and the JSON-lines framing carries the same events;
  2. a slow consumer is disconnected once it falls a full buffer behind
     (bounded memory) and its request is cancelled engine-side; other
     connections are unaffected;
  3. per-tenant quotas reject excess in-flight submissions at the door
     with a typed event + retry hint — they never reach the scheduler;
  4. graceful shutdown drains over the wire: residents stream to a
     token-identical finish, queued requests get terminal ``shed`` events
     with retry metadata, and new connections get 503 + retry hint.
"""

import json
import socket
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.mt import tiny_config
from repro.data import SyntheticReactionDataset
from repro.models import seq2seq as s2s
from repro.serving import (EngineConfig, FrontDoorServer, RequestStatus,
                           ServerConfig, StreamingEngine)
from repro.serving.server import sse_events

MAX_NEW = 64


@pytest.fixture(scope="module")
def toy():
    ds = SyntheticReactionDataset(16, seed=0)
    cfg = tiny_config(ds.tokenizer.vocab_size, depth=2, d_model=64,
                      max_len=192)
    params = s2s.init(jax.random.PRNGKey(0), cfg)
    return ds, cfg, params


def _engine(toy, **kw):
    ds, cfg, params = toy
    base = dict(mode="greedy", max_new=MAX_NEW, max_src=96, n_slots=1)
    base.update(kw)
    eng = StreamingEngine(params, cfg, ds.tokenizer, EngineConfig(**base))
    # compile step + admit before the server owns the pump, so wire tests
    # never race a tracing stall
    eng.submit(ds.pair(0)[0])
    eng.serve()
    eng.reset()
    return eng


@pytest.fixture
def served(toy):
    """A started server over a warmed 1-slot engine; stopped on teardown."""
    eng = _engine(toy)
    srv = FrontDoorServer(eng, ServerConfig(realtime=False)).start()
    yield eng, srv
    srv.shutdown(drain=False)


class SSEClient:
    """Incremental SSE reader: exposes events one at a time so tests can
    act (cancel, shut down, open rival connections) mid-stream."""

    def __init__(self, host, port, payload, timeout=60.0):
        body = json.dumps(payload).encode()
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.sendall(
            f"POST /v1/generate HTTP/1.1\r\nHost: {host}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        self.buf = b""
        while b"\r\n\r\n" not in self.buf:
            self.buf += self.sock.recv(65536)
        head, _, self.buf = self.buf.partition(b"\r\n\r\n")
        self.status = int(head.split(b" ", 2)[1])

    def next_event(self):
        while b"\n\n" not in self.buf:
            chunk = self.sock.recv(65536)
            if not chunk:
                return None
            self.buf += chunk
        frame, self.buf = self.buf.split(b"\n\n", 1)
        assert frame.startswith(b"data: ")
        return json.loads(frame[len(b"data: "):])

    def drain(self):
        out = []
        while (ev := self.next_event()) is not None:
            out.append(ev)
        self.sock.close()
        return out


def _deltas(events):
    return [ev["tokens"] for ev in events if ev["event"] == "delta"]


# ---------------------------------------------------------------------------
# 1. wire identity


def test_sse_stream_byte_identical_to_handle_stream(toy, served):
    """End to end: the SSE event stream's deltas equal a twin engine's
    ``RequestHandle.stream()`` chunk for chunk, and the final payload
    equals its ``result()``."""
    ds, _, _ = toy
    eng, srv = served
    query = ds.pair(3)[0]
    events = sse_events("127.0.0.1", srv.port, {"query": query})
    assert [e["event"] for e in events[:1]] == ["accepted"]
    done = events[-1]
    assert done["event"] == "done" and done["status"] == "finished"

    twin = _engine(toy)
    h = twin.submit(query)
    chunks = [[int(x) for x in d] for d in h.stream()]
    r = twin._done[int(h)]
    assert _deltas(events) == chunks, "delta chunking must match exactly"
    assert done["tokens"] == [[int(x) for x in row[:int(n)]]
                              for row, n in zip(r.tokens, r.lengths)]
    assert done["lengths"] == [int(n) for n in r.lengths]
    assert done["text"] == ds.tokenizer.decode(np.asarray(r.tokens[0]))


def test_ndjson_framing_carries_same_events(toy, served):
    ds, _, _ = toy
    eng, srv = served
    query = ds.pair(4)[0]
    sse = sse_events("127.0.0.1", srv.port, {"query": query})

    body = json.dumps({"op": "generate", "query": query}).encode() + b"\n"
    with socket.create_connection(("127.0.0.1", srv.port), timeout=60) as s:
        s.sendall(body)
        buf = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    nd = [json.loads(line) for line in buf.splitlines() if line]
    # same event sequence modulo rid (fresh request id per submission)
    strip = lambda evs: [{k: v for k, v in e.items() if k != "rid"}
                         for e in evs]
    assert strip(nd) == strip(sse)


def test_bad_request_and_unknown_route(served):
    _, srv = served
    events = sse_events("127.0.0.1", srv.port, {"mode": "greedy"})  # no query
    assert events == [ev for ev in events if ev["event"] == "rejected"]
    assert events[0]["error"] == "bad_request"

    with socket.create_connection(("127.0.0.1", srv.port), timeout=10) as s:
        s.sendall(b"GET /nope HTTP/1.1\r\nHost: x\r\n\r\n")
        buf = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    assert buf.startswith(b"HTTP/1.1 404")


def test_request_the_engine_refuses_is_rejected_not_fatal(toy, served):
    ds, _, _ = toy
    _, srv = served
    events = sse_events("127.0.0.1", srv.port,
                        {"query": ds.pair(1)[0], "mode": "beam"})
    assert [ev["event"] for ev in events] == ["rejected"]
    assert events[0]["error"] == "bad_request"
    # the drive survived: the next request still finishes
    events = sse_events("127.0.0.1", srv.port, {"query": ds.pair(1)[0]})
    assert events[-1]["status"] == "finished"
    assert srv.error is None


def test_drive_failure_fails_streams_and_shutdown_reraises(toy):
    ds, _, _ = toy
    eng = _engine(toy)

    def boom(*, realtime=False):
        raise RuntimeError("megastep failed to compile")

    eng.serve_steps = boom
    srv = FrontDoorServer(eng, ServerConfig(realtime=False)).start()
    try:
        events = sse_events("127.0.0.1", srv.port, {"query": ds.pair(1)[0]})
        assert [ev["event"] for ev in events] == ["accepted", "done"]
        assert events[-1]["status"] == "failed"
        assert "megastep failed to compile" in events[-1]["error"]
        later = sse_events("127.0.0.1", srv.port, {"query": ds.pair(2)[0]})
        assert [ev["event"] for ev in later] == ["rejected"]
        assert later[0]["error"] == "failed"
        assert isinstance(srv.error, RuntimeError)
    finally:
        with pytest.raises(RuntimeError, match="megastep failed to compile"):
            srv.shutdown(drain=True)


# ---------------------------------------------------------------------------
# 2. wire-level cancel


def test_cancel_over_the_wire(toy, served):
    ds, _, _ = toy
    eng, srv = served
    c = SSEClient("127.0.0.1", srv.port, {"query": ds.pair(5)[0]})
    accepted = c.next_event()
    assert accepted["event"] == "accepted"
    rid = accepted["rid"]

    body = json.dumps({"rid": rid}).encode()
    with socket.create_connection(("127.0.0.1", srv.port), timeout=10) as s:
        s.sendall(f"POST /v1/cancel HTTP/1.1\r\nHost: x\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        s.recv(65536)
    rest = c.drain()
    assert rest[-1]["event"] == "done"
    assert rest[-1]["status"] == "cancelled"
    assert eng._done[rid].status == RequestStatus.CANCELLED


# ---------------------------------------------------------------------------
# 3. backpressure: the slow consumer is the one who pays


def test_slow_consumer_disconnected_and_cancelled(toy):
    """writer_delay_s throttles delivery far below the decode rate with a
    2-event buffer: the server must disconnect the consumer, count it,
    and cancel the request engine-side instead of buffering forever."""
    ds, _, _ = toy
    eng = _engine(toy)
    srv = FrontDoorServer(eng, ServerConfig(
        realtime=False, max_buffered_events=2, writer_delay_s=0.2)).start()
    try:
        c = SSEClient("127.0.0.1", srv.port, {"query": ds.pair(6)[0]})
        first = c.next_event()
        assert first["event"] == "accepted"
        rid = first["rid"]
        c.drain()                       # server closes on overflow
        deadline = time.monotonic() + 30.0
        while srv.n_slow_disconnects == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.n_slow_disconnects == 1
        while rid not in eng._done and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng._done[rid].status == RequestStatus.CANCELLED
    finally:
        srv.shutdown(drain=False)


# ---------------------------------------------------------------------------
# 4. per-tenant quotas


def test_tenant_quota_rejects_at_the_door(toy):
    ds, _, _ = toy
    eng = _engine(toy)
    srv = FrontDoorServer(eng, ServerConfig(
        realtime=False, tenant_quota={"acme": 1},
        quota_retry_after=7.5)).start()
    try:
        a = SSEClient("127.0.0.1", srv.port,
                      {"query": ds.pair(1)[0], "tenant": "acme"})
        assert a.next_event()["event"] == "accepted"   # acme is at cap
        rej = sse_events("127.0.0.1", srv.port,
                         {"query": ds.pair(2)[0], "tenant": "acme"})
        assert rej == [{"event": "rejected", "error": "quota",
                        "tenant": "acme", "retry_after": 7.5}]
        assert srv.n_quota_rejected == 1
        # a different tenant is not throttled by acme's cap
        other = sse_events("127.0.0.1", srv.port,
                           {"query": ds.pair(2)[0], "tenant": "zen"})
        assert other[-1]["status"] == "finished"
        # terminal delivery releases the quota slot
        assert a.drain()[-1]["event"] == "done"
        again = sse_events("127.0.0.1", srv.port,
                           {"query": ds.pair(2)[0], "tenant": "acme"})
        assert again[-1]["status"] == "finished"
    finally:
        srv.shutdown(drain=False)


# ---------------------------------------------------------------------------
# 4b. per-tenant token-bucket rate limits


def test_tenant_rate_limit_rejects_with_refill_retry_after(toy):
    """The token bucket caps arrival RATE (the quota caps concurrency):
    with rate=0.5/s and burst=1, the first submission passes, the second
    is rejected with ``retry_after`` equal to the bucket's actual refill
    time, and advancing the (injected) clock past the refill admits
    again. A tenant without a configured rate is untouched."""
    ds, _, _ = toy
    eng = _engine(toy)
    srv = FrontDoorServer(eng, ServerConfig(
        realtime=False, tenant_rate={"acme": 0.5},
        tenant_burst={"acme": 1})).start()
    clk = {"t": 0.0}
    srv._bucket_clock = lambda: clk["t"]
    q = ds.pair(1)[0]
    try:
        first = sse_events("127.0.0.1", srv.port,
                           {"query": q, "tenant": "acme"})
        assert first[-1]["status"] == "finished"

        rej = sse_events("127.0.0.1", srv.port,
                         {"query": q, "tenant": "acme"})
        assert rej == [{"event": "rejected", "error": "rate",
                        "tenant": "acme", "retry_after": 2.0}]
        assert srv.n_rate_limited == 1

        # an unconfigured tenant is not throttled by acme's bucket
        zen = sse_events("127.0.0.1", srv.port,
                         {"query": q, "tenant": "zen"})
        assert zen[-1]["status"] == "finished"

        clk["t"] = 2.0          # exactly the advertised refill
        again = sse_events("127.0.0.1", srv.port,
                           {"query": q, "tenant": "acme"})
        assert again[-1]["status"] == "finished"
    finally:
        srv.shutdown(drain=False)


def test_rate_limit_burst_passes_at_line_rate(toy):
    """A burst-sized volley is admitted before the limiter bites, and the
    rejection's retry_after reflects the partially-refilled bucket."""
    ds, _, _ = toy
    eng = _engine(toy)
    srv = FrontDoorServer(eng, ServerConfig(
        realtime=False, tenant_rate=2.0, tenant_burst=3.0)).start()
    clk = {"t": 0.0}
    srv._bucket_clock = lambda: clk["t"]
    q = ds.pair(2)[0]
    try:
        for _ in range(3):
            evs = sse_events("127.0.0.1", srv.port,
                             {"query": q, "tenant": "burst"})
            assert evs[-1]["status"] == "finished"
        rej = sse_events("127.0.0.1", srv.port,
                         {"query": q, "tenant": "burst"})
        assert rej[0]["error"] == "rate"
        assert rej[0]["retry_after"] == 0.5      # (1 - 0) / rate
    finally:
        srv.shutdown(drain=False)


# ---------------------------------------------------------------------------
# 4c. /v1/stats: the replica surface the fleet router consumes


def test_stats_expose_engine_load_shape_and_shard_prefix_counters(toy):
    """``/v1/stats`` must carry the placement signals (occupancy,
    shed_rate, n_slots, accepting/draining) plus the engine's
    ``shard_stats()`` / ``prefix_stats()`` / overload counters — the
    exact surface ``repro.serving.fleet`` probes."""
    ds, _, _ = toy
    eng = _engine(toy)
    srv = FrontDoorServer(eng, ServerConfig(realtime=False)).start()
    try:
        done = sse_events("127.0.0.1", srv.port, {"query": ds.pair(4)[0]})
        assert done[-1]["status"] == "finished"
        with socket.create_connection(("127.0.0.1", srv.port),
                                      timeout=10) as s:
            s.sendall(json.dumps({"op": "stats"}).encode() + b"\n")
            buf = b""
            while not buf.endswith(b"\n"):
                chunk = s.recv(65536)
                if not chunk:
                    break
                buf += chunk
        stats = json.loads(buf)
        assert stats["accepted"] == 1 and stats["accepting"] is True
        assert stats["n_slots"] == 1 and stats["resident"] == 0
        assert stats["occupancy"] == 0.0 and stats["shed_rate"] == 0.0
        # one slot, resident through every step that served the request
        assert stats["slot_steps"] == stats["capacity_steps"] > 0
        assert stats["slot_occupancy"] == 1.0
        assert stats["rate_limited"] == 0
        assert isinstance(stats["shard_stats"], (list, dict))
        assert isinstance(stats["prefix_stats"], dict)
        ov = stats["overload"]
        for key in ("n_preemptions", "n_expired", "n_shed",
                    "max_resident", "aging_rate", "shed_depth",
                    "deadline_preemption"):
            assert key in ov
    finally:
        srv.shutdown(drain=False)


# ---------------------------------------------------------------------------
# 5. graceful drain over the wire


def test_graceful_drain_over_the_wire(toy):
    """One slot: A resident (mid-stream), B queued. shutdown(drain=True)
    must finish A token-identically, shed B with retry metadata, and 503
    new connections — all observable from the clients' side of the wire."""
    ds, _, _ = toy
    eng = _engine(toy)
    srv = FrontDoorServer(eng, ServerConfig(realtime=False)).start()
    qa, qb = ds.pair(7)[0], ds.pair(8)[0]
    try:
        a = SSEClient("127.0.0.1", srv.port, {"query": qa})
        assert a.next_event()["event"] == "accepted"
        assert a.next_event()["event"] == "delta"      # A is mid-stream
        b = SSEClient("127.0.0.1", srv.port, {"query": qb})
        assert b.next_event()["event"] == "accepted"   # B queued (1 slot)

        stopper = threading.Thread(target=srv.shutdown,
                                   kwargs={"drain": True})
        stopper.start()
        deadline = time.monotonic() + 10.0
        while srv._accepting and time.monotonic() < deadline:
            time.sleep(0.005)
        refused = sse_events("127.0.0.1", srv.port, {"query": qa})
        assert refused[0]["error"] == "draining"
        assert refused[0]["retry_after"] > 0

        b_done = b.drain()[-1]
        assert b_done["event"] == "done" and b_done["status"] == "shed"
        assert b_done["retry_after"] > 0

        a_events = a.drain()
        a_done = a_events[-1]
        assert a_done["status"] == "finished"
        stopper.join(timeout=30.0)
        assert not stopper.is_alive()

        control = _engine(toy)
        r = control.submit(qa).result()
        assert a_done["tokens"] == [[int(x) for x in row[:int(n)]]
                                    for row, n in zip(r.tokens, r.lengths)]
    finally:
        srv.shutdown(drain=False)


# ---------------------------------------------------------------------------
# 6. HTTP metadata: Retry-After header + server-side default deadline


def test_draining_503_sets_retry_after_header(toy):
    """The draining 503 must carry the retry hint as a standard
    ``Retry-After`` header (delta-seconds, rounded up from the JSON
    body's float) so plain HTTP clients can back off without parsing
    the body."""
    eng = _engine(toy)
    srv = FrontDoorServer(eng, ServerConfig(realtime=False,
                                            drain_retry_after=2.5)).start()
    try:
        srv._accepting = False      # what shutdown(drain=True) flips first
        body = json.dumps({"query": toy[0].pair(0)[0]}).encode()
        with socket.create_connection(("127.0.0.1", srv.port),
                                      timeout=10) as s:
            s.sendall(
                f"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                f"Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
            buf = b""
            while b"\r\n\r\n" not in buf:
                buf += s.recv(65536)
        head = buf.partition(b"\r\n\r\n")[0].decode()
        assert int(head.split(" ", 2)[1]) == 503
        headers = {k.strip().lower(): v.strip() for k, v in
                   (ln.split(":", 1) for ln in head.split("\r\n")[1:]
                    if ":" in ln)}
        assert headers["retry-after"] == "3"
    finally:
        srv.shutdown(drain=False)


def test_default_timeout_stamps_deadline_when_client_sets_none(toy):
    """``ServerConfig.default_timeout_s`` becomes the request deadline
    when the wire request carries no ``timeout``: with a 0-second default
    an untimed request expires at its first scheduling opportunity, while
    an explicit client timeout still overrides the default."""
    ds, _, _ = toy
    eng = _engine(toy)
    srv = FrontDoorServer(eng, ServerConfig(realtime=False,
                                            default_timeout_s=0.0)).start()
    q = ds.pair(3)[0]
    try:
        untimed = SSEClient("127.0.0.1", srv.port, {"query": q}).drain()
        assert untimed[0]["event"] == "accepted"
        assert untimed[-1]["event"] == "done"
        assert untimed[-1]["status"] == "expired"

        timed = SSEClient("127.0.0.1", srv.port,
                          {"query": q, "timeout": 1e9}).drain()
        assert timed[-1]["status"] == "finished"
    finally:
        srv.shutdown(drain=False)
