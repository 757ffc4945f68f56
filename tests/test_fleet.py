"""Fleet layer (repro.serving.fleet): placement policy, the prefix-affine
radix index, and the replica router's failover semantics.

The contracts under test:

  1. placement is least-loaded with prefix affinity on top — load order,
     shed-rate and id tie-breaks, the ``min_affinity`` floor, DOWN /
     DRAINING exclusion — and is a PURE function of the replica views +
     index state (a hypothesis property: identical inputs, in any dict
     order, give identical decisions);
  2. the router is wire-invisible: a client sees the same events, the
     same tokens, and working cancel whether it talks to a replica or to
     the router in front of two of them;
  3. the replica-kill drill: killing a replica mid-run completes every
     request queued on it via reroute to the survivor — token-identical,
     with exactly one ``accepted`` and exactly one terminal event per
     request (zero lost or duplicated acks) — while a request that had
     already streamed deltas terminates with the typed retryable
     ``status="lost"`` instead of silently dropping or duplicating.
"""

import json
import socket
import time

import jax
import pytest

from repro.configs.mt import tiny_config
from repro.data import SyntheticReactionDataset
from repro.models import seq2seq as s2s
from repro.serving import (EngineConfig, FleetConfig, FleetRouter,
                           FrontDoorServer, ServerConfig, StreamingEngine)
from repro.serving.fleet import (PrefixIndex, ReplicaHealth, ReplicaView,
                                 place)
from repro.serving.server import sse_events

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from repro.testing import given, settings, strategies as st

MAX_NEW = 64

H, D, X = ReplicaHealth.HEALTHY, ReplicaHealth.DRAINING, ReplicaHealth.DOWN


def _view(health=H, n_slots=1, occupancy=0.0, shed_rate=0.0, inflight=0):
    return ReplicaView(health=health, n_slots=n_slots, occupancy=occupancy,
                       shed_rate=shed_rate, inflight=inflight)


# ---------------------------------------------------------------------------
# 1. placement policy


def test_least_loaded_wins_and_ties_break_on_shed_then_id():
    idx = PrefixIndex()
    views = {0: _view(occupancy=0.8), 1: _view(occupancy=0.2),
             2: _view(occupancy=0.5)}
    assert place(views, idx, "q") == (1, 0)
    # equal load: the shedding replica loses the tie
    views = {0: _view(occupancy=0.5, shed_rate=0.3),
             1: _view(occupancy=0.5, shed_rate=0.0)}
    assert place(views, idx, "q") == (1, 0)
    # full tie: lowest id (ints order numerically, not lexically)
    views = {i: _view(occupancy=0.5) for i in (10, 2, 0)}
    assert place(views, idx, "q") == (0, 0)


def test_router_inflight_counts_as_load():
    """The probe is stale by up to an interval: the router's own
    bookings must count, else a burst piles onto one replica."""
    idx = PrefixIndex()
    views = {0: _view(occupancy=0.0, inflight=2, n_slots=2),
             1: _view(occupancy=0.4)}
    assert views[0].load == 1.0
    assert place(views, idx, "q") == (1, 0)


def test_prefix_affinity_overrides_load_above_the_floor():
    idx = PrefixIndex()
    idx.insert("CCO>>CC", 0)
    busy = {0: _view(occupancy=0.9), 1: _view(occupancy=0.0)}
    # the owner is the worst-loaded replica, but it holds the pages
    assert place(busy, idx, "CCO>>CCN") == (0, 7)
    # below the min_affinity floor the alias is worthless: spread load
    assert place(busy, idx, "CCO>>CCN", min_affinity=8) == (1, 0)
    # unrelated prompt: least-loaded
    assert place(busy, idx, "NNN") == (1, 0)


def test_down_and_draining_replicas_are_never_placed():
    idx = PrefixIndex()
    idx.insert("abc", 0)
    views = {0: _view(health=X), 1: _view(health=D),
             2: _view(occupancy=0.9)}
    # affinity to a dead owner must not resurrect it
    assert place(views, idx, "abcdef") == (2, 0)
    views = {0: _view(health=X), 1: _view(health=D)}
    assert place(views, idx, "abcdef") == (None, 0)


def test_drop_replica_forgets_its_prefixes():
    idx = PrefixIndex()
    idx.insert("abcdef", 0)
    idx.insert("abcxyz", 1)
    assert idx.lookup("abcdefgh") == (0, 6)
    assert idx.drop_replica(0) == 1
    assert idx.lookup("abcdefgh") == (None, 0)
    assert idx.lookup("abcxyz") == (1, 6)       # survivor untouched


def test_index_is_lru_bounded():
    idx = PrefixIndex(max_nodes=8)
    for i in range(50):
        idx.insert((100 + i, 200 + i, 300 + i), i % 2)
    assert len(idx) <= 8
    assert idx.evicted > 0
    # the most recent insert survives
    assert idx.lookup((149, 249, 349)) == (49 % 2, 3)


def test_lookup_is_longest_owned_prefix():
    idx = PrefixIndex()
    idx.insert((1, 2), 0)
    idx.insert((1, 2, 3, 4), 1)
    assert idx.lookup((1, 2, 3, 4, 5)) == (1, 4)
    assert idx.lookup((1, 2, 3)) == (0, 2)      # deeper edge unmatched
    assert idx.lookup((1, 2)) == (0, 2)


def _build(flat, inserts, n_views):
    """Deterministically rebuild (views, index) from flat int streams —
    called twice per example to compare fresh reconstructions."""
    healths = (H, D, X)
    views = {}
    for i in range(n_views):
        chunk = flat[5 * i:5 * i + 5]
        if len(chunk) < 5:
            break
        views[i] = ReplicaView(
            health=healths[chunk[0] % 3], n_slots=1 + chunk[1] % 4,
            occupancy=(chunk[2] % 9) / 4.0, shed_rate=(chunk[3] % 5) / 4.0,
            inflight=chunk[4] % 6)
    idx = PrefixIndex(max_nodes=64)
    for j, seq in enumerate(inserts):
        idx.insert(tuple(seq), j % max(1, n_views))
    return views, idx


@given(st.lists(st.integers(0, 9), min_size=0, max_size=40),
       st.lists(st.lists(st.integers(0, 5), min_size=1, max_size=6),
                min_size=0, max_size=12),
       st.lists(st.integers(0, 5), min_size=0, max_size=8))
@settings(max_examples=60, deadline=None)
def test_placement_is_deterministic(flat, inserts, query):
    """Identical replica stats + identical index state => identical
    placement, independent of dict insertion order. This purity is what
    makes a fleet incident replayable from a stats dump."""
    n = max(1, len(flat) // 5)
    v1, i1 = _build(flat, inserts, n)
    v2, i2 = _build(flat, inserts, n)
    v2 = dict(reversed(list(v2.items())))       # scrambled dict order
    first = place(v1, i1, tuple(query))
    assert first == place(v2, i2, tuple(query))
    assert first == place(v1, i1, tuple(query))  # lookup touch is benign


# ---------------------------------------------------------------------------
# 2/3. the router over live replicas


@pytest.fixture(scope="module")
def toy():
    ds = SyntheticReactionDataset(16, seed=0)
    cfg = tiny_config(ds.tokenizer.vocab_size, depth=2, d_model=64,
                      max_len=192)
    params = s2s.init(jax.random.PRNGKey(0), cfg)
    return ds, cfg, params


def _replica(toy, **kw):
    ds, cfg, params = toy
    base = dict(mode="greedy", max_new=MAX_NEW, max_src=96, n_slots=1)
    base.update(kw)
    eng = StreamingEngine(params, cfg, ds.tokenizer, EngineConfig(**base))
    eng.submit(ds.pair(0)[0])
    eng.serve()
    eng.reset()
    return FrontDoorServer(eng, ServerConfig(realtime=False)).start()


@pytest.fixture
def fleet(toy):
    """Two in-process replicas behind a router; torn down afterwards."""
    srvs = [_replica(toy) for _ in range(2)]
    router = FleetRouter(
        [("127.0.0.1", s.port) for s in srvs],
        FleetConfig(probe_interval_s=0.05)).start()
    time.sleep(0.15)               # let one probe round land
    yield srvs, router
    router.shutdown()
    for s in srvs:
        s.shutdown(drain=False)


class SSEClient:
    """Incremental SSE reader against the router (same shape as the
    test_server one; duplicated to keep both suites self-contained)."""

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

    def drain(self, prior=()):
        out = list(prior)
        while (ev := self.next_event()) is not None:
            out.append(ev)
        self.sock.close()
        return out


def _acks(events):
    """(n_accepted, n_terminal) — every request owes exactly (1, 1)."""
    accepted = sum(e["event"] == "accepted" for e in events)
    terminal = sum(e["event"] == "done" for e in events)
    return accepted, terminal


def test_router_is_wire_invisible_and_prefix_affine(toy, fleet):
    """Same events and tokens through the router as against a bare
    replica, and a repeated prompt sticks to the replica that committed
    it (the affinity counter moves)."""
    ds, _, _ = toy
    srvs, router = fleet
    query = ds.pair(3)[0]
    via_router = sse_events("127.0.0.1", router.port, {"query": query})
    direct = sse_events("127.0.0.1", srvs[0].port, {"query": query})
    assert _acks(via_router) == (1, 1)
    assert via_router[0]["event"] == "accepted"
    assert via_router[0]["replica"] == 0      # first placement: id tie
    assert via_router[-1]["status"] == "finished"
    assert via_router[-1]["tokens"] == direct[-1]["tokens"]
    assert via_router[-1]["text"] == direct[-1]["text"]
    deltas = [e["tokens"] for e in via_router if e["event"] == "delta"]
    assert deltas == [e["tokens"] for e in direct if e["event"] == "delta"]

    again = sse_events("127.0.0.1", router.port, {"query": query})
    assert again[0]["replica"] == 0           # prefix-affine repeat
    st = router.stats()
    assert st["affinity_hits"] >= 1 and st["prefix_hit_rate"] > 0
    assert st["index"]["size"] > 0


def test_concurrent_burst_spreads_over_replicas(toy, fleet):
    """Requests that arrive together are booked as they are placed, so a
    burst spreads instead of piling onto the replica that looked idlest
    before any of them connected."""
    import threading

    ds, _, _ = toy
    _, router = fleet
    out = [None] * 4

    def one(i):
        out[i] = sse_events("127.0.0.1", router.port,
                            {"query": ds.pair(4 + i)[0]})

    ts = [threading.Thread(target=one, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert all(evs[-1]["status"] == "finished" for evs in out)
    assert {evs[0]["replica"] for evs in out} == {0, 1}


def test_cancel_routes_through_to_the_owning_replica(toy, fleet):
    ds, _, _ = toy
    _, router = fleet
    c = SSEClient("127.0.0.1", router.port, {"query": ds.pair(5)[0]})
    accepted = c.next_event()
    assert accepted["event"] == "accepted"
    body = json.dumps({"rid": accepted["rid"]}).encode()
    with socket.create_connection(("127.0.0.1", router.port),
                                  timeout=10) as s:
        s.sendall(f"POST /v1/cancel HTTP/1.1\r\nHost: x\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        s.recv(65536)
    events = c.drain(prior=[accepted])
    assert _acks(events) == (1, 1)
    assert events[-1]["status"] == "cancelled"


def test_fleet_stats_aggregate_per_replica_health(toy, fleet):
    _, router = fleet
    st = router.stats(fresh=True)
    assert st["fleet"] and st["n_replicas"] == 2 and st["n_healthy"] == 2
    for rep in st["replicas"].values():
        assert rep["health"] == "healthy"
        for key in ("occupancy", "shed_rate", "load", "prefix_hit_rate"):
            assert key in rep
    for key in ("rerouted", "reroute_ok", "lost", "prefix_hit_rate",
                "index"):
        assert key in st


def test_replica_kill_drill_reroutes_every_queued_request(toy, fleet):
    """THE failover contract (ISSUE 10 acceptance): seed a prefix onto
    replica 0, pack its single slot (one streaming resident + two
    affine queued requests), then kill it mid-stream. Every request that
    was queued on the dead replica must finish on the survivor,
    token-identically, with exactly one accepted and one terminal event;
    the mid-stream resident must end in the typed retryable ``lost``
    terminal — never a silent drop, never a duplicated stream."""
    ds, _, _ = toy
    srvs, router = fleet
    prompt = ds.pair(7)[0]
    other = ds.pair(8)[0]

    seed = sse_events("127.0.0.1", router.port, {"query": prompt})
    assert seed[-1]["status"] == "finished" and seed[0]["replica"] == 0

    a = SSEClient("127.0.0.1", router.port, {"query": prompt})
    a_pre = [a.next_event()]
    assert a_pre[0]["event"] == "accepted" and a_pre[0]["replica"] == 0
    a_pre.append(a.next_event())
    assert a_pre[1]["event"] == "delta"       # A is mid-stream on r0

    b = SSEClient("127.0.0.1", router.port, {"query": other})
    b_pre = [b.next_event()]
    assert b_pre[0]["replica"] == 1           # least-loaded: r0 is busy

    queued = []
    for _ in range(2):                        # C, D: affine, queued on r0
        c = SSEClient("127.0.0.1", router.port, {"query": prompt})
        ev = c.next_event()
        assert ev["event"] == "accepted" and ev["replica"] == 0
        queued.append((c, [ev]))

    srvs[0].shutdown(drain=False)             # the kill

    for c, pre in queued:
        events = c.drain(prior=pre)
        assert _acks(events) == (1, 1), "lost or duplicated acks"
        done = events[-1]
        assert done["status"] == "finished", "queued request not rerouted"
        assert done["replica"] == 1
        assert done["tokens"] == seed[-1]["tokens"], \
            "reroute must be token-identical"

    a_events = a.drain(prior=a_pre)
    assert _acks(a_events) == (1, 1)
    a_done = a_events[-1]
    # A streamed deltas: a silent restart would duplicate them. Either it
    # finished before the socket died, or it is LOST with retry metadata.
    assert a_done["status"] in ("finished", "lost")
    if a_done["status"] == "lost":
        assert a_done["retryable"] is True and a_done["retry_after"] > 0

    b_events = b.drain(prior=b_pre)
    assert _acks(b_events) == (1, 1)
    assert b_events[-1]["status"] == "finished"   # survivor unaffected

    st = router.stats()
    assert st["rerouted"] == 2 and st["reroute_ok"] == 2
    assert st["n_healthy"] == 1
    # the dead replica's prefixes were dropped: the family re-homes to r1
    again = sse_events("127.0.0.1", router.port, {"query": prompt})
    assert again[0]["replica"] == 1
    assert again[-1]["tokens"] == seed[-1]["tokens"]


def test_no_healthy_replica_is_a_typed_retryable_rejection(toy):
    ds, _, _ = toy
    srv = _replica(toy)
    router = FleetRouter([("127.0.0.1", srv.port)],
                         FleetConfig(probe_interval_s=0.05,
                                     no_replica_retry_after=3.5)).start()
    try:
        time.sleep(0.15)
        srv.shutdown(drain=False)
        deadline = time.monotonic() + 10.0
        while (router.stats()["n_healthy"] and
               time.monotonic() < deadline):
            time.sleep(0.02)
        events = sse_events("127.0.0.1", router.port,
                            {"query": ds.pair(2)[0]})
        assert events == [{"event": "rejected", "error": "no_replica",
                           "retry_after": 3.5}]
        assert router.stats()["no_replica"] == 1
    finally:
        router.shutdown()
        srv.shutdown(drain=False)


def test_spawn_replicas_refuses_to_share_a_chip(monkeypatch):
    """A TPU chip belongs to one process: no replica process may be
    started by a process that holds the TPU, nor two onto one host's
    chips."""
    from repro.launch import runtime
    from repro.serving.fleet import spawn_replicas

    monkeypatch.setattr(runtime, "tpu_backend_live", lambda: True)
    with pytest.raises(RuntimeError, match="holds every chip"):
        spawn_replicas(1)
    monkeypatch.setattr(runtime, "tpu_backend_live", lambda: False)
    monkeypatch.setattr(runtime, "host_tpu_chips", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="share this host's TPU chips"):
        spawn_replicas(2)
