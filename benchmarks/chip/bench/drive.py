"""The measured window: the engine's shared pump
(``serve_steps(realtime=True)``) driven for ``seconds``, every terminal
result stamped on the benchmark's own clock.

The engine is only asked for ``submit``, ``serve_steps`` and
``begin_drain``, so a fake engine and a fake clock can stand in
(``tests/test_harness.py``).
"""

from __future__ import annotations

import dataclasses
import time

import jax


@dataclasses.dataclass
class Record:
    seconds: float
    due: dict = dataclasses.field(default_factory=dict)   # rid -> s from t0
    seen: dict = dataclasses.field(default_factory=dict)  # rid -> (s, result)
    iterations: int = 0            # pump iterations that ended in the window
    pump_gaps: list = dataclasses.field(default_factory=list)
    drain_s: float = 0.0
    shed: set = dataclasses.field(default_factory=set)  # queued at the close
    handles: dict = dataclasses.field(default_factory=dict)
    queries: dict = dataclasses.field(default_factory=dict)  # rid -> ids

    def in_window(self):
        """Terminal results the driving loop saw inside the window."""
        return [(t, r) for t, r in self.seen.values() if t <= self.seconds]

    def finished(self, r) -> bool:
        return str(getattr(r.status, "value", r.status)) == "finished"

    def attempted(self) -> list:
        """Requests the window owes an answer: every one due in it, less
        those still queued (never admitted) when a backlog was shed."""
        return [rid for rid, d in self.due.items()
                if d < self.seconds and rid not in self.shed]

    def failed(self) -> list:
        """Attempted requests that never finished, or ended otherwise."""
        return [rid for rid in self.attempted()
                if rid not in self.seen or not self.finished(self.seen[rid][1])]


def _status(handle) -> str:
    st = handle.status
    return str(getattr(st, "value", st))


def _step(pump, rec: Record, t0: float, clock) -> bool:
    """One pump iteration; stamp its results. False once drained."""
    with jax.profiler.TraceAnnotation("bench/pump"):
        try:
            events = next(pump)
        except StopIteration:
            return False
    t = clock() - t0
    for r in events:
        rec.seen[r.rid] = (t, r)
    return True


def _drain(eng, pump, rec: Record, t0: float, clock, limit_s: float,
           shed: bool):
    """After the window: residents (and, in an open loop, every request
    already due) finish, up to ``limit_s`` past the close. ``shed``: the
    queued backlog is dropped first; those requests were never owed."""
    if shed:
        queued = {rid for rid, h in rec.handles.items()
                  if rid not in rec.seen and _status(h) == "queued"}
        eng.begin_drain()
        rec.shed = queued
    end = rec.seconds + limit_s
    while clock() - t0 < end and _step(pump, rec, t0, clock):
        pass
    rec.drain_s = max(0.0, clock() - t0 - rec.seconds)


def backlog(eng, queries, mode: str, seconds: float, depth: int,
            drain_s: float, clock=time.perf_counter) -> Record:
    """Closed loop: ``depth`` requests beyond the slots stay queued all
    through the window (topped up after every pump iteration); ``queries``
    is an endless iterator of token arrays."""
    rec = Record(seconds=seconds)
    outstanding = set()

    def submit(t):
        with jax.profiler.TraceAnnotation("bench/submit"):
            q = next(queries)
            h = eng.submit(q, mode=mode)
        rid = int(h)
        rec.queries[rid] = q
        rec.due[rid] = t
        rec.handles[rid] = h
        outstanding.add(rid)

    for _ in range(depth):
        submit(0.0)
    pump = eng.serve_steps(realtime=True)
    t0 = last = clock()
    with jax.profiler.TraceAnnotation("bench/window"):
        while True:
            if not _step(pump, rec, t0, clock):
                break
            now = clock()
            rec.pump_gaps.append(now - last)
            last = now
            if now - t0 >= seconds:
                break
            rec.iterations += 1
            outstanding.difference_update(rec.seen)
            while len(outstanding) < depth:
                submit(now - t0)
    _drain(eng, pump, rec, t0, clock, drain_s, shed=True)
    return rec


def open_loop(eng, queries, due, mode: str, seconds: float, drain_s: float,
              clock=time.perf_counter) -> Record:
    """Open loop: every request is submitted before the window with its
    due time as its arrival; the scheduler holds each until it is due.
    After the close, due requests get ``drain_s`` more to finish."""
    rec = Record(seconds=seconds)
    for q, d in zip(queries, due):
        h = eng.submit(q, mode=mode, arrival=float(d))
        rec.due[int(h)] = float(d)
        rec.handles[int(h)] = h
        rec.queries[int(h)] = q
    pump = eng.serve_steps(realtime=True)
    t0 = last = clock()
    with jax.profiler.TraceAnnotation("bench/window"):
        while _step(pump, rec, t0, clock):
            now = clock()
            rec.pump_gaps.append(now - last)
            last = now
            if now - t0 >= seconds:
                break
            rec.iterations += 1
    _drain(eng, pump, rec, t0, clock, drain_s, shed=False)
    return rec
