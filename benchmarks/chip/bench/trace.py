"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

A device plane's operation events give the busy time (the union of their
intervals), the time per operation name and per program (module) name.
The complement of the busy union inside the traced window is idle time;
each idle stretch is put down to the innermost host span that covers it
(the program's ``serve/*`` spans and the benchmark's ``bench/*`` spans),
so the breakdown says what the host was doing while the chip waited.

Which planes and lines are the device's is a parameter: on a TPU the
planes are ``/device:TPU:<i>`` with lines ``XLA Ops`` and
``XLA Modules``; the CPU test points it at the CPU client's thread.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

WINDOW_SPAN = "bench/window"
HOST_PREFIXES = ("serve/", "bench/")


def find_xplane(root: str) -> str:
    found = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def union(intervals) -> list:
    """Merge (start, end) pairs into disjoint sorted intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def short(name: str) -> str:
    """An HLO event's instruction name (``%fusion.12 = ...`` -> ``fusion.12``)."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def self_times(events) -> list:
    """[(start, end, name, self seconds)] of one line's events, where an
    event's self time leaves out the events nested inside it (a
    conditional or loop contains the operations of its body)."""
    out, stack = [], []
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, name, (e - s) * 1e-9]
        if stack:
            stack[-1][3] -= (min(e, stack[-1][1]) - s) * 1e-9
        stack.append(rec)
        out.append(rec)
    return out


def _innermost(spans, s, e) -> list:
    """[(name, seconds)] of [s, e) put down to the shortest covering span."""
    cuts = {s, e}
    cover = [sp for sp in spans if sp[0] < e and sp[1] > s]
    for a, b, _ in cover:
        if s < a < e:
            cuts.add(a)
        if s < b < e:
            cuts.add(b)
    cuts = sorted(cuts)
    out = []
    for a, b in zip(cuts, cuts[1:]):
        inside = [sp for sp in cover if sp[0] <= a and sp[1] >= b]
        name = min(inside, key=lambda sp: sp[1] - sp[0])[2] if inside \
            else "host/other"
        out.append((name, (b - a) * 1e-9))
    return out


def reduce(path: str, *, device_plane=lambda n: n.startswith("/device:TPU"),
           op_line=lambda n: n == "XLA Ops",
           module_line=lambda n: n == "XLA Modules",
           host_plane=lambda n: n.startswith("/host:")) -> dict:
    """Reduce one trace. Returns ``window_s`` and ``busy_s`` (mean over the
    device planes), ``ops`` ({name: [self seconds]}), ``modules`` ({name:
    [durations s]}) and
    ``idle`` ([(host span, seconds)] summed by span, longest first)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, ops, mods = [], [], []
    devices, lines = [], {}
    for plane in pd.planes:
        for line in plane.lines:
            lines[f"{plane.name}|{line.name}"] = len(list(line.events))
        if device_plane(plane.name):
            dev_ops = []
            for line in plane.lines:
                if op_line(line.name):
                    dev_ops += self_times(
                        (e.start_ns, e.start_ns + e.duration_ns, short(e.name))
                        for e in line.events)
                elif module_line(line.name):
                    mods += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events]
            devices.append(dev_ops)
            ops += dev_ops
        if host_plane(plane.name):
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events
                          if e.name.startswith(HOST_PREFIXES)]
    win = [sp for sp in spans if sp[2] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][0], win[0][1]
    elif ops:
        lo, hi = min(o[0] for o in ops), max(o[1] for o in ops)
    else:
        raise ValueError(f"{path}: no window span and no device operation")
    window_s = (hi - lo) * 1e-9
    busy = []
    for dev_ops in devices or [[]]:
        merged = union(_clip(s, e, lo, hi) for s, e, *_ in dev_ops
                       if e > lo and s < hi)
        busy.append(merged)
    busy_s = sum(sum(e - s for s, e in m) for m in busy) * 1e-9 / len(busy)
    by_op, by_mod = defaultdict(list), defaultdict(list)
    for s, e, name, own in ops:
        if e > lo and s < hi:
            by_op[name].append(own)
    for s, e, name in mods:
        if e > lo and s < hi:
            a, b = _clip(s, e, lo, hi)
            by_mod[name].append((b - a) * 1e-9)
    # idle stretches of the first device, put down to host spans
    gaps, t = [], lo
    for s, e in busy[0]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    spans.sort()
    starts = [sp[0] for sp in spans]
    idle = defaultdict(float)
    for s, e in gaps:
        j = bisect.bisect_left(starts, e)
        near = [sp for sp in spans[:j] if sp[1] > s]
        for name, sec in _innermost(near, s, e):
            idle[name] += sec
    return {"window_s": window_s, "busy_s": busy_s,
            "ops": dict(by_op), "modules": dict(by_mod), "lines": lines,
            "idle": sorted(idle.items(), key=lambda kv: -kv[1])}


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time and the idle time by what the host was doing."""
    ops = sorted(((n, sum(v)) for n, v in red["ops"].items()),
                 key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in red["idle"][:top]]}
