"""Device time of one program's operations by named scope.

A device trace names an operation only by its HLO instruction
(``fusion.41``). The program's ``StreamingEngine.op_scopes()`` maps the
instruction names of its compiled megastep to scope paths
(``decoder/self_attn``). ``by_scope`` joins the two over the operations
of that program alone: an admission or release program may hold an
instruction of the same name, and it is not counted.

Which operations belong to the program: on a TPU, those inside the
program's events on the plane's module line (``XLA Modules``); where a
plane has no module line (the CPU client), each operation's
``hlo_module`` stat. A fusion carries the scope of its root; operations
the map does not name go under ``UNSCOPED``.
"""

from __future__ import annotations

import bisect
from collections import defaultdict

from bench import trace

PROGRAM = "jit__megastep_impl"
UNSCOPED = "(unscoped)"


def _stat(event, name):
    for key, value in event.stats:
        if key == name:
            return value
    return None


def program_ops(path: str, program: str = PROGRAM, *,
                device_plane=lambda n: n.startswith("/device:TPU"),
                op_line=lambda n: n == "XLA Ops",
                module_line=lambda n: n == "XLA Modules") -> dict:
    """Operations of ``program`` in one trace: ``ops`` ([(instruction,
    self seconds)]), ``runs`` (its module events, or its distinct run ids
    where a plane has no module line) and ``seconds`` (their summed
    length; without module events, the ops' summed self time)."""
    from jax.profiler import ProfileData

    ops, runs, seconds = [], 0, 0.0
    for plane in ProfileData.from_file(path).planes:
        if not device_plane(plane.name):
            continue
        events, mods = [], []
        for line in plane.lines:
            if op_line(line.name):
                events += list(line.events)
            elif module_line(line.name):
                mods += [(e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name.startswith(program)]
        timed = trace.self_times((e.start_ns, e.start_ns + e.duration_ns,
                                  i) for i, e in enumerate(events))
        if mods:
            mods.sort()
            runs += len(mods)
            seconds += sum(b - a for a, b in mods) * 1e-9
            starts = [a for a, _ in mods]
            for s, _, i, own in timed:
                j = bisect.bisect_right(starts, s) - 1
                if j >= 0 and s < mods[j][1]:
                    ops.append((trace.short(events[i].name), own))
            continue
        ids = set()
        for s, _, i, own in timed:
            mod = _stat(events[i], "hlo_module")
            if mod is not None and str(mod).startswith(program):
                ops.append((trace.short(events[i].name), own))
                ids.add(_stat(events[i], "run_id"))
                seconds += own
        runs += len(ids)
    return {"ops": ops, "runs": runs, "seconds": seconds}


def by_scope(ops, scopes: dict) -> dict:
    """Self seconds of ``ops`` ([(instruction, seconds)]) by top-level
    scope (``top``) and by full scope path (``paths``); instructions
    ``scopes`` does not name go under ``UNSCOPED`` in both."""
    top, paths = defaultdict(float), defaultdict(float)
    for name, own in ops:
        path = scopes.get(name, UNSCOPED)
        top[path.split("/")[0] if path != UNSCOPED else UNSCOPED] += own
        paths[path] += own
    return {"top": dict(top), "paths": dict(paths)}
