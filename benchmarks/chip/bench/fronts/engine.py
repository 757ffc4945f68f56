"""The in-process front: the cell's one paged ``StreamingEngine`` on the
default device, its pump driven by the benchmark itself
(``drive.backlog`` or ``drive.open_loop``).

A front is built in set-up (``Front(...)``: engines made and warmed),
reads the program's counters (``counters()``), serves the window
(``window(...)`` returns a ``drive.Record``), names the megastep's
instructions by scope where it can (``op_scopes()``) and frees what it
holds (``close()``).
"""

from __future__ import annotations

import itertools

from bench import drive, engines, traffic

DRAIN_S = 60.0


class Front:
    def __init__(self, cell, fam, params, src, order, devs,
                 n_slots: int | None = None):
        self.cell = cell
        self.n_slots = n_slots or engines.slots(cell)
        self.eng = engines.make_engine(cell, fam, params, self.n_slots)
        engines.warm(self.eng, cell, src, order, self.n_slots)

    def counters(self) -> dict:
        s = self.eng.loop_stats()
        return {"dispatches": s["n_dispatches"],
                "slot_steps": s["slot_steps"],
                "capacity_steps": s["capacity_steps"]}

    def window(self, src, order, seed: int, seconds: float) -> drive.Record:
        mix = self.cell.traffic
        drain = float(mix.get("drain_s", DRAIN_S))
        if mix["load"] == "backlog":
            feed = (src[i] for i in itertools.cycle(order))
            depth = self.n_slots * (1 + int(mix["backlog_slots"]))
            return drive.backlog(self.eng, feed, mix["mode"], seconds, depth,
                                 drain)
        due = traffic.due_times(float(mix["rate_per_s"]), seconds, seed)
        feed = (src[order[i % len(order)]] for i in range(len(due)))
        return drive.open_loop(self.eng, feed, due, mix["mode"], seconds,
                               drain)

    def op_scopes(self) -> dict:
        return self.eng.op_scopes()

    def close(self) -> None:
        self.eng = None
