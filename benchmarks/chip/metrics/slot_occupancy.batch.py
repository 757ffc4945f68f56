"""Scheduler: resident slots summed over the megasteps dispatched in the
window and its drain, over the slots those megasteps held
(``loop_stats()`` ``slot_steps`` / ``capacity_steps``), in percent."""


def read(ctx):
    cap = ctx.loop.get("capacity_steps", 0)
    return 100.0 * ctx.loop["slot_steps"] / cap if cap else None
