"""Small statistics shared by the metric readers."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (linear), or None with no values."""
    values = list(values)
    return float(np.percentile(values, q)) if values else None


def finished_in_window(ctx) -> list:
    """[(seconds seen, result)] of requests finished inside the window."""
    return [(t, r) for t, r in ctx.rec.in_window() if ctx.rec.finished(r)]


def latencies(ctx) -> list:
    """Seconds from due to seen for every request the window owed; one
    that never finished counts as seen at the end of the drain."""
    rec, end = ctx.rec, ctx.rec.seconds + ctx.rec.drain_s
    out = []
    for rid in rec.attempted():
        t, r = rec.seen.get(rid, (end, None))
        if r is None or not rec.finished(r):
            t = max(t, end)
        out.append(t - rec.due[rid])
    return out


def tokens_per_call(ctx) -> float | None:
    done = finished_in_window(ctx)
    calls = sum(int(r.n_calls) for _, r in done)
    if not calls:
        return None
    return sum(int(r.lengths[0]) for _, r in done) / calls
