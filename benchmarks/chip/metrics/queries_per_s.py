"""Queries finished inside the window over its length (host clock). A
top-5 list is one query."""

from bench import stats


def read(ctx):
    n = len(stats.finished_in_window(ctx))
    return n / ctx.rec.seconds if n else None
