"""Model step: device time of the megastep's ``reorder`` scope (beam
parents, row gathers, cache commits, the group's row slice and merge)
per megastep, from the trace joined to ``StreamingEngine.op_scopes()``
(``bench.scopes``)."""

SCOPE = "reorder"


def read(ctx):
    sc = (ctx.trace or {}).get("scopes")
    if not sc or not sc["runs"] or SCOPE not in sc["top"]:
        return None
    return 1e3 * sc["top"][SCOPE] / sc["runs"]
