"""Model step: device time of the megastep's ``decoder`` scope (the
verify forward, its nested scopes included) per megastep, from the
trace joined to ``StreamingEngine.op_scopes()`` (``bench.scopes``)."""

SCOPE = "decoder"


def read(ctx):
    sc = (ctx.trace or {}).get("scopes")
    if not sc or not sc["runs"] or SCOPE not in sc["top"]:
        return None
    return 1e3 * sc["top"][SCOPE] / sc["runs"]
