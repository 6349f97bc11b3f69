"""Host milliseconds per decode dispatch outside the wait for the
step's tokens, in the traced part of the window: time inside the
program's ``specgen.engine.pump`` spans less the ``specgen.engine.sync``
spans inside them, over the decode dispatches (engine counter).  None
where the program stamps no pump span."""

PUMP, SYNC = "specgen.engine.pump", "specgen.engine.sync"


def read(ctx):
    steps = ctx.c1["decode_dispatches"] - ctx.c0["decode_dispatches"]
    spent = ctx.trace.get("span_s", {})
    if steps <= 0 or PUMP not in spent:
        return None
    return 1e3 * (spent[PUMP] - spent.get(SYNC, 0.0)) / steps
