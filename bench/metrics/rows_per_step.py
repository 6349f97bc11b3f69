"""Rows advanced per decode dispatch in the traced part of the window
(engine counters): how full the engine keeps its batch."""


def read(ctx):
    steps = ctx.c1["decode_dispatches"] - ctx.c0["decode_dispatches"]
    if steps <= 0:
        return None
    return (ctx.c1["tokens_decoded"] - ctx.c0["tokens_decoded"]) / steps
