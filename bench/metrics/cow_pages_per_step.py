"""Copy-on-write page copies per decode dispatch in the traced part of
the window (page-pool counter): pages a writer had to copy because a
fork, a sibling or a stored prefix still held them."""


def read(ctx):
    steps = ctx.c1["decode_dispatches"] - ctx.c0["decode_dispatches"]
    if steps <= 0:
        return None
    return (ctx.c1["page_copies"] - ctx.c0["page_copies"]) / steps
