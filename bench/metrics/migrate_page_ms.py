"""Host milliseconds per page the prefix store migrated in the traced
part of the window: time inside the program's
``specgen.store.migrate_chunk`` spans (the page gather's dispatch, the
device-to-host copies, the release of the pages) over the pages they
moved (store counter ``pages_migrated``).  None where no page moved or
the program stamps no such span."""

SPAN = "specgen.store.migrate_chunk"


def read(ctx):
    pages = ctx.c1.get("pages_migrated", 0) - ctx.c0.get("pages_migrated", 0)
    spent = ctx.trace.get("span_s", {}).get(SPAN)
    if pages <= 0 or spent is None:
        return None
    return 1e3 * spent / pages
