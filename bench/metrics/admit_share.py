"""Device time of the admission programs (gather, suffix prefill, write
back into pages) as a share of the traced window, in percent.  A window
in which no row was admitted reads 0."""


def read(ctx):
    t = ctx.trace
    if not t["window_s"] or "decode" not in t["program_s"] or \
            not t["program_runs"].get("decode"):
        return None
    return 100.0 * t["program_s"].get("admission", 0.0) / t["window_s"]
