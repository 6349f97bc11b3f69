"""Mean device time of one execution of the decode program (trace)."""


def read(ctx):
    runs = ctx.trace["program_runs"].get("decode", 0)
    if not runs:
        return None
    return 1e3 * ctx.trace["program_s"]["decode"] / runs
