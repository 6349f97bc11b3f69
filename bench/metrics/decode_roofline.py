"""Share of the roofline the decode program reaches, in percent: the
least time the chip needs for a step (the larger of the step's bytes
over peak bandwidth and its operations over peak FLOP/s, counted from
the configuration and the live rows' context lengths) over the mean
device time of one decode execution."""
from bench.lib import work


def read(ctx):
    runs = ctx.trace["program_runs"].get("decode", 0)
    if not runs or not ctx.steps:
        return None
    least = [work.least_time_s(*work.decode_step_work(ctx.config, s),
                               ctx.device_kind) for s in ctx.steps]
    mean_least = sum(least) / len(least)
    mean_step = ctx.trace["program_s"]["decode"] / runs
    return 100.0 * mean_least / mean_step
