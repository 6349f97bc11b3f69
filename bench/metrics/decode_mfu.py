"""Model FLOP utilisation of the whole traced window, in percent: the
operations the decoded tokens need (configuration and context lengths)
over the window's seconds and the chip's peak bf16 FLOP/s."""
from bench.lib import work


def read(ctx):
    if not ctx.steps or not ctx.trace["window_s"]:
        return None
    flops = sum(work.decode_step_work(ctx.config, s)[0] for s in ctx.steps)
    peak = work.peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / ctx.host_s / peak
