"""Share of the traced window the host spends inside the transport
plane's transfer events, in percent: the prefix store's streamed page
migrations (``bench.event.xfer-*`` spans around the event loop's
transfer events)."""


def read(ctx):
    t = ctx.trace
    if not t["window_s"]:
        return None
    s = sum(v for k, v in t["host_s"].items() if k.startswith("event.xfer"))
    return 100.0 * s / t["window_s"]
