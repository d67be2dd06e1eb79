"""xla_ns_per_update (ns): device time of every op that is not a Mosaic
kernel (random-word generation, padding, accumulation, the unfused route)
per site update the traced window served."""


def read(ctx):
    t = ctx.trace
    if t is None or t.xla_s <= 0 or ctx.traced_site_updates <= 0:
        return None
    return t.xla_s * 1e9 / ctx.traced_site_updates
