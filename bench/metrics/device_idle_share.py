"""device_idle_share (%): the share of the traced window in which no op
ran on the device, averaged over the chips used."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * t.idle_s / t.window_s
