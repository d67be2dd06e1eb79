"""host_gap_us_per_dispatch (us): the device's idle time in the traced
window per bucket dispatch the engine made in it: what the host path
(engine, batcher, executor) costs the device per dispatch."""


def read(ctx):
    t = ctx.trace
    if t is None or ctx.traced_dispatches <= 0:
        return None
    return t.idle_s * 1e6 / ctx.traced_dispatches
