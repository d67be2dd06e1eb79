"""mrf_kernel_ns_per_update (ns): device time of the Mosaic kernels (the
MRF half-step of `kernels/mrf_gibbs`) per site update the traced window
served, site updates counted from the traffic: chains x sweeps x sites."""


def read(ctx):
    t = ctx.trace
    if (t is None or ctx.kind != "mrf" or t.kernel_s <= 0
            or ctx.traced_site_updates <= 0):
        return None
    return t.kernel_s * 1e9 / ctx.traced_site_updates
