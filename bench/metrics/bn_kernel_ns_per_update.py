"""bn_kernel_ns_per_update (ns): device time of the Mosaic kernels (the
fused BN color round of `kernels/bn_gibbs`) per site update the traced
window served.  A site update is one variable of one chain resampled once,
counted from the traffic: chains x sweeps x free variables."""


def read(ctx):
    t = ctx.trace
    if (t is None or ctx.kind != "bn" or t.kernel_s <= 0
            or ctx.traced_site_updates <= 0):
        return None
    return t.kernel_s * 1e9 / ctx.traced_site_updates
