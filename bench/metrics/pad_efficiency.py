"""pad_efficiency (%): real query lanes over padded lanes, summed over
every bucket dispatch of the window (`runtime/batcher`'s pad ladder)."""


def read(ctx):
    if ctx.n_padded <= 0:
        return None
    return 100.0 * ctx.n_real / ctx.n_padded
