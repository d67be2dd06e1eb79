"""compiles_in_window (count): backend compiles JAX reported inside the
window.  Set-up warms every executable the window uses, so this reads 0;
anything else is compile time charged to queries."""


def read(ctx):
    return ctx.compiles_in_window
