"""Programs JAX compiled (or loaded) inside the window; must read 0."""


def read(ctx):
    return float(ctx.compiles_in_window)
