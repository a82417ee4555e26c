"""XLA compilations requested inside the window (``jax.monitoring``'s
backend-compile events; a persistent-cache hit counts too)."""


def read(ctx):
    return ctx.compiles_in_window
