"""Backend compiles (jax.monitoring events) inside the traced rounds.
Set-up warms every shape the window uses, so this should read 0."""


def read(ctx):
    return float(ctx.compiles)
