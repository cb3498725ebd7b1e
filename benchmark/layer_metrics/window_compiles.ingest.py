"""Backend compile requests inside the window (JAX's own monitoring
events). Should be 0."""


def read(ctx):
    return ctx.counts.requests("window")
