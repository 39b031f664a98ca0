"""Backend compile seconds during set-up (loads from the persistent
cache included), from JAX's monitoring events."""


def read(ctx):
    return ctx.compile.setup_s
