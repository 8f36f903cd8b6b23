"""Programs JAX compiled or loaded from its persistent cache inside the
window (0 when the warm-up covered every shape)."""


def read(ctx):
    return ctx.compiles
