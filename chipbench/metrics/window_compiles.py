"""Runner layer: backend compiles (and compile-cache loads) that ended
inside the window, from JAX's compile-duration events."""


def read(ctx):
    return float(ctx.serve.window_compiles)
