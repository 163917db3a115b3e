"""XLA programs compiled, or loaded from the persistent compilation cache,
while the window ran (JAX's monitoring events): executable-cache misses and
eager programs on shapes not seen in warm-up."""


def read(ctx):
    return float(ctx.window.compiles)
