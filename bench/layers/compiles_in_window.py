"""Programs JAX had to build (compiled, or loaded from the persistent
cache) while the window's requests were served: backend compile events
counted from the first due request to the end of the drain."""


def read(ctx):
    return float(ctx.compiles)
