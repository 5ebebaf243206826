"""Programs compiled or loaded inside the measured window (JAX's
monitoring events): each one stalls every decoding slot."""


def read(ctx):
    return ctx.compiles_in_window
