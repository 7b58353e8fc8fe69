"""Device time per step of everything after the gradient that is not an
exchange between chips: the compressor's local work and the optimizer."""


def read(ctx):
    ns = ctx["trace"].class_ns("post_grad")
    return ns / ctx["steps"] / 1e6 if ns else None
