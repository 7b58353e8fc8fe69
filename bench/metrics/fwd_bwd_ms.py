"""Device time per step of the model's forward and backward pass: the
operations whose name stack holds ``jvp(`` (value_and_grad)."""


def read(ctx):
    ns = ctx["trace"].class_ns("fwd_bwd")
    return ns / ctx["steps"] / 1e6 if ns else None
