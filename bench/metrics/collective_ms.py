"""Device time per step of the exchanges between chips: all-reduce,
all-gather, reduce-scatter, collective-permute and all-to-all, their
async start and done included."""


def read(ctx):
    ns = ctx["trace"].class_ns("collective")
    return ns / ctx["steps"] / 1e6 if ns else None
