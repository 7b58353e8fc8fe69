"""Share of the HBM roofline the post-gradient work reaches: the least
bytes it must move (lgcbench.flops.least_post_grad_bytes) at the chip's
peak bandwidth, over its device time."""


def read(ctx):
    ns = ctx["trace"].class_ns("post_grad")
    if not ns:
        return None
    seconds = ns / ctx["steps"] / 1e9
    least = ctx["least_post_grad_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
