"""Model FLOP/s utilization of the traced window: the model FLOPs of the
window's tokens (lgcbench.flops) over the window, the chips and the
chip's bf16 peak."""


def read(ctx):
    tr = ctx["trace"]
    flops = ctx["flops_per_token"] * ctx["tokens_per_step"] * ctx["steps"]
    return 100.0 * flops / tr.window_s() / (
        ctx["chips"] * ctx["peaks"]["flops_bf16"])
