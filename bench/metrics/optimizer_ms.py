"""Device time per step of the optimizer's update: the operations under
the program's ``optimizer`` scope."""
from lgcbench import scopes as S


def read(ctx):
    return S.per_step_ms(S.scope_ns(
        ctx["trace"], lambda st: S.under(st, S.OPTIMIZER)), ctx)
