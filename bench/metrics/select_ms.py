"""Device time per step of error feedback and top-k selection, the clear
of the sent entries included: the operations under the program's
``select`` scope."""
from lgcbench import scopes as S


def read(ctx):
    return S.per_step_ms(S.scope_ns(
        ctx["trace"], lambda st: S.under(st, S.SELECT)), ctx)
