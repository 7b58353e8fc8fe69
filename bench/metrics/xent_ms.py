"""Device time per step of the chunked cross-entropy and the tied LM head,
forward and backward: the operations under the program's ``xent``
scope."""
from lgcbench import scopes as S


def read(ctx):
    return S.per_step_ms(S.scope_ns(
        ctx["trace"], lambda st: S.under(st, S.XENT)), ctx)
