"""Device time per step of all of the compressor's local work: the
operations under the program's ``compress``, ``select`` or ``ae``
scopes and not under an ``exchange`` scope."""
from lgcbench import scopes as S


def read(ctx):
    return S.per_step_ms(S.scope_ns(
        ctx["trace"], lambda st: S.under(st, *S.COMPRESSOR)
        and not S.exchanged(st)), ctx)
