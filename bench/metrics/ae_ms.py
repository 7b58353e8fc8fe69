"""Device time per step of the gradient autoencoder: encode, decode and
its online update (its all-reduce of gradients included), the
operations under the program's ``ae`` scope."""
from lgcbench import scopes as S


def read(ctx):
    return S.per_step_ms(S.scope_ns(
        ctx["trace"], lambda st: S.under(st, S.AE)), ctx)
