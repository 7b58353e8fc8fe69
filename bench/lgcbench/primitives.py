"""The reference's numerics and the layer pieces that model kinds share.

Straightforward ``jax.numpy`` at HIGHEST matmul precision, importing
nothing of the program.  A kind module (``bench/kinds/<kind>.py``)
builds its layers from these and walks its superblock with
:func:`scan_positions`.

``Numerics`` sets the precision of every matrix product and convolution
and of the residual stream between layers: :data:`F32` is the
reference; :func:`fp8_numerics` rounds both operands of each product
(and the cotangent in the backward pass) and the residual stream to
float8 e4m3 with one scale per tensor, which is the control that has to
come out as not correct.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# numerics


@dataclass(frozen=True)
class Numerics:
    name: str
    quant: Callable          # applied to each operand of a product


def _ident(x):
    return x


F32 = Numerics("f32", _ident)


def _fp8_round(x):
    """Round to float8 e4m3 (4 exponent and 3 mantissa bits) with one
    scale per tensor, amax to e4m3's largest IEEE-style value, 240.
    ``reduce_precision`` is used because XLA may drop a
    convert-to-narrower-and-back pair on the TPU.  The rounding passes
    gradients straight through; the products round their cotangents
    themselves (``_qeinsum``)."""
    x = x.astype(jnp.float32)
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, amax / 240.0, 1.0)
    q = jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                 mantissa_bits=3) * scale
    return x + jax.lax.stop_gradient(q - x)


def fp8_numerics() -> Numerics:
    return Numerics("fp8", _fp8_round)


def einsum(nm: Numerics, eq: str, a, b):
    if nm.quant is _ident:
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    return _qeinsum(nm.quant, eq, a, b)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _qeinsum(quant, eq, a, b):
    return jnp.einsum(eq, quant(a), quant(b), precision=HIGHEST)


def _qeinsum_fwd(quant, eq, a, b):
    qa, qb = quant(a), quant(b)
    return jnp.einsum(eq, qa, qb, precision=HIGHEST), (qa, qb)


def _qeinsum_bwd(quant, eq, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(eq, x, y, precision=HIGHEST),
                     qa, qb)
    return vjp(quant(g))


_qeinsum.defvjp(_qeinsum_fwd, _qeinsum_bwd)


# ---------------------------------------------------------------------------
# layer pieces


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def segsum(x):
    """x: (..., T) -> (..., T, T) with out[i, j] = sum x[j+1..i] for
    i >= j and -inf above the diagonal (the paper's stable segsum)."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], x.shape + (T,))      # [.., i, j]=x_i
    below = jnp.tril(jnp.ones((T, T), bool), -1)
    xx = jnp.where(below, xx, 0.0)
    seg = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), seg, -jnp.inf)


def ssd(nm, x, dt, A, B, C, chunk):
    """SSD scan of one sequence, chunked as in arXiv:2405.21060 Listing 1.

    x (S, H, P), dt (S, H), A (H,), B and C (S, N) -> y (S, H, P) with
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t,  y_t = C_t h_t."""
    S, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    c = S // Q
    X = (x * dt[..., None]).reshape(c, Q, H, P)
    dA = (dt * A[None, :]).reshape(c, Q, H).transpose(2, 0, 1)   # (H,c,Q)
    Bc = B.reshape(c, Q, N)
    Cc = C.reshape(c, Q, N)
    A_cum = jnp.cumsum(dA, axis=-1)                               # (H,c,Q)
    L = jnp.exp(segsum(dA))                                       # (H,c,Q,Q)
    CB = einsum(nm, "cqn,ckn->cqk", Cc, Bc)
    Y_diag = einsum(nm, "hcqk,ckhp->cqhp", L * CB[None], X)
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)               # (H,c,Q)
    states = einsum(nm, "ckn,ckhp->chpn",
                    Bc, X * decay_states.transpose(1, 2, 0)[..., None])
    states = jnp.concatenate([jnp.zeros_like(states[:1]), states], axis=0)
    chunk_decay = jnp.exp(segsum(jnp.pad(A_cum[..., -1], ((0, 0), (1, 0)))))
    new_states = einsum(nm, "hzc,chpn->zhpn", chunk_decay,
                        states)                                   # (c+1,..)
    prev = new_states[:-1]                                        # (c,H,P,N)
    out_decay = jnp.exp(A_cum).transpose(1, 2, 0)                 # (c,Q,H)
    Y_off = einsum(nm, "cqn,chpn->cqhp", Cc, prev) * out_decay[..., None]
    return (Y_diag + Y_off).reshape(S, H, P)


def rope(x, theta):
    """x (S, H, d): rotate the two halves of each head by position."""
    S, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def scan_positions(nm, params, x, layer_of):
    """The residual stream ``x`` through every superblock.

    ``params["blocks"]`` holds the pattern's positions ``p0..p{n-1}``,
    each stacked over the blocks on a leading axis; ``layer_of(i)`` is
    position ``i``'s layer, ``(p, x) -> x``.  One ``lax.scan`` walks the
    blocks, each applying its positions in order under
    ``jax.checkpoint``.  The residual stream is stored at the numerics'
    precision between layers, as the program stores its activations at
    the configured dtype."""
    blocks = params["blocks"]
    layers = [layer_of(i) for i in range(len(blocks))]

    def block(x, p):
        for i, layer in enumerate(layers):
            x = nm.quant(layer(p[f"p{i}"], x))
        return x, None
    x, _ = jax.lax.scan(jax.checkpoint(block), x, blocks)
    return x
