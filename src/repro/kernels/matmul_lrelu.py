"""Tiled matmul + bias + LeakyReLU fusion (TPU Pallas) — the MXU hot spot
of the LGC autoencoder.

Each 1-D conv layer of the encoder/decoder lowers (after an im2col
unfold done in ops.py) to  Y = lrelu(X @ W + b)  with
X: (L_out, K*C_in), W: (K*C_in, C_out).  This kernel runs that matmul in
(TM, TK) x (TK, TN) VMEM tiles with 128-aligned MXU dimensions, f32
accumulation in a VMEM scratch accumulator, and the bias + LeakyReLU
epilogue fused into the final K-step — the activation never round-trips
to HBM between the matmul and the nonlinearity.

Grid: (M/TM, N/TN, K/TK), K innermost so the accumulator revision stays
in VMEM across the contraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode
from jax.experimental.pallas import tpu as pltpu

TM, TN, TK = 128, 128, 128
LEAKY_SLOPE = 0.01


def _matmul_lrelu_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, nk: int,
                         apply_lrelu: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        y = acc_ref[...] + b_ref[...]
        if apply_lrelu:
            y = jnp.where(y >= 0, y, LEAKY_SLOPE * y)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("apply_lrelu", "interpret"))
def matmul_bias_lrelu(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                      apply_lrelu: bool = True, interpret: bool | None = None):
    """x: (M, K), w: (K, N), b: (N,); all dims multiples of the 128 tiles
    (ops.py pads).  Returns lrelu(x @ w + b): (M, N) f32."""
    M, K = x.shape
    N = w.shape[1]
    assert M % TM == 0 and K % TK == 0 and N % TN == 0, (M, K, N)
    nk = K // TK
    kern = functools.partial(_matmul_lrelu_kernel, nk=nk,
                             apply_lrelu=apply_lrelu)
    return pl.pallas_call(
        kern,
        grid=(M // TM, N // TN, nk),
        in_specs=[
            pl.BlockSpec((TM, TK), lambda i, j, k: (i, k)),
            pl.BlockSpec((TK, TN), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, TN), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((TM, TN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((TM, TN), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(x, w, b.reshape(1, N))
