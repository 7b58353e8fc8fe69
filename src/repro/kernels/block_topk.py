"""Block-local top-k kernel (TPU Pallas).

GPU top-k is a global sort; that algorithm doesn't map to the TPU memory
hierarchy.  Instead we re-block the problem: the flat gradient is split
into VMEM-tile-sized blocks and each grid step finds the top-k of ONE
block with k iterations of (max -> record -> mask) on the VPU.  A
hierarchical merge (handled in ops.py with jax.lax.top_k over the tiny
per-block candidate set, k·n_blocks elements) yields the exact global
top-k as long as k_block >= k_global/n_blocks holds — which ops.py
enforces by construction (k_block = k_global, i.e. the per-block candidate
set always contains the global winners).

This is DGC's sampled-threshold idea rethought for HBM->VMEM streaming:
one pass over the data, no global sort, exact result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

from repro.kernels.segmented_topk import (LANE, cand_rows, cand_unpack,
                                          flat_iota, put_candidate)


def _block_topk_kernel(x_ref, vals_ref, idx_ref, *, k: int, block: int):
    x = x_ref[0]                                     # (block//LANE, LANE)
    mag = jnp.abs(x)
    flat_idx = flat_iota(x.shape)

    def body(i, mag):
        m = jnp.max(mag)
        # first position achieving the max
        pos = jnp.min(jnp.where(mag == m, flat_idx, block))
        put_candidate(vals_ref, i, jnp.sum(jnp.where(flat_idx == pos, x,
                                                     0.0)))
        put_candidate(idx_ref, i, pos)
        return jnp.where(flat_idx == pos, -1.0, mag)

    jax.lax.fori_loop(0, k, body, mag)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def block_topk(x: jnp.ndarray, k: int, interpret: bool | None = None):
    """x: (n_blocks, block) f32, block % 128 == 0.  Returns per-block
    (values (n_blocks, k), indices (n_blocks, k) int32, local to block).
    Results are stored lane-dense, (cand_rows(k), 128) per block."""
    n_blocks, block = x.shape
    assert block % LANE == 0, block
    kern = functools.partial(_block_topk_kernel, k=k, block=block)
    out_spec = pl.BlockSpec((1, cand_rows(k), LANE), lambda i: (i, 0, 0))
    out_shape = (n_blocks, cand_rows(k), LANE)
    vals, idx = pl.pallas_call(
        kern,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((1, block // LANE, LANE),
                               lambda i: (i, 0, 0))],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct(out_shape, x.dtype),
                   jax.ShapeDtypeStruct(out_shape, jnp.int32)],
        interpret=interpret_mode(interpret),
    )(x.reshape(n_blocks, block // LANE, LANE))
    return cand_unpack((vals, idx), k)
