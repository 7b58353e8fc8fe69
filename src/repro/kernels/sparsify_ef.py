"""Fused error-feedback sparsification kernel (TPU Pallas).

Algorithm 1/2 inner loop of the paper, fused into ONE pass over HBM:

    u' = m*u + g                      (momentum accumulation)
    v' = v + u'                       (residual accumulation)
    mask = |v'| >= tau                (threshold selection)
    sent = v' * mask                  (transmitted sparse values, dense form)
    v_out = v' * (1-mask);  u_out = u' * (1-mask)

On GPU the paper pays four separate elementwise kernels for this
bookkeeping; on TPU we stream 64K-element VMEM tiles (8×128-aligned) and
do all five ops per tile, so the pass is bounded by one HBM read of (g,u,v)
and one write of (u,v,sent) — purely bandwidth-bound, zero extra traffic.

The threshold tau comes from the sampled-top-k estimator in ops.py (the
DGC trick adapted to TPU: estimate on a strided VMEM-resident sample, then
apply globally with this kernel).

``sparsify_ef_topk`` extends the same one-pass idea to the *exact*
selection the training hot path needs: instead of an approximate
threshold mask, each tile also runs the segmented candidate extraction
from kernels/segmented_topk.py on the freshly accumulated residual, so
accumulate + per-leaf top-k is ONE kernel launch, one HBM read of
(g, u, v) and one write of (u, v) plus a k-scale candidate side output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

from repro.kernels.segmented_topk import (cand_out_shapes, cand_unpack,
                                          extract_fn, sweep_specs)

TILE = 64 * 1024          # elements per VMEM tile (f32: 256 KiB per operand)
LANE = 128                # TPU lane width; tiles are (TILE//LANE, LANE)


def _sparsify_ef_kernel(g_ref, u_ref, v_ref, tau_ref, m_ref, u_out_ref,
                        v_out_ref, sent_ref):
    g = g_ref[...]
    u = u_ref[...]
    v = v_ref[...]
    tau = tau_ref[0]
    m = m_ref[0]
    u_new = m * u + g
    v_new = v + u_new
    keep = jnp.abs(v_new) >= tau
    sent = jnp.where(keep, v_new, 0.0)
    u_out_ref[...] = jnp.where(keep, 0.0, u_new)
    v_out_ref[...] = jnp.where(keep, 0.0, v_new)
    sent_ref[...] = sent


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparsify_ef(g: jnp.ndarray, u: jnp.ndarray, v: jnp.ndarray,
                tau: jnp.ndarray, momentum: jnp.ndarray,
                interpret: bool | None = None):
    """g, u, v: flat f32 (n,) with n % TILE == 0 (pad in ops.py).

    Returns (u_out, v_out, sent).  ``interpret`` defaults to the
    platform (repro.kernels.interpret_mode).
    """
    n = g.shape[0]
    assert n % TILE == 0, n
    rows = TILE // LANE
    shape2d = (n // LANE, LANE)
    grid = (n // TILE,)
    spec = pl.BlockSpec((rows, LANE), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec((1,), lambda i: (0,))
    out = pl.pallas_call(
        _sparsify_ef_kernel,
        grid=grid,
        in_specs=[spec, spec, spec, scalar_spec, scalar_spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct(shape2d, jnp.float32)] * 3,
        interpret=interpret_mode(interpret),
    )(g.reshape(shape2d), u.reshape(shape2d), v.reshape(shape2d),
      tau.reshape(1), momentum.reshape(1))
    u_out, v_out, sent = (o.reshape(n) for o in out)
    return u_out, v_out, sent


# ---------------------------------------------------------------------------
# fused EF accumulate + exact segmented top-k (one sweep)


def _ef_topk_kernel(g_ref, u_ref, v_ref, seg_ref, kcap_ref, scal_ref,
                    u_out_ref, v_out_ref, vals_ref, idx_ref, seg_out_ref,
                    *, use_momentum: bool, n_cand: int, block: int,
                    extract: str):
    g = g_ref[0]
    u = u_ref[0]
    v = v_ref[0]
    if use_momentum:
        u_new = scal_ref[0] * u + g
        v_new = v + u_new
    else:                                # sparse_gd: plain residual accum
        u_new = u
        v_new = v + g
    u_out_ref[0] = u_new
    v_out_ref[0] = v_new
    extract_fn(extract)(v_new, seg_ref[0], kcap_ref[...], n_cand, block,
                        (vals_ref, idx_ref, seg_out_ref),
                        pl.program_id(0) * block)


@functools.partial(jax.jit,
                   static_argnames=("use_momentum", "n_cand", "extract",
                                    "interpret"))
def sparsify_ef_topk(g: jnp.ndarray, u: jnp.ndarray, v: jnp.ndarray,
                     seg: jnp.ndarray, kcap: jnp.ndarray,
                     momentum: jnp.ndarray, use_momentum: bool,
                     n_cand: int, extract: str = "loop",
                     interpret: bool | None = None):
    """Fused Algorithm 1/2 inner loop + exact segmented selection.

    g, u, v, seg: (n_blocks, block); kcap: (n_slots,) int32.  Returns
    (u_out, v_out flat (n_blocks*block,), candidate vals/idx/seg each
    (n_blocks, n_cand) — see segmented_topk.segmented_topk).  With
    use_momentum=False the accumulate is sparse-GD's plain ``v + g``.
    """
    n_blocks, block = g.shape
    assert block % LANE == 0, block
    rows = block // LANE
    scal = jnp.asarray(momentum, jnp.float32).reshape(1)
    kern = functools.partial(_ef_topk_kernel, use_momentum=use_momentum,
                             n_cand=n_cand, block=block, extract=extract)
    tile, cand, kspec = sweep_specs(rows, n_cand, kcap.shape[0])
    out = pl.pallas_call(
        kern,
        grid=(n_blocks,),
        in_specs=[tile, tile, tile, tile, kspec,
                  pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=[tile, tile, cand, cand, cand],
        out_shape=[jax.ShapeDtypeStruct((n_blocks, rows, LANE),
                                        jnp.float32)] * 2 +
                  cand_out_shapes(n_blocks, n_cand, jnp.float32),
        interpret=interpret_mode(interpret),
    )(g.reshape(n_blocks, rows, LANE), u.reshape(n_blocks, rows, LANE),
      v.reshape(n_blocks, rows, LANE), seg.reshape(n_blocks, rows, LANE),
      kcap[None], scal)
    u_out, v_out = out[:2]
    n = n_blocks * block
    return (u_out.reshape(n), v_out.reshape(n)) + cand_unpack(out[2:],
                                                              n_cand)
