"""jit'd public wrappers around the Pallas kernels.

Handle padding to tile alignment, threshold estimation for the fused EF
kernel, the im2col lowering of the LGC encoder convs onto the fused
matmul kernel, and the hierarchical merge for exact global top-k.

``interpret`` defaults to None: compiled on a TPU backend, interpreted
elsewhere (repro.kernels.interpret_mode).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import block_topk as _bt
from repro.kernels import matmul_lrelu as _mm
from repro.kernels import segmented_topk as _st
from repro.kernels import sparsify_ef as _ef

SEG_BLOCK = _st.BLOCK


def _pad_to(x, mult, value=0.0):
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], value,
                                         x.dtype)])
    return x, pad


# ---------------------------------------------------------------------------
# fused error-feedback sparsification


@functools.partial(jax.jit, static_argnames=("k", "sample_stride",
                                             "interpret"))
def estimate_threshold(v: jnp.ndarray, k: int, sample_stride: int = 32,
                       interpret: bool | None = None) -> jnp.ndarray:
    """DGC sampled-threshold on TPU: top-k over a strided VMEM-resident
    subsample, scaled to the full population.  Exactness is not required —
    the EF accumulators re-absorb anything the threshold misses."""
    sample = jnp.abs(v[::sample_stride])
    k_s = max(1, min(sample.shape[0], int(np.ceil(k / sample_stride))))
    vals, _ = jax.lax.top_k(sample, k_s)
    return vals[-1]


def sparsify_ef(g, u, v, tau, momentum, interpret: bool | None = None):
    """Fused EF pass over arbitrary-length flat vectors (auto-padded)."""
    n = g.shape[0]
    gp, pad = _pad_to(g, _ef.TILE)
    up, _ = _pad_to(u, _ef.TILE)
    vp, _ = _pad_to(v, _ef.TILE)
    u2, v2, sent = _ef.sparsify_ef(
        gp, up, vp, jnp.asarray(tau, jnp.float32),
        jnp.asarray(momentum, jnp.float32), interpret=interpret)
    return u2[:n], v2[:n], sent[:n]


# ---------------------------------------------------------------------------
# exact global top-k via block-local top-k + tiny merge


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def global_topk(x: jnp.ndarray, k: int, block: int = 64 * 128,
                interpret: bool | None = None):
    """Exact global top-|x| selection: block-local top-k kernel + merge.

    Each block keeps its own top-k candidates (the global winners are a
    subset by pigeonhole), then jax.lax.top_k merges the tiny candidate
    set (k * n_blocks elements, VMEM-resident).
    Returns (values (k,), global indices (k,) int32).
    """
    n = x.shape[0]
    xp, _ = _pad_to(x, block)
    nb = xp.shape[0] // block
    kb = min(k, block)
    vals, idx = _bt.block_topk(xp.reshape(nb, block), kb,
                               interpret=interpret)
    gidx = idx + (jnp.arange(nb, dtype=jnp.int32) * block)[:, None]
    cand_vals = vals.reshape(-1)
    cand_idx = gidx.reshape(-1)
    # mask padding positions out of candidacy
    valid = cand_idx < n
    mags = jnp.where(valid, jnp.abs(cand_vals), -1.0)
    _, top = jax.lax.top_k(mags, k)
    return cand_vals[top], cand_idx[top]


# ---------------------------------------------------------------------------
# segmented sweep: whole-vector per-leaf selection in ONE launch


@functools.partial(jax.jit, static_argnames=("n_cand", "block", "extract",
                                             "interpret"))
def segmented_topk(x: jnp.ndarray, seg: jnp.ndarray, kcap: jnp.ndarray,
                   n_cand: int, block: int = SEG_BLOCK,
                   extract: str = "loop", interpret: bool | None = None):
    """Candidate sweep over an arbitrary-length flat vector (auto-padded).

    ``seg`` maps each element to a selection slot (-1 = not selectable),
    ``kcap`` gives each slot's top-k cap, ``n_cand`` the per-block
    candidate budget (see sparsify's layout metadata).  ``extract``
    picks the per-block backend ("loop" | "bitonic" — bit-identical,
    see kernels/bitonic.py).  Returns flattened (vals, idx, slot)
    candidate triples with idx in element coordinates of ``x``; the
    exact per-slot top-k is a tiny lax.top_k merge over these
    (core/sparsify._merge_candidates).
    """
    xp, _ = _pad_to(x, block)
    segp, _ = _pad_to(seg, block, value=-1)
    nb = xp.shape[0] // block
    cv, ci, cs = _st.segmented_topk(xp.reshape(nb, block),
                                    segp.reshape(nb, block), kcap, n_cand,
                                    extract=extract, interpret=interpret)
    return cv.reshape(-1), ci.reshape(-1), cs.reshape(-1)


@functools.partial(jax.jit, static_argnames=("use_momentum", "n_cand",
                                             "block", "extract",
                                             "interpret"))
def fused_ef_topk(g, u, v, seg, kcap, momentum, use_momentum: bool,
                  n_cand: int, block: int = SEG_BLOCK,
                  extract: str = "loop", interpret: bool | None = None):
    """One-sweep EF accumulate + segmented top-k candidates (auto-padded).

    u' = m*u + g, v' = v + u' (plain v + g when use_momentum=False) and
    the per-slot candidate extraction of v', in a single kernel launch —
    one HBM read of (g, u, v), one write of (u', v').
    Returns (u', v', cand_vals, cand_idx, cand_seg).
    """
    n = g.shape[0]
    gp, _ = _pad_to(g, block)
    up, _ = _pad_to(u, block)
    vp, _ = _pad_to(v, block)
    segp, _ = _pad_to(seg, block, value=-1)
    nb = gp.shape[0] // block
    u2, v2, cv, ci, cs = _ef.sparsify_ef_topk(
        gp.reshape(nb, block), up.reshape(nb, block), vp.reshape(nb, block),
        segp.reshape(nb, block), kcap, jnp.asarray(momentum, jnp.float32),
        use_momentum, n_cand, extract=extract, interpret=interpret)
    return u2[:n], v2[:n], cv.reshape(-1), ci.reshape(-1), cs.reshape(-1)


# ---------------------------------------------------------------------------
# LGC encoder through the fused matmul kernel


def _im2col_1d(x: jnp.ndarray, ksize: int, stride: int) -> jnp.ndarray:
    """x: (L, C) -> (L_out, ksize*C), SAME padding."""
    L, C = x.shape
    L_out = (L + stride - 1) // stride
    pad_total = max((L_out - 1) * stride + ksize - L, 0)
    lo = pad_total // 2
    xp = jnp.pad(x, ((lo, pad_total - lo), (0, 0)))
    starts = jnp.arange(L_out) * stride
    cols = jax.vmap(
        lambda s: jax.lax.dynamic_slice_in_dim(xp, s, ksize, 0))(starts)
    return cols.reshape(L_out, ksize * C)


def conv1d_lrelu(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 stride: int, apply_lrelu: bool = True,
                 interpret: bool | None = None) -> jnp.ndarray:
    """One LGC-AE conv layer on the MXU kernel.  x: (L, C_in); w:
    (ksize, C_in, C_out).  Returns (L_out, C_out) f32."""
    ksize, C_in, C_out = w.shape
    cols = _im2col_1d(x, ksize, stride)                   # (L_out, k*C_in)
    M, K = cols.shape
    Mp = (-M) % _mm.TM
    Kp = (-K) % _mm.TK
    Np = (-C_out) % _mm.TN
    cols = jnp.pad(cols, ((0, Mp), (0, Kp)))
    wf = jnp.pad(w.reshape(K, C_out), ((0, Kp), (0, Np)))
    bf = jnp.pad(b, (0, Np))
    y = _mm.matmul_bias_lrelu(cols, wf, bf, apply_lrelu=apply_lrelu,
                              interpret=interpret)
    return y[:M, :C_out]


def lgc_encode_fast(ae_params, g: jnp.ndarray, interpret: bool | None = None):
    """Kernel-backed version of core.autoencoder.lgc_encode for a single
    vector g: (L,) with L % 16 == 0.  Returns (L/16, 4)."""
    from repro.core.autoencoder import ENCODER_SPEC
    x = g[:, None].astype(jnp.float32)
    for p, (_c, _k, s) in zip(ae_params["encoder"], ENCODER_SPEC):
        x = conv1d_lrelu(x, p["w"], p["b"], s, apply_lrelu=True,
                         interpret=interpret)
    return x
