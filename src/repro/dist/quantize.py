"""Symmetric int8 block quantization — ONE module for fake and real wires.

``lgc_rar_q8`` claims a 1-byte-per-value encoding reduction.  Whether that
claim is *real* depends on the transport: the int8 ring
(:func:`repro.dist.collectives.ring_allreduce_q8`) actually ships int8
payloads + per-block f32 scales over ``ppermute``, while the float-wire
transports (mesh/ring/hier) can only *fake* it — quantize→dequantize per
node and reduce in f32 (4 bytes/value on the wire, and ``rate.py``
accounts it as such).  Both paths quantize through the functions here, so
Sim (fake) == RingQ8 (real) numerics differ only by the wire's extra
requantization hops — a bounded, testable error — and the byte accounting
has a single source of truth (:func:`wire_nbytes`), shared by the
trace-time wire tally and ``repro.core.rate``.

Scheme: the flat value vector is padded to a multiple of ``scale_block``
and each block gets one f32 scale ``max|x_block| / 127``; values are
round-to-nearest into [-127, 127].  Per-block (not per-tensor) scales
keep the error proportional to the *local* magnitude, which matters for
the ring's partial sums whose dynamic range grows hop over hop.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.dist import chaos as CH

SCALE_BLOCK = 256     # values per f32 scale: 4/256 = 1.6% byte overhead
_EPS = 1e-12          # all-zero blocks quantize to 0 without dividing by 0


def _blocked(x: jnp.ndarray, scale_block: int) -> jnp.ndarray:
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % scale_block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, scale_block)


def quantize_i8(x: jnp.ndarray, scale_block: int = SCALE_BLOCK):
    """-> (q int8 (m, scale_block), scales f32 (m,)) of the flattened,
    zero-padded ``x`` — exactly what the int8 ring puts on the wire.

    Hardened against non-finite input: a NaN/Inf element would otherwise
    poison its whole block's scale (``max|x|`` of anything containing
    NaN is NaN) and from there every downstream partial sum, so
    non-finite elements quantize to zero and their count is a *recorded
    event* — reported to the executor's per-op fault tally when a guard
    policy has a structural sink open, free (an isfinite + where on an
    already-materialized block matrix) when not.  Finite inputs are
    untouched: the masked path is bit-identical to the historical one."""
    xb = _blocked(x.astype(jnp.float32), scale_block)
    nonfinite = ~jnp.isfinite(xb)
    if CH.structural_sink_active():
        CH.report_structural(jnp.sum(nonfinite.astype(jnp.int32)))
    xb = jnp.where(nonfinite, jnp.zeros_like(xb), xb)
    scales = jnp.maximum(jnp.max(jnp.abs(xb), axis=1), _EPS) / 127.0
    q = jnp.clip(jnp.round(xb / scales[:, None]), -127, 127)
    return q.astype(jnp.int8), scales


def dequantize_i8(q: jnp.ndarray, scales: jnp.ndarray, n: int,
                  shape=None) -> jnp.ndarray:
    """Inverse of :func:`quantize_i8`: drop the padding, restore shape."""
    flat = (q.astype(jnp.float32) * scales[:, None]).reshape(-1)[:n]
    return flat.reshape(shape) if shape is not None else flat


def fake_quantize(x: jnp.ndarray, scale_block: int = SCALE_BLOCK):
    """quantize→dequantize roundtrip in the float domain: what a
    float-wire transport applies per node so its numerics track the int8
    wire (the bytes stay f32 — that is the point of calling it fake)."""
    q, scales = quantize_i8(x, scale_block)
    return dequantize_i8(q, scales, x.size, x.shape)


def quantize_pack_fused(vals: jnp.ndarray, idx_lo: jnp.ndarray,
                        width: int, scale_block: int = SCALE_BLOCK,
                        interpret: bool | None = None):
    """Fused single-kernel encode of a sorted sparse payload: ONE
    Pallas launch block-quantizes ``vals`` (-> int8 + per-block scales,
    exactly :func:`quantize_i8`'s math) AND bit-plane packs the masked
    low index bits ``idx_lo`` (-> ``(width, ceil(k/32))`` int32 words,
    exactly ``kernels.bitpack.pack_bits``'s layout), so the (vals, idx)
    pair is read from HBM once per bucket instead of once per pass.
    Returns ``(words, q, scales)``; bit-exact against the composed path.

    No structural-fault reporting: the fused kernel cannot surface the
    non-finite count (it masks them to zero like :func:`quantize_i8`
    does), so callers running under an open structural sink must use the
    composed path instead (see ``packed.encode_sparse_fused``)."""
    from repro.kernels import bitpack as BP
    return BP.quantize_pack(vals, idx_lo, width, scale_block, _EPS,
                            interpret)


def wire_nbytes(n: int, scale_block: int = SCALE_BLOCK) -> int:
    """Wire bytes of the int8 representation of ``n`` values: the padded
    int8 payload + one f32 scale per block.  Single source of truth for
    both the trace-time wire tally (collectives.ring_allreduce_q8) and
    the payload accounting (core.rate) — they match by construction."""
    m = -(-n // scale_block)
    return m * scale_block * 1 + m * 4
