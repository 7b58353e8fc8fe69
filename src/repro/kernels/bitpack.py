"""Bit-packing kernels (TPU Pallas): int32 values <-> b-bit packed words.

The sparse exchanges (sparse_gd / dgc / lgc_ps, plus every method's
exempt-last top-k) ship index sets whose entries fit in
``ceil(log2 n)`` bits, yet the f32 wire moves raw int32 — the
"documented slack" between ``rate.py``'s entropy-coded accounting and
the measured tally.  These kernels close that gap structurally with a
*bit-plane* layout that is exactly vectorizable on the VPU:

  pack    values reshaped (GROUP=32, W) column-major-by-word; plane b,
          word j collects bit b of the 32 values in column j:
          ``word[b, j] = sum_r ((x[r, j] >> b) & 1) << r``  — one shift/
          mask/weighted-sum over a (GROUP, LANE) = (32, 128) int32
          block per plane (four native (8, 128) sublane tiles).
  unpack  the exact inverse: ``x[r, j] = sum_b ((word[b, j] >> r) & 1)
          << b``.

The packed representation of k values at width b is ``b`` planes of
exactly ``ceil(k/32)`` int32 words, i.e. ~``b`` bits per value — this IS
the wire payload the packed transports ppermute, and
:func:`packed_nbytes` is the single accounting source of truth shared by
the trace-time tally and ``repro.core.rate``.  Full (GROUP, LANE) tiles
go through the Pallas kernels; the sub-lane tail columns (< 128 words —
the *whole* payload for small-k exchanges like the PS innovations) take
an identical-semantics jnp path, so small exchanges pay ``ceil(k/32)``
words instead of the old 128-word lane floor that used to force
``make_plan`` into its raw-int32 fallback.

Exactness contract: for any values in ``[0, 2**width)`` the roundtrip
``unpack(pack(x, width), k) == x`` is bit-exact (property-tested over
widths 1..31, unaligned k, all-zero and all-max inputs in
``tests/test_bitpack.py``).  Bit 31 is deliberately unsupported as a
*value* bit (int32 sign); widths run 1..31, enough for any index
``<= n`` at any real model scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

LANE = 128            # TPU lane count: words per plane per grid step
GROUP = 32            # values packed into one int32 word (one per bit row)
MAX_WIDTH = 31        # value bits; bit 31 is the int32 sign


def bit_width(n: int) -> int:
    """Bits needed to represent any value in ``[0, n]`` — *inclusive*,
    because the sparsifier pads index sets with the sentinel ``n``
    (``select_topk``'s mu_pad padding), which must survive the wire."""
    w = max(1, int(n).bit_length())
    assert w <= MAX_WIDTH, (n, w)
    return w


def word_count(k: int) -> int:
    """int32 words per bit-plane for ``k`` values: exactly ceil(k/GROUP).
    No lane padding — the wire ships only real words; the kernels sweep
    the full-LANE prefix and a jnp path handles the sub-lane tail."""
    return -(-max(int(k), 1) // GROUP)


def packed_nbytes(k: int, width: int) -> int:
    """Wire bytes of the packed representation of ``k`` values at
    ``width`` bits — exactly the (width, W) int32 array the packed
    transports put on the wire, padding included.  Single source of
    truth for the trace-time tally and ``repro.core.rate``."""
    return width * word_count(k) * 4


def _pack_kernel(x_ref, out_ref, *, width: int):
    x = x_ref[...]                                      # (GROUP, LANE) i32
    r = jax.lax.broadcasted_iota(jnp.int32, (GROUP, LANE), 0)
    planes = []
    for b in range(width):
        bits = (x >> b) & 1
        planes.append(jnp.sum(bits << r, axis=0))       # (LANE,) i32
    out_ref[...] = jnp.stack(planes)                    # (width, LANE)


def _unpack_kernel(w_ref, out_ref, *, width: int):
    w = w_ref[...]                                      # (width, LANE) i32
    r = jax.lax.broadcasted_iota(jnp.int32, (GROUP, LANE), 0)
    acc = jnp.zeros((GROUP, LANE), jnp.int32)
    for b in range(width):
        bits = (w[b][None, :] >> r) & 1                 # arithmetic >>; &1
        acc = acc | (bits << b)
    out_ref[...] = acc


def _encode_fused_kernel(v_ref, x_ref, words_ref, q_ref, s_ref, *,
                         width: int, eps: float):
    """Fused sparse-wire encode: block-quantize the values AND bit-plane
    pack the (pre-masked) low index bits in one program — the (vals, idx)
    pair is read from HBM exactly once per bucket instead of once per
    pass of the composed quantize -> pack pipeline."""
    xb = v_ref[...]                                     # (m, sb) f32
    xb = jnp.where(jnp.isfinite(xb), xb, jnp.zeros_like(xb))
    scales = jnp.maximum(jnp.max(jnp.abs(xb), axis=1, keepdims=True),
                         eps) / 127.0
    q_ref[...] = jnp.clip(jnp.round(xb / scales), -127, 127
                          ).astype(jnp.int8)
    s_ref[...] = scales
    x = x_ref[...]                                      # (GROUP, W) i32
    r = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    words_ref[...] = jnp.stack(
        [jnp.sum(((x >> b) & 1) << r, axis=0) for b in range(width)])


def quantize_pack(vals: jnp.ndarray, idx_lo: jnp.ndarray, width: int,
                  scale_block: int, eps: float, interpret: bool | None = None):
    """Single-launch fused encode of a sorted sparse payload:
    ``vals`` (k,) f32 block-quantizes to (q int8 (m, scale_block),
    scales f32 (m,)) and ``idx_lo`` (k,) int32 (already masked to
    ``width`` low bits) bit-plane packs to (width, word_count(k)) int32 —
    all three outputs from ONE ``pallas_call``.  Bit-exact against the
    composed :func:`repro.dist.quantize.quantize_i8` + :func:`pack_bits`
    path: the elementwise quantize math is identical, the block max and
    the bit-plane integer sums are order-independent, and the zero
    padding added here matches the composed padding exactly.

    Deliberately NOT jit-wrapped so the single ``pallas_call`` shows up
    plainly in callers' jaxprs (asserted in tests); padding/reshape is
    pure layout the compiler folds into the kernel's operand windows.
    ``eps`` is the caller's all-zero-block guard (quantize._EPS — passed
    in because the kernel layer must not import the dist layer)."""
    assert 1 <= width <= MAX_WIDTH, width
    k = vals.shape[0]
    assert k >= 1 and idx_lo.shape[0] == k, (vals.shape, idx_lo.shape)
    W = word_count(k)
    m = -(-k // scale_block)
    v = vals.astype(jnp.float32)
    vpad = m * scale_block - k
    if vpad:
        v = jnp.concatenate([v, jnp.zeros((vpad,), jnp.float32)])
    x = idx_lo.astype(jnp.int32)
    ipad = GROUP * W - k
    if ipad:
        x = jnp.concatenate([x, jnp.zeros((ipad,), jnp.int32)])
    kern = functools.partial(_encode_fused_kernel, width=width, eps=eps)
    words, q, scales = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((width, W), jnp.int32),
                   jax.ShapeDtypeStruct((m, scale_block), jnp.int8),
                   jax.ShapeDtypeStruct((m, 1), jnp.float32)),
        interpret=interpret_mode(interpret),
    )(v.reshape(m, scale_block), x.reshape(GROUP, W))
    return words, q, scales[:, 0]


def _pack_tail(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """jnp mirror of :func:`_pack_kernel` for < LANE columns: ``x``
    (GROUP, Wt) int32 -> (width, Wt) planes, same shift/mask/weighted-sum
    semantics bit for bit."""
    r = jnp.arange(GROUP, dtype=jnp.int32)[:, None]
    return jnp.stack([jnp.sum(((x >> b) & 1) << r, axis=0)
                      for b in range(width)])


def _unpack_tail(w: jnp.ndarray, width: int) -> jnp.ndarray:
    """jnp mirror of :func:`_unpack_kernel`: (width, Wt) -> (GROUP, Wt)."""
    r = jnp.arange(GROUP, dtype=jnp.int32)[:, None]
    acc = jnp.zeros((GROUP, w.shape[1]), jnp.int32)
    for b in range(width):
        acc = acc | (((w[b][None, :] >> r) & 1) << b)
    return acc


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def pack_bits(x: jnp.ndarray, width: int, interpret: bool | None = None
              ) -> jnp.ndarray:
    """Pack ``x``: (k,) int32 values in ``[0, 2**width)`` into a
    (width, word_count(k)) int32 bit-plane array.  Values beyond the
    width are truncated (callers pick ``width = bit_width(max value)``).
    Full-LANE columns run through the Pallas kernel; the sub-lane tail
    (possibly the whole array, for small k) through the jnp mirror.
    """
    assert 1 <= width <= MAX_WIDTH, width
    k = x.shape[0]
    W = word_count(k)
    pad = GROUP * W - k
    flat = jnp.concatenate([x.astype(jnp.int32),
                            jnp.zeros((pad,), jnp.int32)]) if pad else \
        x.astype(jnp.int32)
    cols = flat.reshape(GROUP, W)
    W_main = (W // LANE) * LANE
    parts = []
    if W_main:
        kern = functools.partial(_pack_kernel, width=width)
        parts.append(pl.pallas_call(
            kern,
            grid=(W_main // LANE,),
            in_specs=[pl.BlockSpec((GROUP, LANE), lambda i: (0, i))],
            out_specs=pl.BlockSpec((width, LANE), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((width, W_main), jnp.int32),
            interpret=interpret_mode(interpret),
        )(cols[:, :W_main]))
    if W > W_main:
        parts.append(_pack_tail(cols[:, W_main:], width))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def unpack_bits(words: jnp.ndarray, k: int, interpret: bool | None = None
                ) -> jnp.ndarray:
    """Inverse of :func:`pack_bits`: (width, W) int32 planes -> the
    first ``k`` original values, bit-exact."""
    width, W = words.shape
    assert 1 <= width <= MAX_WIDTH, width
    assert GROUP * W >= k, (W, k)
    W_main = (W // LANE) * LANE
    parts = []
    if W_main:
        kern = functools.partial(_unpack_kernel, width=width)
        parts.append(pl.pallas_call(
            kern,
            grid=(W_main // LANE,),
            in_specs=[pl.BlockSpec((width, LANE), lambda i: (0, i))],
            out_specs=pl.BlockSpec((GROUP, LANE), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((GROUP, W_main), jnp.int32),
            interpret=interpret_mode(interpret),
        )(words[:, :W_main]))
    if W > W_main:
        parts.append(_unpack_tail(words[:, W_main:], width))
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return out.reshape(-1)[:k]
