"""The packed sparse wire codec: (values, indices) -> real wire payload.

Only ``RingPackedTransport`` ships the payload built here; every other
transport moves the same sparse pairs as exact f32 values + raw int32
indices, so the sparse methods stay bit-exact reproductions unless a
run explicitly opts into the packed wire.  Indices decode bit-exact;
values pay exactly one int8 block quantization (error <= half the
per-block scale — the transport gate's documented q8 bound vs the exact
Sim oracle).  The byte accounting (:func:`wire_nbytes`) is shared with
``repro.core.rate``, so measured == accounted on this wire with no
slack.

Wire format for k (value, index) pairs over a length-n vector, chosen
per (n, k) at trace time by :func:`make_plan`:

  counts   (n_buckets,) int32 — histogram of the *sorted* indices' high
           ``width - lo_bits`` bits.  The receiver re-expands the high
           bits with a fixed-length ``jnp.repeat`` (counts sum to k —
           static), so high bits cost 4·n_buckets bytes TOTAL, not
           per-index.
  words    (lo_bits, W) int32 — the indices' low bits through the
           bit-plane pack kernel (``kernels/bitpack.py``), ~lo_bits bits
           per index.
  q, scales  the values through the shared int8 block quantizer
           (``repro.dist.quantize``): 1 byte/value + one f32 scale per
           ``scale_block`` values.

Pairs are sorted by index before encoding (scatter consumers are
order-free), which is what makes the high bits monotone and
histogram-expressible — the same idea as the Elias-Fano upper structure,
but with fixed shapes end to end so it lives happily inside jit/
shard_map.  ``make_plan`` picks ``lo_bits`` by exact cost minimization
over the (static) (n, k); at n=1M, k=8K the indices cost ~13 bits each
vs 32 raw, and the whole payload lands at ~0.33x of the f32+int32
exchange (gated in ``benchmarks/transports_bench.py``).

Index roundtrip is bit-exact for any indices in ``[0, n]`` (the
``select_topk`` sentinel ``n`` included); values pay exactly one
quantization, bounded by half the per-block scale.  The pack kernels
cost exactly ``ceil(k/32)`` words per plane (the sub-lane tail path in
``kernels/bitpack.py`` — no 128-word lane floor), so even sub-1K-pair
exchanges (k_inv, small k_last) get real bit-packing; ``make_plan``
still falls back to raw sorted int32 indices for the few-index regime
(k ≲ 8) where the bucket histogram alone outweighs 4 bytes/index, so
the packed wire is never worse than raw.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.dist import chaos as CH
from repro.dist import quantize as Q
from repro.kernels import bitpack as BP

# the compressor methods whose sparse exchanges ride this codec (real
# bytes on RingPackedTransport, the fake path elsewhere) — shared by the
# compressor's transport dispatch AND rate.py's byte accounting, so the
# two can never disagree about which exchanges are packed
PACKED_METHODS = ("sparse_gd", "dgc", "lgc_ps")


@dataclass(frozen=True)
class PackPlan:
    """Static wire-format parameters for a (n, k, scale_block) exchange."""
    n: int                  # dense length; indices live in [0, n]
    k: int                  # pairs per node (sentinel padding included)
    width: int              # bit_width(n): total index bits
    lo_bits: int            # bits packed through the bit-plane kernel
    n_buckets: int          # high-bits histogram length
    scale_block: int        # values per f32 scale (shared with quantize)
    raw_index: bool = False  # small-k fallback: sorted raw int32 indices
    checksum: bool = False   # guard option: one trailing int32 sum word

    @property
    def hi_bits(self) -> int:
        return self.width - self.lo_bits


def _index_nbytes(n: int, k: int, lo_bits: int) -> int:
    n_buckets = (n >> lo_bits) + 1
    return 4 * n_buckets + BP.packed_nbytes(k, lo_bits)


def make_plan(n: int, k: int, scale_block: int = 0,
              checksum: bool = False) -> PackPlan:
    """Pick ``lo_bits`` minimizing the exact index payload
    (4·n_buckets + packed_nbytes(k, lo_bits)) — all quantities static,
    so the scan runs at trace time and the optimum is exact.  Plane
    words cost exactly ceil(k/32) each (sub-lane tail path in the
    kernels), so packing wins down to a handful of indices; only when
    even the best (buckets + planes) split costs more than raw int32
    (k ≲ 8) does the plan fall back to shipping the sorted indices raw —
    the packed wire is never worse than 4 bytes/index.  ``checksum``
    appends one int32 sum word to the payload (the guard's structural
    integrity check), priced honestly as +4 bytes in both pricers."""
    assert n >= 1 and k >= 1, (n, k)
    width = BP.bit_width(n)
    best = min(range(1, width + 1),
               key=lambda lo: _index_nbytes(n, k, lo))
    return PackPlan(n=n, k=k, width=width, lo_bits=best,
                    n_buckets=(n >> best) + 1,
                    scale_block=scale_block or Q.SCALE_BLOCK,
                    raw_index=4 * k < _index_nbytes(n, k, best),
                    checksum=checksum)


def bucket_plan(plan: PackPlan, kb: int) -> PackPlan:
    """The per-bucket sub-plan of a bucketed packed exchange: ``kb``
    pairs per bucket with every wire-format parameter (width, lo_bits,
    histogram length, scale_block, checksum) inherited from the parent —
    the buckets are contiguous slices of the SAME sorted index space, so
    each bucket's payload is self-contained and decodes independently.
    Short buckets are sentinel-padded (idx = n, val = 0) to ``kb``;
    the sentinel survives the format (indices live in [0, n]) and the
    receiver's ``mode="drop"`` scatter discards it.  Bucketing keeps the
    parent's lo_bits split rather than re-optimizing per bucket: the
    overhead vs the unbucketed wire is exactly (B-1) extra histograms
    plus the pad pairs — both priced by ``plan.wire_terms``."""
    assert 1 <= kb <= plan.k, (kb, plan.k)
    assert not plan.raw_index, plan
    return PackPlan(n=plan.n, k=kb, width=plan.width,
                    lo_bits=plan.lo_bits, n_buckets=plan.n_buckets,
                    scale_block=plan.scale_block, raw_index=False,
                    checksum=plan.checksum)


def _index_base(plan: PackPlan) -> int:
    # the index half WITHOUT the optional checksum word, so the two
    # public pricers each add exactly one +4 (never double-counted)
    if plan.raw_index:
        return 4 * plan.k
    return _index_nbytes(plan.n, plan.k, plan.lo_bits)


def index_nbytes(plan: PackPlan) -> int:
    """Wire bytes of the index-only payload: counts + packed low-bit
    planes (or the raw int32 indices when the fallback is cheaper),
    plus the checksum word when the plan carries one."""
    return _index_base(plan) + (4 if plan.checksum else 0)


def wire_nbytes(plan: PackPlan) -> int:
    """Total payload bytes one node ships per packed sparse exchange —
    exactly the sum of the encoded arrays' nbytes (asserted against the
    trace-time tally term by term in tests/test_wire_accounting.py).
    The guard's checksum word, when enabled, is one more int32 on the
    wire and is priced here — validation costs bytes, honestly."""
    return _index_base(plan) + Q.wire_nbytes(plan.k, plan.scale_block) \
        + (4 if plan.checksum else 0)


def _sort_pairs(vals: jnp.ndarray, idx: jnp.ndarray):
    order = jnp.argsort(idx)
    return jnp.take(vals, order), jnp.take(idx, order).astype(jnp.int32)


def checksum_word(payload) -> jnp.ndarray:
    """The guard's integrity word over a payload tuple: the int32 sum
    (mod 2^32 — XLA integer adds wrap) of every array viewed as int32
    (int8 widened, f32 bitcast so the check sees the exact wire bits).
    Shape (1,): the word rides the wire as one more payload array and is
    priced as +4 bytes."""
    total = jnp.zeros((), jnp.int32)
    for a in payload:
        if jnp.issubdtype(a.dtype, jnp.floating):
            w = jax.lax.bitcast_convert_type(a.astype(jnp.float32),
                                             jnp.int32)
        else:
            w = a.astype(jnp.int32)
        total = total + jnp.sum(w, dtype=jnp.int32)
    return total.reshape((1,))


def _encode_indices_body(idx: jnp.ndarray, plan: PackPlan,
                         interpret: bool | None = None):
    assert idx.shape == (plan.k,), (idx.shape, plan)
    idx = idx.astype(jnp.int32)
    if plan.raw_index:
        return (idx,)
    hi = idx >> plan.lo_bits
    counts = jnp.zeros((plan.n_buckets,), jnp.int32).at[hi].add(1)
    words = BP.pack_bits(idx & ((1 << plan.lo_bits) - 1), plan.lo_bits,
                         interpret=interpret)
    return counts, words


def _decode_indices_body(payload, plan: PackPlan,
                         interpret: bool | None = None) -> jnp.ndarray:
    if plan.raw_index:
        (idx,) = payload
        return idx
    counts, words = payload
    lo = BP.unpack_bits(words, plan.k, interpret=interpret)
    hi = jnp.repeat(jnp.arange(plan.n_buckets, dtype=jnp.int32),
                    counts, total_repeat_length=plan.k)
    return (hi << plan.lo_bits) | lo


def encode_indices(idx: jnp.ndarray, plan: PackPlan,
                   interpret: bool | None = None) -> Tuple[jnp.ndarray, ...]:
    """The index half of the wire on its own: *sorted-ascending* int32
    ``idx`` (plan.k,) -> (counts, words), or (idx,) on the small-k
    raw-index fallback — plus the trailing checksum word when the plan
    carries one.  The histogram expansion in :func:`decode_indices`
    repeats bucket ids in order, so monotone input is a hard
    precondition (the pair codec sorts for you; index-only callers — the
    leader-support broadcast — must ship a canonical sorted set anyway).
    Indices roundtrip bit-exact for any sorted values in [0, n], the
    ``select_topk`` sentinel ``n`` included."""
    payload = _encode_indices_body(idx, plan, interpret=interpret)
    if plan.checksum:
        payload = payload + (checksum_word(payload),)
    return payload


def decode_indices(payload, plan: PackPlan,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Inverse of :func:`encode_indices` -> sorted int32 (plan.k,).
    The checksum word (when present) is *stripped*, not verified —
    verification is the guard's job (:func:`validate_payload`), so the
    unguarded path pays zero compute for it."""
    if plan.checksum:
        payload = payload[:-1]
    return _decode_indices_body(payload, plan, interpret=interpret)


def encode_sparse(vals: jnp.ndarray, idx: jnp.ndarray, plan: PackPlan,
                  interpret: bool | None = None):
    """-> the real wire payload: (counts, words, q, scales), or
    (idx, q, scales) on the small-k raw-index fallback; one trailing
    int32 checksum word covering every prior array when the plan asks
    for it."""
    assert vals.shape == idx.shape == (plan.k,), (vals.shape, plan)
    vals_s, idx_s = _sort_pairs(vals, idx)
    q, scales = Q.quantize_i8(vals_s, plan.scale_block)
    payload = _encode_indices_body(idx_s, plan,
                                   interpret=interpret) + (q, scales)
    if plan.checksum:
        payload = payload + (checksum_word(payload),)
    return payload


def encode_sparse_fused(vals: jnp.ndarray, idx: jnp.ndarray,
                        plan: PackPlan, interpret: bool | None = None):
    """:func:`encode_sparse` with the quantize + bit-plane-pack passes
    collapsed into ONE Pallas launch (``quantize.quantize_pack_fused``):
    the sorted (vals, idx) pair is read from HBM once instead of once
    per pass.  Bit-exact against the composed path — same payload tuple,
    same bytes, gated in tests/test_overlap.py.

    The sort stays outside (a global argsort cannot be tile-local) and
    the high-bits histogram is one cheap scatter-add; both consume the
    sorted pair the fused kernel also reads.  Falls back to the composed
    path when the plan is raw-index (nothing to pack) or when a guard
    policy holds the structural sink open — the fused kernel masks
    non-finite values like :func:`quantize.quantize_i8` does but cannot
    report their count, and guarded runs must not lose fault events."""
    if plan.raw_index or CH.structural_sink_active():
        return encode_sparse(vals, idx, plan, interpret=interpret)
    assert vals.shape == idx.shape == (plan.k,), (vals.shape, plan)
    vals_s, idx_s = _sort_pairs(vals, idx)
    counts = jnp.zeros((plan.n_buckets,), jnp.int32
                       ).at[idx_s >> plan.lo_bits].add(1)
    words, q, scales = Q.quantize_pack_fused(
        vals_s, idx_s & ((1 << plan.lo_bits) - 1), plan.lo_bits,
        plan.scale_block, interpret=interpret)
    payload = (counts, words, q, scales)
    if plan.checksum:
        payload = payload + (checksum_word(payload),)
    return payload


def decode_sparse(payload, plan: PackPlan, interpret: bool | None = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inverse of :func:`encode_sparse` -> (vals f32 (k,), idx int32
    (k,)) in index-sorted order: indices bit-exact, values dequantized.
    Checksum stripped, not verified (see :func:`decode_indices`)."""
    if plan.checksum:
        payload = payload[:-1]
    q, scales = payload[-2], payload[-1]
    idx = _decode_indices_body(payload[:-2], plan, interpret=interpret)
    return Q.dequantize_i8(q, scales, plan.k), idx


def validate_payload(payload, plan: PackPlan, values: bool = True,
                     interpret: bool | None = None):
    """Structural validation of one node's received payload — the guard
    hook the packed transport runs per contribution when a guard policy
    is on.  Checks (each a traced predicate):

      * checksum word matches a recompute over the prior arrays (only
        when the plan carries one — the check that catches arbitrary
        finite bit-flips the value predicates can't);
      * bucket histogram is non-negative and sums to exactly k;
      * value scales are finite (``values=True`` payloads only);
      * decoded indices lie in [0, n] (sentinel n included) and are
        monotone non-decreasing.

    Returns ``(ok, bad)``: ``ok`` a scalar bool (all predicates hold),
    ``bad`` the int32 count of failed predicates — what the executor
    feeds the per-op fault tally through the structural sink."""
    checks = []
    body = payload
    if plan.checksum:
        body, chk = payload[:-1], payload[-1]
        checks.append(jnp.all(checksum_word(body) == chk))
    ipay = body[:-2] if values else body
    if not plan.raw_index:
        counts = ipay[0]
        checks.append(jnp.all(counts >= 0))
        checks.append(jnp.sum(counts) == plan.k)
    if values:
        checks.append(jnp.all(jnp.isfinite(body[-1])))
    idx = _decode_indices_body(ipay, plan, interpret=interpret)
    checks.append(jnp.all((idx >= 0) & (idx <= plan.n)))
    if plan.k > 1:
        checks.append(jnp.all(idx[1:] >= idx[:-1]))
    flags = jnp.stack([jnp.logical_not(c) for c in checks])
    bad = jnp.sum(flags.astype(jnp.int32))
    return bad == 0, bad


def fake_roundtrip(vals: jnp.ndarray, idx: jnp.ndarray,
                   scale_block: int = 0):
    """The float-domain mirror of encode->decode (sort pairs by index,
    quantize->dequantize the sorted values with the wire's exact
    blocks).  Not on any transport path — float wires ship exact pairs —
    but the executable definition of the wire's value error, used by the
    codec tests."""
    vals_s, idx_s = _sort_pairs(vals, idx)
    return Q.fake_quantize(vals_s, scale_block or Q.SCALE_BLOCK), idx_s
