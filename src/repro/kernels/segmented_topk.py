"""Segmented block-local top-k kernel (TPU Pallas).

``global_topk`` (block_topk.py + the merge in ops.py) is exact top-k for
ONE segment per kernel launch; the training hot path needs top-k per
*leaf* of the flat gradient, which the jnp reference implements as a
Python loop of dynamic_slice + lax.top_k — one kernel launch and one HBM
round-trip per leaf.  This kernel restores the streaming shape of the
problem: ONE grid sweep over the whole flat vector, with the static
``GradientLayout`` baked in as a per-element segment-id array.  Each grid
step loads one VMEM-sized block plus its segment ids and extracts, for
every segment piece present in the block, that piece's top-min(k_seg,
|piece|) candidates by |value| (a segment's global winners are a subset
of its per-block winners by pigeonhole).  A tiny host-side merge
(lax.top_k over the candidate pool, k·n_blocks-scale and VMEM-resident)
finishes the exact per-segment result — see core/sparsify.py.

The candidate loop is segment-aware: one (max -> record -> mask)
iteration per candidate slot, masking a whole segment out of contention
once its cap is reached, so a block straddling leaf boundaries cannot
crowd a small leaf's winners out with a big leaf's values.  Tie-break
(equal |value|) is lowest-index-first, matching lax.top_k's stable
order, so the merged result is *identical* to the per-leaf reference —
not just equivalent.

Per-block extraction is pluggable (``extract=``): "loop" is the
sequential candidate loop above (O(n_cand) global reductions per
block, cheapest at small k); "bitonic" is the lanes-parallel sorting
network in kernels/bitonic.py (O(log² block) stages independent of k,
the large-k backend).  Both are bit-identical — the dispatch changes
cost only, never output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

LANE = 128
SUBLANE = 8
TILE = SUBLANE * LANE     # one (8, 128) f32 vreg tile
BLOCK = TILE              # default sweep block: one (8, 128) f32 VMEM tile


def flat_iota(shape) -> jnp.ndarray:
    """Row-major flat element index over a (rows, 128) tile."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1]
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def cand_rows(n_cand: int) -> int:
    """Rows of the lane-dense (rows, 128) candidate tile holding
    ``n_cand`` slots, rounded up to whole (8, 128) tiles."""
    rows = -(-n_cand // LANE)
    return -(-rows // SUBLANE) * SUBLANE


def put_candidate(ref, i, value):
    """Store scalar ``value`` at flat candidate slot ``i`` of a
    (1, rows, 128) ref: read-modify-write of the one (8, 128) tile that
    holds the slot (the TPU lowering has no scatter)."""
    r0 = pl.multiple_of((i // TILE) * SUBLANE, SUBLANE)
    t = ref[0, pl.ds(r0, SUBLANE), :]
    hit = flat_iota(t.shape) == i % TILE
    ref[0, pl.ds(r0, SUBLANE), :] = jnp.where(hit, value, t)


def select_candidates(x, seg, kcap, n_cand: int, block: int, out, base):
    """Per-block segmented candidate extraction (runs inside a kernel).

    x, seg: (block//LANE, LANE) VMEM-resident value / segment-id tiles
    (seg < 0 = not selectable); kcap: (1, n_slots) per-slot top-k caps.
    Writes candidate slot i of ``out`` = (vals, idx, seg) refs, each
    (1, cand_rows(n_cand), 128), in extraction order: value, GLOBAL
    index ``base`` + block-local index, slot id.  Unused slots carry
    (0, base + block, -1).
    """
    flat_idx = flat_iota(x.shape)
    n_slots = kcap.shape[-1]
    slot_iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_slots), 1)
    mag0 = jnp.where(seg >= 0, jnp.abs(x), -1.0)
    vals_ref, idx_ref, seg_ref = out
    shape = vals_ref.shape[1:]
    vals_ref[0] = jnp.zeros(shape, vals_ref.dtype)
    idx_ref[0] = jnp.full(shape, base + block, jnp.int32)
    seg_ref[0] = jnp.full(shape, -1, jnp.int32)

    def body(i, carry):
        mag, counts = carry
        m = jnp.max(mag)
        valid = m >= 0.0                     # all-masked block => m == -1
        pos = jnp.min(jnp.where(mag == m, flat_idx, block))
        hit = (flat_idx == pos) & valid
        val = jnp.sum(jnp.where(hit, x, 0.0))
        s = jnp.where(valid, jnp.sum(jnp.where(hit, seg, 0)), -1)
        one = slot_iota == s
        counts = counts + one.astype(jnp.int32)
        cap = jnp.sum(jnp.where(one, kcap, 0))
        cnt = jnp.sum(jnp.where(one, counts, 0))
        mag = jnp.where(hit, -1.0, mag)
        # slot s reached its cap: its remaining elements can never win
        mag = jnp.where((seg == s) & valid & (cnt >= cap), -1.0, mag)
        put_candidate(vals_ref, i, val)
        put_candidate(idx_ref, i, base + jnp.where(valid, pos, block))
        put_candidate(seg_ref, i, s)
        return mag, counts

    jax.lax.fori_loop(0, n_cand, body,
                      (mag0, jnp.zeros((1, n_slots), jnp.int32)))


def extract_fn(extract: str):
    """Resolve an extraction-backend name to its per-block function.
    Lazy import: bitonic.py is only pulled in when selected."""
    if extract == "loop":
        return select_candidates
    if extract == "bitonic":
        from repro.kernels.bitonic import select_candidates_bitonic
        return select_candidates_bitonic
    raise ValueError(f"unknown extract backend: {extract!r}")


def sweep_specs(rows: int, n_cand: int, n_slots: int):
    """Shared pallas_call scaffolding for the segmented-sweep kernels
    (this one and sparsify_ef.sparsify_ef_topk): per-block tile spec,
    per-block candidate spec, broadcast kcap spec.  Candidates are
    stored lane-dense, (cand_rows(n_cand), 128) per block, so every
    block obeys the TPU lowering's (8, 128) tiling rule."""
    tile = pl.BlockSpec((1, rows, LANE), lambda i: (i, 0, 0))
    cand = pl.BlockSpec((1, cand_rows(n_cand), LANE), lambda i: (i, 0, 0))
    kcap = pl.BlockSpec((1, n_slots), lambda i: (0, 0))
    return tile, cand, kcap


def cand_out_shapes(n_blocks: int, n_cand: int, dtype):
    """(vals, idx, seg) candidate output shapes for a sweep kernel."""
    shape = (n_blocks, cand_rows(n_cand), LANE)
    return [jax.ShapeDtypeStruct(shape, dtype),
            jax.ShapeDtypeStruct(shape, jnp.int32),
            jax.ShapeDtypeStruct(shape, jnp.int32)]


def cand_unpack(cands, n_cand: int):
    """Kernel candidate tiles -> (n_blocks, n_cand) arrays."""
    return tuple(c.reshape(c.shape[0], -1)[:, :n_cand] for c in cands)


def _segmented_topk_kernel(x_ref, seg_ref, kcap_ref, vals_ref, idx_ref,
                           seg_out_ref, *, n_cand: int, block: int,
                           extract: str):
    extract_fn(extract)(x_ref[0], seg_ref[0], kcap_ref[...], n_cand, block,
                        (vals_ref, idx_ref, seg_out_ref),
                        pl.program_id(0) * block)


@functools.partial(jax.jit,
                   static_argnames=("n_cand", "extract", "interpret"))
def segmented_topk(x: jnp.ndarray, seg: jnp.ndarray, kcap: jnp.ndarray,
                   n_cand: int, extract: str = "loop",
                   interpret: bool | None = None):
    """x, seg: (n_blocks, block) f32/int32, block % 128 == 0; kcap:
    (n_slots,) int32 per-slot caps.  Returns per-block candidate triples
    (vals (n_blocks, n_cand), idx (n_blocks, n_cand) in GLOBAL element
    coordinates, seg (n_blocks, n_cand) slot id or -1 for unused)."""
    n_blocks, block = x.shape
    assert block % LANE == 0, block
    rows = block // LANE
    kern = functools.partial(_segmented_topk_kernel, n_cand=n_cand,
                             block=block, extract=extract)
    tile, cand, kspec = sweep_specs(rows, n_cand, kcap.shape[0])
    out = pl.pallas_call(
        kern,
        grid=(n_blocks,),
        in_specs=[tile, tile, kspec],
        out_specs=[cand, cand, cand],
        out_shape=cand_out_shapes(n_blocks, n_cand, x.dtype),
        interpret=interpret_mode(interpret),
    )(x.reshape(n_blocks, rows, LANE), seg.reshape(n_blocks, rows, LANE),
      kcap[None])
    return cand_unpack(out, n_cand)
