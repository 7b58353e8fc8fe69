"""Bitonic per-block partial sort for the fused segmented sweep.

``segmented_topk.select_candidates`` extracts a block's per-segment
top-k candidates with a sequential (max -> record -> mask) loop: one
global VPU reduction per candidate slot, so per-block work grows with
``n_cand`` (~k).  Past ``FUSED_BLOCK_MAX`` (k_max > 16Ki, i.e. leaves
>= ~16M params at the paper's alpha=0.1%) that loop approaches O(block)
serial reductions and the sweep loses its speed advantage — DESIGN.md's
"Scaling note".  This module is the named fix: a lanes-parallel bitonic
sorting network whose sequential depth is O(log^2 block) compare-
exchange stages *independent of k*.

``select_candidates_bitonic`` is a drop-in for the loop (same signature,
same outputs, bit-identical — property-tested in tests/test_bitonic.py):

  1. sort the whole block by (slot asc, |value| desc, index asc);
     masked elements (seg < 0, power-of-two padding) carry slot
     ``n_slots`` and sink to the back.  Each slot's piece is now a run
     in ``lax.top_k``'s stable lowest-index-first order;
  2. cap pass: an element's rank within its slot is its position minus
     the run's start (one count per slot, no scan), and it is kept iff
     that rank is below the slot's cap — exactly the set the loop
     backend's cap-out masking keeps;
  3. sort again by (|value| desc, index asc) with dropped elements'
     magnitude set to −1, which restores the loop's emission order with
     the kept elements in front; the first ``n_cand`` slots are written,
     past the kept count with the loop's (0, block, −1) fill.

The emission order (magnitude-descending, ties by index) is the loop's,
so the candidate triples — and therefore the merged per-leaf result —
are *identical*, not just equivalent.

The operands live in VMEM scratch as (rows, 128) arrays, one (8, 128)
vreg tile per 1024 elements.  Compare-exchange stages at distances of a
tile or more pair whole tiles (a loop over tile pairs); stages within a
tile run on vregs, fetching each element's partner (position p ^ j)
with two rotations (``pltpu.roll``: along lanes for j < 128, along
sublanes otherwise) and one select.  So every stage is one VPU pass over
the data, the kernel's code size grows with the number of merge levels
and not with the block, and nothing needs a gather, a scatter or a scan
(the TPU lowering has none of the three).  Runs inside the same Pallas
kernels as the loop backend (``extract="bitonic"`` on the sweep entry
points) — the one-launch / one-HBM-pass structure of the sweep is
untouched.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segmented_topk import (LANE, SUBLANE, TILE, cand_rows,
                                          flat_iota)


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (the network's operand length)."""
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def _log2(n: int) -> int:
    return n.bit_length() - 1


def _bit(pos, n: int):
    """Bit ``log2(n)`` of ``pos`` as an int32 0/1 array (the TPU
    lowering cannot compare or select i1 vectors, so direction bits
    stay integers)."""
    return (pos >> _log2(n)) & 1


def _exchange(mine, theirs, first, lt, n_keys: int):
    """One compare-exchange: where ``first``, keep the earlier of the
    two elements under ``lt``, elsewhere the later."""
    take = ((first & lt(theirs[:n_keys], mine[:n_keys]))
            | (~first & lt(mine[:n_keys], theirs[:n_keys])))
    return [jnp.where(take, b, a) for a, b in zip(mine, theirs)]


def _in_tile_stages(vals, gpos, local, lt, n_keys: int, kk: int, j0: int):
    """Stages (kk, j0), (kk, j0/2), ..., (kk, 1) of one (8, 128) tile.
    Of the two rotations by a distance, the one whose rotated position
    grid lands on p ^ j supplies the partner, so the result does not
    depend on the rotation's sign convention."""
    rows, lanes = local.shape
    j = j0
    while j >= 1:
        axis, d, size = (1, j, lanes) if j < lanes else (0, j // lanes, rows)
        fwd = pltpu.roll(local, d, axis) == (local ^ j)
        part = [jnp.where(fwd, pltpu.roll(a, d, axis),
                          pltpu.roll(a, size - d, axis)) for a in vals]
        vals = _exchange(vals, part, _bit(local, j) == _bit(gpos, kk), lt,
                         n_keys)
        j //= 2
    return vals


def bitonic_sort(refs, lt, n_keys: int):
    """Sort the (rows, 128) VMEM refs ``refs`` in place, ascending in
    row-major order by the strict total order ``lt`` over the first
    ``n_keys`` of them, carrying the rest as payload.  ``lt(a_keys,
    b_keys)`` gets tuples of key arrays.  rows * 128 must be a power of
    two and rows a multiple of 8.

    Levels up to one tile (kk <= 1024) sort every tile in one pass; each
    later level runs its tile-distance stages as loops over tile pairs,
    then its in-tile stages as one loop over tiles.  Runs inside a
    Pallas kernel.
    """
    rows = refs[0].shape[0]
    n2 = rows * LANE
    assert n2 & (n2 - 1) == 0 and rows % SUBLANE == 0, refs[0].shape
    n_tiles = rows // SUBLANE
    local = flat_iota((SUBLANE, LANE))

    def load(t):
        r0 = pl.multiple_of(t * SUBLANE, SUBLANE)
        return [r[pl.ds(r0, SUBLANE), :] for r in refs]

    def store(t, vals):
        r0 = pl.multiple_of(t * SUBLANE, SUBLANE)
        for r, v in zip(refs, vals):
            r[pl.ds(r0, SUBLANE), :] = v

    def tile_pass(levels):
        def body(t, c):
            vals = load(t)
            gpos = t * TILE + local
            for kk, j0 in levels:
                vals = _in_tile_stages(vals, gpos, local, lt, n_keys, kk, j0)
            store(t, vals)
            return c
        jax.lax.fori_loop(0, n_tiles, body, 0)

    def pair_pass(kk, jt):
        # stage (kk, jt * TILE): tile t pairs with t + jt; the pair shares
        # one direction since kk > jt * TILE
        def body(i, c):
            t = (i // jt) * 2 * jt + i % jt
            lo, hi = load(t), load(t + jt)
            first = _bit(t * TILE + local, kk) == 0
            store(t, _exchange(lo, hi, first, lt, n_keys))
            store(t + jt, _exchange(hi, lo, ~first, lt, n_keys))
            return c
        jax.lax.fori_loop(0, n_tiles // 2, body, 0)

    kk_tile = min(n2, TILE)
    tile_pass([(kk, kk // 2) for kk in (2 ** e for e in
                                         range(1, _log2(kk_tile) + 1))])
    kk = 2 * TILE
    while kk <= n2:
        jt = kk // 2 // TILE
        while jt >= 1:
            pair_pass(kk, jt)
            jt //= 2
        tile_pass([(kk, TILE // 2)])
        kk *= 2


def _lt_desc(a, b):
    """Strictly-before: magnitude desc, index asc."""
    return (a[0] > b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))


def _lt_slot_desc(a, b):
    """Strictly-before: slot asc, then magnitude desc, index asc."""
    return (a[0] < b[0]) | ((a[0] == b[0]) & _lt_desc(a[1:], b[1:]))


def select_candidates_bitonic(x, seg, kcap, n_cand: int, block: int, out,
                              base):
    """Bitonic drop-in for ``segmented_topk.select_candidates`` (same
    contract: x, seg are (block//LANE, LANE) VMEM tiles, kcap is
    (1, n_slots); writes the (vals, GLOBAL idx, seg) candidate tiles of
    ``out`` with unused slots = (0, base + block, −1)).  Bit-identical to
    the loop extractor on materialized inputs; the sequential depth is
    2·O(log² block) stages + one count per slot, independent of the
    candidate count.  The block is sorted in VMEM scratch, so every
    stage reads the one stored copy of x.
    """
    n2 = next_pow2(block)
    rows = n2 // LANE
    c = cand_rows(n_cand)
    assert c <= rows, (n_cand, block)
    scratch = [pltpu.VMEM((rows, LANE), jnp.int32),      # slot
               pltpu.VMEM((rows, LANE), jnp.float32),    # magnitude
               pltpu.VMEM((rows, LANE), jnp.int32),      # index
               pltpu.VMEM((rows, LANE), x.dtype)]        # value

    def run(slot_ref, mag_ref, idx_ref, x_ref):
        n_slots = kcap.shape[-1]
        rb = x.shape[0]
        idx_ref[...] = flat_iota((rows, LANE))
        mag_ref[:rb] = jnp.where(seg >= 0, jnp.abs(x), -1.0)
        slot_ref[:rb] = jnp.where(seg >= 0, seg, n_slots)
        x_ref[:rb] = x
        if rows != rb:                       # non-power-of-two blocks
            pad = (rows - rb, LANE)
            mag_ref[rb:] = jnp.full(pad, -1.0, jnp.float32)
            slot_ref[rb:] = jnp.full(pad, n_slots, jnp.int32)
            x_ref[rb:] = jnp.zeros(pad, x.dtype)
        bitonic_sort([slot_ref, mag_ref, idx_ref, x_ref], _lt_slot_desc, 3)

        # cap pass: slot s now occupies one run starting at the count of
        # elements of lower slots, so rank-in-slot = position - run start
        slot = slot_ref[...]
        slot_iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_slots), 1)
        start = jnp.zeros((rows, LANE), jnp.int32)
        cap = jnp.zeros((rows, LANE), jnp.int32)
        for s in range(n_slots):
            is_s = slot == s
            start = jnp.where(is_s, jnp.sum((slot < s).astype(jnp.int32)),
                              start)
            cap = jnp.where(is_s,
                            jnp.sum(jnp.where(slot_iota == s, kcap, 0)), cap)
        pos = flat_iota((rows, LANE))
        keep = (slot < n_slots) & (pos - start < cap)
        total = jnp.sum(keep.astype(jnp.int32))   # kept count, <= n_cand

        # restore the loop's emission order, kept elements first
        mag_ref[...] = jnp.where(keep, mag_ref[...], -1.0)
        bitonic_sort([mag_ref, idx_ref, x_ref, slot_ref], _lt_desc, 2)
        live = pos[:c] < total
        vals_ref, gidx_ref, seg_ref = out
        vals_ref[0] = jnp.where(live, x_ref[:c], 0.0)
        gidx_ref[0] = base + jnp.where(live, idx_ref[:c], block)
        seg_ref[0] = jnp.where(live, slot_ref[:c], -1)

    pl.run_scoped(run, *scratch)
