"""Gradient sparsification machinery (paper Section V-A, Algorithms 1-2).

Per-layer top-k selection at fixed rate alpha (0.1%), with DGC-style
momentum-corrected local accumulation of the unsent gradients:

    u <- m*u + g          (momentum accumulation)
    v <- v + u            (residual accumulation)
    send top-k(v); zero u, v at the sent coordinates.

Layer exemptions (Section VI-A): the first layer's weights update with raw
dense gradients; the last layer's top-k values are transmitted without the
autoencoder.  Everything else is concatenated into the length-mu vector
``g~`` that feeds the LGC autoencoder (padded to a multiple of 16 so the
stride-2 conv stack is shape-exact).

All functions operate on the *flat* gradient vector (leaf tensors raveled
and concatenated with static offsets), so they are jit-friendly with fully
static shapes.

Selection dispatches on a backend ("jnp" | "pallas" | "fused"); the
"fused" path (:func:`fused_accumulate_select`) folds the EF accumulate
and the per-leaf selection of every selectable leaf into ONE segmented
Pallas sweep — see DESIGN.md "The fused sparsification sweep".
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bitonic import next_pow2
from repro.kernels.segmented_topk import BLOCK as _SEG_BLOCK
from repro.utils.tree import keystr_path

ROLE_DENSE = "dense"            # exempt: raw dense gradient (first layer)
ROLE_TOPK_ONLY = "topk_only"    # top-k transmitted, but not AE-compressed
ROLE_COMPRESSED = "compressed"  # top-k -> autoencoder

AE_ALIGN = 16                   # encoder downsamples by 16


@dataclass(frozen=True)
class LeafSpec:
    path: str
    offset: int
    size: int
    role: str
    k: int                      # top-k count (0 for dense leaves)


@dataclass(frozen=True)
class GradientLayout:
    leaves: Tuple[LeafSpec, ...]
    n_total: int
    mu: int                     # sum of k over COMPRESSED leaves
    mu_pad: int                 # mu rounded up to AE_ALIGN
    k_last: int                 # sum of k over TOPK_ONLY leaves

    @property
    def compressed(self) -> Tuple[LeafSpec, ...]:
        return tuple(l for l in self.leaves if l.role == ROLE_COMPRESSED)

    @property
    def topk_only(self) -> Tuple[LeafSpec, ...]:
        return tuple(l for l in self.leaves if l.role == ROLE_TOPK_ONLY)

    @property
    def dense(self) -> Tuple[LeafSpec, ...]:
        return tuple(l for l in self.leaves if l.role == ROLE_DENSE)


def default_role_fn(path: str, index: int, n_leaves: int) -> str:
    """Paper Section VI-A: first layer dense, last layer top-k w/o AE."""
    segments = path.lower().split("/")
    if "embed" in segments or "conv0" in segments:
        return ROLE_DENSE
    if "lm_head" in segments or "fc" in segments:
        return ROLE_TOPK_ONLY
    return ROLE_COMPRESSED


def build_layout(params_template, sparsity: float,
                 role_fn: Callable[[str, int, int], str] = default_role_fn,
                 ) -> GradientLayout:
    flat, _ = jax.tree_util.tree_flatten_with_path(params_template)
    specs: List[LeafSpec] = []
    offset = 0
    n_leaves = len(flat)
    for i, (path, leaf) in enumerate(flat):
        pstr = keystr_path(path)
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        role = role_fn(pstr, i, n_leaves)
        k = 0
        if role in (ROLE_COMPRESSED, ROLE_TOPK_ONLY):
            k = max(1, int(round(size * sparsity)))
        specs.append(LeafSpec(pstr, offset, size, role, k))
        offset += size
    mu = sum(l.k for l in specs if l.role == ROLE_COMPRESSED)
    mu_pad = ((mu + AE_ALIGN - 1) // AE_ALIGN) * AE_ALIGN
    k_last = sum(l.k for l in specs if l.role == ROLE_TOPK_ONLY)
    return GradientLayout(tuple(specs), offset, mu, mu_pad, k_last)


# ---------------------------------------------------------------------------
# error feedback (DGC momentum correction)


def momentum_correct(u: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
                     m: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    u_new = m * u + g
    v_new = v + u_new
    return u_new, v_new


def clear_sent(u: jnp.ndarray, v: jnp.ndarray, indices: jnp.ndarray,
               n: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Zero the accumulators at transmitted coordinates (sentinel index = n
    is dropped)."""
    u = u.at[indices].set(0.0, mode="drop")
    v = v.at[indices].set(0.0, mode="drop")
    return u, v


def clear_sent_merged(u: jnp.ndarray, v: jnp.ndarray, idx_a: jnp.ndarray,
                      idx_b: jnp.ndarray, n: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """clear_sent over idx_a ∪ idx_b as ONE combined scatter per
    accumulator: 2 passes over (u, v) instead of the 4 that two separate
    clear_sent calls cost.  The index sets are disjoint in the compressor
    (compressed vs exempt-last leaves), but overlap would be harmless —
    both scatters write the same 0."""
    cat = jnp.concatenate([idx_a.astype(jnp.int32), idx_b.astype(jnp.int32)])
    return clear_sent(u, v, cat, n)


# ---------------------------------------------------------------------------
# top-k selection per leaf (static shapes)


def _leaf_topk(seg: jnp.ndarray, k: int, offset: int):
    vals_abs, idx = jax.lax.top_k(jnp.abs(seg), k)
    vals = seg[idx]
    return vals, idx + offset


_PALLAS_BLOCK = 8192            # global_topk block (64 sublanes x 128 lanes)


def _leaf_topk_pallas(seg: jnp.ndarray, k: int, offset: int,
                      interpret: bool):
    """Same contract as :func:`_leaf_topk` through the Pallas block-local
    top-k kernel + merge (kernels/ops.global_topk): exact, descending
    |value| order, so it is a drop-in for the jnp reference."""
    from repro.kernels import ops as K_ops
    block = max(_PALLAS_BLOCK, ((k + 127) // 128) * 128)
    vals, idx = K_ops.global_topk(seg, k, block=block, interpret=interpret)
    return vals, idx + offset


SELECT_BACKENDS = ("jnp", "pallas", "fused")

# segmented-sweep minimum block size: the kernel's tile constant is the
# single source of truth (one (8, 128) f32 VMEM tile).  The actual block
# is scaled per layout — see _fused_block.
FUSED_BLOCK = _SEG_BLOCK
# VMEM ceiling for the scaled block: the fused kernel keeps ~6
# block-sized f32/int32 tiles resident per grid step (~24*block bytes),
# so 128Ki elements ≈ 3 MiB; both extractors compile for a v5e at this
# block within the default scoped VMEM limit (tests/test_tpu_compile.py)
FUSED_BLOCK_MAX = 128 * 1024

# per-block candidate-extraction backends (kernels/segmented_topk vs
# kernels/bitonic — bit-identical output, different cost shape).  "auto"
# picks loop at small k (fewer total ops) and bitonic once the loop's
# 8*k_max block rule would blow past FUSED_BLOCK_MAX, i.e. the regime
# where the loop degrades toward O(block) serial reductions per block.
EXTRACT_BACKENDS = ("auto", "loop", "bitonic")


def _resolve_extract(extract: str, slots) -> str:
    assert extract in EXTRACT_BACKENDS, extract
    if extract != "auto":
        return extract
    k_max = max((l.k for l in slots), default=1)
    return "bitonic" if 8 * k_max > FUSED_BLOCK_MAX else "loop"


def _fused_block(slots, extract: str = "loop") -> int:
    """Per-layout sweep block size.  Exact block-local selection must keep
    min(k, block) candidates per block (pigeonhole), so with the default
    tile a leaf with k >= 1024 would make EVERY element a candidate.

    loop: per-block extraction costs n_cand (~k) sequential global
    reductions, so the block scales to >= 8*k_max — candidate pool
    <= ~n/8 and the extraction loop <= ~block/8 iterations — capped at
    FUSED_BLOCK_MAX to bound VMEM; past that cap the pool bound degrades
    (correctness is unaffected — n_cand stays exact).

    bitonic: extraction is O(log² block) stages independent of k, so the
    block is chosen on VMEM alone — the smallest power of two covering
    k_max (keeping the pool <= ~n/block · k ≈ k per block), up to the
    same VMEM ceiling.  Power-of-two blocks also make the sorting
    network padding-free."""
    k_max = max((l.k for l in slots), default=1)
    if extract == "bitonic":
        return min(FUSED_BLOCK_MAX, next_pow2(max(FUSED_BLOCK, k_max)))
    want = -(-8 * k_max // FUSED_BLOCK) * FUSED_BLOCK
    return max(FUSED_BLOCK, min(FUSED_BLOCK_MAX, want))


@functools.lru_cache(maxsize=64)
def _fused_meta(layout: GradientLayout, roles: Tuple[str, ...],
                extract: str = "auto"):
    """Static segmented-sweep metadata for ``layout``: the resolved
    extraction backend, the block size, per-slot top-k caps, and the exact
    per-block candidate budget (worst case over blocks of sum_slots
    min(k_slot, |slot piece in block|) — the pigeonhole bound that makes
    the merged result exact)."""
    slots = tuple(l for role in roles for l in layout.leaves
                  if l.role == role)
    ex = _resolve_extract(extract, slots)
    block = _fused_block(slots, ex)
    n_pad = -(-layout.n_total // block) * block
    kcap = np.asarray([l.k for l in slots], np.int32)
    # per-block candidate budget: each slot's piece size in block b is a
    # range overlap, so the budget is computed analytically per slot
    # (vectorized over the blocks it spans) — no O(n) scan
    budget = np.zeros((n_pad // block,), np.int64)
    for leaf in slots:
        b0 = leaf.offset // block
        b1 = (leaf.offset + leaf.size - 1) // block
        bs = np.arange(b0, b1 + 1)
        pieces = (np.minimum(leaf.offset + leaf.size, (bs + 1) * block)
                  - np.maximum(leaf.offset, bs * block))
        budget[b0:b1 + 1] += np.minimum(pieces, leaf.k)
    n_cand = max(1, int(budget.max(initial=0)))
    return ex, block, kcap, n_cand, slots


def _slot_map(n: int, slots) -> jnp.ndarray:
    """Element -> slot id over a length-``n`` vector (-1 = not
    selectable), computed from an iota at run time: as a trace-time
    constant the map would put 4n bytes into the executable."""
    pos = jax.lax.iota(jnp.int32, n)
    seg = jnp.full((n,), -1, jnp.int32)
    for j, leaf in enumerate(slots):
        seg = jnp.where((pos >= leaf.offset)
                        & (pos < leaf.offset + leaf.size), j, seg)
    return seg


def fused_plan_info(layout: GradientLayout,
                    roles: Tuple[str, ...] = (ROLE_COMPRESSED,
                                              ROLE_TOPK_ONLY),
                    extract: str = "auto") -> dict:
    """Self-describing sweep plan for bench artifacts: the chosen block
    size, per-block candidate-pool bound, and resolved extraction
    backend for ``layout`` (same derivation the hot path uses)."""
    ex, block, _, n_cand, _ = _fused_meta(layout, roles, extract)
    return {"fused_block": block, "n_cand": n_cand, "extract_backend": ex}


def _merge_candidates(cvals, cidx, cseg, slots):
    """Exact per-slot top-k from the one-sweep candidate pool.  The
    per-leaf lax.top_k here runs over the candidate arrays
    (n_blocks*n_cand elements), not the full vector — the same merge
    shape ops.global_topk uses."""
    mags = jnp.abs(cvals)
    vals_list, idx_list = [], []
    for j, leaf in enumerate(slots):
        m = jnp.where(cseg == j, mags, -1.0)
        _, top = jax.lax.top_k(m, leaf.k)
        vals_list.append(cvals[top])
        idx_list.append(cidx[top].astype(jnp.int32))
    return vals_list, idx_list


def _fused_select_lists(v: jnp.ndarray, layout: GradientLayout,
                        roles: Tuple[str, ...], interpret: bool,
                        extract: str = "auto"):
    """Per-leaf (vals, idx) lists for all leaves of ``roles`` via ONE
    segmented-sweep kernel launch."""
    from repro.kernels import ops as K_ops
    ex, block, kcap, n_cand, slots = _fused_meta(layout, roles, extract)
    if not slots:
        return [], []
    seg = _slot_map(layout.n_total, slots)
    cv, ci, cs = K_ops.segmented_topk(v, seg,
                                      jnp.asarray(kcap), n_cand=n_cand,
                                      block=block, extract=ex,
                                      interpret=interpret)
    return _merge_candidates(cv, ci, cs, slots)


def _per_leaf_select(v, leaves, backend, interpret):
    """Per-leaf (vals, idx) lists via one dynamic_slice + top-k per leaf
    (the "jnp" and "pallas" backends)."""
    vals_list, idx_list = [], []
    for leaf in leaves:
        seg = jax.lax.dynamic_slice_in_dim(v, leaf.offset, leaf.size)
        if backend == "pallas":
            vals, idx = _leaf_topk_pallas(seg, leaf.k, leaf.offset,
                                          interpret)
        else:
            vals, idx = _leaf_topk(seg, leaf.k, leaf.offset)
        vals_list.append(vals)
        idx_list.append(idx)
    return vals_list, idx_list


def _pad_compressed(vals_list, idx_list, layout, dtype):
    pad = layout.mu_pad - layout.mu
    if pad:
        vals_list = vals_list + [jnp.zeros((pad,), dtype)]
        idx_list = idx_list + [jnp.full((pad,), layout.n_total, jnp.int32)]
    return (jnp.concatenate(vals_list),
            jnp.concatenate(idx_list).astype(jnp.int32))


def select_topk(v: jnp.ndarray, layout: GradientLayout,
                backend: str = "jnp", interpret: bool | None = None,
                extract: str = "auto"):
    """Top-k per compressed leaf of the residual vector ``v``.

    ``backend`` picks the selection implementation: "jnp" (lax.top_k
    reference), "pallas" (the block-local top-k kernel, one launch per
    leaf) or "fused" (the segmented sweep in kernels/segmented_topk.py,
    ONE launch for the whole vector).  All are exact and return the same
    ordering (ties break lowest-index-first).  ``extract`` picks the
    fused sweep's per-block extraction ("auto" | "loop" | "bitonic" —
    see EXTRACT_BACKENDS; ignored by the other backends).
    ``interpret`` overrides the platform default (compiled on TPU).

    Returns (values (mu_pad,), indices (mu_pad,) int32).  Padding entries
    carry value 0 and sentinel index n_total (dropped by scatters).
    """
    assert backend in SELECT_BACKENDS, backend
    if backend == "fused":
        vals_list, idx_list = _fused_select_lists(
            v, layout, (ROLE_COMPRESSED,), interpret, extract)
    else:
        vals_list, idx_list = _per_leaf_select(v, layout.compressed,
                                               backend, interpret)
    return _pad_compressed(vals_list, idx_list, layout, v.dtype)


def select_topk_last(v: jnp.ndarray, layout: GradientLayout,
                     backend: str = "jnp", interpret: bool | None = None,
                     extract: str = "auto"):
    """Top-k over the exempt last layer(s) (sent raw, no AE), through the
    same backend dispatch as :func:`select_topk`."""
    assert backend in SELECT_BACKENDS, backend
    if not layout.topk_only:
        return (jnp.zeros((0,), v.dtype), jnp.zeros((0,), jnp.int32))
    if backend == "fused":
        vals_list, idx_list = _fused_select_lists(
            v, layout, (ROLE_TOPK_ONLY,), interpret, extract)
    else:
        vals_list, idx_list = _per_leaf_select(v, layout.topk_only,
                                               backend, interpret)
    return (jnp.concatenate(vals_list),
            jnp.concatenate(idx_list).astype(jnp.int32))


def fused_accumulate_select(g: jnp.ndarray, u: jnp.ndarray, v: jnp.ndarray,
                            layout: GradientLayout, momentum: float,
                            use_momentum: bool = True,
                            interpret: bool | None = None,
                            extract: str = "auto"):
    """THE fused hot path (``topk_backend="fused"``): one kernel sweep
    does the EF accumulate (u' = m*u + g, v' = v + u'; plain residual
    accumulation when ``use_momentum=False``) AND the segmented top-k
    over compressed *and* topk_only leaves.

    Returns (u', v', vals (mu_pad,), idx (mu_pad,), last_vals (k_last,),
    last_idx (k_last,)) — exactly what momentum_correct + select_topk +
    select_topk_last produce in ~6 full-length HBM passes and one kernel
    launch per leaf, in one read of (g, u, v) and one write of (u', v').
    """
    roles = (ROLE_COMPRESSED, ROLE_TOPK_ONLY)
    ex, block, kcap, n_cand, slots = _fused_meta(layout, roles, extract)
    if not slots:                        # degenerate: nothing selectable
        # (no compressed and no topk_only leaves => mu_pad == k_last == 0)
        if use_momentum:
            u2, v2 = momentum_correct(u, v, g, momentum)
        else:
            u2, v2 = u, v + g
        empty = (jnp.zeros((0,), v.dtype), jnp.zeros((0,), jnp.int32))
        return (u2, v2) + empty + empty
    from repro.kernels import ops as K_ops
    u2, v2, cv, ci, cs = K_ops.fused_ef_topk(
        g, u, v, _slot_map(layout.n_total, slots), jnp.asarray(kcap),
        momentum,
        bool(use_momentum), n_cand, block=block, extract=ex,
        interpret=interpret)
    vals_list, idx_list = _merge_candidates(cv, ci, cs, slots)
    nc = len(layout.compressed)
    vals, idx = _pad_compressed(vals_list[:nc], idx_list[:nc], layout,
                                v.dtype)
    if layout.topk_only:
        last_vals = jnp.concatenate(vals_list[nc:])
        last_idx = jnp.concatenate(idx_list[nc:]).astype(jnp.int32)
    else:
        last_vals = jnp.zeros((0,), v.dtype)
        last_idx = jnp.zeros((0,), jnp.int32)
    return u2, v2, vals, idx, last_vals, last_idx


def dense_segments(g: jnp.ndarray, layout: GradientLayout) -> jnp.ndarray:
    """Concatenate ONLY the exempt-dense leaf segments (so the cross-node
    reduction moves sum(dense sizes) floats, not n — psum'ing a masked
    full-length vector would put n-float traffic on the wire and defeat
    the compression)."""
    if not layout.dense:
        return jnp.zeros((0,), g.dtype)
    return jnp.concatenate([
        jax.lax.dynamic_slice_in_dim(g, l.offset, l.size)
        for l in layout.dense])


def scatter_dense_segments(vec: jnp.ndarray, layout: GradientLayout,
                           n: int) -> jnp.ndarray:
    """Inverse of :func:`dense_segments` into a length-n dense vector."""
    out = jnp.zeros((n,), vec.dtype)
    off = 0
    for l in layout.dense:
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jax.lax.dynamic_slice_in_dim(vec, off, l.size), l.offset,
            axis=0)
        off += l.size
    return out


def scatter_to_dense(values: jnp.ndarray, indices: jnp.ndarray,
                     n: int) -> jnp.ndarray:
    """Scatter sparse (values, indices) into a length-n dense vector."""
    return jnp.zeros((n,), values.dtype).at[indices].add(values, mode="drop")


def gather_at(v: jnp.ndarray, indices: jnp.ndarray) -> jnp.ndarray:
    """Gather v at indices; sentinel index (>= len(v)) yields 0."""
    safe = jnp.minimum(indices, v.shape[0] - 1)
    vals = v[safe]
    return jnp.where(indices < v.shape[0], vals, 0.0)


def innovation_frac(innovation_sparsity: float, sparsity: float) -> float:
    """The PS innovation fraction of the top-k support."""
    return innovation_sparsity / max(sparsity, 1e-12)


def innovation_k(mu: int, frac: float) -> int:
    """Static innovation count for a length-``mu`` support.  ONE rounding
    for the compressor (select_innovation) and the byte accounting
    (core.rate) — evaluated with the same float association, so the
    accounted payload can never be off by one from the shipped one."""
    return max(1, int(round(mu * frac)))


def select_innovation(values: jnp.ndarray, frac: float):
    """PS innovation: the top ``frac`` fraction (by magnitude) of the top-k
    values vector, kept in-place (zeros elsewhere) — Section V / Fig. 5a.

    Returns (innovation vector (mu_pad,), local indices (k_inv,)).
    """
    k_inv = innovation_k(values.shape[0], frac)
    _, idx = jax.lax.top_k(jnp.abs(values), k_inv)
    inno = jnp.zeros_like(values).at[idx].set(values[idx])
    return inno, idx
