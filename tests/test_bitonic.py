"""Property + adversarial tests for the bitonic extraction backend
(``kernels/bitonic.py``, ``extract_backend="bitonic"``).

The contract under test: the sorting-network extractor is *bit-identical*
to the sequential loop extractor on materialized inputs — same kept set,
same emission order (magnitude-descending, ties lowest-index-first, the
``lax.top_k`` stable order), same (0, block, −1) dead-slot fill — under
the adversarial structure that breaks naive partial sorts: heavy ties,
non-power-of-two blocks (network padding), all-masked and all-zero
blocks, unaligned leaf boundaries straddling blocks, and mu_pad
sentinels.  On the fused *accumulate* path the indices and accumulators
stay exact and candidate values get the 1-ulp fma slack the loop
backend's own gates already use (see select_candidates_bitonic's
docstring).
"""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st
from jax.experimental import pallas as pl

from repro.core import sparsify as SP
from repro.kernels import bitonic as B
from repro.kernels import count_pallas_calls
from repro.kernels import ops

# Odd sizes: no leaf boundary is a multiple of 128 (lane) or a power of
# two — same adversarial layout as tests/test_fused_sweep.py
PARAMS_ODD = {
    "embed": {"w": jnp.zeros((11, 3))},                      # dense, 33
    "block1": {"w": jnp.zeros((57, 31)), "b": jnp.zeros((13,))},
    "block2": {"w": jnp.zeros((41, 29))},
    "fc": {"w": jnp.zeros((17, 19))},                        # topk_only, 323
}
LAYOUT = SP.build_layout(PARAMS_ODD, sparsity=0.05)
N = LAYOUT.n_total

SETTINGS = dict(max_examples=15, deadline=None)

# non-power-of-two block (9 lanes): the network must pad to 2048 and
# keep the pad elements (mag −1, idx past the block) out of every result
ODD_BLOCK = 1152


# ---------------------------------------------------------------------------
# the sorting network itself


def test_bitonic_sort_matches_lexsort_with_ties():
    rng = np.random.default_rng(0)
    n2 = 4096                    # 4 tiles: the tile-pair stages run too
    shape = (n2 // 128, 128)
    keys = jnp.asarray(rng.integers(0, 40, size=shape), jnp.int32)
    tie = jnp.arange(n2, dtype=jnp.int32).reshape(shape)
    payload = jnp.asarray(rng.normal(size=shape), jnp.float32)

    def lt(a, b):
        return (a[0] < b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))

    def kernel(k_ref, t_ref, p_ref, ko, to, po):
        ko[...], to[...], po[...] = k_ref[...], t_ref[...], p_ref[...]
        B.bitonic_sort([ko, to, po], lt, 2)

    sk, stie, sp = pl.pallas_call(
        kernel, interpret=True,
        out_shape=[jax.ShapeDtypeStruct(shape, a.dtype)
                   for a in (keys, tie, payload)])(keys, tie, payload)
    keys, tie, payload = (np.asarray(a).reshape(-1)
                          for a in (keys, tie, payload))
    order = np.lexsort((tie, keys))
    np.testing.assert_array_equal(np.asarray(sk).reshape(-1), keys[order])
    np.testing.assert_array_equal(np.asarray(stie).reshape(-1), tie[order])
    np.testing.assert_array_equal(np.asarray(sp).reshape(-1), payload[order])


def test_next_pow2():
    assert [B.next_pow2(n) for n in (0, 1, 2, 3, 1024, 1025, 1152)] == \
        [1, 1, 2, 4, 1024, 2048, 2048]


# ---------------------------------------------------------------------------
# kernel-level: loop vs bitonic through the same segmented sweep,
# bitwise, at the SAME block/n_cand geometry


def _both_extracts(x, seg, kcap, n_cand, block):
    return [ops.segmented_topk(x, seg, kcap, n_cand, block=block,
                               extract=e) for e in ("loop", "bitonic")]


def _assert_bitwise(outs_a, outs_b):
    for a, b in zip(outs_a, outs_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1))
def test_segmented_extract_bitwise_random(seed):
    rng = np.random.default_rng(seed)
    n = 2 * ODD_BLOCK
    x = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    seg = jnp.asarray(rng.integers(-1, 3, size=(n,)), jnp.int32)
    kcap = jnp.asarray(rng.integers(1, 40, size=(3,)), jnp.int32)
    _assert_bitwise(*_both_extracts(x, seg, kcap, 96, ODD_BLOCK))


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1))
def test_segmented_extract_bitwise_ties(seed):
    """Integer-valued inputs: nearly every magnitude is tied, so only an
    extractor reproducing the lowest-index-first tie-break exactly can
    match the loop bitwise."""
    rng = np.random.default_rng(seed)
    n = 2 * ODD_BLOCK
    x = jnp.asarray(rng.integers(-2, 3, size=(n,)), jnp.float32)
    seg = jnp.asarray(rng.integers(-1, 3, size=(n,)), jnp.int32)
    kcap = jnp.asarray(rng.integers(1, 40, size=(3,)), jnp.int32)
    _assert_bitwise(*_both_extracts(x, seg, kcap, 96, ODD_BLOCK))


def test_segmented_extract_all_masked_and_all_zero_blocks():
    n = 2 * ODD_BLOCK
    kcap = jnp.asarray([7, 5], jnp.int32)
    # block 0 entirely masked (seg = -1), block 1 live
    seg = jnp.concatenate([jnp.full((ODD_BLOCK,), -1, jnp.int32),
                           jnp.ones((ODD_BLOCK,), jnp.int32)])
    x = jax.random.normal(jax.random.PRNGKey(0), (n,))
    _assert_bitwise(*_both_extracts(x, seg, kcap, 48, ODD_BLOCK))
    # all-zero values: kept set is still cap-sized, order is pure
    # index tie-break
    _assert_bitwise(*_both_extracts(jnp.zeros((n,)), seg, kcap, 48,
                                    ODD_BLOCK))
    # everything masked everywhere: both must emit only (0, block, -1)
    dead = jnp.full((n,), -1, jnp.int32)
    outs_l, outs_b = _both_extracts(x, dead, kcap, 48, ODD_BLOCK)
    _assert_bitwise(outs_l, outs_b)
    assert (np.asarray(outs_b[2]) == -1).all()


# ---------------------------------------------------------------------------
# whole-path: select_topk through the fused sweep, bitonic extraction


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1), sparsity=st.floats(0.01, 0.2))
def test_bitonic_select_bitwise_matches_jnp_unaligned(seed, sparsity):
    """Materialized input (no accumulate arithmetic): the bitonic path
    must match the per-leaf lax.top_k reference BITWISE — indices and
    values — across unaligned leaf boundaries."""
    layout = SP.build_layout(PARAMS_ODD, sparsity=sparsity)
    v = jax.random.normal(jax.random.PRNGKey(seed), (layout.n_total,))
    vj, ij = SP.select_topk(v, layout, backend="jnp")
    vb, ib = SP.select_topk(v, layout, backend="fused", extract="bitonic")
    np.testing.assert_array_equal(np.asarray(ij), np.asarray(ib))
    np.testing.assert_array_equal(np.asarray(vj), np.asarray(vb))


def test_bitonic_select_mu_pad_sentinels():
    assert LAYOUT.mu_pad > LAYOUT.mu, "layout must exercise padding"
    v = jax.random.normal(jax.random.PRNGKey(7), (N,))
    vals, idx = SP.select_topk(v, LAYOUT, backend="fused",
                               extract="bitonic")
    vals, idx = np.asarray(vals), np.asarray(idx)
    pad = idx >= N
    assert pad.sum() == LAYOUT.mu_pad - LAYOUT.mu
    assert (vals[pad] == 0).all()
    assert (idx[pad] == N).all()


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1), m=st.floats(0.0, 0.99),
       momentum_on=st.sampled_from([True, False]))
def test_fused_accumulate_loop_vs_bitonic(seed, m, momentum_on):
    """The fused accumulate+select sweep, loop vs bitonic extraction:
    accumulators and ALL indices bitwise; candidate values bitwise
    without momentum (single add), and within the 1-ulp fma slack with
    it (which fma contraction of v + (m·u + g) each backend's
    materialized copy sees is XLA's per-compile choice — the same slack
    the jnp-oracle gates grant the loop backend)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    g = jax.random.normal(ks[0], (N,))
    u = jax.random.normal(ks[1], (N,))
    v = jax.random.normal(ks[2], (N,))
    outs = [SP.fused_accumulate_select(g, u, v, LAYOUT, momentum=m,
                                       use_momentum=momentum_on,
                                       extract=e)
            for e in ("loop", "bitonic")]
    (u_l, v_l, vals_l, idx_l, lv_l, li_l), \
        (u_b, v_b, vals_b, idx_b, lv_b, li_b) = outs
    np.testing.assert_array_equal(np.asarray(u_l), np.asarray(u_b))
    np.testing.assert_array_equal(np.asarray(v_l), np.asarray(v_b))
    np.testing.assert_array_equal(np.asarray(idx_l), np.asarray(idx_b))
    np.testing.assert_array_equal(np.asarray(li_l), np.asarray(li_b))
    if momentum_on:
        np.testing.assert_allclose(np.asarray(vals_l), np.asarray(vals_b),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(lv_l), np.asarray(lv_b),
                                   atol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(vals_l),
                                      np.asarray(vals_b))
        np.testing.assert_array_equal(np.asarray(lv_l), np.asarray(lv_b))


def test_fused_accumulate_bitonic_matches_three_pass_reference():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    g = jax.random.normal(ks[0], (N,))
    u = jax.random.normal(ks[1], (N,))
    v = jax.random.normal(ks[2], (N,))
    u2, v2, vals, idx, lvals, lidx = SP.fused_accumulate_select(
        g, u, v, LAYOUT, momentum=0.9, extract="bitonic")
    u_ref, v_ref = SP.momentum_correct(u, v, g, 0.9)
    np.testing.assert_allclose(np.asarray(u2), np.asarray(u_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(v_ref),
                               atol=1e-5)
    vr, ir = SP.select_topk(v_ref, LAYOUT)
    lvr, lir = SP.select_topk_last(v_ref, LAYOUT)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(vr),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(lidx), np.asarray(lir))
    np.testing.assert_allclose(np.asarray(lvals), np.asarray(lvr),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# dispatch rules + structural guarantees


def _slots(*ks):
    return tuple(SimpleNamespace(k=k) for k in ks)


def test_fused_block_rules():
    # loop: >= 8*k_max, block-rounded, capped
    assert SP._fused_block(_slots(10), "loop") == SP.FUSED_BLOCK
    assert SP._fused_block(_slots(1000), "loop") == 8192
    assert SP._fused_block(_slots(65536), "loop") == SP.FUSED_BLOCK_MAX
    # bitonic: next power of two >= k_max, independent of the 8x margin
    assert SP._fused_block(_slots(10), "bitonic") == SP.FUSED_BLOCK
    assert SP._fused_block(_slots(1000), "bitonic") == 1024
    assert SP._fused_block(_slots(20480), "bitonic") == 32768
    assert SP._fused_block(_slots(200000), "bitonic") == SP.FUSED_BLOCK_MAX


def test_resolve_extract_auto_threshold():
    # explicit backends pass through untouched
    assert SP._resolve_extract("loop", _slots(10**6)) == "loop"
    assert SP._resolve_extract("bitonic", _slots(1)) == "bitonic"
    # auto: loop while 8*k_max fits in one max-size block, else bitonic
    at_cap = SP.FUSED_BLOCK_MAX // 8
    assert SP._resolve_extract("auto", _slots(at_cap)) == "loop"
    assert SP._resolve_extract("auto", _slots(at_cap + 1)) == "bitonic"


def test_bitonic_path_is_one_kernel_launch():
    """Swapping the extractor must not change the sweep's structure:
    still ONE pallas launch for select and for the fused accumulate."""
    v = jnp.zeros((N,))
    sel = jax.make_jaxpr(lambda x: SP.select_topk(
        x, LAYOUT, backend="fused", extract="bitonic"))(v)
    assert count_pallas_calls(sel) == 1
    sweep = jax.make_jaxpr(lambda gg, uu, vv: SP.fused_accumulate_select(
        gg, uu, vv, LAYOUT, 0.9, extract="bitonic"))(v, v, v)
    assert count_pallas_calls(sweep) == 1


def test_big_k_layout_auto_selects_bitonic_and_matches_jnp():
    """A >16Ki-k leaf (the regime the loop extractor cannot serve —
    DESIGN.md's struck Scaling note): auto resolves to bitonic, the
    block is the next power of two, and the selection still matches the
    per-leaf lax.top_k reference bitwise."""
    params = {"embed": {"w": jnp.zeros((16,))},
              "mid": {"w": jnp.zeros((81920,))},
              "fc": {"w": jnp.zeros((37,))}}
    layout = SP.build_layout(params, sparsity=0.25)
    info = SP.fused_plan_info(layout)
    assert info["extract_backend"] == "bitonic", info
    assert info["fused_block"] == 32768, info
    k_max = max(l.k for l in layout.compressed)
    assert 8 * k_max > SP.FUSED_BLOCK_MAX, k_max
    v = jax.random.normal(jax.random.PRNGKey(9), (layout.n_total,))
    vj, ij = SP.select_topk(v, layout, backend="jnp")
    vb, ib = SP.select_topk(v, layout, backend="fused", extract="auto")
    np.testing.assert_array_equal(np.asarray(ij), np.asarray(ib))
    np.testing.assert_array_equal(np.asarray(vj), np.asarray(vb))
