"""Property + adversarial tests for the fused single-sweep sparsification
path (segmented block-topk kernel + EF fold, ``topk_backend="fused"``).

Adversarial structure on purpose: leaf boundaries NOT lane/block aligned,
heavy magnitude ties, all-zero segments, and mu_pad sentinel padding —
the cases where a block-sweep selection can silently diverge from the
per-leaf lax.top_k reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import sparsify as SP
from repro.kernels import count_pallas_calls

# Odd sizes: no leaf boundary is a multiple of 128 (lane) or 1024 (block)
PARAMS_ODD = {
    "embed": {"w": jnp.zeros((11, 3))},                      # dense, 33
    "block1": {"w": jnp.zeros((57, 31)), "b": jnp.zeros((13,))},
    "block2": {"w": jnp.zeros((41, 29))},
    "fc": {"w": jnp.zeros((17, 19))},                        # topk_only, 323
}
LAYOUT = SP.build_layout(PARAMS_ODD, sparsity=0.05)
N = LAYOUT.n_total

SETTINGS = dict(max_examples=15, deadline=None)


def _assert_select_equal(v, layout):
    vj, ij = SP.select_topk(v, layout, backend="jnp")
    vf, if_ = SP.select_topk(v, layout, backend="fused")
    np.testing.assert_array_equal(np.asarray(ij), np.asarray(if_))
    np.testing.assert_allclose(np.asarray(vj), np.asarray(vf), atol=1e-6)


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1), sparsity=st.floats(0.01, 0.2))
def test_fused_select_matches_reference_unaligned(seed, sparsity):
    layout = SP.build_layout(PARAMS_ODD, sparsity=sparsity)
    v = jax.random.normal(jax.random.PRNGKey(seed), (layout.n_total,))
    _assert_select_equal(v, layout)


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1))
def test_fused_select_with_ties(seed):
    """Integer-valued residuals: nearly every magnitude is tied.  The
    sweep must reproduce lax.top_k's stable lowest-index-first order."""
    v = jax.random.randint(jax.random.PRNGKey(seed), (N,), -2, 3
                           ).astype(jnp.float32)
    _assert_select_equal(v, LAYOUT)


def test_fused_select_all_zero_segments():
    _assert_select_equal(jnp.zeros((N,)), LAYOUT)
    # one live leaf, everything else exactly zero
    v = jnp.zeros((N,))
    leaf = LAYOUT.compressed[1]
    v = v.at[leaf.offset + 5].set(3.0)
    _assert_select_equal(v, LAYOUT)


def test_fused_select_mu_pad_sentinels():
    assert LAYOUT.mu_pad > LAYOUT.mu, "layout must exercise padding"
    v = jax.random.normal(jax.random.PRNGKey(7), (N,))
    vals, idx = SP.select_topk(v, LAYOUT, backend="fused")
    vals, idx = np.asarray(vals), np.asarray(idx)
    pad = idx >= N
    assert pad.sum() == LAYOUT.mu_pad - LAYOUT.mu
    assert (vals[pad] == 0).all()
    assert (idx[pad] == N).all()


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1), m=st.floats(0.0, 0.99),
       momentum_on=st.sampled_from([True, False]))
def test_fused_accumulate_select_matches_three_pass_reference(
        seed, m, momentum_on):
    """The one-sweep kernel == momentum_correct + select_topk +
    select_topk_last, including the sparse-GD (no momentum) accumulate."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    g = jax.random.normal(ks[0], (N,))
    u = jax.random.normal(ks[1], (N,))
    v = jax.random.normal(ks[2], (N,))
    u2, v2, vals, idx, lvals, lidx = SP.fused_accumulate_select(
        g, u, v, LAYOUT, momentum=m, use_momentum=momentum_on)
    if momentum_on:
        u_ref, v_ref = SP.momentum_correct(u, v, g, m)
    else:
        u_ref, v_ref = u, v + g
    np.testing.assert_allclose(np.asarray(u2), np.asarray(u_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(v_ref), atol=1e-5)
    vr, ir = SP.select_topk(v_ref, LAYOUT)
    lvr, lir = SP.select_topk_last(v_ref, LAYOUT)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(vr), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(lidx), np.asarray(lir))
    np.testing.assert_allclose(np.asarray(lvals), np.asarray(lvr),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# structural guarantees


def test_fused_path_is_one_kernel_launch():
    """The acceptance property of this refactor: ONE selection launch per
    compress step, not one per leaf (the pallas backend's shape)."""
    v = jnp.zeros((N,))
    fused = jax.make_jaxpr(
        lambda x: SP.select_topk(x, LAYOUT, backend="fused"))(v)
    assert count_pallas_calls(fused) == 1
    per_leaf = jax.make_jaxpr(
        lambda x: SP.select_topk(x, LAYOUT, backend="pallas"))(v)
    assert count_pallas_calls(per_leaf) == len(LAYOUT.compressed)
    sweep = jax.make_jaxpr(
        lambda gg, uu, vv: SP.fused_accumulate_select(gg, uu, vv, LAYOUT,
                                                      0.9))(v, v, v)
    assert count_pallas_calls(sweep) == 1


def test_select_topk_last_backend_dispatch_agrees():
    v = jax.random.normal(jax.random.PRNGKey(11), (N,))
    vj, ij = SP.select_topk_last(v, LAYOUT, backend="jnp")
    assert vj.shape == (LAYOUT.k_last,)
    for backend in ("pallas", "fused"):
        vb, ib = SP.select_topk_last(v, LAYOUT, backend=backend)
        np.testing.assert_array_equal(np.asarray(ij), np.asarray(ib))
        np.testing.assert_allclose(np.asarray(vj), np.asarray(vb),
                                   atol=1e-6)


@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1))
def test_clear_sent_merged_equals_sequential_clears(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    u = jax.random.normal(ks[0], (N,))
    v = jax.random.normal(ks[1], (N,))
    # index sets with sentinel entries (== N, must be dropped)
    ia = jax.random.randint(ks[2], (37,), 0, N + 1)
    ib = jax.random.randint(ks[3], (11,), 0, N + 1)
    u_ref, v_ref = SP.clear_sent(u, v, ia, N)
    u_ref, v_ref = SP.clear_sent(u_ref, v_ref, ib, N)
    u2, v2 = SP.clear_sent_merged(u, v, ia, ib, N)
    np.testing.assert_array_equal(np.asarray(u2), np.asarray(u_ref))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v_ref))
