"""Compile the Pallas kernels of the compression hot path for a TPU v5e
that is described, not attached.

Interpret mode cannot see what the TPU's compiler refuses: block shapes
off the (8, 128) tiling, primitives Mosaic has no lowering for (scatter,
cumsum), more VMEM than a kernel may use.  These tests compile each
kernel at a realistic size with ``interpret=False`` and check that the
executable holds it as a ``tpu_custom_call``.  Nothing runs.

The topology is described inside a module fixture — never at import —
and the tests skip where no v5e can be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import sparsify as SP
from repro.core.autoencoder import init_lgc_autoencoder
from repro.dist import packed as PK
from repro.kernels import ops, tpu_kernel_names

# one 2^24-element leaf: 17,205,216 elements in all, k_max = 16,777 >
# 16Ki, so the loop extractor runs at FUSED_BLOCK_MAX and the bitonic
# one at a 32Ki block
BIG = {"embed": {"w": jnp.zeros((1000, 64))},
       "big": {"w": jnp.zeros((4096, 4096))},
       "mid": {"w": jnp.zeros((300, 1000))},
       "fc": {"w": jnp.zeros((64, 1000))}}
SMALL = {"a": {"w": jnp.zeros((256, 64))}, "b": {"w": jnp.zeros((2048,))},
         "c": {"w": jnp.zeros((64, 32))}}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(fn, *args):
    return tpu_kernel_names(jax.jit(fn).lower(*args).compile().as_text())


@pytest.mark.parametrize("extract", ["loop", "bitonic"])
def test_fused_sweep_compiles_at_big_k(one_chip, extract):
    layout = SP.build_layout(BIG, 0.001)
    n = layout.n_total
    roles = (SP.ROLE_COMPRESSED, SP.ROLE_TOPK_ONLY)
    ex, block, kcap, n_cand, slots = SP._fused_meta(layout, roles, extract)
    assert n >= 10**7 and max(kcap) > 16 * 1024, (n, max(kcap))

    def sweep(g, u, v):
        return ops.fused_ef_topk(g, u, v, SP._slot_map(n, slots),
                                 jnp.asarray(kcap), 0.9, True, n_cand,
                                 block=block, extract=ex, interpret=False)

    x = _spec(one_chip, (n,))
    assert _kernels(sweep, x, x, x) == ["_ef_topk_kernel"]


def test_select_topk_fused_compiles(one_chip):
    layout = SP.build_layout(SMALL, 0.01)

    def select(v):
        return SP.select_topk(v, layout, backend="fused", interpret=False)

    assert _kernels(select, _spec(one_chip, (layout.n_total,))) == \
        ["_segmented_topk_kernel"]


def test_packed_roundtrip_compiles(one_chip):
    n, k = 10**7, 32 * 1024
    plan = PK.make_plan(n, k)

    def roundtrip(vals, idx):
        payload = PK.encode_sparse_fused(vals, idx, plan, interpret=False)
        return PK.decode_sparse(payload, plan, interpret=False)

    names = _kernels(roundtrip, _spec(one_chip, (k,)),
                     _spec(one_chip, (k,), jnp.int32))
    assert {"_encode_fused_kernel", "_unpack_kernel"} <= set(names), names


def test_lgc_encode_fast_compiles(one_chip):
    ae = jax.eval_shape(lambda: init_lgc_autoencoder(jax.random.PRNGKey(0)))
    ae = jax.tree_util.tree_map(lambda a: _spec(one_chip, a.shape, a.dtype),
                                ae)
    names = _kernels(lambda a, g: ops.lgc_encode_fast(a, g, interpret=False),
                     ae, _spec(one_chip, (64 * 1024,)))
    assert set(names) == {"_matmul_lrelu_kernel"}, names


def test_global_topk_compiles(one_chip):
    names = _kernels(lambda x: ops.global_topk(x, 64, interpret=False),
                     _spec(one_chip, (1 << 20,)))
    assert names == ["_block_topk_kernel"]
