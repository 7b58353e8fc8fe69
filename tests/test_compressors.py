"""Compressor behaviour: phases, EF semantics, reconstruction quality,
quantized variant, and convergence of the online AE."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CompressionConfig
from repro.core import build_compressor
from repro.core.phases import (PHASE_COMPRESSED, PHASE_TOPK_AE, PHASE_WARMUP,
                               phase_for_step)

PARAMS = {
    "embed": {"w": jnp.zeros((32, 16))},
    "layer1": {"w": jnp.zeros((64, 64)), "b": jnp.zeros((64,))},
    "layer2": {"w": jnp.zeros((64, 64))},
    "lm_head": {"w": jnp.zeros((16, 32))},
}
K = 4


def _cc(method, **kw):
    kw.setdefault("sparsity", 0.05)
    kw.setdefault("innovation_sparsity", 0.005)
    kw.setdefault("warmup_steps", 2)
    kw.setdefault("ae_train_steps", 3)
    return CompressionConfig(method=method, **kw)


def test_phase_schedule():
    cc = _cc("lgc_rar")
    assert phase_for_step(0, cc) == PHASE_WARMUP
    assert phase_for_step(1, cc) == PHASE_WARMUP
    assert phase_for_step(2, cc) == PHASE_TOPK_AE
    assert phase_for_step(4, cc) == PHASE_TOPK_AE
    assert phase_for_step(5, cc) == PHASE_COMPRESSED
    assert phase_for_step(10**6, cc) == PHASE_COMPRESSED
    assert phase_for_step(99, _cc("dgc")) == PHASE_TOPK_AE
    assert phase_for_step(99, _cc("none")) == PHASE_WARMUP


def test_warmup_is_exact_mean():
    comp = build_compressor(_cc("dgc"), PARAMS, K)
    states = comp.init_sim_states(jax.random.PRNGKey(0))
    g = jax.random.normal(jax.random.PRNGKey(1), (K, comp.layout.n_total))
    gg, _, _ = comp.sim_step(states, g, 0, PHASE_WARMUP)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(g.mean(0)),
                               atol=1e-6)


def test_dgc_topk_sends_only_topk_plus_exempt():
    comp = build_compressor(_cc("dgc"), PARAMS, K)
    states = comp.init_sim_states(jax.random.PRNGKey(0))
    g = jax.random.normal(jax.random.PRNGKey(1), (K, comp.layout.n_total))
    gg, states, _ = comp.sim_step(states, g, 2, PHASE_TOPK_AE)
    gg = np.asarray(gg)
    layout = comp.layout
    # compressed leaves: at most K * k_l nonzeros per leaf
    for leaf in layout.compressed:
        nz = np.count_nonzero(gg[leaf.offset : leaf.offset + leaf.size])
        assert nz <= K * leaf.k
    # dense leaf transmitted exactly
    for leaf in layout.dense:
        seg = gg[leaf.offset : leaf.offset + leaf.size]
        ref = np.asarray(g.mean(0))[leaf.offset : leaf.offset + leaf.size]
        np.testing.assert_allclose(seg, ref, atol=1e-6)
    # residual holds the unsent mass
    assert float(jnp.abs(states["v"]).sum()) > 0


def test_sparse_gd_has_no_momentum_correction():
    """sparse_gd accumulates plain residuals; dgc momentum-corrects.
    After two steps with identical gradients, their residuals differ."""
    g = jax.random.normal(jax.random.PRNGKey(1), (K, 9280))
    res = {}
    for method in ("sparse_gd", "dgc"):
        comp = build_compressor(_cc(method, warmup_steps=0), PARAMS, K)
        states = comp.init_sim_states(jax.random.PRNGKey(0))
        for step in range(2):
            _, states, _ = comp.sim_step(states, g, step, PHASE_TOPK_AE)
        res[method] = np.asarray(states["v"])
    assert not np.allclose(res["sparse_gd"], res["dgc"])


@pytest.mark.parametrize("method", ["lgc_rar", "lgc_rar_q8", "lgc_ps"])
def test_lgc_full_cycle_finite_and_sparse(method):
    comp = build_compressor(_cc(method), PARAMS, K)
    states = comp.init_sim_states(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)
    cc = _cc(method)
    for step in range(8):
        rng, k = jax.random.split(rng)
        g = jax.random.normal(k, (K, comp.layout.n_total)) * 0.01
        phase = phase_for_step(step, cc)
        gg, states, stats = comp.sim_step(states, g, step, phase)
        assert bool(jnp.all(jnp.isfinite(gg))), (method, step)
    assert phase == PHASE_COMPRESSED


def test_lgc_rar_reconstruction_tracks_average_after_training():
    """After enough online AE steps, the decoded aggregate correlates with
    the true top-k average (the paper's Fig. 14 convergence claim).
    Node gradients share a PERSISTENT common component (the paper's
    Section III structure) — that is what the AE learns to compress."""
    from repro.core import autoencoder as AE
    cc = _cc("lgc_rar", warmup_steps=0, ae_train_steps=200)
    comp = build_compressor(cc, PARAMS, K)
    states = comp.init_sim_states(jax.random.PRNGKey(0))
    untrained_ae = states["ae"]
    rng = jax.random.PRNGKey(1)
    n = comp.layout.n_total
    # smooth heavy-amplitude base: its top-k value sequence retains local
    # 1-D structure, which is what the conv AE compresses (real gradients
    # have this property — paper Section III; checked on real ConvNet5
    # gradients in benchmarks/fig14_ae_convergence.py)
    t = jnp.arange(n) / n
    base = (jnp.sin(2 * jnp.pi * 3 * t) + 0.5 * jnp.sin(2 * jnp.pi * 7 * t)
            + 0.1 * jax.random.normal(jax.random.PRNGKey(42), (n,)))
    step_fn = jax.jit(comp.sim_step, static_argnums=(3,))
    vals_last = None
    for step in range(200):
        rng, k1, k2 = jax.random.split(rng, 3)
        # slowly-varying common direction + small per-node innovation
        common = base * (1.0 + 0.1 * jax.random.normal(k1, ()))
        inno = jax.random.normal(k2, (K, n)) * 0.05
        g = (common[None] + inno) * 0.01
        _, states, stats = step_fn(states, g, step, PHASE_TOPK_AE)

    # Note: the raw ae_loss drifts upward because EF accumulation grows
    # the top-k magnitudes; the meaningful metric is RELATIVE
    # reconstruction error of the trained AE vs the untrained one on a
    # fresh sample of the same family.
    from repro.core import sparsify as SP
    # the AE's input as the compressor builds it: values at the top-k
    # support in index order (the canonical sorted support), not in
    # top-k (magnitude) order
    v = states["v"][0]
    _, idx = SP.select_topk(v, comp.layout)
    vals = SP.gather_at(v, jnp.sort(idx))

    def rel_err(ae):
        z = AE.lgc_encode(ae, vals)
        rec = AE.lgc_decode_rar(ae, z)[0]
        return float(jnp.linalg.norm(rec - vals)
                     / jnp.maximum(jnp.linalg.norm(vals), 1e-9))

    trained = rel_err(states["ae"])
    untrained = rel_err(untrained_ae)
    assert trained < untrained, (trained, untrained)
    assert trained < 0.9, trained       # better than predicting zero


def test_q8_quantization_bounded_error():
    """The shared quantize module (fake path == wire path) keeps the
    per-value error under half the per-block scale — which is itself
    bounded by the old per-tensor scale."""
    from repro.dist import quantize as Q
    z = jax.random.normal(jax.random.PRNGKey(0), (26, 4))
    zq = Q.fake_quantize(z)
    scale = float(jnp.max(jnp.abs(z))) / 127.0
    assert zq.shape == z.shape
    assert float(jnp.max(jnp.abs(z - zq))) <= scale * 0.5 + 1e-7


def test_sim_equals_dist_on_fake_mesh(subproc):
    """The shard_map (production) path and the stacked-sim path agree.
    AE-conv gradients reduce in different orders across layouts, so lgc
    methods get a 1e-3 tolerance (documented numerical divergence)."""
    out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs.base import CompressionConfig
from repro.core import build_compressor
from repro.core.phases import phase_for_step

params = {"embed": {"w": jnp.zeros((32, 16))},
          "layer1": {"w": jnp.zeros((64, 64)), "b": jnp.zeros((64,))},
          "lm_head": {"w": jnp.zeros((16, 32))}}
K = 4
mesh = jax.make_mesh((4,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
for method in ["dgc", "sparse_gd", "lgc_rar", "lgc_ps"]:
    cc = CompressionConfig(method=method, sparsity=0.05,
                           innovation_sparsity=0.005,
                           warmup_steps=1, ae_train_steps=2)
    comp = build_compressor(cc, params, K)
    n = comp.layout.n_total
    sim_states = comp.init_sim_states(jax.random.PRNGKey(0))
    base = comp.init_state(jax.random.PRNGKey(0))
    ae_keys = tuple(k for k in ("ae", "ae_mom") if k in base)

    def dist_fn(step, phase):
        def inner(uv, ae_part, g):
            state = {"u": uv["u"][0], "v": uv["v"][0], **ae_part}
            gg, new_state, _ = comp.dist_step(state, g[0], step, phase,
                                              ("data",))
            return (gg, {"u": new_state["u"][None],
                         "v": new_state["v"][None]},
                    {k: new_state[k] for k in ae_part})
        return jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=({"u": P("data"), "v": P("data")}, P(), P("data")),
            out_specs=(P(), {"u": P("data"), "v": P("data")}, P()),
            axis_names={"data"}, check_vma=False))

    uv = {"u": jnp.zeros((K, n)), "v": jnp.zeros((K, n))}
    ae_part = {k: base[k] for k in ae_keys}
    rng = jax.random.PRNGKey(1)
    tol = 1e-3 if method.startswith("lgc") else 1e-5
    for step in range(5):
        rng, k2 = jax.random.split(rng)
        g = jax.random.normal(k2, (K, n)) * 0.01
        phase = phase_for_step(step, cc)
        g_sim, sim_states, _ = comp.sim_step(sim_states, g, step, phase)
        g_dist, uv, ae_part = dist_fn(step, phase)(uv, ae_part, g)
        err = float(jnp.max(jnp.abs(g_sim - g_dist)))
        assert err < tol, (method, step, phase, err)
    print(method, "OK")
print("PASS")
""", devices=4, timeout=900)
    assert "PASS" in out
