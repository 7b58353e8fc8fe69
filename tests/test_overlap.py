"""The overlapped bucketed exchange (PR: wire_buckets) — regression net.

Three contracts, each against an independent reference:

  * **Schedule equivalence**: a pipelined bucketed exchange
    (``wire_buckets`` > 1) computes the SAME gradient as the historical
    unbucketed schedule — bit-for-bit on every float wire (bucketing a
    ring is a pure column re-batching of the chunk matrix; the per-node
    accumulation order is untouched), and within the documented q8
    bound where per-bucket quantization re-groups scale blocks
    (lgc_rar_q8 on ring_q8; the packed value payload on ring_packed).
    Every configuration is ALSO checked against the Sim oracle.
  * **Fused encode**: ``packed.encode_sparse_fused`` — the one-kernel
    block-quantize + bit-plane pack — is bit-exact against the composed
    quantize→pack path and costs exactly ONE pallas_call in its jaxpr.
  * **Per-bucket accounting**: ``wire_report(by_op=True)`` under a
    bucketed lowering equals ``plan.wire_terms_by_op`` label-for-label
    (one ``op#b<i>`` row per bucket, zero slack), and the bucket/chunk
    zero-padding is priced explicitly: ``accounted == ideal +
    padding_overhead_terms`` per op, at every bucket count — so the
    bucketed-vs-unbucketed byte delta IS the padding delta
    (property-tested over awkward sizes).

Chaos rides along: the guarded bucketed packed path (eager per-bucket
encode under the structural sink) is scrubbed against the chaos Sim
oracle under the identical seeded fault spec.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import CompressionConfig
from repro.core import build_compressor
from repro.dist import collectives as C
from repro.dist import packed as PK
from repro.dist import plan as XP
from repro.dist import quantize as Q
from repro.kernels import count_pallas_calls

K = 4
METHODS = ["none", "sparse_gd", "dgc", "lgc_rar", "lgc_rar_q8", "lgc_ps"]


def _cc(method, transport="ring", **kw):
    kw.setdefault("sparsity", 0.05)
    kw.setdefault("innovation_sparsity", 0.005)
    kw.setdefault("warmup_steps", 1)
    kw.setdefault("ae_train_steps", 2)
    return CompressionConfig(method=method, transport=transport, **kw)


# ---------------------------------------------------------------------------
# the bucket split rule


def test_bucket_widths_contract():
    for c in (1, 2, 5, 37, 64, 600):
        for nb in (1, 2, 3, 5, 11, 1000):
            B, cb = C.bucket_widths(c, nb)
            assert 1 <= B <= min(max(nb, 1), c)
            assert (B - 1) * cb < c <= B * cb      # covers, no empty bucket
            if nb == 1:
                assert (B, cb) == (1, c)
    assert C.bucket_widths(0, 4) == (1, 0)         # degenerate: one bucket


# ---------------------------------------------------------------------------
# the fused packed-wire encode: bit-exact, one kernel launch


@pytest.mark.parametrize("n,k,sb,checksum", [
    (600, 48, 256, False),
    (600, 48, 64, True),
    (8192, 33, 256, False),
    (1000, 70, 128, True),
])
def test_encode_sparse_fused_bit_exact_single_launch(n, k, sb, checksum):
    plan = PK.make_plan(n, k, sb, checksum=checksum)
    assert not plan.raw_index, plan       # the fused path's regime
    rng = np.random.default_rng(n + k)
    idx = jnp.asarray(np.sort(rng.choice(n, size=k, replace=False)),
                      jnp.int32)
    vals = jnp.asarray(rng.normal(size=k).astype(np.float32))
    ref = PK.encode_sparse(vals, idx, plan)
    got = PK.encode_sparse_fused(vals, idx, plan)
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert bool(jnp.all(a == b)), (n, k, sb, checksum)
    # launch count: ONE fused kernel reads (vals, idx) from HBM once;
    # the composed path pays separate quantize + pack passes
    jx = jax.make_jaxpr(
        lambda v, i: PK.encode_sparse_fused(v, i, plan))(vals, idx)
    assert count_pallas_calls(jx) == 1, jx
    # decodes identically too (same payload, same codec)
    dv, di = PK.decode_sparse(got, plan)
    vs, is_ = PK._sort_pairs(vals, idx)
    assert bool(jnp.all(di == is_))
    q_err = float(jnp.max(jnp.abs(dv - vs)))
    assert q_err <= float(jnp.max(jnp.abs(vals))) / 127.0 + 1e-7


def test_encode_sparse_fused_falls_back_for_raw_index():
    plan = PK.make_plan(65536, 3, 256)             # raw-index regime
    assert plan.raw_index
    idx = jnp.asarray([5, 99, 60000], jnp.int32)
    vals = jnp.asarray([1.0, -2.0, 3.0], jnp.float32)
    ref = PK.encode_sparse(vals, idx, plan)
    got = PK.encode_sparse_fused(vals, idx, plan)
    for a, b in zip(ref, got):
        assert bool(jnp.all(a == b))


def test_packed_bucket_plan_subformat():
    plan = PK.make_plan(4096, 100, 64, checksum=True)
    assert not plan.raw_index
    sub = PK.bucket_plan(plan, 23)
    assert sub.k == 23 and sub.n == plan.n
    assert (sub.width, sub.lo_bits, sub.n_buckets, sub.scale_block,
            sub.checksum) == (plan.width, plan.lo_bits, plan.n_buckets,
                              plan.scale_block, plan.checksum)
    assert not sub.raw_index
    # the per-bucket payload is a real sub-format: encodable/decodable
    rng = np.random.default_rng(0)
    idx = jnp.asarray(np.sort(rng.choice(4096, 23, replace=False)),
                      jnp.int32)
    vals = jnp.asarray(rng.normal(size=23).astype(np.float32))
    pay = PK.encode_sparse_fused(vals, idx, sub)
    dv, di = PK.decode_sparse(pay, sub)
    assert bool(jnp.all(di == idx))


# ---------------------------------------------------------------------------
# pricer properties: padding is priced, buckets only add priced padding


def _base_label(lbl):
    return lbl.split("#b")[0]


def _accounted_by_op(plan, wb):
    out = {}
    for lbl, terms in XP.wire_terms_by_op(plan, wire_buckets=wb).items():
        base = _base_label(lbl)
        out[base] = out.get(base, 0.0) + sum(terms.values())
    return out


@settings(max_examples=12, deadline=None)
@given(extra=st.integers(1, 257), wb=st.integers(1, 7),
       transport=st.sampled_from(("ring", "ring_q8", "ring_hier",
                                  "ring_packed")))
def test_padding_priced_exactly(extra, wb, transport):
    """accounted == ideal + padding_overhead, per op, at EVERY bucket
    count — so raising wire_buckets changes an op's bytes by exactly its
    padding-overhead delta.  Sizes are deliberately awkward (one leaf of
    4096+extra values, extra in [1, 257]) so the ``_to_chunks`` ceil-pad
    and the bucket pad are both live."""
    params = {"embed": {"w": jnp.zeros((16, 8))},
              "mid": {"w": jnp.zeros((4096 + extra,))},
              "lm_head": {"w": jnp.zeros((100,))}}
    method = "lgc_rar_q8" if transport == "ring_q8" else "dgc"
    cc = _cc(method, transport, wire_buckets=wb)
    layout = build_compressor(cc, params, K).layout
    plan = XP.build_plan(cc, layout, K)
    axes = (2, 2) if transport == "ring_hier" else None
    pad_b = XP.padding_overhead_terms(plan, axis_sizes=axes)
    pad_1 = XP.padding_overhead_terms(plan, axis_sizes=axes,
                                      wire_buckets=1)
    acc_b, acc_1 = {}, {}
    for wbk, acc in ((wb, acc_b), (1, acc_1)):
        for lbl, terms in XP.wire_terms_by_op(
                plan, axis_sizes=axes, wire_buckets=wbk).items():
            b = _base_label(lbl)
            acc[b] = acc.get(b, 0.0) + sum(terms.values())
    assert set(acc_b) == set(acc_1)
    for lbl in acc_b:
        # the pad-free ideal payload is bucket-count invariant
        ideal_b = acc_b[lbl] - pad_b.get(lbl, 0.0)
        ideal_1 = acc_1[lbl] - pad_1.get(lbl, 0.0)
        assert ideal_b == pytest.approx(ideal_1, rel=1e-9), (
            transport, wb, lbl)
        # buckets never make an exchange cheaper
        assert acc_b[lbl] >= acc_1[lbl] - 1e-9
    # overhead is overhead: nonnegative, and bounded by one bucket's
    # worth of columns per ring hop (sanity, not exact)
    for lbl, pad in pad_b.items():
        assert pad >= -1e-9, (lbl, pad)


def test_padding_overhead_chunk_pad_unbucketed():
    """The historical ``_to_chunks`` ceil-pad is now priced: a dense
    reduce of n = c*K - r values ships K chunks of ceil(n/K), i.e.
    2(K-1)*ceil(n/K)*4 accounted vs the pad-free 2(K-1)/K*n*4."""
    params = {"a": {"w": jnp.zeros((4097,))}}
    cc = _cc("none", "ring")
    layout = build_compressor(cc, params, K).layout
    plan = XP.build_plan(cc, layout, K, phase="warmup")
    n = layout.n_total
    pad = XP.padding_overhead_terms(plan)
    c = -(-n // K)
    want = 2 * (K - 1) * c * 4 - 2 * (K - 1) / K * n * 4
    assert pad["grad"] == pytest.approx(want)
    # exact multiples pad nothing
    params2 = {"a": {"w": jnp.zeros((4096,))}}
    layout2 = build_compressor(cc, params2, K).layout
    plan2 = XP.build_plan(cc, layout2, K, phase="warmup")
    assert XP.padding_overhead_terms(plan2) == {}


# ---------------------------------------------------------------------------
# the schedule-equivalence gate: bucketed == unbucketed == Sim oracle

_PARAMS_SRC = """
params = {"embed": {"w": jnp.zeros((32, 16))},
          "layer1": {"w": jnp.zeros((64, 64)), "b": jnp.zeros((64,))},
          "layer2": {"w": jnp.zeros((64, 64))},
          "lm_head": {"w": jnp.zeros((16, 32))}}
"""


def test_bucketed_matches_unbucketed_and_sim_oracle(subproc):
    """All 6 methods x (ring, ring_q8, ring_packed) x wire_buckets in
    {1, 2, 5}, 4 steps through all three phases on a real 4-device
    mesh.  Bucketed output is BIT-IDENTICAL to wire_buckets=1 except
    (a) where per-bucket quantization re-groups scale blocks (lgc_rar_q8
    on ring_q8; the packed value payload for sparse_gd/dgc/lgc_ps on
    ring_packed) — there the documented q8 bound applies — and (b)
    lgc_rar_q8's fake-quantized payload on float wires, whose dequant
    multiply FMA-contracts into the ring adds differently across
    program shapes (a ~1 ULP CPU-backend effect, gated 2000x tighter
    than the q8 bound; see DESIGN.md).  Every run also matches the Sim
    oracle."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import CompressionConfig
from repro.core import build_compressor
from repro.core.phases import phase_for_step
""" + _PARAMS_SRC + """
K = 4
Q8_TOL = 2e-3
mesh = jax.make_mesh((K,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))

def run(method, transport, wb):
    cc = CompressionConfig(method=method, sparsity=0.05,
                           innovation_sparsity=0.005, warmup_steps=1,
                           ae_train_steps=2, transport=transport,
                           wire_buckets=wb)
    comp = build_compressor(cc, params, K)
    n = comp.layout.n_total
    base = comp.init_state(jax.random.PRNGKey(0))
    ae_keys = tuple(k for k in ("ae", "ae_mom") if k in base)
    fns = {}
    def dist_fn(phase):
        if phase not in fns:
            def inner(uv, ae_part, g, step):
                state = {"u": uv["u"][0], "v": uv["v"][0], **ae_part}
                gg, ns, _ = comp.dist_step(state, g[0], step[0], phase,
                                           ("data",))
                return (gg, {"u": ns["u"][None], "v": ns["v"][None]},
                        {k: ns[k] for k in ae_part})
            fns[phase] = jax.jit(jax.shard_map(
                inner, mesh=mesh,
                in_specs=({"u": P("data"), "v": P("data")}, P(),
                          P("data"), P()),
                out_specs=(P(), {"u": P("data"), "v": P("data")}, P()),
                axis_names={"data"}, check_vma=False))
        return fns[phase]
    uv = {"u": jnp.zeros((K, n)), "v": jnp.zeros((K, n))}
    ae = {k: base[k] for k in ae_keys}
    sim_states = comp.init_sim_states(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)
    out = []
    for step in range(4):
        rng, k2 = jax.random.split(rng)
        g = jax.random.normal(k2, (K, n)) * 0.01
        phase = phase_for_step(step, cc)
        gg, uv, ae = dist_fn(phase)(uv, ae, g,
                                    jnp.asarray([step], jnp.int32))
        g_sim, sim_states, _ = comp.sim_step(sim_states, g, step, phase)
        quantized = ((transport == "ring_q8" and method == "lgc_rar_q8")
                     or (transport == "ring_packed"
                         and method in ("sparse_gd", "dgc", "lgc_ps")))
        tol = Q8_TOL if quantized else 1e-5
        err = float(jnp.max(jnp.abs(g_sim - gg)))
        assert err < tol, (method, transport, wb, step, err)
        out.append((np.asarray(gg), np.asarray(uv["v"])))
    return out

for method in ["none", "sparse_gd", "dgc", "lgc_rar", "lgc_rar_q8",
               "lgc_ps"]:
    for transport in ("ring", "ring_q8", "ring_packed"):
        ref = run(method, transport, 1)
        # three equivalence tiers vs wire_buckets=1:
        #   None     bitwise — every un-multiplied payload: the bucketed
        #            schedule preserves each element's accumulation chain
        #   Q8_TOL   per-bucket quantization re-groups scale blocks
        #            (real int8 / packed value payloads)
        #   1e-6     lgc_rar_q8 on float wires: the fake-dequant multiply
        #            feeding the ring adds is FMA-contracted by the CPU
        #            backend differently across program shapes (~1 ULP —
        #            a codegen effect, not the schedule: identity/add
        #            producers are bit-exact at any bucket count)
        if (transport == "ring_q8" and method == "lgc_rar_q8") or (
                transport == "ring_packed"
                and method in ("sparse_gd", "dgc", "lgc_ps")):
            tol = Q8_TOL
        elif method == "lgc_rar_q8":
            tol = 1e-6
        else:
            tol = None
        for wb in (2, 5):
            got = run(method, transport, wb)
            for step, ((g1, v1), (gb, vb)) in enumerate(zip(ref, got)):
                if tol is None:
                    assert (g1 == gb).all(), (method, transport, wb, step)
                    assert (v1 == vb).all(), (method, transport, wb, step)
                else:
                    assert np.abs(g1 - gb).max() < tol, (
                        method, transport, wb, step)
                    assert np.abs(v1 - vb).max() < tol, (
                        method, transport, wb, step)
        print(method, transport, "OK")
print("PASS")
""", devices=4, timeout=1800)
    assert "PASS" in out


def test_bucketed_hierarchical_two_axis(subproc):
    """ring_hier on a real 2x2 (pod x data) mesh: the two-level bucketed
    schedule (intra columns x inter columns) is bit-identical to the
    unbucketed hierarchy (up to backend FMA contraction of lgc_rar_q8's
    fake-dequant producer, ~1 ULP), matches the Sim oracle, and its
    per-bucket tally rows match the pricer."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import CompressionConfig
from repro.core import build_compressor
from repro.core.phases import PHASE_COMPRESSED, PHASE_TOPK_AE
from repro.dist import collectives as C
from repro.dist import plan as XP
""" + _PARAMS_SRC + """
K = 4
mesh = jax.make_mesh((2, 2), ("pod", "data"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

def run(method, phase, wb):
    cc = CompressionConfig(method=method, sparsity=0.05,
                           innovation_sparsity=0.005, warmup_steps=1,
                           ae_train_steps=2, transport="ring_hier",
                           wire_buckets=wb)
    comp = build_compressor(cc, params, K)
    n = comp.layout.n_total
    base = comp.init_state(jax.random.PRNGKey(0))
    ae_keys = tuple(k for k in ("ae", "ae_mom") if k in base)
    def inner(uv, ae_part, g):
        state = {"u": uv["u"][0, 0], "v": uv["v"][0, 0], **ae_part}
        gg, ns, _ = comp.dist_step(state, g[0, 0], jnp.asarray(3), phase,
                                   ("pod", "data"))
        return (gg, {"u": ns["u"][None, None], "v": ns["v"][None, None]},
                {k: ns[k] for k in ae_keys})
    f = jax.jit(jax.shard_map(
        inner, mesh=mesh,
        in_specs=({"u": P("pod", "data"), "v": P("pod", "data")}, P(),
                  P("pod", "data")),
        out_specs=(P(), {"u": P("pod", "data"), "v": P("pod", "data")},
                   P()),
        axis_names={"pod", "data"}, check_vma=False))
    C.reset_wire_tally()
    uv = {"u": jnp.zeros((2, 2, n)), "v": jnp.zeros((2, 2, n))}
    ae = {k: base[k] for k in ae_keys}
    g = jax.random.normal(jax.random.PRNGKey(1), (2, 2, n)) * 0.01
    gg, _, _ = f(uv, ae, g)
    by_op = C.wire_report(by_op=True)
    want = XP.wire_terms_by_op(XP.build_plan(cc, comp.layout, K,
                                             phase=phase),
                               axis_sizes=(2, 2))
    assert set(by_op) == set(want), (method, wb, set(by_op) ^ set(want))
    for lbl in by_op:
        for kind in by_op[lbl]:
            assert np.isclose(by_op[lbl][kind], want[lbl][kind],
                              rtol=1e-9), (method, wb, lbl, kind)
    if wb > 1:
        assert any("#b" in lbl for lbl in by_op), by_op
    # oracle
    sim_states = comp.init_sim_states(jax.random.PRNGKey(0))
    g_sim, _, _ = comp.sim_step(sim_states, g.reshape(K, n), 3, phase)
    assert float(jnp.max(jnp.abs(g_sim - gg))) < 1e-5, (method, wb)
    return np.asarray(gg)

for method, phase in (("dgc", PHASE_TOPK_AE),
                      ("lgc_rar", PHASE_COMPRESSED),
                      ("lgc_rar_q8", PHASE_COMPRESSED)):
    ref = run(method, phase, 1)
    # lgc_rar_q8's fake-dequant multiply FMA-contracts into the ring
    # adds differently across program shapes (~1 ULP CPU-backend
    # effect; see the schedule-equivalence test) — everyone else is
    # bitwise
    tol = 1e-6 if method == "lgc_rar_q8" else 0.0
    for wb in (2, 3):
        got = run(method, phase, wb)
        assert np.abs(ref - got).max() <= tol, (method, wb)
    print(method, "OK")
print("PASS")
""", devices=4, timeout=1800)
    assert "PASS" in out


def test_bucketed_wire_trace_matches_pricer(subproc):
    """The per-bucket accounting acceptance gate: lower one bucketed
    (wire_buckets=3) steady step per headline (method, transport) and
    assert the measured ``wire_report(by_op=True)`` equals
    ``wire_terms_by_op`` — per ``op#b<i>`` row, zero slack — and that
    the aggregate equals the unbucketed total plus the priced padding
    delta."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import CompressionConfig
from repro.core import build_compressor
from repro.core.phases import PHASE_COMPRESSED, PHASE_TOPK_AE
from repro.dist import collectives as C
from repro.dist import plan as XP
""" + _PARAMS_SRC + """
K = 4
mesh = jax.make_mesh((K,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))

for method, transport, phase in (
        ("dgc", "ring_packed", PHASE_TOPK_AE),
        ("lgc_rar_q8", "ring_q8", PHASE_COMPRESSED),
        ("lgc_rar", "ring", PHASE_COMPRESSED)):
    cc = CompressionConfig(method=method, sparsity=0.05,
                           innovation_sparsity=0.005, warmup_steps=1,
                           ae_train_steps=2, transport=transport,
                           wire_buckets=3)
    comp = build_compressor(cc, params, K)
    n = comp.layout.n_total
    base = comp.init_state(jax.random.PRNGKey(0))
    ae_keys = tuple(k for k in ("ae", "ae_mom") if k in base)
    def inner(uv, ae_part, g):
        state = {"u": uv["u"][0], "v": uv["v"][0], **ae_part}
        gg, ns, _ = comp.dist_step(state, g[0], jnp.asarray(3), phase,
                                   ("data",))
        return (gg, {"u": ns["u"][None], "v": ns["v"][None]},
                {k: ns[k] for k in ae_keys})
    f = jax.jit(jax.shard_map(
        inner, mesh=mesh,
        in_specs=({"u": P("data"), "v": P("data")}, P(), P("data")),
        out_specs=(P(), {"u": P("data"), "v": P("data")}, P()),
        axis_names={"data"}, check_vma=False))
    sds = jax.ShapeDtypeStruct
    uv_s = {"u": sds((K, n), "float32"), "v": sds((K, n), "float32")}
    ae_s = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                  {k: base[k] for k in ae_keys})
    C.reset_wire_tally()
    f.lower(uv_s, ae_s, sds((K, n), "float32"))
    by_op = C.wire_report(by_op=True)
    plan = XP.build_plan(cc, comp.layout, K, phase=phase)
    want = XP.wire_terms_by_op(plan)
    assert set(by_op) == set(want), (method, set(by_op) ^ set(want))
    for lbl in by_op:
        assert set(by_op[lbl]) == set(want[lbl]), (method, lbl)
        for kind in by_op[lbl]:
            assert np.isclose(by_op[lbl][kind], want[lbl][kind],
                              rtol=1e-9), (method, lbl, kind)
    assert any("#b" in lbl for lbl in by_op), (method, by_op)
    # aggregate: bucketed total == unbucketed total + padding delta
    tot_b = sum(C.wire_report().values())
    tot_1 = sum(XP.wire_terms(plan, wire_buckets=1).values())
    pad_b = sum(XP.padding_overhead_terms(plan).values())
    pad_1 = sum(XP.padding_overhead_terms(plan, wire_buckets=1).values())
    assert np.isclose(tot_b - tot_1, pad_b - pad_1, rtol=1e-9), method
    print(method, transport, "OK")
print("PASS")
""", devices=4, timeout=1200)
    assert "PASS" in out


def test_bucketed_chaos_scrub_matches_chaos_sim(subproc):
    """The guarded bucketed packed path (per-bucket eager encode under
    the structural sink) and the bucketed q8 ring, under the seeded
    NaN/Inf fault spec with guard=scrub: outputs stay finite, match the
    chaos Sim oracle under the identical spec, and the injected-fault
    tally is non-empty."""
    out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs.base import CompressionConfig
from repro.core import build_compressor
from repro.core.phases import PHASE_WARMUP, phase_for_step
from repro.dist import chaos as CH
""" + _PARAMS_SRC + """
K = 4
Q8_TOL = 2e-3
mesh = jax.make_mesh((K,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))

for method, transport in (("dgc", "chaos:ring_packed"),
                          ("lgc_rar_q8", "chaos:ring_q8")):
    cc = CompressionConfig(method=method, sparsity=0.05,
                           warmup_steps=1, ae_train_steps=2,
                           guard="scrub", guard_checksum=True,
                           fault_seed=11, fault_nans=2, fault_infs=1,
                           wire_buckets=3)
    comp = build_compressor(cc, params, K)
    n = comp.layout.n_total
    base = comp.init_state(jax.random.PRNGKey(0))
    ae_keys = tuple(k for k in ("ae", "ae_mom") if k in base)

    def dist_fn(step, phase):
        def inner(uv, ae_part, g):
            state = {"u": uv["u"][0], "v": uv["v"][0], **ae_part}
            gg, ns, _ = comp.dist_step(state, g[0], step, phase,
                                       ("data",), transport=transport)
            return (gg, {"u": ns["u"][None], "v": ns["v"][None]},
                    {k: ns[k] for k in ae_part})
        return jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=({"u": P("data"), "v": P("data")}, P(), P("data")),
            out_specs=(P(), {"u": P("data"), "v": P("data")}, P()),
            axis_names={"data"}, check_vma=False))

    sim_states = comp.init_sim_states(jax.random.PRNGKey(0))
    uv = {"u": jnp.zeros((K, n)), "v": jnp.zeros((K, n))}
    ae = {k: base[k] for k in ae_keys}
    rng = jax.random.PRNGKey(1)
    CH.reset_fault_tally()
    for step in range(4):
        rng, k2 = jax.random.split(rng)
        g = jax.random.normal(k2, (K, n)) * 0.01
        phase = phase_for_step(step, cc)
        g_sim, sim_states, _ = comp.sim_step(sim_states, g, step, phase)
        gg, uv, ae = dist_fn(step, phase)(uv, ae, g)
        assert bool(jnp.all(jnp.isfinite(gg))), (method, step)
        quantized = (transport.endswith("ring_packed")
                     and phase != PHASE_WARMUP)
        tol = Q8_TOL if quantized or method == "lgc_rar_q8" else 1e-3
        err = float(jnp.max(jnp.abs(g_sim - gg)))
        assert err < tol, (method, step, err)
    rep = CH.fault_report()
    assert rep and all(set(v) <= {"nan", "inf"} for v in rep.values()), rep
    print(method, "OK")
print("PASS")
""", devices=4, timeout=1800)
    assert "PASS" in out
