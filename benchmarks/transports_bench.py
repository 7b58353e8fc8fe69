"""Transport micro-benchmarks AND the CI transport gate.

One compressed step per (method x transport) on the sim substrate, plus
a fake-4-device subprocess that exercises EVERY distributed transport in
``repro.dist.transport.TRANSPORTS`` and gates it against the Sim oracle:

  mesh / ring / ring_hier   exact (1e-5; ring_hier == ring bit-identical
                            on a single dp axis — same schedule)
  ring_q8                   quantization-aware tolerance (the real int8
                            wire adds K bounded requantization hops over
                            the fake-quant oracle)
  ring_packed               quantization-aware tolerance for the sparse
                            methods: indices are bit-exact through the
                            packed wire and values pay ONE int8 block
                            quantization (error <= per-block scale/2 —
                            the documented q8 bound); float wires stay
                            exact, so only ring_packed runs opt into it

Exits nonzero on any divergence — run by scripts/ci.sh.  The gate runs
both the historical unbucketed schedule and one overlapped bucketed
configuration (``wire_buckets=3`` — bucket b's ring hops overlap bucket
b+1's encode) through every transport.  Also prints the per-op wire
trace (``wire_report(by_op=True)``): which exchange-plan op moved which
bytes through which collective — including the per-bucket ``op#b<i>``
rows of a bucketed lowering — gated against the plan pricer's
``wire_terms_by_op`` (the op-level wire contract).  The measured
ring wire bytes are reported against the analytic all-reduce bound
(derived column = per-node wire bytes, the quantity the paper's Tables
IV/VI are about), and the packed sparse exchange is gated at <= 0.35x of
the raw f32+int32 exchange at n=1M (the ISSUE 4 acceptance bar).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import row, time_call
from repro.configs.base import CompressionConfig
from repro.core import build_compressor
from repro.core.phases import PHASE_COMPRESSED, PHASE_TOPK_AE
from repro.dist.transport import EXACT_TOL, Q8_TOL
PARAMS = {
    "embed": {"w": jnp.zeros((128, 64))},
    "layer1": {"w": jnp.zeros((256, 256)), "b": jnp.zeros((256,))},
    "layer2": {"w": jnp.zeros((256, 256))},
    "lm_head": {"w": jnp.zeros((64, 128))},
}
K = 4
# gates against the Sim oracle: repro.dist.transport.EXACT_TOL/Q8_TOL
# (ring_packed's one value quantization measured ~3e-4 at this scale;
# see tests/test_transports.py)


def sim_latency_rows():
    for method in ("dgc", "lgc_rar", "lgc_rar_q8", "lgc_ps"):
        cc = CompressionConfig(method=method, sparsity=0.01,
                               innovation_sparsity=0.001, warmup_steps=0,
                               ae_train_steps=1)
        comp = build_compressor(cc, PARAMS, K)
        n = comp.layout.n_total
        states = comp.init_sim_states(jax.random.PRNGKey(0))
        g = jax.random.normal(jax.random.PRNGKey(1), (K, n)) * 0.01
        phase = PHASE_COMPRESSED if method.startswith("lgc") \
            else PHASE_TOPK_AE
        # burn one AE-phase step so lgc state is warm
        _, states, _ = comp.sim_step(states, g, 0, PHASE_TOPK_AE)
        step_fn = jax.jit(comp.sim_step, static_argnums=(3,))
        us = time_call(lambda: step_fn(states, g, 1, phase))
        gg, _, _ = step_fn(states, g, 1, phase)
        finite = bool(jnp.all(jnp.isfinite(gg)))
        row(f"transports/sim_{method}", us,
            f"finite={'yes' if finite else 'NO'}")

    # selection backends on the hot path
    for backend in ("jnp", "pallas", "fused"):
        comp = build_compressor(
            CompressionConfig(method="dgc", sparsity=0.01,
                              topk_backend=backend), PARAMS, K)
        v = jax.random.normal(jax.random.PRNGKey(2),
                              (comp.layout.n_total,))
        sel = jax.jit(comp._select)
        us = time_call(lambda: sel(v))
        row(f"transports/select_topk_{backend}", us,
            f"mu_pad={comp.layout.mu_pad}")


def guard_overhead_rows():
    """Guarded vs unguarded execution on the sim substrate: the same
    steady-state step with ``guard=off`` (the historical zero-cost path)
    vs ``guard=scrub`` + payload checksum (finite-value scrub per op,
    packed structural validation, per-op fault counters threaded into
    the step stats).  No faults are injected — this row prices the
    clean-path toll the guard charges EVERY step, the number DESIGN.md
    "Faults on the wire" quotes for the off-by-default decision."""
    for method in ("dgc", "lgc_rar_q8"):
        base_us = None
        for guard in ("off", "scrub"):
            cc = CompressionConfig(method=method, sparsity=0.01,
                                   innovation_sparsity=0.001,
                                   warmup_steps=0, ae_train_steps=1,
                                   guard=guard,
                                   guard_checksum=(guard != "off"))
            comp = build_compressor(cc, PARAMS, K)
            states = comp.init_sim_states(jax.random.PRNGKey(0))
            g = jax.random.normal(jax.random.PRNGKey(1),
                                  (K, comp.layout.n_total)) * 0.01
            phase = PHASE_COMPRESSED if method.startswith("lgc") \
                else PHASE_TOPK_AE
            _, states, _ = comp.sim_step(states, g, 0, PHASE_TOPK_AE)
            step_fn = jax.jit(comp.sim_step, static_argnums=(3,))
            us = time_call(lambda: step_fn(states, g, 1, phase))
            if guard == "off":
                base_us = us
                row(f"transports/guard_off_{method}", us, "baseline")
            else:
                row(f"transports/guard_scrub_{method}", us,
                    f"{us / base_us:.2f}x of unguarded (scrub + "
                    "checksum + per-op fault tally)")


def _traced_subprocess(code: str, devices: int) -> str:
    """Run a tracing snippet under a forced fake-device count (must be
    set before jax first initializes, hence the subprocess) and return
    its stdout; surfaces stderr on failure instead of swallowing it."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True)
    if proc.returncode != 0:
        raise SystemExit(f"trace subprocess failed:\n{proc.stderr[-4000:]}")
    return proc.stdout


def ring_wire_row():
    # measured ring wire bytes: trace the real ring_allreduce schedule on
    # an 8-fake-device mesh and read the trace-time tally
    n = 1 << 20
    K_ring = 8
    code = f"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.dist import collectives as C
mesh = jax.make_mesh(({K_ring},), ("data",))
C.reset_wire_tally()
jax.jit(jax.shard_map(lambda x: C.ring_allreduce(x[0], "data")[None],
                      mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                      check_vma=False)).lower(
    jax.ShapeDtypeStruct(({K_ring}, {n}), "float32"))
f32 = int(C.wire_report()["ring_allreduce"])
C.reset_wire_tally()
jax.jit(jax.shard_map(lambda x: C.ring_allreduce_q8(x[0], "data")[None],
                      mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                      check_vma=False)).lower(
    jax.ShapeDtypeStruct(({K_ring}, {n}), "float32"))
q8 = int(C.wire_report()["ring_allreduce_q8"])
print(f32, q8)
"""
    f32_wire, q8_wire = (float(v)
                         for v in _traced_subprocess(code, K_ring).split())
    dense = n * 4
    row("transports/ring_wire_1M_f32_8n", 0.0,
        f"bytes/node={int(f32_wire)} ({f32_wire / dense:.2f}x of dense)")
    row("transports/ring_q8_wire_1M_8n", 0.0,
        f"bytes/node={int(q8_wire)} ({q8_wire / f32_wire:.3f}x of f32 ring"
        " incl. per-block scales)")


# the ISSUE 4 acceptance bar: at n=1M the packed sparse exchange must
# move <= 0.35x of the f32+int32 bytes the same exchange costs on a
# float-wire transport
PACKED_RATIO_BOUND = 0.35


def packed_wire_row():
    """Measured packed vs f32 sparse-exchange bytes at n=1M on a fake
    8-device mesh: trace sparse_mean (raw f32 values + int32 indices)
    and sparse_mean_packed on ring_packed (bucket counts + bit-packed
    low index bits + int8 values + scales) and compare the tallies.
    CI-gates the <= 0.35x bound."""
    n = 1 << 20
    k = 8192
    K_ring = 8
    code = f"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.dist import collectives as C
from repro.dist.transport import make_transport

K, n, k = {K_ring}, {n}, {k}
mesh = jax.make_mesh((K,), ("data",))
vals = jax.ShapeDtypeStruct((K, k), "float32")
idx = jax.ShapeDtypeStruct((K, k), "int32")

def run(kind, attr):
    t = make_transport(kind, K, axes=("data",))
    def f(v, i):
        return getattr(t, attr)(v[0], i[0], n)[None]
    C.reset_wire_tally()
    jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                          out_specs=P("data"), check_vma=False)
            ).lower(vals, idx)
    return sum(C.wire_report().values())

print(run("ring", "sparse_mean"), run("ring_packed", "sparse_mean_packed"))
"""
    f32_wire, packed_wire = (float(v) for v in
                             _traced_subprocess(code, K_ring).split())
    ratio = packed_wire / f32_wire
    row("transports/sparse_f32_wire_1M_8n", 0.0,
        f"bytes/node={int(f32_wire)} (k={k} f32 vals + raw i32 idx)")
    row("transports/sparse_packed_wire_1M_8n", 0.0,
        f"bytes/node={int(packed_wire)} ({ratio:.3f}x of f32 sparse "
        "exchange incl. counts+scales)")
    if ratio > PACKED_RATIO_BOUND:
        raise SystemExit(
            f"packed sparse exchange at {ratio:.3f}x of f32 exceeds the "
            f"{PACKED_RATIO_BOUND}x bound")


def plan_trace_rows():
    """The per-op wire trace: lower one steady-state step per method on
    the packed wire and print where every byte went, by exchange-plan op
    label (``collectives.wire_report(by_op=True)``).  CI-gates that the
    measured per-op tally equals the plan pricer's ``wire_terms_by_op``
    — the op-level refinement of the aggregate wire contract.  The
    ``wb3`` configs repeat the lowering with ``wire_buckets=3``: the
    tally then carries one ``op#b<i>`` row per pipeline bucket and must
    still match the pricer row for row."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import CompressionConfig
from repro.core import build_compressor
from repro.dist import collectives as C
from repro.dist import plan as XP

params = {"embed": {"w": jnp.zeros((32, 16))},
          "layer1": {"w": jnp.zeros((64, 64))},
          "layer2": {"w": jnp.zeros((64, 64))},
          "lm_head": {"w": jnp.zeros((16, 32))}}
K = 4
mesh = jax.make_mesh((K,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
for method, wb in (("dgc", 1), ("lgc_rar_q8", 1), ("lgc_ps", 1),
                   ("dgc", 3), ("lgc_rar_q8", 3)):
    transport = "ring_q8" if method == "lgc_rar_q8" else "ring_packed"
    cc = CompressionConfig(method=method, sparsity=0.05,
                           innovation_sparsity=0.005, warmup_steps=1,
                           ae_train_steps=2, transport=transport,
                           wire_buckets=wb)
    comp = build_compressor(cc, params, K)
    n = comp.layout.n_total
    base = comp.init_state(jax.random.PRNGKey(0))
    ae_keys = tuple(k for k in ("ae", "ae_mom") if k in base)
    phase = XP.steady_phase(method)

    def inner(uv, ae_part, g):
        st = {"u": uv["u"][0], "v": uv["v"][0], **ae_part}
        gg, ns, _ = comp.dist_step(st, g[0], jnp.asarray(3), phase,
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
    measured = C.wire_report(by_op=True)
    priced = XP.wire_terms_by_op(XP.build_plan(cc, comp.layout, K))
    assert set(measured) == set(priced), (method, measured, priced)
    for label in priced:
        for kind in set(measured[label]) | set(priced[label]):
            assert np.isclose(measured[label].get(kind, 0),
                              priced[label].get(kind, 0), rtol=1e-9), (
                method, label, kind)
    if wb > 1:
        assert any("#b" in lbl for lbl in measured), (method, measured)
    for label, terms in measured.items():
        print("TRACE", f"{method}@wb{wb}", transport, label,
              "+".join(sorted(terms)), int(sum(terms.values())))
print("TRACE-PASS")
"""
    out = _traced_subprocess(code, 4)
    if "TRACE-PASS" not in out:
        raise SystemExit("per-op wire trace gate failed")
    for line in out.splitlines():
        if line.startswith("TRACE "):
            _, method, transport, label, kinds, nbytes = line.split()
            row(f"transports/wire_by_op_{method}_{label}", 0.0,
                f"{nbytes}B via {kinds} on {transport} "
                "(== plan.wire_terms_by_op)")


def dist_transport_gate():
    """Every distributed transport vs the Sim oracle on a fake 4-device
    mesh (subprocess for the forced device count).  Raises on
    divergence; the per-transport worst error is the derived column."""
    import os
    import subprocess
    import sys
    code = f"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs.base import CompressionConfig
from repro.core import build_compressor
from repro.core.phases import (PHASE_COMPRESSED, PHASE_WARMUP,
                               phase_for_step)
from repro.dist.transport import RING_TRANSPORTS

params = {{"embed": {{"w": jnp.zeros((32, 16))}},
          "layer1": {{"w": jnp.zeros((64, 64))}},
          "layer2": {{"w": jnp.zeros((64, 64))}},
          "lm_head": {{"w": jnp.zeros((16, 32))}}}}
K = 4
Q8_TOL, EXACT_TOL = {Q8_TOL}, {EXACT_TOL}
mesh = jax.make_mesh((K,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
for method, wb in (("dgc", 1), ("lgc_rar", 1), ("lgc_rar_q8", 1),
                   ("lgc_ps", 1), ("dgc", 3)):
    # the wb=3 run drives the SAME method through the overlapped
    # bucketed schedule on every transport — the pipelined executor
    # must clear the identical oracle gate as the unbucketed one
    cc = CompressionConfig(method=method, sparsity=0.05,
                           innovation_sparsity=0.005,
                           warmup_steps=1, ae_train_steps=2,
                           wire_buckets=wb)
    comp = build_compressor(cc, params, K)
    n = comp.layout.n_total
    base = comp.init_state(jax.random.PRNGKey(0))
    ae_keys = tuple(k for k in ("ae", "ae_mom") if k in base)

    def dist_fn(step, phase, transport):
        def inner(uv, ae_part, g):
            st = {{"u": uv["u"][0], "v": uv["v"][0], **ae_part}}
            gg, ns, _ = comp.dist_step(st, g[0], step, phase, ("data",),
                                       transport=transport)
            return (gg, {{"u": ns["u"][None], "v": ns["v"][None]}},
                    {{k: ns[k] for k in ae_part}})
        return jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=({{"u": P("data"), "v": P("data")}}, P(), P("data")),
            out_specs=(P(), {{"u": P("data"), "v": P("data")}}, P()),
            axis_names={{"data"}}, check_vma=False))

    transports = ("mesh",) + RING_TRANSPORTS
    sim = comp.init_sim_states(jax.random.PRNGKey(0))
    uvs = {{t: {{"u": jnp.zeros((K, n)), "v": jnp.zeros((K, n))}}
           for t in transports}}
    aes = {{t: {{k: base[k] for k in ae_keys}} for t in transports}}
    rng = jax.random.PRNGKey(1)
    worst = {{t: 0.0 for t in transports}}
    outs = {{}}
    for step in range(4):
        rng, k2 = jax.random.split(rng)
        g = jax.random.normal(k2, (K, n)) * 0.01
        phase = phase_for_step(step, cc)
        g_sim, sim, _ = comp.sim_step(sim, g, step, phase)
        for t in transports:
            gg, uvs[t], aes[t] = dist_fn(step, phase, t)(
                uvs[t], aes[t], g)
            outs[t] = gg
            err = float(jnp.max(jnp.abs(g_sim - gg)))
            worst[t] = max(worst[t], err)
            quantized = (t == "ring_q8" and method == "lgc_rar_q8"
                         and phase == PHASE_COMPRESSED) \\
                or (t == "ring_packed" and phase != PHASE_WARMUP
                    and method in ("dgc", "lgc_ps"))
            tol = Q8_TOL if quantized else EXACT_TOL
            assert err <= tol, (method, t, step, err, tol)
        # single-axis hierarchy IS the ring schedule: bit-identical
        assert bool(jnp.all(outs["ring_hier"] == outs["ring"])), (
            method, step)
    print("GATE", method + (f"_wb{{wb}}" if wb > 1 else ""),
          " ".join(f"{{t}}={{worst[t]:.2e}}" for t in transports))
print("GATE-PASS")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True)
    print(proc.stdout, end="")
    if proc.returncode != 0 or "GATE-PASS" not in proc.stdout:
        raise SystemExit(
            f"transport gate failed:\n{proc.stderr[-4000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("GATE "):
            _, method, *errs = line.split()
            row(f"transports/dist_gate_{method}", 0.0, " ".join(errs))


def main():
    sim_latency_rows()
    guard_overhead_rows()
    ring_wire_row()
    packed_wire_row()
    plan_trace_rows()
    dist_transport_gate()


if __name__ == "__main__":
    main()
