"""End-to-end compress-step latency: a full ``sim_step`` for every
method (the five paper methods + the beyond-paper lgc_rar_q8; the
dense "none" baseline as a single reference row) x {jnp, pallas, fused}
selection backends (plus the ``ae_backend="pallas"`` phase-3 encoder
for the LGC methods), written to
``BENCH_step_latency.json`` — the machine-readable perf trajectory the
ROADMAP tracks PR-over-PR.

Doubles as a correctness gate (run by scripts/ci.sh): every kernel
backend's global gradient and accumulator states are compared against
the jnp oracle over the full phase schedule and the process exits
nonzero if any divergence exceeds 1e-5.  The ``packed_encode`` rows
gate the one-launch fused packed-wire encode (bit-exact vs the composed
quantize->pack path, pallas_call count jaxpr-asserted == 1) and record
the ``--wire-buckets`` overlapped-exchange pricing (per-node wire bytes
+ explicit padding overhead at that pipeline depth).

The kernels compile on a TPU and run in the Pallas interpreter
elsewhere (``repro.kernels.interpret_mode``), so CPU timings are
structural (launch counts, pass structure), not TPU wall-clock; the
derived ``max_err_vs_jnp`` column is exact either way.  The artifact
records ``device_kind``/``interpret`` so CPU-interpret rows and TPU
rows are distinguishable in the trajectory.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from benchmarks.common import row, time_call
from repro.configs.base import CompressionConfig
from repro.core import build_compressor
from repro.core.phases import phase_for_step
from repro.core.sparsify import build_layout, fused_plan_info
from repro.kernels import count_pallas_calls, interpret_mode

PARAMS = {
    "embed": {"w": jnp.zeros((128, 64))},
    "layer1": {"w": jnp.zeros((160, 160)), "b": jnp.zeros((160,))},
    "layer2": {"w": jnp.zeros((160, 160))},
    "lm_head": {"w": jnp.zeros((64, 128))},
}
K = 4
METHODS = ("none", "sparse_gd", "dgc", "lgc_ps", "lgc_rar", "lgc_rar_q8")
BACKENDS = ("jnp", "pallas", "fused")
STEPS = 4                       # warmup(1) -> topk+AE(2) -> compressed
TOL = 1e-5


def run_method(method: str, backend: str, ae_backend: str = "jnp"):
    """Full phase schedule; returns (stacked global grads, final u, v,
    us_per_step of the steady-state last-phase step)."""
    cc = CompressionConfig(method=method, sparsity=0.02,
                           innovation_sparsity=0.002, warmup_steps=1,
                           ae_train_steps=2, topk_backend=backend,
                           ae_backend=ae_backend)
    comp = build_compressor(cc, PARAMS, K)
    n = comp.layout.n_total
    states = comp.init_sim_states(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)
    gs = []
    for step in range(STEPS):
        rng, k2 = jax.random.split(rng)
        g = jax.random.normal(k2, (K, n)) * 0.01
        gg, states, _ = comp.sim_step(states, g, step,
                                      phase_for_step(step, cc))
        gs.append(gg)
    # steady state: time the last phase's jitted step on fixed inputs
    phase = phase_for_step(STEPS - 1, cc)
    step_fn = jax.jit(lambda st, gn, i: comp.sim_step(st, gn, i, phase))
    g = jax.random.normal(jax.random.PRNGKey(2), (K, n)) * 0.01
    us = time_call(lambda: step_fn(states, g, STEPS - 1))
    return jnp.stack(gs), states["u"], states["v"], us


def packed_encode_rows(report, wire_buckets):
    """The packed-wire encode collapsed into ONE kernel: time the
    composed multi-pass path (block-quantize, then bit-plane pack —
    separate HBM round-trips) against ``packed.encode_sparse_fused``,
    record the jaxpr-counted pallas_call launches for both (the fused
    path MUST be exactly 1: one HBM read of (vals, idx) per bucket),
    gate bit-exactness, and price a dgc/ring_packed plan's wire bytes
    at ``--wire-buckets`` — per-bucket totals plus the explicitly
    priced bucket/chunk padding overhead.  Returns False on any gate
    miss (main() turns that into a nonzero exit)."""
    from repro.dist import packed as PK
    from repro.dist import plan as XP

    layout = build_layout(PARAMS, 0.02)
    n, k = layout.n_total, layout.mu_pad
    pack = PK.make_plan(n, k, 256)

    def composed(v, i):
        return PK.encode_sparse(v, i, pack)

    def fused(v, i):
        return PK.encode_sparse_fused(v, i, pack)

    idx = jnp.sort(jax.random.choice(jax.random.PRNGKey(3), n, (k,),
                                     replace=False).astype(jnp.int32))
    vals = jax.random.normal(jax.random.PRNGKey(4), (k,))
    ref, got = composed(vals, idx), fused(vals, idx)
    bitwise = all(bool(jnp.all(a == b)) for a, b in zip(ref, got))
    launches = {name: count_pallas_calls(jax.make_jaxpr(f)(vals, idx))
                for name, f in (("composed", composed), ("fused", fused))}
    us_c = time_call(jax.jit(composed), vals, idx)
    us_f = time_call(jax.jit(fused), vals, idx)
    row("step_latency/packed_encode_composed", us_c,
        f"pallas_launches={launches['composed']} (quantize+pack passes)")
    row("step_latency/packed_encode_fused", us_f,
        f"pallas_launches={launches['fused']} "
        f"bit_exact={'yes' if bitwise else 'NO'}")
    entry = {"k": int(k), "bit_exact": bitwise, "launches": launches,
             "us_composed": round(us_c, 1), "us_fused": round(us_f, 1)}

    xplan = XP.build_plan(
        CompressionConfig(method="dgc", sparsity=0.02,
                          transport="ring_packed",
                          wire_buckets=wire_buckets), layout, K)
    entry["wire_buckets"] = {}
    for wb in sorted({1, wire_buckets}):
        total = sum(XP.wire_terms(xplan, wire_buckets=wb).values())
        pad = sum(XP.padding_overhead_terms(xplan,
                                            wire_buckets=wb).values())
        row(f"step_latency/wire_buckets_{wb}", 0.0,
            f"bytes/node={int(total)} pad={int(pad)} (dgc/ring_packed)")
        entry["wire_buckets"][str(wb)] = {"bytes_per_node": total,
                                          "padding": pad}
    report["packed_encode"] = entry
    return bitwise and launches["fused"] == 1


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", default="BENCH_step_latency.json")
    p.add_argument("--wire-buckets", type=int, default=4,
                   help="bucket count for the overlapped-exchange "
                        "pricing rows (wire bytes + padding at this "
                        "pipeline depth vs unbucketed)")
    # tolerate foreign flags when run via benchmarks.run's module loop
    args, _ = p.parse_known_args(argv)

    device = jax.devices()[0]
    interpret = interpret_mode()

    # self-describing artifact: device_kind + interpret distinguish
    # interpret-mode CPU rows (structural: launch counts, pass
    # structure — interpret overhead inverts the latency ordering vs
    # compiled execution, e.g. fused at ~10^5us vs jnp at ~10^3us) from
    # real compiled accelerator rows (wall-clock) — without these fields
    # the PR-over-PR trajectory reads as a regression
    report = {
        "K": K, "steps": STEPS, "tol": TOL,
        "interpret": interpret,
        "device_kind": device.device_kind,
        "platform": device.platform,
        "note": (("us_per_step timings are compiled on "
                  f"{device.device_kind}: real wall-clock rows")
                 if not interpret else
                 ("us_per_step timings are Pallas interpret-mode on "
                  f"{device.device_kind}: structural (launch counts, "
                  "pass structure), NOT accelerator wall-clock — "
                  "interpret overhead scales with kernel complexity, so "
                  "fused/pallas rows are expected to be slower than jnp "
                  "here; max_err_vs_jnp is exact either way")),
        "methods": {},
    }
    # the fused sweep's self-describing plan (same derivation the hot
    # path uses): chosen block size, per-block candidate-pool bound and
    # the resolved extraction backend — recorded on every fused row so
    # the perf trajectory says WHAT ran, not just how long it took
    plan = fused_plan_info(build_layout(PARAMS, 0.02))
    failures = []
    for method in METHODS:
        oracle = run_method(method, "jnp")
        # "none" never touches a selection kernel: one baseline row only
        variants = [("jnp", "jnp", "jnp")] if method == "none" \
            else [(b, "jnp", b) for b in BACKENDS]
        if method.startswith("lgc"):
            # phase-3 encoder kernel gated against the same oracle
            variants.append(("fused", "pallas", "fused_ae_pallas"))
        entry = {}
        for backend, ae_backend, label in variants:
            res = oracle if (backend, ae_backend) == ("jnp", "jnp") \
                else run_method(method, backend, ae_backend)
            gs, u, v, us = res
            err = max(float(jnp.max(jnp.abs(a - b)))
                      for a, b in zip(oracle[:3], (gs, u, v)))
            entry[label] = {"us_per_step": round(us, 1),
                            "max_err_vs_jnp": err}
            if backend == "fused":
                entry[label].update(plan)
            row(f"step_latency/{method}_{label}", us,
                f"max_err={err:.1e}")
            if err > TOL:
                failures.append((method, label, err))
        report["methods"][method] = entry

    if not packed_encode_rows(report, args.wire_buckets):
        failures.append(("packed_encode",
                         report["packed_encode"]["launches"],
                         report["packed_encode"]["bit_exact"]))

    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {args.out}")
    if failures:
        raise SystemExit(f"backend divergence beyond {TOL}: {failures}")


if __name__ == "__main__":
    main()
