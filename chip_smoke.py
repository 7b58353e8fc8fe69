#!/usr/bin/env python3
"""Run the LGC training step on a TPU and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the data-parallel path on 4 chips

One chip, three phases:

  A. the compiled kernels of the compression hot path against their
     references at mamba2-130m's gradient layout (167,598,528 elements):
     the fused accumulate + select sweep with both extractors, bit for
     bit against the jnp reference, and the packed wire's fused encode
     -> decode (indices exact, values within the q8 bound);
  B. ``repro.launch.train.main`` on mamba2-130m at published widths and
     full depth: lgc_ps on the packed ring with the fused sweep and the
     Pallas encoder, through warm-up, top-k + AE training and the
     compressed phase.  Every loss must be finite, and each phase's
     compiled step must hold the Pallas kernels as ``tpu_custom_call``s,
     so none of them fell back to the interpreter;
  C. the same model uncompressed, the plain step users compare against.

Four chips (``--chips 4``): mamba2-130m at published widths and full
depth on a data=4 mesh through ``train.main`` (none on mesh, dgc on
ring_packed, lgc_rar on ring), each pairing's global gradient against
``GradientCompressor.sim_step`` fed the same four node gradients at the
same layout, and the error-feedback state one shard per chip.

Where JAX finds no TPU the script exits with status 2 and prints no
result.  When every phase passed, the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "mamba2-130m"
SEED = 0
MOMENTUM = 0.9
EXTRACTS = ("loop", "bitonic")

TRAIN_ARGS = ["--arch", ARCH, "--batch", "8", "--seq", "2048",
              "--log-every", "1", "--seed", str(SEED)]
LGC_ARGS = TRAIN_ARGS + ["--compression", "lgc_ps",
                         "--transport", "ring_packed",
                         "--topk-backend", "fused", "--ae-backend", "pallas",
                         "--steps", "6", "--warmup-steps", "2",
                         "--ae-train-steps", "2"]
PLAIN_ARGS = TRAIN_ARGS + ["--compression", "none", "--steps", "4"]
# the Pallas kernels each compressed phase's step must hold
STEP_KERNELS = {
    "topk_ae": {"_ef_topk_kernel"},
    "compressed": {"_ef_topk_kernel", "_matmul_lrelu_kernel",
                   "_encode_fused_kernel"},
}

# four chips: (method, transport) pairings through train.main, at
# published widths and full depth; data=4 splits the batch of 8.  The
# per-node selection runs on the jnp backend: phase A shows the fused
# sweep bit-identical to it at this layout, where the bitonic sweep
# takes ~50 s to compile and ~10 s per call on a v5e
MULTI_RUNS = (("none", "mesh"), ("dgc", "ring_packed"), ("lgc_rar", "ring"))
MULTI_ARGS = ["--arch", ARCH, "--data-shards", "4", "--batch", "8",
              "--seq", "2048", "--log-every", "1", "--steps", "5",
              "--warmup-steps", "1", "--ae-train-steps", "2",
              "--topk-backend", "jnp", "--seed", str(SEED)]


def say(*parts):
    print(*parts, flush=True)


class CheckFailed(Exception):
    pass


def check(ok, *what):
    """A phase's pass condition (kept under ``python -O``, unlike
    assert)."""
    if not ok:
        raise CheckFailed(*what)


def compile_all(progs):
    """{name: (jitted fn, args)} -> {name: (compiled, seconds)}.  Lowering
    runs here, one program at a time; XLA compiles them concurrently."""
    lowered = {name: fn.lower(*args) for name, (fn, args) in progs.items()}

    def comp(item):
        name, low = item
        t = time.time()
        return name, (low.compile(), time.time() - t)

    with ThreadPoolExecutor(len(lowered)) as pool:
        return dict(pool.map(comp, lowered.items()))


def timed(fn, *args):
    import jax
    t = time.time()
    out = jax.block_until_ready(fn(*args))
    return out, (time.time() - t) * 1e3


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


# ---------------------------------------------------------------------------
# phase A: kernels at the real layout


def phase_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch
    from repro.core import sparsify as SP
    from repro.dist import packed as PK
    from repro.kernels import tpu_kernel_names
    from repro.launch.input_specs import params_specs
    from repro.models import build_model

    layout = SP.build_layout(params_specs(build_model(get_arch(ARCH))),
                             0.001)
    n = layout.n_total
    keys = jax.random.split(jax.random.PRNGKey(SEED), 3)
    g, u, v = (jax.random.normal(k, (n,), jnp.float32) for k in keys)

    def reference(g, u, v):
        u2, v2 = SP.momentum_correct(u, v, g, MOMENTUM)
        return ((u2, v2) + SP.select_topk(v2, layout)
                + SP.select_topk_last(v2, layout))

    k = layout.mu_pad
    plan = PK.make_plan(n, k)

    def packed_roundtrip(vals, idx):
        return PK.decode_sparse(PK.encode_sparse_fused(vals, idx, plan),
                                plan)

    progs = {"jnp": (jax.jit(reference), (g, u, v)),
             "packed": (jax.jit(packed_roundtrip),
                        (jax.ShapeDtypeStruct((k,), jnp.float32),
                         jax.ShapeDtypeStruct((k,), jnp.int32)))}
    for ex in EXTRACTS:
        progs[ex] = (jax.jit(partial(SP.fused_accumulate_select,
                                     layout=layout, momentum=MOMENTUM,
                                     extract=ex)), (g, u, v))
    plans = {ex: SP.fused_plan_info(layout, extract=ex) for ex in EXTRACTS}
    say(f"[A] layout n={n:,} leaves={len(layout.leaves)} "
        f"k_max={max(l.k for l in layout.leaves)} mu_pad={k} "
        f"plans={plans}")
    compiled = compile_all(progs)
    for name, (c, secs) in compiled.items():
        say(f"[A] compile {name}: {secs:.1f} s, kernels "
            f"{sorted(set(tpu_kernel_names(c.as_text())))}")
    for ex in EXTRACTS:
        names = tpu_kernel_names(compiled[ex][0].as_text())
        check("_ef_topk_kernel" in names, (ex, names))
    names = set(tpu_kernel_names(compiled["packed"][0].as_text()))
    check("_encode_fused_kernel" in names, names)

    ref, ms = timed(compiled["jnp"][0], g, u, v)
    say(f"[A] jnp reference: {ms:.1f} ms")
    fields = ("u", "v", "vals", "idx", "last_vals", "last_idx")
    for ex in EXTRACTS:
        out, ms = timed(compiled[ex][0], g, u, v)
        bad = [f for f, a, b in zip(fields, out, ref)
               if not bool(jnp.array_equal(a, b))]
        say(f"[A] fused sweep extract={ex}: {ms:.1f} ms, "
            f"bitwise equal to jnp: {not bad}")
        check(not bad, (ex, bad, [float(jnp.max(jnp.abs(a - b)))
                                   for a, b in zip(out, ref)]))
        del out

    vals, idx = ref[2], ref[3]
    (dvals, didx), ms = timed(compiled["packed"][0], vals, idx)
    order = np.argsort(np.asarray(idx), kind="stable")
    want_idx = np.asarray(idx)[order]
    want = np.asarray(vals)[order]
    check(np.array_equal(np.asarray(didx), want_idx), "indices not exact")
    sb = plan.scale_block
    pad = -len(want) % sb
    blocks = np.abs(np.pad(want, (0, pad))).reshape(-1, sb).max(axis=1)
    bound = np.repeat(blocks / 254.0, sb)[:len(want)] * (1 + 1e-6)
    err = np.abs(np.asarray(dvals) - want)
    say(f"[A] packed encode->decode k={k}: {ms:.1f} ms, indices exact, "
        f"max value error {err.max():.3e} (bound {bound.max():.3e})")
    check((err <= bound + 1e-30).all(), "values outside the q8 bound")
    say(f"[A] peak_bytes_in_use {peak_bytes()}")


# ---------------------------------------------------------------------------
# phases B and C: the trainer's own entry point


class StepRecorder:
    """Within ``with StepRecorder() as rec:``, every LGC step program
    that ``train.main`` builds is recorded with the abstract arguments
    of its first call, so ``rec.compiled_text(phase)`` is the HLO of the
    very executable the trainer ran (JAX's in-memory caches return it
    without compiling again)."""

    def __enter__(self):
        from repro.launch import steps
        self._steps, self._make = steps, steps.make_lgc_train_step
        self.built = {}

        def make_lgc_train_step(*args, **kwargs):
            lts = self._make(*args, **kwargs)
            make_step = lts.make_step

            def recording_make_step(phase, batch_tree):
                fn = make_step(phase, batch_tree)

                def step(*call_args):
                    if phase not in self.built:
                        self.built[phase] = (fn, _abstract(call_args))
                    return fn(*call_args)
                return step
            lts.make_step = recording_make_step
            return lts

        steps.make_lgc_train_step = make_lgc_train_step
        return self

    def __exit__(self, *exc):
        self._steps.make_lgc_train_step = self._make

    def compiled_text(self, phase):
        fn, args = self.built[phase]
        return fn.lower(*args).compile().as_text()


def _abstract(tree):
    import jax

    def spec(x):
        if not hasattr(x, "shape"):
            return x                      # the step number: a Python int
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=getattr(x, "sharding", None),
                                    weak_type=getattr(x, "weak_type", False))
    return jax.tree_util.tree_map(spec, tree)


def run_train(tag, argv):
    from repro.launch import train

    t = time.time()
    hist = train.main(argv)
    wall = time.time() - t
    steps = int(argv[argv.index("--steps") + 1])
    check([h["step"] for h in hist] == list(range(steps)), hist)
    check(all(math.isfinite(h["loss"]) for h in hist), hist)
    by_phase = {}
    for h in hist:
        by_phase.setdefault(h["phase"], []).append(h)
    for ph, hs in by_phase.items():
        steady = [h["seconds"] for h in hs[1:]]
        say(f"[{tag}] phase {ph}: first step (compile included) "
            f"{hs[0]['seconds']:.1f} s, steady step "
            + (f"{min(steady) * 1e3:.1f} ms (min of {len(steady)})"
               if steady else "not measured")
            + f", losses {[round(h['loss'], 4) for h in hs]}")
    say(f"[{tag}] {steps} steps in {wall:.1f} s, "
        f"peak_bytes_in_use {peak_bytes()}")
    return hist


def phase_train_lgc():
    from repro.kernels import tpu_kernel_names

    with StepRecorder() as rec:
        hist = run_train("B", LGC_ARGS)
    check({h["phase"] for h in hist} == set(rec.built), hist)
    for ph in sorted(rec.built):
        names = tpu_kernel_names(rec.compiled_text(ph))
        say(f"[B] step {ph}: kernels {sorted(set(names))}")
        check(STEP_KERNELS.get(ph, set()) <= set(names), (ph, names))


def phase_train_plain():
    run_train("C", PLAIN_ARGS)


# ---------------------------------------------------------------------------
# four chips: the multi-node path


def phase_multinode_train():
    for method, transport in MULTI_RUNS:
        run_train(f"4x {method}/{transport}",
                  MULTI_ARGS + ["--compression", method,
                                "--transport", transport])


def phase_multinode_oracle():
    """One compressed step per pairing at mamba2-130m's layout: the
    transport inside the trainer's shard_map against ``sim_step`` on the
    stacked (4, n) node arrays, 2.7 GB each.  The stacks are split by
    node over the four chips, so each chip holds one row of each, and
    XLA partitions the oracle's vmap over nodes and reduces across
    chips with its own collectives."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_arch
    from repro.configs.base import CompressionConfig
    from repro.core import build_compressor
    from repro.core.phases import PHASE_COMPRESSED
    from repro.dist import EXACT_TOL, Q8_TOL
    from repro.launch.input_specs import params_specs
    from repro.models import build_model

    K = 4
    template = params_specs(build_model(get_arch(ARCH)))
    mesh = jax.make_mesh((K,), ("data",), devices=jax.devices()[:K],
                         axis_types=(jax.sharding.AxisType.Auto,))
    nodes = NamedSharding(mesh, P("data"))
    comps = {(m, t): build_compressor(
        CompressionConfig(method=m, transport=t), template, K)
        for m, t in MULTI_RUNS}
    n = comps[MULTI_RUNS[0]].layout.n_total

    @partial(jax.jit, out_shardings=(nodes,) * 3)
    def node_arrays(key):
        return tuple(jax.random.normal(k, (K, n)) * 0.01
                     for k in jax.random.split(key, 3))

    g, u, v = node_arrays(jax.random.PRNGKey(SEED + 1))
    uv = {"u": u, "v": v}
    progs = {}
    for (method, transport), comp in comps.items():
        ae = {}
        if method.startswith("lgc"):
            # the AE state only: init_state's n-length u/v are not needed
            state = comp.init_state(jax.random.PRNGKey(SEED))
            ae = jax.device_put({k: state[k] for k in ("ae", "ae_mom")},
                                NamedSharding(mesh, P()))
            del state

        def inner(uv, ae_part, g, comp=comp):
            st = {"u": uv["u"][0], "v": uv["v"][0], **ae_part}
            gg, _, _ = comp.dist_step(st, g[0], 0, PHASE_COMPRESSED,
                                      ("data",))
            return gg

        dist = jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=({"u": P("data"), "v": P("data")}, P(), P("data")),
            out_specs=P(), axis_names={"data"}, check_vma=False))
        sim = jax.jit(lambda st, gn, comp=comp: comp.sim_step(
            st, gn, 0, PHASE_COMPRESSED)[0])
        progs[method, transport, "dist"] = (dist, (uv, ae, g))
        progs[method, transport, "sim"] = (sim, ({**uv, **ae}, g))
    say(f"[4x oracle] layout n={n:,}, node arrays {g.shape} split "
        f"{g.sharding.spec} over {len(g.sharding.device_set)} devices")
    # the reference precision for both sides: XLA's default TPU matmul
    # rounds f32 operands to bf16, in a different order per program
    with jax.default_matmul_precision("highest"):
        compiled = compile_all(progs)
    for name, (_, secs) in compiled.items():
        say(f"[4x oracle] compile {'/'.join(name)}: {secs:.1f} s")
    for method, transport in MULTI_RUNS:
        got, ms_dist = timed(compiled[method, transport, "dist"][0],
                             *progs[method, transport, "dist"][1])
        want, ms_sim = timed(compiled[method, transport, "sim"][0],
                             *progs[method, transport, "sim"][1])
        err = float(jax.jit(lambda a, b: abs(a - b).max())(got, want))
        tol = Q8_TOL if transport == "ring_packed" else EXACT_TOL
        say(f"[4x oracle] {method}/{transport}: n={got.shape[0]:,} max "
            f"|global gradient - sim_step| = {err:.3e} (bound {tol:g}); "
            f"dist {ms_dist:.1f} ms, sim {ms_sim:.1f} ms")
        check(err <= tol, (method, transport, err, tol))
        del got, want
    say(f"[4x oracle] peak_bytes_in_use {peak_bytes()}")


def phase_multinode_sharding():
    import jax

    from repro.launch import train
    from repro.launch.steps import make_lgc_train_step
    from repro.models import build_model

    args = train.parse_args(MULTI_ARGS + ["--compression", "lgc_rar",
                                          "--transport", "ring"])
    cfg, tc, mesh = train.build_configs(args)
    model = build_model(cfg)
    lts = make_lgc_train_step(model, tc, mesh)
    _, _, comp_state = lts.init(jax.random.PRNGKey(SEED), model, mesh)
    for name in ("u", "v"):
        shards = comp_state[name].addressable_shards
        devs = {s.device for s in shards}
        say(f"[4x sharding] comp_state[{name!r}] {comp_state[name].shape}: "
            f"{len(shards)} shards of {shards[0].data.shape} on "
            f"{len(devs)} devices")
        check(len(shards) == 4 and len(devs) == 4, (name, shards))


# ---------------------------------------------------------------------------


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the multi-node path on a 4-chip host")
    args = p.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    say(f"devices: {len(devices)} x {devices[0].device_kind}, "
        f"jax {jax.__version__}")

    if args.chips == 4:
        phases = [("4x train", phase_multinode_train),
                  ("4x oracle", phase_multinode_oracle),
                  ("4x sharding", phase_multinode_sharding)]
    else:
        phases = [("A kernels", phase_kernels),
                  ("B train lgc_ps", phase_train_lgc),
                  ("C train none", phase_train_plain)]
    failed = []
    for name, fn in phases:
        t = time.time()
        try:
            fn()
            say(f"== {name}: PASS in {time.time() - t:.1f} s")
        except Exception:
            traceback.print_exc()
            say(f"== {name}: FAIL after {time.time() - t:.1f} s")
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
