#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) is one model
configuration under one traffic mix.  The run builds the LGC training
step as the trainer does, makes its weights and batches from the seed,
runs three set-up steps, then measures ``--seconds`` of steps
(``--trace 0``: the end-to-end metrics) or traces a few steps
(``--trace 1``: the per-layer metrics).  After the window it runs the
float32 reference over the three set-up steps and decides ``correct``.

It exits 2 and prints no result where JAX finds no TPU or fewer chips
than the cell asks for.  The last line of standard output is one JSON
object; the last lines of standard error list each number compared
beside its limit.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


def setup_paths(root: Path = ROOT):
    """Import paths, and JAX's persistent compilation cache at a fixed
    path inside the checkout unless one is given."""
    for p in (str(root / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(root / ".jax_cache"))


def device_info(chips: int, require_tpu: bool = True):
    """The devices the cell runs on, or None where there is no TPU or
    too few chips."""
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        say(f"bench: no TPU found (JAX platform {devices[0].platform!r})")
        return None
    if len(devices) < chips:
        say(f"bench: the cell needs {chips} chips, JAX found "
            f"{len(devices)}")
        return None
    return devices[:chips]


def result(cell, seed: int, seconds: float, trace: bool, devices,
           t_start: float, root: Path = ROOT, bench_dir: Path = BENCH):
    """Run the cell once; the result object (and the lines to print on
    standard error)."""
    import numpy as np

    from lgcbench import cell as C
    from lgcbench import check, flops, reference, spec
    from lgcbench import trace as T

    t = cell.traffic
    model = cell.config["model"]
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        r = C.run(cell, seed, seconds, trace, t_start, trace_dir=tmp)
        tokens_per_step = t["batch_per_chip"] * t["chips"] * t["seq_len"]
        dev = {"platform": devices[0].platform,
               "kind": devices[0].device_kind, "count": len(devices),
               "memory_peak_bytes": r.peak_bytes}
        metrics = {}
        out = {}
        if not trace:
            values = {
                "tokens_per_s": r.attempted * tokens_per_step / r.window_s,
                "step_p90_ms": float(np.percentile(r.step_times, 90)) * 1e3,
                "peak_hbm_gb": r.peak_bytes / 1e9,
                "setup_s": r.setup_s,
            }
            for m in cell.end_to_end:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        else:
            tr = T.load(r.trace_dir, r.hlo_text)
            ctx = {
                "trace": tr, "steps": r.traced_steps, "chips": cell.chips,
                "tokens_per_step": tokens_per_step,
                "flops_per_token": flops.flops_per_token(
                    model, t["seq_len"], bench_dir)["total"],
                "least_post_grad_bytes": flops.least_post_grad_bytes(
                    model, t["method"], bench_dir),
                "peaks": spec.peaks(devices[0].device_kind, bench_dir),
                "wire": r.wire,
            }
            for m in cell.per_layer:
                v = spec.metric_reader(m["name"], bench_dir)(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            dev["busy_s"] = tr.busy_s()
            dev["window_s"] = tr.window_s()
            out["breakdown"] = tr.breakdown()
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)

    # ---- correctness: the reference after the window -------------------
    t0 = time.time()
    train = C.reference_train(t)
    ref = reference.run(reference.F32, model, train, r.params0, r.ae0,
                        r.batches, t["start_step"], bench_dir=bench_dir)
    prog = {"loss": r.losses, "grad": r.readings["grad"],
            "change": C.change_norms(r.readings["params3"], r.params0)}
    if "ef_u" in r.readings:
        prog["ef_u"], prog["ef_v"] = r.readings["ef_u"], r.readings["ef_v"]
    nums = check.numbers(prog, ref)
    correct = check.verdict(nums, cell.limits) and r.failed == 0
    ref_s = time.time() - t0
    lines = [f"set-up {r.setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in r.setup.items()),
        f"window {r.window_s:.3f} s, {r.attempted} steps, reference "
        f"{ref_s:.1f} s",
        "memory (bytes): " + ", ".join(f"{k} {v}"
                                       for k, v in r.memory.items()),
        _stalls(r)]
    checks = {}
    for k, v in nums.items():
        lim = cell.limits.get(k)
        checks[f"{k}_gap"] = {"value": v, "limit": lim}
        lines.append(f"check {k}_gap {v:.6g} limit {lim}")
    res = {"correct": bool(correct), "attempted": r.attempted,
           "failed": r.failed, "metrics": metrics, "device": dev, **out,
           "checks": checks}
    return res, lines


def _stalls(r) -> str:
    """The window's steps that took over 1.5 times the median step (index
    in the window, its time and the part spent dispatching it), and the
    garbage collections inside the window."""
    import numpy as np
    med = float(np.median(r.step_times))
    slow = [f"{j}: {t * 1e3:.1f} ms ({d * 1e3:.1f} dispatch)"
            for j, (t, d) in enumerate(zip(r.step_times, r.dispatch_times))
            if t > 1.5 * med]
    return (f"steps over 1.5 x the median {med * 1e3:.1f} ms: "
            f"{', '.join(slow) or 'none'}; garbage collections "
            f"{len(r.gc_pauses)}, {sum(r.gc_pauses) * 1e3:.1f} ms")


def main(argv=None) -> int:
    args = parse_args(argv)
    setup_paths()
    from lgcbench import spec
    cell = spec.resolve(ROOT, args.workload)
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = device_info(cell.chips)
    if devices is None:
        return 2
    res, lines = result(cell, args.seed, args.seconds, bool(args.trace),
                        devices, T_START)
    for line in lines:
        say(line)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
