#!/usr/bin/env python3
"""Readings that set a cell's correctness limits (bench/limits/<cell>.json).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 \
        [--control-seeds 1,2,3] [--faults half_batch,no_exchange] \
        [--look] [--out <file.jsonl>]

In one process: for every seed, the set-up of a run (the program's step
from the seed through its first three steps) and the float32 reference
over those steps, giving the program's numbers (the lower readings); for
the control seeds, the reference again in the program's place computed in
float8 (the control, which has to fail); for each fault, the reference
with that fault planted in the program's place.  One JSON line per
reading goes to ``--out`` and standard output, then the summary: each
number's largest program reading and smallest control and fault readings.

``--look`` adds a line for every seed that says, leaf by leaf, where its
gaps come from: how many of the elements sent at each set-up step differ
between the program and the reference (node 0's error-feedback elements
left at zero), how many elements of the leaf changed on each side, and
how many of its weights differ between the two after the set-up steps,
by how many units in the last place of the weights' dtype at most, and
how many the two moved in opposite directions.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

import run as R  # noqa: E402  (bench/ is the script's directory)


def _lists(readings):
    return {k: np.asarray(v, float).tolist() for k, v in readings.items()
            if k not in ("paths", "v_zero", "final")}


def _differ(a, b, a0):
    """How many weights differ between the program's ``a`` and the
    reference's ``b``, the largest gap in units in the last place of
    ``a``'s dtype, and how many weights the two moved away from ``a0``
    in opposite directions."""
    import jax.numpy as jnp
    nmant = jnp.finfo(a.dtype).nmant
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    a0 = np.asarray(a0, np.float64).ravel()
    mag = np.maximum(np.maximum(np.abs(a), np.abs(b)), 1e-30)
    gap = np.abs(a - b) / np.exp2(np.floor(np.log2(mag)) - nmant)
    return {"differ": int(np.count_nonzero(a != b)),
            "max_ulps": float(gap.max()) if gap.size else 0.0,
            "opposite": int(np.count_nonzero(
                np.sign(a - a0) * np.sign(b - a0) < 0))}


def look(prog, readings, ref, v_zero, r):
    """Per leaf: its gaps, the elements sent that differ at each set-up
    step, and the elements changed by the program and the reference."""
    import jax
    from lgcbench import check
    p3 = jax.tree_util.tree_leaves(r.readings["params3"])
    p0 = jax.tree_util.tree_leaves(r.params0)
    moved = [int(np.count_nonzero(np.asarray(a, np.float32)
                                  != np.asarray(b, np.float32)))
             for a, b in zip(p3, p0)]
    gaps = {k: check.leaf_gaps(readings[k], ref[k])
            for k in ("grad", "change")}
    if "ef_v" in ref:
        gaps["ef"] = np.maximum(
            check.leaf_gaps(readings["ef_u"], ref["ef_u"]),
            check.leaf_gaps(readings["ef_v"], ref["ef_v"])).max(axis=0)
    out = []
    for j, leaf in enumerate(prog.leaves):
        seg = slice(leaf.offset, leaf.offset + leaf.size)
        out.append({
            "path": leaf.path, "role": leaf.role, "k": leaf.k,
            "size": leaf.size,
            "sent_differ": [int(np.count_nonzero(p[seg] != q[seg]))
                            for p, q in zip(v_zero, ref.get("v_zero", []))],
            "moved": [moved[j], int(ref["moved"][j])],
            **_differ(p3[j], ref["final"][j], p0[j]),
            **{f"{k}_gap": float(g[j]) for k, g in gaps.items()}})
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--faults", default="")
    p.add_argument("--look", action="store_true")
    p.add_argument("--out", default="")
    args = p.parse_args(argv)
    R.setup_paths()
    from lgcbench import spec
    cell = spec.resolve(R.ROOT, args.workload)
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if R.device_info(cell.chips) is None:
        return 2

    from lgcbench import cell as C
    from lgcbench import check, reference

    t = cell.traffic
    model = cell.config["model"]
    train = C.reference_train(t)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    faults = [f for f in args.faults.split(",") if f]
    out = open(args.out, "a") if args.out else None
    rows = []

    def emit(rec):
        rows.append(rec)
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    prog = C.build_program(cell, seeds[0])
    counter = C.CompileCounter()
    for seed in seeds:
        t0 = time.time()
        v_zero = []

        def sent(i, state):
            if args.look and prog.lgc:
                v_zero.append(np.asarray(jax.device_get(
                    state[2]["v"][0, 0])) == 0)
        r = C.run(cell, seed, 0.0, False, time.time(), prog=prog,
                  counter=counter, on_step=sent)
        t1 = time.time()
        ref = reference.run(reference.F32, model, train, r.params0, r.ae0,
                            r.batches, t["start_step"], detail=args.look)
        t2 = time.time()
        readings = {"loss": r.losses, "grad": r.readings["grad"],
                    "change": C.change_norms(r.readings["params3"],
                                             r.params0)}
        if "ef_u" in r.readings:
            readings["ef_u"] = r.readings["ef_u"]
            readings["ef_v"] = r.readings["ef_v"]
        emit({"kind": "program", "seed": seed,
              "numbers": check.numbers(readings, ref),
              "setup": r.setup, "reference_s": t2 - t1,
              "peak_bytes": r.peak_bytes, "loss": r.losses,
              "ref_loss": list(map(float, ref["loss"])),
              "memory": r.memory,
              "readings": _lists(readings), "reference": _lists(ref)})
        if args.look:
            emit({"kind": "look", "seed": seed,
                  "leaves": look(prog, readings, ref, v_zero, r)})
        others = []
        if seed in controls:
            others.append(("control", reference.fp8_numerics(), None))
            others += [(f, reference.F32, f) for f in faults]
        for kind, nm, fault in others:
            t3 = time.time()
            got = reference.run(nm, model, train, r.params0, r.ae0,
                                r.batches, t["start_step"], fault=fault)
            emit({"kind": kind, "seed": seed,
                  "numbers": check.numbers(got, ref),
                  "seconds": time.time() - t3, "readings": _lists(got)})
        del r, ref
        print(f"# seed {seed}: {time.time() - t0:.1f} s", file=sys.stderr,
              flush=True)

    summary = {}
    for rec in rows:
        for k, v in rec.get("numbers", {}).items():
            s = summary.setdefault(k, {})
            if rec["kind"] == "program":
                s["lower"] = max(s.get("lower", 0.0), v)
            else:
                s[rec["kind"]] = min(s.get(rec["kind"], float("inf")), v)
    emit({"kind": "summary", "workload": cell.name, "numbers": summary})
    return 0


if __name__ == "__main__":
    sys.exit(main())
