"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train \
        --arch llama3.2-1b --smoke --steps 50 --compression lgc_rar \
        --data-shards 2 --model-shards 1 --batch 8 --seq 128

Runs the three-phase LGC schedule (warm-up -> top-k+AE-online ->
compressed) with per-phase jit specialization, periodic checkpointing and
a compression-rate report at the end.  ``--smoke`` selects the reduced
config of the same architecture family (CPU-tractable).
"""
from __future__ import annotations

import argparse
import json
import os
import time


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="llama3.2-1b")
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced (smoke) config variant")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--compression", default="none",
                   choices=["none", "sparse_gd", "dgc", "lgc_ps", "lgc_rar",
                            "lgc_rar_q8"])
    p.add_argument("--sparsity", type=float, default=0.001)
    base_transports = ["mesh", "ring", "ring_q8", "ring_hier",
                       "ring_packed"]
    p.add_argument("--transport", default="mesh",
                   choices=base_transports + ["chaos:" + t
                                              for t in base_transports],
                   help="communication substrate: lax collectives (mesh), "
                        "the explicit chunked ring with measured wire "
                        "bytes (ring), the int8-wire ring that makes "
                        "lgc_rar_q8's 1-byte/value claim real (ring_q8), "
                        "hierarchical intra/inter-pod rings on "
                        "multi-axis dp meshes (ring_hier), or the packed "
                        "sparse wire — bit-packed indices + int8 values "
                        "for the sparse_gd/dgc/lgc_ps top-k exchanges "
                        "(ring_packed).  A chaos:<base> prefix wraps the "
                        "substrate in the seeded fault injector "
                        "(--fault-*); setting any --fault-* flag wraps "
                        "automatically")
    p.add_argument("--topk-backend", default="jnp",
                   choices=["jnp", "pallas", "fused"],
                   help="residual top-k selection backend (fused = the "
                        "one-launch segmented accumulate+select sweep)")
    p.add_argument("--ae-backend", default="jnp",
                   choices=["jnp", "pallas"],
                   help="phase-3 encoder backend (pallas = im2col + "
                        "fused MXU matmul kernel, ops.lgc_encode_fast)")
    p.add_argument("--extract-backend", default="auto",
                   choices=["auto", "loop", "bitonic"],
                   help="per-block candidate extractor inside the fused "
                        "sweep: the sequential argmax loop, the bitonic "
                        "partial sort (k-independent depth), or auto "
                        "(bitonic once 8*k_max outgrows the max block)")
    p.add_argument("--warmup-steps", type=int, default=10)
    p.add_argument("--ae-train-steps", type=int, default=15)
    p.add_argument("--optimizer", default="adamw",
                   choices=["adamw", "sgd_momentum"])
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--data-shards", type=int, default=1)
    p.add_argument("--model-shards", type=int, default=1)
    p.add_argument("--wire-buckets", type=int, default=1,
                   help="split every bucketable ring exchange into this "
                        "many pipeline buckets: bucket b's ppermute chain "
                        "runs while bucket b+1 encodes (reduce-scatter / "
                        "quantize / packed encode), overlapping "
                        "compression compute with the wire.  1 = the "
                        "historical unbucketed schedule, bit-for-bit")
    p.add_argument("--pod-shards", type=int, default=1,
                   help="prepend a pod axis of this size to the host "
                        "mesh: dp becomes (pod x data), which is the "
                        "2-level topology ring_hier's intra/inter-pod "
                        "schedule is built for")
    p.add_argument("--device-count", type=int, default=0,
                   help="force this many host platform devices")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint-dir", default="")
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument("--resume", default="",
                   help="checkpoint .npz to resume from: restores the "
                        "FULL train state — (params, opt_state, "
                        "comp_state) for compressed runs, EF residuals "
                        "included — fast-forwards the data stream and "
                        "continues at the saved step, bit-identically to "
                        "an uninterrupted run")
    p.add_argument("--guard", default="off",
                   choices=["off", "scrub", "skip_round", "fail_fast"],
                   help="exchange guard policy (repro.dist.chaos): scrub "
                        "zeroes non-finite/invalid wire payloads (the "
                        "masked gradient stays in the EF residual), "
                        "skip_round additionally drops a faulty round's "
                        "whole gradient, fail_fast raises WireFaultError "
                        "naming the faulting op labels")
    p.add_argument("--guard-checksum", action="store_true",
                   help="append one int32 checksum word to every packed "
                        "payload (+4 wire bytes, priced honestly) so the "
                        "guard catches arbitrary finite bit-flips")
    p.add_argument("--fault-seed", type=int, default=0)
    p.add_argument("--fault-bitflips", type=int, default=0,
                   help="XOR this many seeded bit positions into each "
                        "targeted op's result payload per step")
    p.add_argument("--fault-nans", type=int, default=0,
                   help="overwrite this many seeded result elements "
                        "with NaN per targeted op per step")
    p.add_argument("--fault-infs", type=int, default=0,
                   help="overwrite this many seeded result elements "
                        "with +Inf per targeted op per step")
    p.add_argument("--fault-drop-node", type=int, default=-1,
                   help="this node's contribution to every targeted "
                        "collective becomes zeros")
    p.add_argument("--fault-stale-node", type=int, default=-1,
                   help="this node contributes a rolled (finite, wrong) "
                        "payload to every targeted collective")
    p.add_argument("--fault-ops", default="",
                   help="comma-separated exchange-plan op labels to "
                        "target (default: all ops)")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--metrics-out", default="")
    return p.parse_args(argv)


def build_configs(args):
    """(model config, train config, host mesh) for parsed ``args`` —
    what :func:`main` trains, for callers that build the same step."""
    from repro.configs import get_arch
    from repro.configs.base import CompressionConfig, TrainConfig
    from repro.launch.mesh import make_host_mesh

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    cc = CompressionConfig(method=args.compression, sparsity=args.sparsity,
                           warmup_steps=args.warmup_steps,
                           ae_train_steps=args.ae_train_steps,
                           transport=args.transport,
                           topk_backend=args.topk_backend,
                           ae_backend=args.ae_backend,
                           extract_backend=args.extract_backend,
                           wire_buckets=args.wire_buckets,
                           guard=args.guard,
                           guard_checksum=args.guard_checksum,
                           fault_seed=args.fault_seed,
                           fault_bitflips=args.fault_bitflips,
                           fault_nans=args.fault_nans,
                           fault_infs=args.fault_infs,
                           fault_drop_node=args.fault_drop_node,
                           fault_stale_node=args.fault_stale_node,
                           fault_ops=args.fault_ops)
    tc = TrainConfig(optimizer=args.optimizer, learning_rate=args.lr,
                     steps=args.steps, seed=args.seed, compression=cc)
    mesh = make_host_mesh(args.data_shards, args.model_shards,
                          pod=args.pod_shards)
    return cfg, tc, mesh


def main(argv=None):
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = parse_args(argv)
    needed = args.pod_shards * args.data_shards * args.model_shards
    if args.device_count or needed > 1:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count="
            f"{args.device_count or needed}")

    import jax
    import jax.tree_util as jtu
    import numpy as np

    from repro.checkpoint import load_checkpoint, save_checkpoint
    from repro.core.phases import phase_for_step
    from repro.core.rate import rate_report
    from repro.data import synthetic_token_batches
    from repro.launch.steps import make_auto_train_step, make_lgc_train_step
    from repro.models import build_model
    from repro.utils import get_logger

    log = get_logger("train")
    cfg, tc, mesh = build_configs(args)
    cc = tc.compression
    model = build_model(cfg)
    log.info("arch=%s params=%s devices=%d mesh=%s",
             cfg.name, f"{model.param_count():,}", len(jax.devices()),
             dict(zip(mesh.axis_names, mesh.devices.shape)))

    data = synthetic_token_batches(
        cfg.vocab_size, args.batch, args.seq, seed=args.seed,
        encoder_tokens=cfg.num_encoder_tokens, encoder_dim=cfg.encoder_dim)
    first = next(data)
    sds = jtu.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       first)

    rng = jax.random.PRNGKey(args.seed)
    use_lgc = args.compression != "none"
    history = []
    from repro.dist import chaos

    guard_on = cc.guard != "off"
    faults_total = 0

    def _count_faults(metrics):
        # per-op guard counters -> one host-side running total (what the
        # ci chaos gate asserts nonzero; also the fail_fast trigger set)
        return sum(int(np.asarray(v).sum()) for k, v in metrics.items()
                   if k.startswith("fault/"))

    if use_lgc:
        from repro.dist import collectives as coll
        lts = make_lgc_train_step(model, tc, mesh)
        params, opt_state, comp_state = lts.init(rng, model, mesh)
        start_step = 0
        if args.resume:
            # full-state resume: the freshly-initialized state is the
            # shape/dtype template; EF residuals (comp_state u/v) and
            # the optimizer moments come back exactly, so the continued
            # trajectory is bit-identical to an uninterrupted run
            tree = {"params": params, "opt_state": opt_state,
                    "comp_state": comp_state}
            loaded, start_step = load_checkpoint(args.resume, tree)
            params = jax.device_put(loaded["params"],
                                    lts.params_sharding)
            opt_state = jax.device_put(loaded["opt_state"],
                                       lts.opt_sharding)
            comp_state = jax.device_put(loaded["comp_state"],
                                        lts.comp_sharding)
            log.info("resumed full train state from %s at step %d",
                     args.resume, start_step)
        report = rate_report(cc, lts.compressor.layout, lts.dp_size)
        log.info("compression=%s CR(avg)=%.1fx bytes/node=%.0f",
                 cc.method, report.compression_ratio, report.bytes_per_node)
        fns = {}
        fault_ops_by_phase = {}
        batch = first
        for _ in range(start_step):
            # the batch at step s is the s-th yield of the stream —
            # fast-forward so the resumed run consumes the same data an
            # uninterrupted run would have at this step
            batch = next(data)
        t0 = time.time()
        for step in range(start_step, args.steps):
            t_step = time.time()
            phase = phase_for_step(step, cc)
            if phase not in fns:
                # per-phase wire accounting: bytes are recorded at trace
                # time, so reset before each phase build and report what
                # one step of this phase moves per node
                coll.reset_wire_tally()
                chaos.reset_fault_tally()
                fns[phase] = lts.make_step(phase, sds)
            params, opt_state, comp_state, metrics = fns[phase](
                params, opt_state, comp_state, batch, step)
            if step == start_step or phase_for_step(step - 1, cc) != phase:
                # the first call of a phase is the one that traces it:
                # both tallies (wire bytes AND injected faults) fill in
                # at trace time, so sample them here, not at build time
                fault_ops_by_phase[phase] = chaos.fault_report()
                wire = coll.wire_report()
                if wire:
                    log.info("phase=%s wire bytes/node/step: %s", phase,
                             {k: int(v) for k, v in wire.items()})
            batch = next(data)
            if guard_on:
                faults_total += _count_faults(metrics)
                if cc.guard == "fail_fast":
                    chaos.raise_on_faults(metrics, step=step)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                # wall time of this step, first-call compile included
                # (the loss fetch waits for the device)
                rec = {"step": step, "phase": phase, "loss": loss,
                       "seconds": time.time() - t_step}
                if guard_on:
                    rec["faults"] = faults_total
                if fault_ops_by_phase.get(phase):
                    # per-op injected-fault counts ({op label: {fault
                    # kind: count}}, static trace-time ints) ride along
                    # next to the loss so a metrics consumer can
                    # attribute a bad step to the op the spec targeted
                    rec["fault_ops"] = fault_ops_by_phase[phase]
                history.append(rec)
                log.info("step %4d  phase=%-10s loss=%.4f", step, phase,
                         loss)
            if args.checkpoint_every and args.checkpoint_dir \
                    and step and step % args.checkpoint_every == 0:
                # step+1 = the next step to run on resume; the FULL
                # state ships, EF residuals included — params alone
                # would silently drop every coordinate parked in u/v
                save_checkpoint(
                    os.path.join(args.checkpoint_dir, "ckpt.npz"),
                    {"params": params, "opt_state": opt_state,
                     "comp_state": comp_state}, step + 1)
        log.info("done in %.1fs", time.time() - t0)
        final_tree = {"params": params, "opt_state": opt_state,
                      "comp_state": comp_state}
    else:
        ats = make_auto_train_step(model, tc, mesh)
        params, opt_state = ats.init(rng, model)
        start_step = 0
        if args.resume:
            tree = {"params": params, "opt_state": opt_state}
            loaded, start_step = load_checkpoint(args.resume, tree)
            params = jax.device_put(loaded["params"], ats.params_sharding)
            opt_state = jax.device_put(loaded["opt_state"],
                                       ats.opt_sharding)
            log.info("resumed train state from %s at step %d",
                     args.resume, start_step)
        fn = ats.step_fn(sds)
        batch = first
        for _ in range(start_step):
            batch = next(data)
        t0 = time.time()
        for step in range(start_step, args.steps):
            t_step = time.time()
            params, opt_state, metrics = fn(params, opt_state, batch, step)
            batch = next(data)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                history.append({"step": step, "phase": "dense",
                                "loss": loss,
                                "seconds": time.time() - t_step})
                log.info("step %4d  loss=%.4f", step, loss)
            if args.checkpoint_every and args.checkpoint_dir \
                    and step and step % args.checkpoint_every == 0:
                save_checkpoint(
                    os.path.join(args.checkpoint_dir, "ckpt.npz"),
                    {"params": params, "opt_state": opt_state}, step + 1)
        log.info("done in %.1fs", time.time() - t0)
        final_tree = {"params": params, "opt_state": opt_state}

    if args.checkpoint_dir:
        save_checkpoint(os.path.join(args.checkpoint_dir, "ckpt.npz"),
                        final_tree, args.steps)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f, indent=1)
    return history


if __name__ == "__main__":
    main()
