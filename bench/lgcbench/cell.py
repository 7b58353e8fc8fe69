"""One run of one cell: build the program's step as the trainer does,
drive it from the seed through set-up, measure a window, and read what
the correctness check compares.

The system under test is entered only through
``repro.launch.train.build_configs`` and the step builders of
``repro.launch.steps``; its spans, counters and kernel names are read
from the profiler's trace and from ``repro.dist.collectives``.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from lgcbench import reference, tokens, weights
from lgcbench.spec import Cell

SETUP_STEPS = 3            # the steps the reference follows
TRACE_STEPS = 4            # steps in a traced window


# ---------------------------------------------------------------------------
# the program, as the trainer builds it


def _train_argv(cell: Cell, seed: int) -> List[str]:
    t, c = cell.traffic, cell.config
    opt = t["optimizer"]
    return ["--arch", c["arch"],
            "--batch", str(t["batch_per_chip"] * t["chips"]),
            "--seq", str(t["seq_len"]),
            "--compression", t["method"],
            "--sparsity", str(t["sparsity"]),
            "--transport", t["transport"],
            "--topk-backend", t["topk_backend"],
            "--ae-backend", t["ae_backend"],
            "--optimizer", opt["name"],
            "--lr", str(opt["lr"]),
            "--steps", str(opt["steps"]),
            "--data-shards", str(t["data_shards"]),
            "--seed", str(seed)]


def _override(obj, overrides: dict):
    """dataclasses.replace, into nested config groups where the value is
    a dict; a JSON list becomes a tuple where the field holds one (a
    superblock's ``block_pattern``)."""
    kw = {}
    for k, v in overrides.items():
        cur = getattr(obj, k)
        if isinstance(v, dict):
            v = _override(cur, v)
        elif isinstance(v, list) and isinstance(cur, tuple):
            v = tuple(v)
        kw[k] = v
    return dataclasses.replace(obj, **kw)


def _check_shape(cfg, kind, model: dict):
    """The program's model config has to be the configuration file's, as
    the model kind maps the file's keys to the config's attributes."""
    want = {**kind.program_shape(model), "dtype": model["dtype"]}
    bad = {}
    for key, v in want.items():
        obj = cfg
        for part in key.split("."):
            obj = getattr(obj, part)
        if obj != v:
            bad[key] = (obj, v)
    if bad:
        raise ValueError(f"program config differs from the configuration "
                         f"file (program, file): {bad}")


def _paths(tree):
    import jax
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return ([(jax.tree_util.keystr(p, simple=True, separator="/"), x)
             for p, x in flat], treedef)


@dataclass
class Program:
    """The cell's compiled step and the readers of its state."""
    lgc: bool
    step_fn: Callable
    batch_sharding: Any
    init: Callable            # key -> (state, params0, ae0)
    b1: float
    leaves: tuple             # the reference's layout of the gradient


def build_program(cell: Cell, seed: int) -> Program:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch import train
    from repro.launch.steps import make_auto_train_step, make_lgc_train_step
    from repro.models import build_model

    t = cell.traffic
    args = train.parse_args(_train_argv(cell, seed))
    cfg, tc, mesh = train.build_configs(args)
    cfg = _override(cfg, cell.config["overrides"])
    _check_shape(cfg, cell.kind, cell.config["model"])
    model = build_model(cfg)
    B, S = t["batch_per_chip"] * t["chips"], t["seq_len"]
    sds = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
           "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    batch_sharding = NamedSharding(mesh, P(dp if len(dp) > 1 else dp[0]))
    key0 = jax.random.PRNGKey(0)
    p_shapes = jax.eval_shape(model.init, key0)
    p_items, p_def = _paths(p_shapes)
    p_specs = [(path, x.shape, x.dtype) for path, x in p_items]
    lgc = t["method"] != "none"
    if lgc:
        lts = make_lgc_train_step(model, tc, mesh)
        step_fn = lts.make_step(t["phase"], sds)
        opt_init, p_sh, o_sh = lts.optimizer.init, lts.params_sharding, \
            lts.opt_sharding
        a_shapes = jax.eval_shape(lts.compressor.init_state, key0)["ae"]
        a_items, a_def = _paths(a_shapes)
        a_specs = [(path, x.shape, x.dtype) for path, x in a_items]
        n, K = lts.n_local, lts.dp_size
        c_sh = lts.comp_sharding
    else:
        ats = make_auto_train_step(model, tc, mesh)
        step_fn = ats.step_fn(sds)
        opt_init, p_sh, o_sh = ats.optimizer.init, ats.params_sharding, \
            ats.opt_sharding
        a_specs = []
    repl = NamedSharding(mesh, P())

    def make_weights(key):
        params = p_def.unflatten(list(weights.make(
            p_specs, jax.random.fold_in(key, 0),
            getattr(cell.kind, "leaf_init", None)).values()))
        ae = None
        if a_specs:
            ae = a_def.unflatten(list(weights.make(
                a_specs, jax.random.fold_in(key, 1)).values()))
        return params, ae

    make_weights = jax.jit(make_weights, out_shardings=(
        p_sh, repl if a_specs else None))
    opt_init = jax.jit(opt_init, out_shardings=o_sh)
    if lgc:
        def comp_init(ae):
            return {"u": jnp.zeros((K, 1, n), jnp.float32),
                    "v": jnp.zeros((K, 1, n), jnp.float32), "ae": ae,
                    "ae_mom": jax.tree_util.tree_map(jnp.zeros_like, ae)}
        comp_init = jax.jit(comp_init, out_shardings=c_sh)

    def init(key):
        params, ae = make_weights(key)
        # the weights the reference starts from: a host copy, taken
        # before the first step consumes (donates) the device buffers
        params0 = jax.device_get(params)
        ae0 = jax.device_get(ae)
        state = (params, opt_init(params))
        if lgc:
            state += (comp_init(ae),)
        return state, params0, ae0

    shapes = [(path, tuple(s)) for path, s, _ in p_specs]
    leaves = tuple(reference.layout(shapes, t["sparsity"]))
    return Program(lgc, step_fn, batch_sharding, init,
                   t["optimizer"]["b1"], leaves)


def reference_train(traffic: dict) -> dict:
    """What the reference needs of a traffic mix."""
    t = traffic
    return {"method": t["method"], "nodes": t["data_shards"],
            "sparsity": t["sparsity"], "momentum": t["momentum_correction"],
            "innovation_sparsity": t["innovation_sparsity"],
            "optimizer": t["optimizer"]}


def call(prog: Program, state, batch, step_no: int):
    out = prog.step_fn(*state, batch, step_no)
    return tuple(out[:-1]), out[-1]


# ---------------------------------------------------------------------------
# readings of the program's state


def grad_norms(prog: Program, state) -> np.ndarray:
    """Per-leaf norm of the first global gradient as AdamW got it, worked
    out from its state after one step from zero: m = (1 - b1) g."""
    import jax
    import jax.numpy as jnp
    b1 = prog.b1
    m = state[1]["m"]
    f = jax.jit(lambda m: [jnp.linalg.norm(x.astype(jnp.float32).ravel())
                           / (1.0 - b1) for x in jax.tree_util.tree_leaves(m)])
    return np.array([float(x) for x in f(m)])


def ef_norms(prog: Program, state) -> Dict[str, np.ndarray]:
    """Per-node, per-leaf norms of the error-feedback state."""
    import jax
    import jax.numpy as jnp
    leaves = prog.leaves

    def norms(x):                              # (K, 1, n) -> (K, L)
        x = x[:, 0]
        return jnp.stack([jnp.linalg.norm(x[:, l.offset:l.offset + l.size],
                                          axis=1) for l in leaves], axis=1)
    f = jax.jit(norms)
    comp = state[2]
    return {"ef_u": np.asarray(f(comp["u"])),
            "ef_v": np.asarray(f(comp["v"]))}


def change_norms(params3, params0) -> np.ndarray:
    """Per-leaf norm of the parameters' change, from host copies."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a, b: [jnp.linalg.norm(
        (x.astype(jnp.float32) - y.astype(jnp.float32)).ravel())
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])
    return np.array([float(x) for x in f(params3, params0)])


def _abstract(args):
    """Shapes, dtypes and shardings of a step's arguments."""
    import jax

    def spec(x):
        if not hasattr(x, "shape"):
            return x                      # the step number, a Python int
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding,
                                    weak_type=getattr(x, "weak_type", False))
    return jax.tree_util.tree_map(spec, args)


# ---------------------------------------------------------------------------
# compile counting


class CompileCounter:
    """Counts the programs JAX traces or compiles.  None may appear
    inside the measured window."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, duration, **kw):
        if event in self.EVENTS:
            self.n += 1


# ---------------------------------------------------------------------------
# memory


def _stat(devices, key: str) -> int:
    """The largest of the devices' ``memory_stats()[key]`` (0 where the
    backend keeps none)."""
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devices)


def step_memory(compiled, live: int, peak_in_use: int) -> Dict[str, int]:
    """The fullest chip's peak while a step of the window runs.

    ``peak_bytes_in_use`` counts the buffers the runtime hands out, and
    on the TPU not the temporaries a program lays out for itself.  So
    the peak is what was live as the window opened (at least the step's
    arguments), plus the step's outputs that reuse no argument and its
    temporaries, as the compiler laid them out for one chip; or the
    runtime's own peak, where that is larger."""
    ma = compiled.memory_analysis()
    if ma is None:
        raise RuntimeError("the compiled step reports no memory layout")
    m = {"arguments": int(ma.argument_size_in_bytes),
         "outputs": int(ma.output_size_in_bytes),
         "aliased": int(ma.alias_size_in_bytes),
         "temporaries": int(ma.temp_size_in_bytes),
         "live_at_window": live, "peak_in_use": peak_in_use}
    m["peak"] = max(peak_in_use, max(live, m["arguments"]) + m["outputs"]
                    - m["aliased"] + m["temporaries"])
    return m


# ---------------------------------------------------------------------------
# one run


@dataclass
class Run:
    setup: Dict[str, float] = field(default_factory=dict)
    setup_s: float = 0.0
    step_times: List[float] = field(default_factory=list)
    dispatch_times: List[float] = field(default_factory=list)
    gc_pauses: List[float] = field(default_factory=list)
    window_s: float = 0.0
    losses: List[float] = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    peak_bytes: int = 0
    memory: Dict[str, int] = field(default_factory=dict)
    readings: Dict[str, Any] = field(default_factory=dict)
    wire: Dict[str, float] = field(default_factory=dict)
    trace_dir: Optional[str] = None
    hlo_text: str = ""
    traced_steps: int = 0
    batches: List[dict] = field(default_factory=list)
    params0: Any = None
    ae0: Any = None


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, trace_dir: Optional[str] = None,
        prog: Optional[Program] = None, counter=None,
        on_step: Optional[Callable] = None) -> Run:
    """Set-up, the measured (or traced) window and the program's
    readings.  ``t_start`` is the process's start on the host clock.
    ``prog`` reuses a program built for an earlier seed (calibration);
    ``on_step(i, state)`` sees the state after each set-up step."""
    import jax

    from repro.dist import collectives as coll

    r = Run()
    t = cell.traffic
    counter = counter or CompileCounter()
    t0 = time.time()
    prog = prog or build_program(cell, seed)
    r.setup["build_s"] = time.time() - t0

    t0 = time.time()
    state, r.params0, r.ae0 = prog.init(weights.seed_key(seed))
    jax.block_until_ready(state)
    r.setup["init_s"] = time.time() - t0

    t0 = time.time()
    model = cell.config["model"]
    B, S = t["batch_per_chip"] * t["chips"], t["seq_len"]
    host = tokens.batches(model["vocab_size"], B, S, seed, t["pool"])
    r.batches = host[:SETUP_STEPS]
    pool = [jax.device_put(b, prog.batch_sharding) for b in host]
    jax.block_until_ready(pool)
    r.setup["batches_s"] = time.time() - t0

    start = t["start_step"]
    coll.reset_wire_tally()
    for i in range(SETUP_STEPS):
        t0 = time.time()
        state, metrics = call(prog, state, pool[i], start + i)
        r.losses.append(float(metrics["loss"]))
        if i == 0:
            r.wire = {k: float(v) for k, v in coll.wire_report().items()}
            r.readings["grad"] = grad_norms(prog, state)
        if on_step is not None:
            on_step(i, state)
        r.setup[f"step{i + 1}_s"] = time.time() - t0
    t0 = time.time()
    if prog.lgc:
        r.readings.update(ef_norms(prog, state))
    r.readings["params3"] = jax.device_get(state[0])
    r.setup["readings_s"] = time.time() - t0
    compiles_before = counter.n
    abstract = _abstract((*state, pool[0], start))
    devices = jax.devices()[:cell.chips]
    live = _stat(devices, "bytes_in_use")

    # ---- the window ---------------------------------------------------
    # what set-up left on the host is kept out of the window's garbage
    # collections, which are timed
    gc.collect()
    gc.freeze()
    gc_start = []

    def gc_timer(phase, info):
        if phase == "start":
            gc_start.append(time.time())
        elif gc_start:
            r.gc_pauses.append(time.time() - gc_start.pop())
    gc.callbacks.append(gc_timer)
    i = SETUP_STEPS
    if trace:
        jax.profiler.start_trace(trace_dir)
    t_prev = time.time()
    r.setup_s = t_prev - t_start
    t_window = t_prev
    while True:
        if trace:
            with jax.profiler.StepTraceAnnotation("train", step_num=i):
                state, metrics = call(prog, state, pool[i % len(pool)],
                                      start + i)
                t_call = time.time()
                loss = float(metrics["loss"])
        else:
            state, metrics = call(prog, state, pool[i % len(pool)],
                                  start + i)
            t_call = time.time()
            loss = float(metrics["loss"])
        now = time.time()
        r.step_times.append(now - t_prev)
        r.dispatch_times.append(t_call - t_prev)
        t_prev = now
        r.attempted += 1
        r.failed += 0 if math.isfinite(loss) else 1
        i += 1
        done = (r.attempted >= TRACE_STEPS) if trace \
            else (now - t_window >= seconds)
        if done:
            break
    r.window_s = t_prev - t_window
    gc.callbacks.remove(gc_timer)
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
        r.trace_dir = trace_dir
        r.traced_steps = r.attempted
    if counter.n != compiles_before:
        raise RuntimeError(f"{counter.n - compiles_before} program(s) "
                           f"traced or compiled inside the window")
    # the executable that ran (the same program, so the persistent
    # cache returns it): its memory layout, and in a traced run the name
    # stacks of its operations
    compiled = prog.step_fn.lower(*abstract).compile()
    if trace:
        r.hlo_text = compiled.as_text()
    r.memory = step_memory(compiled, live,
                           _stat(devices, "peak_bytes_in_use"))
    r.memory["limit"] = _stat(devices, "bytes_limit")
    r.peak_bytes = r.memory["peak"]
    del state, metrics, pool
    gc.collect()
    return r
