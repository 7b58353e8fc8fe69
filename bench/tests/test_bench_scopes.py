"""The program's named scopes and the readers that match them.

The step is compiled at a tiny size for each kind of step the cells run
(the plain step, lgc_ps on the mesh transport on one device, lgc_rar on
the ring over four devices), and every scope the readers match has to
be in the compiled HLO's ``op_name`` metadata, as the readers parse it.
The benchmark keeps its own copy of the names; they have to be the
program's, and every operation the program names has to lie under one
of them.  The readers are replayed on a slice of a trace recorded on a
TPU v5e.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

import tinybench
from tinybench import BENCH, ROOT

for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from lgcbench import scopes as S  # noqa: E402
from lgcbench import spec  # noqa: E402
from lgcbench import trace as T  # noqa: E402

# ---------------------------------------------------------------------------
# the scopes in the compiled step

CHILD = textwrap.dedent('''
    import json, sys
    from pathlib import Path
    sys.path[:0] = [{src!r}, {bench!r}]
    import jax
    from lgcbench import cell as C, spec, trace as T
    from lgcbench import weights
    root = Path(sys.argv[1])
    cell = spec.resolve(root, sys.argv[2], root / "bench")
    prog = C.build_program(cell, 0)
    state, _, _ = prog.init(weights.seed_key(0))
    t = cell.traffic
    B, L = t["batch_per_chip"] * t["chips"], t["seq_len"]
    batch = jax.device_put(
        {{"tokens": jax.numpy.zeros((B, L), "int32"),
          "labels": jax.numpy.zeros((B, L), "int32")}},
        prog.batch_sharding)
    text = prog.step_fn.lower(*state, batch, t["start_step"]) \\
        .compile().as_text()
    print(json.dumps(sorted(set(T.op_names(text).values()))))
''')

STEPS = {
    "none": ({"method": "none"}, 1),
    "lgc_ps-mesh": ({}, 1),
    "lgc_rar-ring-x4": ({"method": "lgc_rar", "transport": "ring",
                         "chips": 4, "data_shards": 4}, 4),
}
LGC = {S.FWD_BWD, S.XENT, S.COMPRESS, S.SELECT, S.AE, S.EXCHANGE,
       S.OPTIMIZER}
WANT = {"none": {S.FWD_BWD, S.XENT, S.OPTIMIZER},
        "lgc_ps-mesh": LGC, "lgc_rar-ring-x4": LGC}
LABELS = {"lgc_ps-mesh": {"exempt_dense", "support", "z_common",
                          "innovations"},
          "lgc_rar-ring-x4": {"exempt_dense", "support", "encoding"}}


_COMPILED = {}


def compiled_stacks(tmp_path, kind):
    """The name stacks of the compiled step ``kind``, compiled once per
    test run."""
    if kind not in _COMPILED:
        _COMPILED[kind] = _compile(tmp_path, kind)
    return _COMPILED[kind]


def _compile(tmp_path, kind):
    update, devices = STEPS[kind]
    cell = tinybench.make_root(tmp_path, kind, update, {})
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{devices}")
    code = CHILD.format(src=str(ROOT / "src"), bench=str(BENCH))
    proc = subprocess.run([sys.executable, "-c", code, str(tmp_path), cell],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind", sorted(STEPS))
def test_every_scope_is_in_the_compiled_step(tmp_path, kind):
    stacks = compiled_stacks(tmp_path, kind)
    found = {c for st in stacks for c in S.components(st)}
    assert WANT[kind] <= found, sorted(WANT[kind] - found)
    if kind == "none":
        assert not found & {S.COMPRESS, S.SELECT, S.AE, S.EXCHANGE}
        return
    # every exchange runs under exchange/<plan op label>, inside the
    # compressor's region; the loss under the model's
    labels = {S.components(st)[S.components(st).index(S.EXCHANGE) + 1]
              for st in stacks if S.exchanged(st)}
    assert LABELS[kind] <= labels, labels
    assert all(S.under(st, S.COMPRESS) for st in stacks if S.exchanged(st))
    assert all(S.under(st, S.FWD_BWD) for st in stacks
               if S.under(st, S.XENT))


# what the program names outside every scope: the model's constant masks
# and broadcasts, hoisted out of its scopes, a rematerialised reduction,
# and the step's averages of the nodes' metrics.  A layer's arithmetic
# (AdamW's sqrt, the top-k's sort, the AE's convolutions) is none of
# these, so a layer that loses its scope fails here.
OUTSIDE = {"broadcast", "broadcast_in_dim", "closed_call", "div", "ge",
           "iota", "jit(clip)", "reduce_max", "reduce_sum", "shard_map"}


@pytest.mark.parametrize("kind", sorted(STEPS))
def test_every_op_the_program_names_is_under_a_scope(tmp_path, kind):
    stacks = compiled_stacks(tmp_path, kind)
    loose = {st for st in stacks if not S.unnamed(st) and not S.scoped(st)}
    prims = {re.sub(r"\.\d+$", "", S._split(st)[-1]) for st in loose}
    assert prims <= OUTSIDE, sorted(st for st in loose if re.sub(
        r"\.\d+$", "", S._split(st)[-1]) not in OUTSIDE)


def test_the_names_are_the_programs():
    from repro.utils import scopes as P
    program = {k: v for k, v in vars(P).items() if k.isupper()}
    assert program == {k: getattr(S, k) for k in program}
    assert set(program.values()) == set(S.NAMES)


# ---------------------------------------------------------------------------
# the matching rule


@pytest.mark.parametrize("stack,comps", [
    ("jit(step_fn)/fwd_bwd/vmap(transpose(jvp(xent)))/while/body/dot",
     ("step_fn", "fwd_bwd", "xent", "while", "body", "dot")),
    ("jit(step_fn)/fwd_bwd/vmap(transpose(jvp()))/mul",
     ("step_fn", "fwd_bwd", "mul")),
    ("jit(step_fn)/shard_map/compress/exchange/support/all-gather",
     ("step_fn", "shard_map", "compress", "exchange", "support",
      "all-gather")),
    ("jit(f)/jvp(exchange/lab)/add", ("f", "exchange", "lab", "add")),
    ("", ()),
])
def test_components(stack, comps):
    assert S.components(stack) == comps


def test_the_rule_on_the_chips_compiled_step():
    """The name stacks of cell 1's step as the TPU compiler kept them:
    every scope is found as a component, a transformation wraps the
    first scope under it, and a name that merely contains a scope's
    (``select_n``, ``rematted_computation``) matches nothing."""
    import gzip
    with gzip.open(BENCH / "tests/data/stacks_mamba2-130m.lgc-ps.8x2048"
                   ".json.gz", "rt") as f:
        stacks = json.load(f)
    found = {n: [st for st in stacks if S.under(st, n)] for n in S.NAMES}
    assert all(found.values()), {n: len(v) for n, v in found.items()}
    assert "jit(step_fn)/fwd_bwd/vmap(jvp(xent))/while" in found[S.XENT]
    for st in stacks:
        if "select_n" in st and "/select/" not in st:
            assert not S.under(st, S.SELECT), st
        if S.under(st, S.XENT):
            assert S.under(st, S.FWD_BWD), st
        if S.exchanged(st):
            assert S.under(st, S.COMPRESS), st


def test_a_scope_matches_a_whole_component():
    assert S.under("jit(f)/compress/select/top_k", S.SELECT)
    assert S.under("jit(f)/vmap(jvp(ae))/conv", S.AE)
    assert not S.under("jit(f)/compress/aes/x", S.AE)
    assert not S.under("jit(f)/compressor/x", S.COMPRESS)
    assert not S.under("jit(f)/transpose(jvp(xent_2))/x", S.XENT)
    assert S.exchanged("jit(f)/compress/exchange/encoding/ppermute")
    assert not S.exchanged("jit(f)/compress/exchanges/x")


# ---------------------------------------------------------------------------
# the readers on a trace recorded on the chip

DATA = BENCH / "tests/data/trace_scopes_mamba2-130m.lgc-ps.8x2048.json.gz"
OLD = BENCH / "tests/data/trace_mamba2-130m.lgc-ps.8x2048.json.gz"
NEW_METRICS = ("xent_ms", "select_ms", "ae_ms", "compress_ms",
               "optimizer_ms")


def readings(tr, steps=1):         # the recorded trace holds one step
    ctx = {"trace": tr, "steps": steps}
    return {m: spec.metric_reader(m)(ctx) for m in NEW_METRICS}


@pytest.fixture(scope="module")
def chip():
    return T.from_json(str(DATA))


def test_a_program_without_scopes_reads_nothing():
    """The parent program's trace: every new reader returns None."""
    assert set(readings(T.from_json(str(OLD))).values()) == {None}


def test_readers_on_the_chip_trace(chip):
    """One step of cell 1 (lgc_ps on the mesh transport, one chip)."""
    r = readings(chip)
    for m in NEW_METRICS:
        assert r[m] and r[m] > 0, m
    # one chip: the exchange scopes hold only the mesh transport's local
    # ops, which compress_ms leaves out
    assert 0 < S.scope_ns(chip, S.exchanged) / 1e6 < 0.1
    assert r["select_ms"] + r["ae_ms"] <= r["compress_ms"]
    assert r["xent_ms"] < chip.class_ns("fwd_bwd") / 1e6
    assert r["compress_ms"] + r["optimizer_ms"] < \
        chip.class_ns("post_grad") / 1e6


def family(stack):
    return ("compressor" if S.under(stack, *S.COMPRESSOR) else
            "optimizer" if S.under(stack, S.OPTIMIZER) else
            "model" if S.under(stack, S.FWD_BWD) else "none")


def post_grad_by_scope(tr):
    """Self ns of the operations the ``jvp(`` rule counts as
    post-gradient, by the scope family of their own name stacks
    (``unnamed`` for the operations the compiler made without one)."""
    out = {}
    for pairs in S.effective(tr).pairs.values():
        for op, t in pairs:
            own = op[5]
            if T.classify(op[2], own) != "post_grad":
                continue
            key = "unnamed" if S.unnamed(own) else family(own)
            out[key] = out.get(key, 0) + t
    return out


def test_post_grad_time_outside_the_scopes_is_small(chip):
    """On the operations' own name stacks: what the program names lies
    under the compressor's or the optimizer's scopes, all but under 2 %
    of post_grad_ms.  The compiler's unnamed operations are not: on
    this trace they are 15.6 % of it (the model's ``reduce-window`` ops
    and copies, which the ``jvp(`` rule counts as post-gradient, 10.3 %,
    and copies and rewritten ops inside the compressor and the
    optimizer, 5.4 %), so the criterion does not hold on the ops' own
    stacks."""
    by = post_grad_by_scope(chip)
    post = chip.class_ns("post_grad")
    assert sum(by.values()) == pytest.approx(post, rel=1e-9)
    assert by.get("none", 0) + by.get("model", 0) < 0.02 * post, by
    assert by["compressor"] + by["optimizer"] > 0.8 * post, by
    assert 0.12 * post < by["unnamed"] < 0.18 * post, by


def test_effective_moves_little_into_the_readers_scopes(chip):
    """What ``effective`` re-attributes to the compressor's scopes is
    under 5 % of what they hold on their own stacks (13.6 of 301 ms on
    this trace); the optimizer gets only copies of its state and a
    conversion (5.1 ms beside its own 7.3); and no sort and no
    operation of 3 ms or more goes without its own scope: the work of
    each layer is read where the program names it."""
    moved, own, kinds = {}, {}, {}
    for pairs in S.effective(chip).pairs.values():
        for op, t in pairs:
            if S.unnamed(op[5]):
                f = family(op[3])
                moved[f] = moved.get(f, 0) + t
                kinds.setdefault(f, set()).add(
                    re.sub(r"\.\d+$", "", op[2]))
            else:
                f = family(op[5])
                own[f] = own.get(f, 0) + t
                if op[2].startswith("sort") and t > 1e6:
                    assert S.under(op[5], S.SELECT), op
            assert t < 3e6 or S.scoped(op[5]), op
    assert moved["compressor"] < 0.05 * own["compressor"], (moved, own)
    assert moved["optimizer"] < own["optimizer"], (moved, own)
    assert kinds["optimizer"] <= {"copy", "copy-start", "copy-done",
                                  "convert", "custom-call", "slice-start",
                                  "slice-done"}, kinds["optimizer"]
    assert moved.get("none", 0) == 0


def test_xent_on_a_grid(chip):
    """xent_ms against a brute-force count of the innermost operation on
    a 1 us grid, under the effective name stacks."""
    import numpy as np
    lo, hi = chip.window
    n = int((hi - lo) // 1000)
    dev = next(iter(S.effective(chip).devices.values()))
    depth = np.full(n, np.inf)
    xent = np.zeros(n, bool)
    for s, d, _name, st, line, _own in dev:
        if line != "sync":
            continue
        a, b = max(int((s - lo) // 1000), 0), min(int((s + d - lo)
                                                      // 1000), n)
        if b <= a:
            continue
        idx = np.arange(a, b)[depth[a:b] > d]
        depth[idx] = d
        xent[idx] = S.under(st, S.XENT)
    want = xent.sum() * 1000.0
    got = S.scope_ns(chip, lambda st: S.under(st, S.XENT))
    assert got == pytest.approx(want, rel=0.02)


WORK = "jit(f)/fwd_bwd/vmap(jvp())/dot"


def synthetic(ops, window=(0, 10_000)):
    return T.Trace({"window": list(window), "devices": {"/device:TPU:0":
                                                         ops}, "host": []})


def test_unscoped_ops_count_with_the_scoped_op_before_them():
    """An operation with no name stack, or only an argument's, counts
    with the scoped operation before it; one the program names outside
    every scope counts under none."""
    sel = "jit(f)/compress/select/top_k"
    loose = "jit(f)/jit(tril)/ge"
    tr = synthetic([[0, 100, "copy.1", "", "sync"],
                    [100, 100, "sort.2", sel, "sync"],
                    [200, 100, "copy.3", "", "sync"],
                    [300, 100, "reduce.4", "comp_state['v']", "sync"],
                    [400, 100, "fusion.5", WORK, "sync"],
                    [450, 10, "copy-start.6", "", "async"],
                    [500, 100, "reduce-window.7", "", "sync"],
                    [600, 100, "fusion.8", loose, "sync"]])
    eff = [op[3] for op in S.effective(tr).devices["/device:TPU:0"]]
    own = [op[5] for op in S.effective(tr).devices["/device:TPU:0"]]
    assert own == [op[3] for op in sorted(tr.devices["/device:TPU:0"])]
    assert eff == ["", sel, sel, sel, WORK, WORK, WORK, loose]
    assert S.scope_ns(tr, lambda st: S.under(st, S.SELECT)) == 300
    assert S.scope_ns(tr, lambda st: S.under(st, S.FWD_BWD)) == 200
    assert S.scope_ns(tr, lambda st: not S.scoped(st)) == 200


@pytest.mark.parametrize("stack,unnamed", [
    ("", True), ("comp_state['v']", True), ("batch['labels']", True),
    ("jit(step_fn)", False), ("jit(step_fn)/jit(tril)/ge", False),
    ("checkpoint/reduce_sum", False),
    ("jit(step_fn)/compress/select/top_k", False),
])
def test_unnamed(stack, unnamed):
    assert S.unnamed(stack) is unnamed
