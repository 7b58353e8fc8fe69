"""BENCHMARK.json and the files it names: every entry resolves by name,
keeps to the contract's shapes, and a new cell, traffic mix, metric or
model kind is added by adding files alone."""
from __future__ import annotations

import json
import re
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import tinybench  # noqa: E402
from lgcbench import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
KIND_FUNCTIONS = ("program_shape", "leaf_sizes", "matmul_params",
                  "other_flops", "hidden", "head")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:2] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in bench[k]}) == len(bench[k])
    metrics = [m["name"] for k in ("end_to_end", "per_layer")
               for m in bench[k]]
    assert len(set(metrics)) == len(metrics)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)


@pytest.mark.parametrize("kind", ["configs", "workloads", "per_layer"])
def test_every_entry_resolves(bench, kind):
    for entry in bench[kind]:
        if kind == "configs":
            cfg = json.loads((ROOT / entry["file"]).read_text())
            assert cfg["name"] == entry["name"]
            assert entry["file"].startswith("bench/")
            module = spec.kind_module(cfg["model"]["kind"])
            for f in KIND_FUNCTIONS:
                assert callable(getattr(module, f)), f
        elif kind == "workloads":
            cell = spec.resolve(ROOT, entry["name"])
            assert cell.chips == entry["chips"]
            assert cell.limits, f"no limits for {entry['name']}"
            assert set(cell.limits) >= {"loss", "grad", "change"}
            assert any(m["name"] != "setup_s" for m in cell.end_to_end)
            assert cell.per_layer
        else:
            assert callable(spec.metric_reader(entry["name"]))


def test_peaks_refuse_an_unknown_device():
    assert spec.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")


def test_a_new_cell_needs_new_files_only(tmp_path, bench):
    """A throwaway configuration, traffic mix, metric and cell, added as
    files and entries beside copies of the committed ones."""
    root = tmp_path
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    b = json.loads(json.dumps(bench))
    cfg = json.loads((ROOT / "bench/configs/mamba2-130m.json").read_text())
    cfg["name"] = "throwaway-cfg"
    (root / "bench/configs/throwaway-cfg.json").write_text(json.dumps(cfg))
    traffic = json.loads(
        (ROOT / "bench/traffic/lgc-ps.8x2048.json").read_text())
    traffic["seq_len"] = 4096
    (root / "bench/traffic/throwaway-mix.json").write_text(
        json.dumps(traffic))
    (root / "bench/limits/throwaway-cfg.throwaway-mix.json").write_text(
        json.dumps({"loss": 1.0, "grad": 1.0, "change": 1.0, "ef": 1.0}))
    (root / "bench/metrics/throwaway_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b["configs"].append({"name": "throwaway-cfg", "source": "x",
                         "file": "bench/configs/throwaway-cfg.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "throwaway-cfg.throwaway-mix",
                           "config": "throwaway-cfg",
                           "traffic": "throwaway-mix", "chips": 1,
                           "why": "test"})
    b["per_layer"].append({"name": "throwaway_metric", "unit": "%",
                           "better": "higher", "source": "device_trace",
                           "layer": "device", "moves": "tokens_per_s",
                           "workloads": ["throwaway-cfg.throwaway-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.resolve(root, "throwaway-cfg.throwaway-mix",
                        root / "bench")
    assert cell.traffic["seq_len"] == 4096
    assert cell.config["name"] == "throwaway-cfg"
    assert [m["name"] for m in cell.per_layer][-1] == "throwaway_metric"
    assert spec.metric_reader("throwaway_metric", root / "bench")({}) == 42
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} had to change"


# A throwaway kind: Mamba2 layers in a superblock of two positions, its
# pattern given as a string of layer letters, as hybrid configs give it.
PAIR_KIND = '''
from pathlib import Path

from lgcbench import spec
from lgcbench.primitives import rmsnorm, scan_positions

mamba2 = spec.kind_module("mamba2", Path(__file__).resolve().parents[1])
leaf_sizes = mamba2.leaf_sizes
matmul_params = mamba2.matmul_params
other_flops = mamba2.other_flops
head = mamba2.head
LAYERS = {"M": "mamba"}


def program_shape(model):
    return {**mamba2.program_shape(model),
            "block_pattern": tuple(LAYERS[c] for c in model["pattern"])}


def hidden(nm, model, params, tokens):
    def layer_of(i):
        assert model["pattern"][i] == "M"
        return lambda p, x: mamba2.layer(nm, model, p["mixer"], x)
    x = nm.quant(params["embed"]["w"][tokens])
    x = scan_positions(nm, params, x, layer_of)
    return rmsnorm(x, params["final_norm"]["scale"], model["rms_norm_eps"])
'''
# Limits from CPU readings at this size, seeds 1-12: sound runs read loss
# gaps up to 1.2e-4 (the float8 control 2.7e-4 and more), gradient gaps up
# to 3.2e-3 and change gaps up to 0.0073.  Split in two positions, a leaf
# such as ``D`` keeps 8 elements and one top-k pick, so one pick moved by
# rounding reads an error-feedback gap up to 0.21 (seed 1); the tiny
# lgc_ps cell's 0.015 (test_bench_correct.py) is for leaves twice as long.
PAIR_LIMITS = {"loss": 2e-4, "grad": 0.01, "change": 0.9, "ef": 0.5}


def test_a_new_kind_needs_new_files_only(tmp_path, bench):
    """A throwaway model kind with a two-position superblock, its
    configuration and its cell, added as files and entries beside copies
    of the committed ones: the program's config is checked against it,
    flops counts it, and a tiny sound run is correct."""
    import jax

    import run as R
    from lgcbench import cell as C
    from lgcbench import flops

    root = tmp_path
    b = root / "bench"
    shutil.copytree(BENCH, b,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    (b / "kinds/mamba2-pair.py").write_text(PAIR_KIND)
    cfg = json.loads(json.dumps(tinybench.CONFIG))
    cfg["name"] = "tiny-pair"
    cfg["overrides"]["block_pattern"] = ["mamba", "mamba"]
    cfg["model"].update(kind="mamba2-pair", pattern="MM")
    (b / "configs/tiny-pair.json").write_text(json.dumps(cfg))
    traffic = json.loads((b / "traffic/lgc-ps.8x2048.json").read_text())
    traffic.update(batch_per_chip=2, seq_len=32, pool=4, sparsity=0.05)
    (b / "traffic/lgc-ps.tiny.json").write_text(json.dumps(traffic))
    name = "tiny-pair.lgc-ps.tiny"
    (b / f"limits/{name}.json").write_text(json.dumps(PAIR_LIMITS))
    bj = json.loads(json.dumps(bench))
    bj["configs"].append({"name": "tiny-pair", "source": "test",
                          "file": "bench/configs/tiny-pair.json",
                          "reduced": [], "why": "test"})
    bj["workloads"].append({"name": name, "config": "tiny-pair",
                            "traffic": "lgc-ps.tiny", "chips": 1,
                            "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bj))

    cell = spec.resolve(root, name, b)
    model = cell.config["model"]
    prog = C.build_program(cell, 5)          # checks the program's config
    n = flops.gradient_elements(model, b)
    assert n["total"] == sum(leaf.size for leaf in prog.leaves)
    assert flops.flops_per_token(model, 32, b)["ssd"] > 0
    from repro.configs import get_arch
    cfg_prog = C._override(get_arch("mamba2-130m"), cfg["overrides"])
    assert cfg_prog.block_pattern == ("mamba", "mamba")
    with pytest.raises(ValueError, match="block_pattern"):
        C._check_shape(cfg_prog, cell.kind, {**model, "pattern": "M"})

    res, _ = R.result(cell, 5, 0.2, False, jax.devices()[:1], time.time(),
                      root, b)
    assert res["correct"], res["checks"]
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} had to change"


@pytest.mark.parametrize("numerics", ["f32", "fp8"])
def test_scan_positions_applies_the_positions_in_turn(numerics):
    """Two positions over three blocks: the scan equals the layers
    applied one after the other, block by block, the residual stream
    stored at the numerics' precision after each."""
    import jax
    import jax.numpy as jnp
    from lgcbench import primitives

    nm = primitives.F32 if numerics == "f32" else \
        primitives.fp8_numerics()
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {"blocks": {
        "p0": {"w": jax.random.normal(k[0], (3, 8, 8)) / 3},
        "p1": {"w": jax.random.normal(k[1], (3, 8, 8)) / 3}}}
    layers = [lambda p, x: jnp.tanh(x @ p["w"]),
              lambda p, x: x + jnp.sin(x @ p["w"])]
    x = jax.random.normal(k[2], (5, 8))
    got = primitives.scan_positions(nm, params, x, lambda i: layers[i])
    for blk in range(3):
        for i, layer in enumerate(layers):
            p = jax.tree_util.tree_map(lambda a: a[blk],
                                       params["blocks"][f"p{i}"])
            x = nm.quant(layer(p, x))
    np.testing.assert_allclose(got, x, rtol=1e-6, atol=1e-7)
