"""BENCHMARK.json and the files it names: every entry resolves by name,
keeps to the contract's shapes, and a new cell, traffic mix or metric is
added by adding files alone."""
from __future__ import annotations

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from lgcbench import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
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
