"""``BENCHMARK.json`` and the files it names, found by name.

A cell ``<config>.<traffic>`` reads ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` and ``bench/limits/<cell>.json``; a
per-layer metric ``<name>`` is read by ``bench/metrics/<name>.py``; a
configuration's model kind (its ``model.kind``) is described by
``bench/kinds/<kind>.py``: the program's shape it implies, its counts
for :mod:`lgcbench.flops` and its float32 reference.  Adding a
configuration, a traffic mix, a cell, a metric or a model kind therefore
adds files and entries and edits none.

A kind module defines

* ``program_shape(model)``: {dotted ModelConfig attribute: value} that
  the program's config has to hold (:mod:`lgcbench.cell`);
* ``leaf_sizes(model)``, ``matmul_params(model)`` and
  ``other_flops(model, seq_len)`` (:mod:`lgcbench.flops`);
* ``hidden(nm, model, params, tokens)``, one sequence's float32 hidden
  states after the final norm, (S, D), and ``head(model, params)``, the
  LM head as (V, D) (:mod:`lgcbench.reference`);
* optionally ``leaf_init(path, shape, key)``: the published
  initialisation of a leaf the shared rules of :mod:`lgcbench.weights`
  do not cover, or None.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file
    kind: ModuleType        # its model kind, bench/kinds/<kind>.py
    traffic: dict           # the traffic file
    limits: dict            # the limits file ({} where not set yet)
    end_to_end: List[dict]  # the end-to-end metrics this cell reports
    per_layer: List[dict]   # the per-layer metrics this cell reports


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path) -> dict:
    return _read(Path(root) / "BENCHMARK.json")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(root: Path, name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = _read(Path(root) / entry["file"])
    traffic = _read(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits_path = bench_dir / "limits" / f"{name}.json"
    limits = _read(limits_path) if limits_path.exists() else {}
    if traffic["chips"] != w["chips"]:
        raise ValueError(f"{name}: traffic {w['traffic']!r} is for "
                         f"{traffic['chips']} chips, the cell for "
                         f"{w['chips']}")
    return Cell(name, w["chips"], config,
                kind_module(config["model"]["kind"], bench_dir),
                traffic, limits,
                [m for m in bench["end_to_end"] if _reports(m, name)],
                [m for m in bench["per_layer"] if _reports(m, name)])


def _load(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    return _load(bench_dir / "metrics" / f"{name}.py",
                 f"bench_metric_{name}").read


def kind_module(kind: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The model kind ``bench/kinds/<kind>.py``.  A kind without a file
    is an error."""
    path = bench_dir / "kinds" / f"{kind}.py"
    if not path.exists():
        known = sorted(p.stem for p in (bench_dir / "kinds").glob("*.py"))
        raise KeyError(f"no model kind {kind!r} in {bench_dir / 'kinds'}; "
                       f"known: {known}")
    return _load(path, f"bench_kind_{kind}")


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> Dict:
    """The chip's published peaks.  A kind missing from the table is an
    error, never a default."""
    table = _read(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]
