"""The model kinds (``bench/kinds/<kind>.py``): the reference's readings
over one step on tiny seeded configurations of each committed kind stay
those the harness gave before the kinds moved into files of their own,
and no harness module names a kind."""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import tinybench  # noqa: E402
from lgcbench import reference, spec, tokens, weights  # noqa: E402

PINS = json.loads((BENCH / "tests/data/reference_pins.json").read_text())


@pytest.mark.parametrize("config", [tinybench.CONFIG, tinybench.QWEN2],
                         ids=lambda c: c["model"]["kind"])
def test_reference_readings_are_pinned(tmp_path, config):
    from lgcbench import cell as C

    seed = PINS["seed"]
    name = tinybench.make_root(tmp_path, "lgc-ps.tiny", {}, {}, config)
    cell = spec.resolve(tmp_path, name, tmp_path / "bench")
    t, model = cell.traffic, cell.config["model"]
    _, params0, ae0 = C.build_program(cell, seed).init(
        weights.seed_key(seed))
    batches = tokens.batches(model["vocab_size"], t["batch_per_chip"],
                             t["seq_len"], seed, t["pool"])[:1]
    ref = reference.run(reference.F32, model, C.reference_train(t),
                        params0, ae0, batches, t["start_step"],
                        bench_dir=tmp_path / "bench")
    want = PINS["readings"][model["kind"]]
    for k in ("loss", "grad", "change", "ef_u", "ef_v"):
        np.testing.assert_allclose(np.ravel(ref[k]), want[k], rtol=1e-6,
                                   err_msg=k)


def test_no_harness_module_names_a_kind():
    branch = re.compile(r"""\[\s*["']kind["']\s*\]\s*[!=]=""")
    for path in sorted((BENCH / "lgcbench").glob("*.py")):
        assert not branch.search(path.read_text()), path.name
