"""The correctness check at a size a CPU test can hold: a sound run is
correct, and a run whose timed path is broken underneath, or the
float8 control put in the program's place, is not.

The cell is a throwaway two-layer Mamba2 under lgc_ps with the limits
below, set from CPU readings at this size (sound runs read loss gaps
under 1e-4 and embedding gradient gaps under 1e-5; the float8 control
4.7e-4 and more on the loss; half a batch 2.5e-3 and more; a state left
unchanged reads 1 on the change).  At this size each leaf keeps a few
elements, so the change reads up to 0.33 on sound runs."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as R  # noqa: E402
import tinybench  # noqa: E402
from lgcbench import check, reference, spec  # noqa: E402

CELL = "tiny-mamba.lgc-ps.tiny"
SEED = 2
LIMITS = {"loss": 2e-4, "grad": 0.01, "change": 0.9, "ef": 0.015}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tmp_path_factory.mktemp("tinybench")
    assert tinybench.make_root(root, "lgc-ps.tiny", {}, LIMITS) == CELL
    return root


def run_cell(root):
    import jax
    cell = spec.resolve(root, CELL, root / "bench")
    res, lines = R.result(cell, SEED, 0.2, False, jax.devices()[:1],
                          time.time(), root, root / "bench")
    return res


def test_sound_run_is_correct(tiny):
    res = run_cell(tiny)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"tokens_per_s", "step_p90_ms",
                                   "peak_hbm_gb", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(res["device"])


def test_a_step_that_leaves_the_state_unchanged_is_caught(tiny,
                                                          monkeypatch):
    from repro.launch import steps
    from repro.optim.optimizers import Optimizer

    real = steps.build_optimizer

    def frozen(tc, *a, **k):
        opt = real(tc, *a, **k)
        return Optimizer(opt.init, lambda g, s, p, step: (p, s))
    monkeypatch.setattr(steps, "build_optimizer", frozen)
    res = run_cell(tiny)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] > 0.99


def test_half_the_batch_left_out_is_caught(tiny, monkeypatch):
    import jax
    from repro.models.model import Model

    real = Model.loss

    def half(self, params, batch, remat=None):
        n = batch["tokens"].shape[0] // 2
        return real(self, params, jax.tree_util.tree_map(
            lambda x: x[:n], batch), remat=remat)
    monkeypatch.setattr(Model, "loss", half)
    res = run_cell(tiny)
    assert not res["correct"]
    assert res["checks"]["loss_gap"]["value"] > LIMITS["loss"]


def test_the_float8_control_is_not_correct(tiny):
    """The reference in the program's place, computed in float8."""
    from lgcbench import cell as C

    cell = spec.resolve(tiny, CELL, tiny / "bench")
    t = cell.traffic
    r = C.run(cell, SEED, 0.0, False, time.time())
    args = (cell.config["model"], C.reference_train(t), r.params0, r.ae0,
            r.batches, t["start_step"])
    ref = reference.run(reference.F32, *args)
    control = reference.run(reference.fp8_numerics(), *args)
    nums = check.numbers(control, ref)
    assert not check.verdict(nums, LIMITS), nums


def test_the_peak_holds_the_steps_arguments_and_temporaries(tiny):
    """The peak counts the temporaries the compiled step lays out, which
    the runtime's ``peak_bytes_in_use`` leaves out on the TPU."""
    from lgcbench import cell as C

    cell = spec.resolve(tiny, CELL, tiny / "bench")
    r = C.run(cell, SEED, 0.2, False, time.time())
    m = r.memory
    assert m["arguments"] > 0 and m["temporaries"] > 0
    assert r.peak_bytes >= m["arguments"] + m["temporaries"]
    assert r.peak_bytes >= m["peak_in_use"]


class _Layout:
    argument_size_in_bytes = 1000
    output_size_in_bytes = 900
    alias_size_in_bytes = 850
    temp_size_in_bytes = 400


class _Compiled:
    def memory_analysis(self):
        return _Layout()


@pytest.mark.parametrize("live,peak_in_use,peak", [
    (1200, 0, 1200 + 50 + 400),      # live buffers beyond the arguments
    (0, 0, 1000 + 50 + 400),         # a backend that keeps no stats
    (1000, 5000, 5000),              # the runtime saw more
])
def test_step_memory(live, peak_in_use, peak):
    from lgcbench import cell as C

    assert C.step_memory(_Compiled(), live, peak_in_use)["peak"] == peak
