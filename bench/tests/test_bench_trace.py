"""The trace reducer on a 20 ms slice of a trace recorded on a TPU v5e
(cell mamba2-130m.lgc-ps.8x2048, the end of one step and the start of
the next), against a brute-force count on a 1 us grid."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from lgcbench import trace as T  # noqa: E402

DATA = BENCH / "tests/data/trace_mamba2-130m.lgc-ps.8x2048.json.gz"


@pytest.fixture(scope="module")
def tr():
    return T.from_json(str(DATA))


def grid(tr):
    """For every microsecond of the window, the class of the innermost
    synchronous operation running then ("" when idle)."""
    lo, hi = tr.window
    n = int((hi - lo) // 1000)
    depth = np.full(n, -1.0)
    cls = np.full(n, "", dtype=object)
    for s, d, name, stack, line in next(iter(tr.devices.values())):
        if line != "sync":
            continue
        a = max(int((s - lo) // 1000), 0)
        b = min(int((s + d - lo) // 1000), n)
        if b <= a:
            continue
        # nested ops are shorter: the innermost op has the least duration
        span = slice(a, b)
        inner = (depth[span] < 0) | (depth[span] > d)
        idx = np.arange(a, b)[inner]
        depth[idx] = d
        cls[idx] = T.classify(name, stack)
    return cls


def test_busy_and_idle_share(tr):
    cls = grid(tr)
    busy = np.mean(cls != "")
    assert tr.window_s() == pytest.approx(0.02)
    assert tr.busy_s() / tr.window_s() == pytest.approx(busy, abs=0.01)
    # the step boundary leaves the device idle while the host reads the
    # loss (about 3.3 ms of the 20)
    assert 0.1 < 1 - busy < 0.25
    gap = tr.breakdown()["idle_gaps"][0]
    assert gap[0].endswith("_value") and 0.003 < gap[1] < 0.004


@pytest.mark.parametrize("cls", ["fwd_bwd", "post_grad"])
def test_class_times_are_self_times(tr, cls):
    cells = grid(tr)
    want = np.sum(cells == cls) * 1000.0
    assert want > 1e6                            # both classes are present
    assert tr.class_ns(cls) == pytest.approx(want, rel=0.02)


def test_classes_cover_the_busy_time_once(tr):
    total = sum(tr.class_ns(c) for c in ("fwd_bwd", "post_grad",
                                          "collective"))
    assert total / 1e9 == pytest.approx(tr.busy_s(), rel=1e-6)
    assert tr.class_ns("collective") == 0.0     # one chip: no exchange


def test_name_stack_rules():
    assert T.classify("fusion.3", "jit(step_fn)/vmap(transpose(jvp()))/"
                      "while/body/dot_general") == "fwd_bwd"
    assert T.classify("sort.8", "jit(step_fn)/top_k") == "post_grad"
    for name in ("all-reduce.1", "all-gather-start.2", "reduce-scatter.7",
                 "collective-permute-done.4", "all-to-all.9"):
        assert T.classify(name, "jit(step_fn)/vmap(jvp())/x") == \
            "collective"


def test_op_names_from_hlo_text():
    text = ('  %fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
            'calls=%c, metadata={op_name="jit(step_fn)/vmap(jvp())/mul" '
            'source_file="m.py" source_line=3}\n'
            '  ROOT %tuple.1 = (f32[8]{0}) tuple(%fusion.12)\n')
    assert T.op_names(text) == {"fusion.12": "jit(step_fn)/vmap(jvp())/mul",
                                "tuple.1": ""}
    assert T._instr("%sort.8 = (f32[61764]{0}) sort(...)") == "sort.8"
