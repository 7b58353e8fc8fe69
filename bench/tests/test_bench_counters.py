"""The FLOP and least-byte counters against hand counts at the committed
configurations' shapes."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from lgcbench import flops  # noqa: E402


def model(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())[
        "model"]


@pytest.mark.parametrize("name,total,dense,compressed", [
    # mamba2-130m, tied: 24 x 3,765,320 + 768 compressed, 50,288 x 768
    ("mamba2-130m", 128_989_632, 38_621_184, 90_368_448),
    # qwen2-1.5b at 2 layers, tied: 151,936 x 1,536 dense
    ("qwen2-1.5b-d2", 326_970_880, 233_373_696, 93_597_184),
])
def test_gradient_elements(name, total, dense, compressed):
    n = flops.gradient_elements(model(name))
    assert (n["total"], n["dense"], n["compressed"], n["topk_only"]) == \
        (total, dense, compressed, 0)


def test_least_post_grad_bytes():
    m = model("mamba2-130m")
    # 20 B x 90,368,448 + 4 B x 38,621,184 + 22 B x 128,989,632
    assert flops.least_post_grad_bytes(m, "lgc_ps") == 4_799_625_600
    assert flops.least_post_grad_bytes(m, "none") == 22 * 128_989_632
    q = model("qwen2-1.5b-d2")
    assert flops.least_post_grad_bytes(q, "lgc_ps") == \
        20 * 93_597_184 + 4 * 233_373_696 + 22 * 326_970_880


def test_flops_per_token():
    m = flops.flops_per_token(model("mamba2-130m"), 2048)
    # 6 x (24 x (768 x 3,352 + 1,536 x 768) + 768 x 50,288)
    assert m["matmul"] == 6 * (24 * (768 * 3352 + 1536 * 768)
                               + 768 * 50288)
    # 3 x 24 x (2*256*128 + 2*256*24*64 + 4*128*24*64)
    assert m["ssd"] == 3 * 24 * (65536 + 786432 + 786432)
    assert 8.8e8 < m["total"] < 9.0e8
    q = flops.flops_per_token(model("qwen2-1.5b-d2"), 2048)
    per_layer = 1536 * 1536 * 2 + 2 * 1536 * 256 + 3 * 1536 * 8960
    assert q["matmul"] == 6 * (2 * per_layer + 1536 * 151936)
    assert q["attention"] == 12 * 2 * 12 * 128 * 2048
