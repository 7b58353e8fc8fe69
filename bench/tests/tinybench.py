"""A throwaway benchmark at a size a CPU test can hold: a two-layer
Mamba2 or Qwen2 at d_model 64 under a traffic mix derived from the
committed lgc-ps mix, written as files beside a copy of the harness's
data."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

CONFIG = {
    "name": "tiny-mamba", "source": "test", "arch": "mamba2-130m",
    "overrides": {"tie_embeddings": True, "n_layers": 2, "d_model": 64,
                  "vocab_size": 256,
                  "ssm": {"d_state": 16, "head_dim": 16, "chunk_size": 16}},
    "model": {"kind": "mamba2", "d_model": 64, "n_layer": 2,
              "d_intermediate": 0, "vocab_size": 256,
              "tie_embeddings": True, "rms_norm_eps": 1e-5,
              "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4,
                          "expand": 2, "headdim": 16, "ngroups": 1,
                          "chunk_size": 16},
              "dtype": "bfloat16"},
}

QWEN2 = {
    "name": "tiny-qwen2", "source": "test", "arch": "qwen2-1.5b",
    "overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "vocab_size": 256, "rms_norm_eps": 1e-6},
    "model": {"kind": "qwen2", "hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "num_hidden_layers": 2, "vocab_size": 256,
              "rope_theta": 1000000.0, "rms_norm_eps": 1e-6,
              "tie_word_embeddings": True, "dtype": "bfloat16"},
}


def make_root(root: Path, traffic_name: str, traffic_update: dict,
              limits: dict, config: dict = CONFIG) -> str:
    """Write the tiny benchmark under ``root``; returns the cell name."""
    b = root / "bench"
    for d in ("configs", "traffic", "limits"):
        (b / d).mkdir(parents=True)
    for d in ("metrics", "kinds"):
        shutil.copytree(BENCH / d, b / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH / "peaks.json", b / "peaks.json")
    name = config["name"]
    (b / f"configs/{name}.json").write_text(json.dumps(config))
    traffic = json.loads((BENCH / "traffic/lgc-ps.8x2048.json").read_text())
    traffic.update(batch_per_chip=2, seq_len=32, pool=4, sparsity=0.05,
                   **traffic_update)
    (b / f"traffic/{traffic_name}.json").write_text(json.dumps(traffic))
    cell = f"{name}.{traffic_name}"
    (b / f"limits/{cell}.json").write_text(json.dumps(limits))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": name, "source": "test",
                         "file": f"bench/configs/{name}.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": cell, "config": name,
                           "traffic": traffic_name,
                           "chips": traffic["chips"], "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell
