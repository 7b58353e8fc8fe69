"""Operations and bytes a training step needs, counted from the
configuration's shapes alone, so every implementation is read against
the same work.  The model kind's module (``bench/kinds/<kind>.py``)
gives its parameters (``leaf_sizes``), those that take part in a matrix
product (``matmul_params``) and its other model FLOPs (``other_flops``);
this module sums them.

Model FLOPs per token (the MFU numerator; recomputation does not count):

* 6 x the parameters that take part in a matrix product, forward and
  backward: the projections of every layer and the LM head (tied or
  not); the embedding lookup, norms, biases, the conv and the SSD's
  per-head scalars do not;
* attention, PaLM's convention: 12 * layers * heads * head_dim * seq_len
  (QK^T and PV, forward and backward, the causal half not removed);
* Mamba2's SSD in its chunked form (arXiv:2405.21060, chunk Q, state N,
  heads H of size P), per token and layer forward: 2QN for C B^T inside
  the chunk, 2QHP for applying the masked (Q, Q) kernel to x, 2NHP for
  the chunk states and 2NHP for their output; times 3 for the backward.

Least bytes after the gradient (the ``post_grad_hbm_pct`` numerator):
each compressed or top-k-only element reads its gradient and writes the
aggregated gradient at the parameter dtype and reads and writes ``u`` and
``v`` in float32 (20 B at bfloat16); each dense-exempt element reads and
writes its gradient (4 B); AdamW reads the gradient and the parameter
and reads and writes ``m`` and ``v`` in float32 and writes the
parameter (22 B at bfloat16).
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Tuple

from lgcbench.spec import BENCH_DIR, kind_module


def head_leaves(d: int, V: int, tied: bool) -> List[Tuple[str, int, str]]:
    """The embedding, the final norm and an untied LM head, as a kind's
    ``leaf_sizes`` lists them: (what, elements, role), role as the LGC
    layout assigns it: "dense" (the embedding), "topk_only" (an untied LM
    head) or "compressed"."""
    out = [("embed", V * d, "dense"), ("final_norm", d, "compressed")]
    if not tied:
        out.append(("lm_head", d * V, "topk_only"))
    return out


def gradient_elements(model: dict,
                      bench_dir: Path = BENCH_DIR) -> Dict[str, int]:
    sizes = kind_module(model["kind"], bench_dir).leaf_sizes(model)
    out = {"total": sum(n for _, n, _ in sizes)}
    for role in ("dense", "topk_only", "compressed"):
        out[role] = sum(n for _, n, r in sizes if r == role)
    return out


def flops_per_token(model: dict, seq_len: int,
                    bench_dir: Path = BENCH_DIR) -> Dict[str, float]:
    """Model FLOPs per trained token, by term, and their sum."""
    kind = kind_module(model["kind"], bench_dir)
    terms = {"matmul": 6.0 * kind.matmul_params(model),
             **kind.other_flops(model, seq_len)}
    terms["total"] = sum(terms.values())
    return terms


DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def least_post_grad_bytes(model: dict, method: str,
                          bench_dir: Path = BENCH_DIR) -> float:
    """Least HBM bytes per node per step between the gradient and the
    updated parameters (see the module docstring)."""
    p = DTYPE_BYTES[model["dtype"]]
    n = gradient_elements(model, bench_dir)
    adamw = (2 * p + 16 + p) * n["total"]
    if method == "none":
        return float(adamw)
    sent = n["compressed"] + n["topk_only"]
    return float((2 * p + 16) * sent + 2 * p * n["dense"] + adamw)
