"""Operations and bytes a training step needs, counted from the
configuration's shapes alone, so every implementation is read against
the same work.

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

from typing import Dict, List, Tuple


def _dims(model: dict) -> dict:
    if model["kind"] == "mamba2":
        s = model["ssm_cfg"]
        d = model["d_model"]
        di = s["expand"] * d
        return {"d": d, "L": model["n_layer"], "V": model["vocab_size"],
                "tied": model["tie_embeddings"], "di": di,
                "N": s["d_state"] * s["ngroups"], "H": di // s["headdim"],
                "P": s["headdim"], "K": s["d_conv"], "Q": s["chunk_size"]}
    d = model["hidden_size"]
    H = model["num_attention_heads"]
    return {"d": d, "L": model["num_hidden_layers"],
            "V": model["vocab_size"], "tied": model["tie_word_embeddings"],
            "H": H, "KH": model["num_key_value_heads"], "hd": d // H,
            "ff": model["intermediate_size"]}


def leaf_sizes(model: dict) -> List[Tuple[str, int, str]]:
    """(what, elements, role) for every parameter, role as the LGC
    layout assigns it: "dense" (the embedding), "topk_only" (an untied LM
    head) or "compressed"."""
    m = _dims(model)
    d, L, V = m["d"], m["L"], m["V"]
    out = [("embed", V * d, "dense"), ("final_norm", d, "compressed")]
    if not m["tied"]:
        out.append(("lm_head", d * V, "topk_only"))
    if model["kind"] == "mamba2":
        di, N, H, K = m["di"], m["N"], m["H"], m["K"]
        per = {"norm": d, "in_proj": d * (2 * di + 2 * N + H),
               "conv_w": K * (di + 2 * N), "conv_b": di + 2 * N,
               "A_log": H, "dt_bias": H, "D": H, "out_norm": di,
               "out_proj": di * d}
    else:
        H, KH, hd, ff = m["H"], m["KH"], m["hd"], m["ff"]
        per = {"attn_norm": d, "wq": d * H * hd + H * hd,
               "wk": d * KH * hd + KH * hd, "wv": d * KH * hd + KH * hd,
               "wo": H * hd * d, "ffn_norm": d, "w_gate": d * ff,
               "w_up": d * ff, "w_down": ff * d}
    out += [(f"layers/{k}", L * v, "compressed") for k, v in per.items()]
    return out


def gradient_elements(model: dict) -> Dict[str, int]:
    sizes = leaf_sizes(model)
    out = {"total": sum(n for _, n, _ in sizes)}
    for role in ("dense", "topk_only", "compressed"):
        out[role] = sum(n for _, n, r in sizes if r == role)
    return out


def matmul_params(model: dict) -> int:
    m = _dims(model)
    d, L, V = m["d"], m["L"], m["V"]
    if model["kind"] == "mamba2":
        di, N, H = m["di"], m["N"], m["H"]
        per = d * (2 * di + 2 * N + H) + di * d
    else:
        H, KH, hd, ff = m["H"], m["KH"], m["hd"], m["ff"]
        per = d * H * hd + 2 * d * KH * hd + H * hd * d + 3 * d * ff
    return L * per + d * V


def flops_per_token(model: dict, seq_len: int) -> Dict[str, float]:
    """Model FLOPs per trained token, by term, and their sum."""
    m = _dims(model)
    terms = {"matmul": 6.0 * matmul_params(model)}
    if model["kind"] == "mamba2":
        Q, N, H, P = min(m["Q"], seq_len), m["N"], m["H"], m["P"]
        terms["ssd"] = 3.0 * m["L"] * (2 * Q * N + 2 * Q * H * P
                                       + 4 * N * H * P)
    else:
        terms["attention"] = 12.0 * m["L"] * m["H"] * m["hd"] * seq_len
    terms["total"] = sum(terms.values())
    return terms


DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def least_post_grad_bytes(model: dict, method: str) -> float:
    """Least HBM bytes per node per step between the gradient and the
    updated parameters (see the module docstring)."""
    p = DTYPE_BYTES[model["dtype"]]
    n = gradient_elements(model)
    adamw = (2 * p + 16 + p) * n["total"]
    if method == "none":
        return float(adamw)
    sent = n["compressed"] + n["topk_only"]
    return float((2 * p + 16) * sent + 2 * p * n["dense"] + adamw)
