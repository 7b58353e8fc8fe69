"""Qwen2 (arXiv:2407.10671), configured as its ``config.json``.

Each layer: RMSNorm, grouped-query attention with bias on q, k and v and
half-split RoPE, causal softmax, then RMSNorm and a SwiGLU MLP, each
with its residual.  The head size is ``hidden_size`` over the heads and
the q, k and v biases are the architecture's (neither is a key of the
published config).  The LM head is the embedding where
``tie_word_embeddings`` is set.
"""
import math

import jax
import jax.numpy as jnp

from lgcbench.flops import head_leaves
from lgcbench.primitives import einsum, rmsnorm, rope, scan_positions


def program_shape(model):
    H = model["num_attention_heads"]
    return {"d_model": model["hidden_size"],
            "n_layers": model["num_hidden_layers"],
            "vocab_size": model["vocab_size"],
            "tie_embeddings": model["tie_word_embeddings"],
            "rms_norm_eps": model["rms_norm_eps"],
            "n_heads": H, "n_kv_heads": model["num_key_value_heads"],
            "head_dim": model["hidden_size"] // H,
            "d_ff": model["intermediate_size"],
            "rope_theta": model["rope_theta"], "qkv_bias": True}


# ---------------------------------------------------------------------------
# counts (lgcbench.flops)


def _dims(model):
    d = model["hidden_size"]
    H = model["num_attention_heads"]
    return {"d": d, "L": model["num_hidden_layers"],
            "V": model["vocab_size"], "tied": model["tie_word_embeddings"],
            "H": H, "KH": model["num_key_value_heads"], "hd": d // H,
            "ff": model["intermediate_size"]}


def leaf_sizes(model):
    m = _dims(model)
    d, L = m["d"], m["L"]
    H, KH, hd, ff = m["H"], m["KH"], m["hd"], m["ff"]
    per = {"attn_norm": d, "wq": d * H * hd + H * hd,
           "wk": d * KH * hd + KH * hd, "wv": d * KH * hd + KH * hd,
           "wo": H * hd * d, "ffn_norm": d, "w_gate": d * ff,
           "w_up": d * ff, "w_down": ff * d}
    return head_leaves(d, m["V"], m["tied"]) + [
        (f"layers/{k}", L * v, "compressed") for k, v in per.items()]


def matmul_params(model):
    m = _dims(model)
    d = m["d"]
    H, KH, hd, ff = m["H"], m["KH"], m["hd"], m["ff"]
    per = d * H * hd + 2 * d * KH * hd + H * hd * d + 3 * d * ff
    return m["L"] * per + d * m["V"]


def other_flops(model, seq_len):
    """Attention's QK^T and PV (lgcbench.flops)."""
    m = _dims(model)
    return {"attention": 12.0 * m["L"] * m["H"] * m["hd"] * seq_len}


# ---------------------------------------------------------------------------
# the reference (lgcbench.reference)


def layer(nm, m, p, x):
    S = x.shape[0]
    H, KH = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"] // H
    eps = m["rms_norm_eps"]
    a = p["mixer"]
    h = rmsnorm(x, a["norm"]["scale"], eps)
    q = (einsum(nm, "sd,de->se", h, a["wq"]["w"]) + a["wq"]["b"])
    k = (einsum(nm, "sd,de->se", h, a["wk"]["w"]) + a["wk"]["b"])
    v = (einsum(nm, "sd,de->se", h, a["wv"]["w"]) + a["wv"]["b"])
    q = rope(q.reshape(S, H, d), m["rope_theta"])
    k = rope(k.reshape(S, KH, d), m["rope_theta"])
    v = v.reshape(S, KH, d)
    G = H // KH
    q = q.reshape(S, KH, G, d)
    s = einsum(nm, "qhgd,khd->hgqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = einsum(nm, "hgqk,khd->qhgd", pr, v).reshape(S, H * d)
    x = x + einsum(nm, "se,ed->sd", o, a["wo"]["w"])
    f = p["ffn"]
    h = rmsnorm(x, f["norm"]["scale"], eps)
    g = einsum(nm, "sd,df->sf", h, f["w_gate"]["w"])
    u = einsum(nm, "sd,df->sf", h, f["w_up"]["w"])
    return x + einsum(nm, "sf,fd->sd", jax.nn.silu(g) * u,
                      f["w_down"]["w"])


def hidden(nm, m, params, tokens):
    x = nm.quant(params["embed"]["w"][tokens])
    x = scan_positions(nm, params, x,
                       lambda i: lambda p, x: layer(nm, m, p, x))
    return rmsnorm(x, params["final_norm"]["scale"], m["rms_norm_eps"])


def head(m, params):
    return params["embed"]["w"] if m["tie_word_embeddings"] \
        else params["lm_head"]["w"].T
