"""Mamba2 (arXiv:2405.21060), configured as mamba_ssm's ``config.json``.

Each layer: RMSNorm, ``in_proj`` to ``[z, x, B, C, dt]``, causal
depthwise conv with SiLU, the SSD scan in the paper's chunked "minimal"
form with a stable segment sum, the ``D`` skip, RMSNorm gated by
``silu(z)``, ``out_proj`` and the residual.  One group (``ngroups`` 1),
as configured, and no MLP (``d_intermediate`` 0, as published).  The LM
head is the embedding where ``tie_embeddings`` is set.
"""
import jax
import jax.numpy as jnp

from lgcbench.flops import head_leaves
from lgcbench.primitives import einsum, rmsnorm, scan_positions, ssd


def program_shape(model):
    s = model["ssm_cfg"]
    return {"d_model": model["d_model"], "n_layers": model["n_layer"],
            "vocab_size": model["vocab_size"],
            "tie_embeddings": model["tie_embeddings"],
            "rms_norm_eps": model["rms_norm_eps"],
            "ssm.d_state": s["d_state"], "ssm.d_conv": s["d_conv"],
            "ssm.expand": s["expand"], "ssm.head_dim": s["headdim"],
            "ssm.chunk_size": s["chunk_size"], "d_ff": 0}


# ---------------------------------------------------------------------------
# counts (lgcbench.flops)


def _dims(model):
    s = model["ssm_cfg"]
    d = model["d_model"]
    di = s["expand"] * d
    return {"d": d, "L": model["n_layer"], "V": model["vocab_size"],
            "tied": model["tie_embeddings"], "di": di,
            "N": s["d_state"] * s["ngroups"], "H": di // s["headdim"],
            "P": s["headdim"], "K": s["d_conv"], "Q": s["chunk_size"]}


def leaf_sizes(model):
    m = _dims(model)
    d, L = m["d"], m["L"]
    di, N, H, K = m["di"], m["N"], m["H"], m["K"]
    per = {"norm": d, "in_proj": d * (2 * di + 2 * N + H),
           "conv_w": K * (di + 2 * N), "conv_b": di + 2 * N,
           "A_log": H, "dt_bias": H, "D": H, "out_norm": di,
           "out_proj": di * d}
    return head_leaves(d, m["V"], m["tied"]) + [
        (f"layers/{k}", L * v, "compressed") for k, v in per.items()]


def matmul_params(model):
    m = _dims(model)
    d, di, N, H = m["d"], m["di"], m["N"], m["H"]
    per = d * (2 * di + 2 * N + H) + di * d
    return m["L"] * per + d * m["V"]


def other_flops(model, seq_len):
    """The SSD's chunked scan (lgcbench.flops)."""
    m = _dims(model)
    Q, N, H, P = min(m["Q"], seq_len), m["N"], m["H"], m["P"]
    return {"ssd": 3.0 * m["L"] * (2 * Q * N + 2 * Q * H * P
                                   + 4 * N * H * P)}


# ---------------------------------------------------------------------------
# the reference (lgcbench.reference)


def layer(nm, m, p, x):
    D = m["d_model"]
    ssm = m["ssm_cfg"]
    N, P, K = ssm["d_state"], ssm["headdim"], ssm["d_conv"]
    d_inner = ssm["expand"] * D
    H = d_inner // P
    S = x.shape[0]
    h = rmsnorm(x, p["norm"]["scale"], m["rms_norm_eps"])
    zxbcdt = einsum(nm, "sd,de->se", h, p["in_proj"]["w"])
    z = zxbcdt[:, :d_inner]
    xBC = zxbcdt[:, d_inner:2 * d_inner + 2 * N]
    dt = zxbcdt[:, 2 * d_inner + 2 * N:]
    xp = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1])), xBC], axis=0)
    conv = sum(xp[i:i + S] * p["conv_w"][i] for i in range(K))
    xBC = jax.nn.silu(conv + p["conv_b"])
    xs = xBC[:, :d_inner].reshape(S, H, P)
    Bm = xBC[:, d_inner:d_inner + N]
    Cm = xBC[:, d_inner + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = ssd(nm, xs, dt, A, Bm, Cm, ssm["chunk_size"])
    y = y + xs * p["D"][None, :, None]
    y = y.reshape(S, d_inner) * jax.nn.silu(z)
    y = rmsnorm(y, p["out_norm"]["scale"], m["rms_norm_eps"])
    return x + einsum(nm, "se,ed->sd", y, p["out_proj"]["w"])


def hidden(nm, m, params, tokens):
    x = nm.quant(params["embed"]["w"][tokens])
    x = scan_positions(nm, params, x,
                       lambda i: lambda p, x: layer(nm, m, p["mixer"], x))
    return rmsnorm(x, params["final_norm"]["scale"], m["rms_norm_eps"])


def head(m, params):
    return params["embed"]["w"] if m["tie_embeddings"] \
        else params["lm_head"]["w"].T
