"""Model building blocks shared by all ten assigned architectures.

Everything is functional: ``init_*`` builds a parameter PyTree from a PRNG
key (usable under ``jax.eval_shape`` for the allocation-free dry-run), and
the matching ``apply`` function consumes it.

Attention is computed block-wise (outer scan over query chunks, inner scan
over KV chunks, online softmax) so the peak activation footprint is
O(S·chunk) instead of O(S²) — required for the 32k prefill shape to fit a
v5e's 16 GB HBM without a handwritten kernel.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

# ---------------------------------------------------------------------------
# init helpers


def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    # fan-in is the contracted (second-to-last) dim, also for stacked
    # (E, d_in, d_out) expert weights
    fan_in = shape[-2] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_linear(key, d_in, d_out, dtype, bias=False, scale=None):
    p = {"w": _dense_init(key, (d_in, d_out), dtype, scale)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------------------------------------------------------------------
# norms


def init_rmsnorm(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                       # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (...,S,hd/2)
    cos = jnp.cos(angles)[..., :, None, :]                    # (...,S,1,hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# blockwise attention (pure-JAX flash-style)


def _attn_chunk_sizes(q_len: int, kv_len: int) -> Tuple[int, int]:
    cq = min(q_len, 512)
    ck = min(kv_len, 1024)
    # chunk sizes must divide lengths; shrink until they do
    while q_len % cq:
        cq //= 2
    while kv_len % ck:
        ck //= 2
    return max(cq, 1), max(ck, 1)


def blockwise_attention(q, k, v, *, causal: bool, window: int = 0,
                        q_positions=None, kv_positions=None):
    """Online-softmax attention, tiled over both query and KV chunks.

    q: (B, Sq, H, D); k/v: (B, Sk, KH, D) with H % KH == 0 (GQA).
    window > 0 enables sliding-window masking (j in (i-window, i]).
    Positions default to arange; pass explicit positions for decode.
    Returns (B, Sq, H, D).
    """
    B, Sq, H, D = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    Dv = v.shape[-1]                     # may differ from D (e.g. MLA)
    G = H // KH
    scale = 1.0 / np.sqrt(D)
    if q_positions is None:
        q_positions = jnp.arange(Sq)
    if kv_positions is None:
        kv_positions = jnp.arange(Sk)

    cq, ck = _attn_chunk_sizes(Sq, Sk)
    nq, nk = Sq // cq, Sk // ck

    # (nq, B, cq, KH, G, D)
    qc = q.reshape(B, nq, cq, KH, G, D).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, ck, KH, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, ck, KH, Dv).transpose(1, 0, 2, 3, 4)
    qpos = q_positions.reshape(nq, cq)
    kpos = kv_positions.reshape(nk, ck)

    def q_block(carry, qi):
        qb, qp = qi                                   # (B,cq,KH,G,D), (cq,)

        def kv_block(acc, ki):
            kb, vb, kp = ki
            m, l, o = acc
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window:
                mask &= qp[:, None] - kp[None, :] < window
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            # guard fully-masked rows
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
            corr = jnp.where(jnp.isfinite(m), corr, 0.0)
            l_new = l * corr + p.sum(-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, KH, G, cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KH, G, cq), jnp.float32)
        o0 = jnp.zeros((B, KH, G, cq, Dv), jnp.float32)
        (m, l, o), _ = jax.lax.scan(kv_block, (m0, l0, o0), (kc, vc, kpos))
        o = o / jnp.maximum(l, 1e-20)[..., None]
        # (B,KH,G,cq,Dv) -> (B,cq,KH*G,Dv)
        o = o.transpose(0, 3, 1, 2, 4).reshape(B, cq, H, Dv)
        return carry, o.astype(q.dtype)

    _, out = jax.lax.scan(q_block, None, (qc, qpos))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, Dv)


def decode_attention(q, k_cache, v_cache, *, kv_positions, pos, window: int = 0):
    """Single-token attention against a (possibly only partially valid) cache.

    q: (B, 1, H, D); caches: (B, S, KH, D); kv_positions: (S,) absolute
    positions held by each cache slot; pos: scalar current position.
    Slots with kv_positions > pos (unwritten/ring-overwritten) are masked.
    """
    B, _, H, D = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    scale = 1.0 / np.sqrt(D)
    qr = q.reshape(B, KH, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qr, k_cache,
                   preferred_element_type=jnp.float32) * scale
    valid = kv_positions <= pos
    if window:
        valid &= pos - kv_positions < window
    s = jnp.where(valid[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA self-attention layer


def init_attention(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 4)
    D, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "norm": init_rmsnorm(D, dtype),
        "wq": init_linear(ks[0], D, H * hd, dtype, bias=cfg.qkv_bias),
        "wk": init_linear(ks[1], D, KH * hd, dtype, bias=cfg.qkv_bias),
        "wv": init_linear(ks[2], D, KH * hd, dtype, bias=cfg.qkv_bias),
        "wo": init_linear(ks[3], H * hd, D, dtype),
    }


def attention_fwd(p, cfg: ModelConfig, x, positions):
    """Training/prefill self-attention. x: (B,S,D).

    Uses the flash custom-VJP path (recompute-in-backward): jax's scan VJP
    through the plain blockwise attention stacks every KV chunk's
    probability matrix, which dominated train-step temp memory (§Perf
    iteration 1 in EXPERIMENTS.md)."""
    from repro.models.flash import flash_attention
    B, S, _ = x.shape
    h = rmsnorm(p["norm"], x, cfg.rms_norm_eps)
    q = linear(p["wq"], h).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = linear(p["wk"], h).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = linear(p["wv"], h).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = flash_attention(q, k, v, True, cfg.sliding_window)
    return x + linear(p["wo"], o.reshape(B, S, -1)), (k, v)


def attention_decode(p, cfg: ModelConfig, x, cache, pos):
    """x: (B,1,D); cache: {"k","v": (B,S,KH,hd), "pos": (S,) abs positions}."""
    B = x.shape[0]
    h = rmsnorm(p["norm"], x, cfg.rms_norm_eps)
    q = linear(p["wq"], h).reshape(B, 1, cfg.n_heads, cfg.head_dim)
    k = linear(p["wk"], h).reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
    v = linear(p["wv"], h).reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
    posv = jnp.full((1,), pos, jnp.int32)
    q = apply_rope(q, posv, cfg.rope_theta)
    k = apply_rope(k, posv, cfg.rope_theta)
    S = cache["k"].shape[1]
    slot = pos % S if cfg.sliding_window else pos      # ring buffer if windowed
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    kv_pos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], posv, slot, axis=0)
    o = decode_attention(q, k_cache, v_cache, kv_positions=kv_pos, pos=pos,
                         window=cfg.sliding_window)
    new_cache = {"k": k_cache, "v": v_cache, "pos": kv_pos}
    return x + linear(p["wo"], o.reshape(B, 1, -1)), new_cache


def init_attention_cache(cfg: ModelConfig, batch, seq_len, dtype):
    S = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    return {
        "k": jnp.zeros((batch, S, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, S, cfg.n_kv_heads, cfg.head_dim), dtype),
        # int32 max = "not yet written" so masking treats slots as invalid
        "pos": jnp.full((S,), jnp.iinfo(jnp.int32).max, jnp.int32),
    }


# ---------------------------------------------------------------------------
# cross-attention layer (VLM): queries from text, KV from patch embeddings


def init_cross_attention(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 5)
    D, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "norm": init_rmsnorm(D, dtype),
        "wq": init_linear(ks[0], D, H * hd, dtype),
        "wk": init_linear(ks[1], cfg.encoder_dim, KH * hd, dtype),
        "wv": init_linear(ks[2], cfg.encoder_dim, KH * hd, dtype),
        "wo": init_linear(ks[3], H * hd, D, dtype),
        "gate": jnp.zeros((1,), dtype),      # llama-vision style tanh gate
    }


def cross_attention_kv(p, cfg: ModelConfig, enc):
    """enc: (B, T, enc_dim) -> k, v (B, T, KH, hd). Computed once per image."""
    B, T, _ = enc.shape
    k = linear(p["wk"], enc).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = linear(p["wv"], enc).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    return k, v


def cross_attention_fwd(p, cfg: ModelConfig, x, enc_kv):
    B, S, _ = x.shape
    k, v = enc_kv
    from repro.models.flash import flash_attention
    h = rmsnorm(p["norm"], x, cfg.rms_norm_eps)
    q = linear(p["wq"], h).reshape(B, S, cfg.n_heads, cfg.head_dim)
    o = flash_attention(q, k, v, False, 0)
    gate = jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype)
    return x + gate * linear(p["wo"], o.reshape(B, S, -1))


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V3 multi-head latent attention


def init_mla(key, cfg: ModelConfig, dtype):
    m: MLAConfig = cfg.mla
    ks = jax.random.split(key, 7)
    D, H = cfg.d_model, cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "norm": init_rmsnorm(D, dtype),
        "wq_a": init_linear(ks[0], D, m.q_lora_rank, dtype),
        "q_norm": init_rmsnorm(m.q_lora_rank, dtype),
        "wq_b": init_linear(ks[1], m.q_lora_rank, H * qk_dim, dtype),
        "wkv_a": init_linear(ks[2], D, m.kv_lora_rank + m.qk_rope_head_dim,
                             dtype),
        "kv_norm": init_rmsnorm(m.kv_lora_rank, dtype),
        "wkv_b": init_linear(ks[3], m.kv_lora_rank,
                             H * (m.qk_nope_head_dim + m.v_head_dim), dtype),
        "wo": init_linear(ks[4], H * m.v_head_dim, D, dtype),
    }


def _mla_qkv(p, cfg: ModelConfig, h, positions):
    """Shared q/k/v construction. h: (B,S,D) normed input."""
    m: MLAConfig = cfg.mla
    B, S, _ = h.shape
    H = cfg.n_heads
    q = linear(p["wq_b"], rmsnorm(p["q_norm"], linear(p["wq_a"], h),
                                  cfg.rms_norm_eps))
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = linear(p["wkv_a"], h)
    c_kv, k_rope = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(p["kv_norm"], c_kv, cfg.rms_norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope            # k_rope: (B,S,1,rope_dim)


def _mla_expand_kv(p, cfg: ModelConfig, c_kv, k_rope):
    """Expand latent cache to per-head K/V."""
    m: MLAConfig = cfg.mla
    B, S, _ = c_kv.shape
    H = cfg.n_heads
    kv = linear(p["wkv_b"], c_kv).reshape(
        B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, m.qk_rope_head_dim))],
        axis=-1)
    return k, v


def mla_fwd(p, cfg: ModelConfig, x, positions):
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape
    h = rmsnorm(p["norm"], x, cfg.rms_norm_eps)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, cfg, h, positions)
    k, v = _mla_expand_kv(p, cfg, c_kv, k_rope)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    from repro.models.flash import flash_attention
    o = flash_attention(q, k, v, True, 0)
    return x + linear(p["wo"], o.reshape(B, S, -1)), (c_kv, k_rope[:, :, 0, :])


def mla_decode(p, cfg: ModelConfig, x, cache, pos):
    """Latent-cache decode: cache holds c_kv (B,S,r) + k_rope (B,S,rope_dim).

    The expansion ``wkv_b`` is applied to the *whole* latent cache each step.
    This is the "naive" MLA decode; the absorbed-matmul variant (fold wkv_b
    into the query/output projections so attention runs directly in latent
    space) is the perf-iteration target recorded in EXPERIMENTS.md §Perf.
    """
    m: MLAConfig = cfg.mla
    B = x.shape[0]
    h = rmsnorm(p["norm"], x, cfg.rms_norm_eps)
    posv = jnp.full((1,), pos, jnp.int32)
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(p, cfg, h, posv)
    c_kv = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv_new,
                                               pos, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope_new[:, :, 0, :], pos, axis=1)
    kv_pos = jax.lax.dynamic_update_slice_in_dim(cache["pos"], posv, pos,
                                                 axis=0)
    # absorbed attention: q_nope lifted into latent space via wkv_b^K, and
    # attention output computed in latent space then lifted via wkv_b^V.
    H = cfg.n_heads
    wkv_b = p["wkv_b"]["w"].reshape(m.kv_lora_rank, H,
                                    m.qk_nope_head_dim + m.v_head_dim)
    wk_b = wkv_b[..., :m.qk_nope_head_dim]           # (r, H, nope)
    wv_b = wkv_b[..., m.qk_nope_head_dim:]           # (r, H, v)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, wk_b)    # (B,1,H,r)
    scale = 1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    s = (jnp.einsum("bqhr,bkr->bhk", q_lat, c_kv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhe,bke->bhk", q_rope, k_rope,
                      preferred_element_type=jnp.float32)) * scale
    valid = kv_pos <= pos
    s = jnp.where(valid[None, None], s, -jnp.inf)
    pattn = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhk,bkr->bhr", pattn, c_kv.astype(jnp.float32))
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(x.dtype), wv_b)
    new_cache = {"c_kv": c_kv, "k_rope": k_rope, "pos": kv_pos}
    return x + linear(p["wo"], o.reshape(B, 1, -1)), new_cache


def init_mla_cache(cfg: ModelConfig, batch, seq_len, dtype):
    m: MLAConfig = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, seq_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, seq_len, m.qk_rope_head_dim), dtype),
        "pos": jnp.full((seq_len,), jnp.iinfo(jnp.int32).max, jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLPs


def init_swiglu(key, d_model, d_ff, dtype):
    ks = jax.random.split(key, 3)
    return {
        "norm": init_rmsnorm(d_model, dtype),
        "w_gate": init_linear(ks[0], d_model, d_ff, dtype),
        "w_up": init_linear(ks[1], d_model, d_ff, dtype),
        "w_down": init_linear(ks[2], d_ff, d_model, dtype),
    }


def swiglu_fwd(p, x, eps=1e-5, residual=True):
    h = rmsnorm(p["norm"], x, eps)
    y = linear(p["w_down"],
               jax.nn.silu(linear(p["w_gate"], h)) * linear(p["w_up"], h))
    return x + y if residual else y


# ---------------------------------------------------------------------------
# MoE (token-choice top-k routing, per-expert capacity via top-C selection)


def init_moe(key, cfg: ModelConfig, dtype):
    mo: MoEConfig = cfg.moe
    ks = jax.random.split(key, 5)
    D, E, F = cfg.d_model, mo.num_experts, mo.d_ff_expert
    p = {
        "norm": init_rmsnorm(D, dtype),
        "router": init_linear(ks[0], D, E, jnp.float32),
        "w_gate": _dense_init(ks[1], (E, D, F), dtype),
        "w_up": _dense_init(ks[2], (E, D, F), dtype),
        "w_down": _dense_init(ks[3], (E, F, D), dtype),
    }
    sub = jax.random.split(ks[4], 2)
    if mo.num_shared_experts:
        p["shared"] = init_swiglu(sub[0], D, F * mo.num_shared_experts, dtype)
    if mo.dense_residual_d_ff:
        p["dense_residual"] = init_swiglu(sub[1], D, mo.dense_residual_d_ff,
                                          dtype)
    return p


MOE_DISPATCH_GROUPS = 32   # aligns with the production dp width (pod*data)


def _constrain(x, *spec):
    """Best-effort sharding hint: apply with_sharding_constraint using only
    mesh axes that exist AND are Auto in the current (abstract) mesh; a
    no-op under plain CPU tests or for axes that are Manual (shard_map)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    try:
        type_of = dict(zip(mesh.axis_names, mesh.axis_types))
    except Exception:
        return x
    auto = {n for n, t in type_of.items()
            if str(t).lower().endswith("auto")}
    clean = []
    for s in spec:
        if s is None:
            clean.append(None)
            continue
        names = tuple(n for n in ((s,) if isinstance(s, str) else s)
                      if n in auto)
        clean.append(names if len(names) > 1 else
                     (names[0] if names else None))
    if all(c is None for c in clean):
        return x
    from jax.sharding import PartitionSpec as _P
    try:
        return jax.lax.with_sharding_constraint(x, _P(*clean))
    except Exception:
        return x


DP_AXES = ("pod", "data")


def moe_fwd(p, cfg: ModelConfig, x, dropless: bool = False):
    """Token-choice top-k routing with grouped per-expert capacity.

    Tokens are split into G dispatch groups (G aligned with the
    data-parallel width); each expert takes its top-C tokens *per group*
    (C = tokens_per_group*top_k/E * capacity_factor).  The group dim
    inherits the batch sharding, so the (G, E, C, D) dispatch tensor
    shards over data x model (expert parallel) and per-device dispatch
    memory is O(T_local/E_local) — without grouping the (E, C_global, D)
    gather only shards over experts and is TBs/device at the 671B dry-run
    scale.  Per-group capacity is also what real expert-parallel systems
    implement (capacity is enforced per data shard).
    Returns (y, aux_loss).
    """
    mo: MoEConfig = cfg.moe
    B, S, D = x.shape
    T = B * S
    E, K = mo.num_experts, mo.top_k
    h = rmsnorm(p["norm"], x, cfg.rms_norm_eps).reshape(T, D)

    logits = linear(p["router"], h.astype(jnp.float32))        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_i = jax.lax.top_k(probs, K)                   # (T, K)
    # normalized combine weights (DeepSeek/Mixtral style)
    topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)
    # per-token-per-expert gate (zero when expert not in token's top-k)
    gates = jnp.zeros((T, E), jnp.float32)
    gates = gates.at[jnp.arange(T)[:, None], topk_i].set(topk_p)  # scatter

    G = MOE_DISPATCH_GROUPS
    if dropless or T % G or T // G < E:
        G = 1
    Tg = T // G
    if dropless:
        C = Tg          # every expert could take every token: no drops
    else:
        C = min(max(1, int(Tg * K / E * mo.capacity_factor)), Tg)

    hg = _constrain(h.reshape(G, Tg, D), DP_AXES, None, None)
    gg = _constrain(gates.reshape(G, Tg, E), DP_AXES, None, None)
    # each expert takes its top-C tokens per group by gate value
    gsel, tok_idx = jax.lax.top_k(gg.transpose(0, 2, 1), C)   # (G, E, C)
    gsel = _constrain(gsel, DP_AXES, "model", None)
    tok_idx = _constrain(tok_idx, DP_AXES, "model", None)
    valid = gsel > 0.0
    xg = jnp.take_along_axis(hg[:, None], tok_idx[..., None],
                             axis=2)                           # (G, E, C, D)
    xg = _constrain(xg, DP_AXES, "model", None, None)
    act = (jax.nn.silu(jnp.einsum("gecd,edf->gecf", xg, p["w_gate"]))
           * jnp.einsum("gecd,edf->gecf", xg, p["w_up"]))
    yo = jnp.einsum("gecf,efd->gecd", act, p["w_down"])        # (G, E, C, D)
    yo = _constrain(yo, DP_AXES, "model", None, None)
    yo = yo * (gsel * valid).astype(yo.dtype)[..., None]
    out = jax.vmap(
        lambda yg, ig: jnp.zeros((Tg, D), yo.dtype).at[
            ig.reshape(-1)].add(yg.reshape(E * C, D)))(yo, tok_idx)
    out = _constrain(out.reshape(G, Tg, D), DP_AXES, None, None)
    out = out.reshape(T, D)

    # load-balance aux loss (Switch-style)
    me = probs.mean(0)                                         # (E,)
    ce = (gates > 0).astype(jnp.float32).mean(0) * E / K
    aux = mo.aux_loss_coef * E * jnp.sum(me * ce) / E

    if "shared" in p:
        out = out + swiglu_fwd(p["shared"], h, cfg.rms_norm_eps,
                               residual=False)
    if "dense_residual" in p:
        out = out + swiglu_fwd(p["dense_residual"], h, cfg.rms_norm_eps,
                               residual=False)
    return x + out.reshape(B, S, D).astype(x.dtype), aux
