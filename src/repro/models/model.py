"""Model assembly: superblock-scanned decoder covering all six families.

Layer stacks are grouped into repeated *superblocks* (``cfg.block_pattern``)
whose parameters are stacked along a leading ``n_blocks`` axis and executed
with ``lax.scan`` — HLO size stays O(|pattern|) for 61–100-layer configs.

Public API (all functional):
    model = build_model(cfg)
    params = model.init(rng)                       # or jax.eval_shape(...)
    loss, metrics = model.loss(params, batch)      # training
    logits, cache = model.prefill(params, batch)   # inference prefill
    logits, cache = model.decode_step(params, cache, tokens, pos)
    cache = model.init_cache(batch, seq_len)
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN, CROSS, MAMBA, MLA, ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.utils import scopes as S
from repro.utils.logging import get_logger

log = get_logger(__name__)


def _moe_at(cfg: ModelConfig, pos: int) -> bool:
    if cfg.moe is None:
        return False
    n = cfg.moe.every_n_layers
    return pos % n == n - 1


def _has_ffn(cfg: ModelConfig) -> bool:
    return cfg.d_ff > 0 or cfg.moe is not None


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # -- init ---------------------------------------------------------------

    def _init_position(self, key, pos: int):
        """Params for pattern position ``pos`` of ONE superblock."""
        cfg, dtype = self.cfg, _dtype(self.cfg)
        kind = cfg.block_pattern[pos]
        k_mix, k_ffn = jax.random.split(key)
        p: Dict[str, Any] = {}
        if kind == ATTN:
            p["mixer"] = (L.init_mla(k_mix, cfg, dtype) if cfg.mla is not None
                          else L.init_attention(k_mix, cfg, dtype))
        elif kind == MLA:
            p["mixer"] = L.init_mla(k_mix, cfg, dtype)
        elif kind == MAMBA:
            p["mixer"] = M.init_mamba(k_mix, cfg, dtype)
        elif kind == CROSS:
            p["mixer"] = L.init_cross_attention(k_mix, cfg, dtype)
        else:
            raise ValueError(kind)
        if _has_ffn(cfg):
            if _moe_at(cfg, pos):
                p["ffn"] = L.init_moe(k_ffn, cfg, dtype)
            else:
                p["ffn"] = L.init_swiglu(k_ffn, cfg.d_model, cfg.d_ff, dtype)
        return p

    def init(self, key) -> Dict[str, Any]:
        cfg, dtype = self.cfg, _dtype(self.cfg)
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": {"w": L._dense_init(keys[0], (cfg.vocab_size, cfg.d_model),
                                         dtype, scale=0.02)},
            "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
        }
        # stacked superblock params: vmap init over the block axis
        def init_block(k):
            ks = jax.random.split(k, len(cfg.block_pattern))
            return {f"p{i}": self._init_position(ks[i], i)
                    for i in range(len(cfg.block_pattern))}
        params["blocks"] = jax.vmap(init_block)(
            jax.random.split(keys[1], cfg.n_blocks))
        if not cfg.tie_embeddings:
            params["lm_head"] = L.init_linear(keys[2], cfg.d_model,
                                              cfg.vocab_size, dtype)
        if cfg.mtp_depth > 0:
            # DeepSeek-style MTP: project [h_t ; emb(t+1)] and run one extra
            # block, predicting token t+2.
            params["mtp"] = {
                "proj": L.init_linear(keys[3], 2 * cfg.d_model, cfg.d_model,
                                      dtype),
                "norm_h": L.init_rmsnorm(cfg.d_model, dtype),
                "norm_e": L.init_rmsnorm(cfg.d_model, dtype),
                "block": self._init_position(keys[4], 0),
            }
        return params

    # -- shared block application --------------------------------------------

    def _apply_position(self, p, pos: int, h, positions, enc, aux):
        cfg = self.cfg
        kind = cfg.block_pattern[pos]
        if kind == ATTN:
            if cfg.mla is not None:
                h, _ = L.mla_fwd(p["mixer"], cfg, h, positions)
            else:
                h, _ = L.attention_fwd(p["mixer"], cfg, h, positions)
        elif kind == MAMBA:
            h = M.mamba_fwd(p["mixer"], cfg, h)
        elif kind == CROSS:
            enc_kv = L.cross_attention_kv(p["mixer"], cfg, enc)
            h = L.cross_attention_fwd(p["mixer"], cfg, h, enc_kv)
        if "ffn" in p:
            if _moe_at(cfg, pos):
                h, a = L.moe_fwd(p["ffn"], cfg, h)
                aux = aux + a
            else:
                h = L.swiglu_fwd(p["ffn"], h, cfg.rms_norm_eps)
        return h, aux

    def _backbone(self, params, h, positions, enc, remat: bool):
        """Run all superblocks. h: (B,S,D). Returns (h, aux_loss)."""
        cfg = self.cfg

        def block_fn(carry, block_params):
            h, aux = carry
            for i in range(len(cfg.block_pattern)):
                h, aux = self._apply_position(block_params[f"p{i}"], i, h,
                                              positions, enc, aux)
            return (h, aux), None

        body = jax.checkpoint(block_fn) if remat else block_fn
        (h, aux), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                                   params["blocks"])
        return h, aux

    def _lm_head_w(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"]["w"].T
        return params["lm_head"]["w"]

    # -- training -------------------------------------------------------------

    def loss(self, params, batch, remat: Optional[bool] = None):
        """batch: {"tokens": (B,S) int32, "labels": (B,S) int32 (-1 = pad),
        optional "encoder_embeds": (B,T,enc_dim)}."""
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        enc = batch.get("encoder_embeds")
        positions = jnp.arange(S)
        h = params["embed"]["w"][tokens]
        h, aux = self._backbone(params, h, positions, enc,
                                remat=True if remat is None else remat)
        h = L.rmsnorm(params["final_norm"], h, cfg.rms_norm_eps)
        w = self._lm_head_w(params)
        xent, n_tok = _chunked_xent(h, w, labels)
        loss = xent / jnp.maximum(n_tok, 1.0)
        metrics = {"xent": loss, "aux_loss": aux, "tokens": n_tok}
        if cfg.mtp_depth > 0:
            mtp_loss = self._mtp_loss(params, h, tokens, labels, positions)
            metrics["mtp_loss"] = mtp_loss
            loss = loss + 0.3 * mtp_loss
        loss = loss + aux
        metrics["loss"] = loss
        return loss, metrics

    def _mtp_loss(self, params, h, tokens, labels, positions):
        """Multi-token prediction head (depth 1): predict t+2 from
        [h_t ; emb(token_{t+1})]."""
        cfg = self.cfg
        p = params["mtp"]
        B, S = tokens.shape
        # shift: combine h[:, :-1] with embedding of tokens[:, 1:]
        e_next = params["embed"]["w"][tokens[:, 1:]]
        hh = jnp.concatenate(
            [L.rmsnorm(p["norm_h"], h[:, :-1], cfg.rms_norm_eps),
             L.rmsnorm(p["norm_e"], e_next, cfg.rms_norm_eps)], axis=-1)
        hm = L.linear(p["proj"], hh)
        hm, _ = self._apply_position(p["block"], 0, hm, positions[:-1], None,
                                     jnp.zeros((), jnp.float32))
        hm = L.rmsnorm(params["final_norm"], hm, cfg.rms_norm_eps)
        # labels shifted by one more step
        lab = labels[:, 1:]
        xent, n_tok = _chunked_xent(hm, self._lm_head_w(params), lab)
        return xent / jnp.maximum(n_tok, 1.0)

    # -- inference ------------------------------------------------------------

    def init_cache(self, batch: int, seq_len: int):
        """Cache PyTree: {"p{i}": stacked-over-blocks per-position cache}."""
        cfg, dtype = self.cfg, _dtype(self.cfg)

        def one_position(pos: int):
            kind = cfg.block_pattern[pos]
            if kind == ATTN:
                if cfg.mla is not None:
                    return L.init_mla_cache(cfg, batch, seq_len, dtype)
                return L.init_attention_cache(cfg, batch, seq_len, dtype)
            if kind == MAMBA:
                return M.init_mamba_cache(cfg, batch, dtype)
            if kind == CROSS:
                # cross-attn KV over encoder tokens, computed at prefill
                return {
                    "k": jnp.zeros((batch, cfg.num_encoder_tokens,
                                    cfg.n_kv_heads, cfg.head_dim), dtype),
                    "v": jnp.zeros((batch, cfg.num_encoder_tokens,
                                    cfg.n_kv_heads, cfg.head_dim), dtype),
                }
            raise ValueError(kind)

        def stack(tree):
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (cfg.n_blocks,) + x.shape), tree)

        return {f"p{i}": stack(one_position(i))
                for i in range(len(cfg.block_pattern))}

    def prefill(self, params, batch, cache_len: Optional[int] = None):
        """Process a full prompt, returning last-token logits + filled cache.

        batch: {"tokens": (B,S), optional "encoder_embeds"}.
        cache_len: total cache capacity (>= S); defaults to S.
        """
        cfg, dtype = self.cfg, _dtype(self.cfg)
        tokens = batch["tokens"]
        enc = batch.get("encoder_embeds")
        B, S = tokens.shape
        cap = cache_len or S
        positions = jnp.arange(S)
        h = params["embed"]["w"][tokens]

        def block_fn(carry, block_params):
            h = carry
            caches = {}
            for i, kind in enumerate(cfg.block_pattern):
                p = block_params[f"p{i}"]
                if kind == ATTN:
                    if cfg.mla is not None:
                        h, (c_kv, k_rope) = L.mla_fwd(p["mixer"], cfg, h,
                                                      positions)
                        caches[f"p{i}"] = _pad_cache(
                            {"c_kv": c_kv, "k_rope": k_rope,
                             "pos": positions.astype(jnp.int32)}, cap)
                    else:
                        h, (k, v) = L.attention_fwd(p["mixer"], cfg, h,
                                                    positions)
                        caches[f"p{i}"] = _window_cache(
                            k, v, positions, cap, cfg.sliding_window)
                elif kind == MAMBA:
                    # rerun as decode-style to also get states cheaply: use
                    # fwd then recompute final state via a short conv tail.
                    h, st = _mamba_fwd_with_state(p["mixer"], cfg, h)
                    caches[f"p{i}"] = st
                elif kind == CROSS:
                    enc_kv = L.cross_attention_kv(p["mixer"], cfg, enc)
                    h = L.cross_attention_fwd(p["mixer"], cfg, h, enc_kv)
                    caches[f"p{i}"] = {"k": enc_kv[0], "v": enc_kv[1]}
                if "ffn" in p:
                    if _moe_at(cfg, i):
                        h, _ = L.moe_fwd(p["ffn"], cfg, h)
                    else:
                        h = L.swiglu_fwd(p["ffn"], h, cfg.rms_norm_eps)
            return h, caches

        h, cache = jax.lax.scan(block_fn, h, params["blocks"])
        h = L.rmsnorm(params["final_norm"], h[:, -1:], cfg.rms_norm_eps)
        logits = (h @ self._lm_head_w(params)).astype(jnp.float32)
        return logits, cache

    def decode_step(self, params, cache, tokens, pos):
        """One decode step. tokens: (B,1) int32; pos: scalar int32 (current
        absolute position). Returns (logits (B,1,V) f32, new cache)."""
        cfg = self.cfg
        h = params["embed"]["w"][tokens]

        def block_fn(carry, xs):
            h = carry
            block_params, block_cache = xs
            new_caches = {}
            for i, kind in enumerate(cfg.block_pattern):
                p, c = block_params[f"p{i}"], block_cache.get(f"p{i}")
                if kind == ATTN:
                    if cfg.mla is not None:
                        h, nc = L.mla_decode(p["mixer"], cfg, h, c, pos)
                    else:
                        h, nc = L.attention_decode(p["mixer"], cfg, h, c, pos)
                    new_caches[f"p{i}"] = nc
                elif kind == MAMBA:
                    h, nc = M.mamba_decode(p["mixer"], cfg, h, c, pos)
                    new_caches[f"p{i}"] = nc
                elif kind == CROSS:
                    h = L.cross_attention_fwd(p["mixer"], cfg, h,
                                              (c["k"], c["v"]))
                    new_caches[f"p{i}"] = c
                if "ffn" in p:
                    if _moe_at(cfg, i):
                        # decode has few tokens per shard: dropless dispatch
                        h, _ = L.moe_fwd(p["ffn"], cfg, h, dropless=True)
                    else:
                        h = L.swiglu_fwd(p["ffn"], h, cfg.rms_norm_eps)
            return h, new_caches

        h, new_cache = jax.lax.scan(block_fn, h, (params["blocks"], cache))
        h = L.rmsnorm(params["final_norm"], h, cfg.rms_norm_eps)
        logits = (h @ self._lm_head_w(params)).astype(jnp.float32)
        return logits, new_cache

    def param_count(self, params=None) -> int:
        from repro.utils import tree_count_params
        if params is None:
            params = jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))
        return tree_count_params(params)


# ---------------------------------------------------------------------------
# helpers


def _pad_cache(cache, cap: int):
    """Grow seq axis of a prefill cache to capacity ``cap``."""
    S = cache["pos"].shape[0]
    if cap == S:
        return cache
    pad = cap - S
    out = dict(cache)
    for k in cache:
        if k == "pos":
            out[k] = jnp.concatenate(
                [cache[k], jnp.full((pad,), jnp.iinfo(jnp.int32).max,
                                    jnp.int32)])
        else:
            x = cache[k]
            out[k] = jnp.concatenate(
                [x, jnp.zeros((x.shape[0], pad) + x.shape[2:], x.dtype)],
                axis=1)
    return out


def _window_cache(k, v, positions, cap: int, window: int):
    """Build the decode cache from prefill K/V (ring layout if windowed)."""
    B, S = k.shape[0], k.shape[1]
    if not window or S <= window:
        c = {"k": k, "v": v, "pos": positions.astype(jnp.int32)}
        return _pad_cache(c, cap if not window else min(window, cap))
    # keep last `window` positions arranged by slot = pos % window
    start = S - window
    slot_to_pos = start + (jnp.arange(window) - start) % window
    c = {
        "k": jnp.take(k, slot_to_pos, axis=1),
        "v": jnp.take(v, slot_to_pos, axis=1),
        "pos": slot_to_pos.astype(jnp.int32),
    }
    return c


def _mamba_fwd_with_state(p, cfg, h0):
    """Mamba forward that also returns the decode cache (conv + ssm state)."""
    s, d_inner, H = M._dims(cfg)
    N, P = s.d_state, s.head_dim
    b, S, _ = h0.shape
    h = L.rmsnorm(p["norm"], h0, cfg.rms_norm_eps)
    z, xBC_raw, dt = M._split_in_proj(cfg, L.linear(p["in_proj"], h))
    xBC, conv_state = M._causal_conv(p["conv_w"], p["conv_b"], xBC_raw)
    xs, B, C = jnp.split(xBC, [d_inner, d_inner + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, final_state = _ssd_with_state(xs.reshape(b, S, H, P), dt, A, B, C,
                                     p["D"], s.chunk_size)
    y = y.reshape(b, S, d_inner) * jax.nn.silu(z)
    y = L.rmsnorm(p["out_norm"], y, cfg.rms_norm_eps)
    out = h0 + L.linear(p["out_proj"], y)
    # conv state must be the PRE-activation last K-1 inputs
    raw_state = xBC_raw[:, -(s.d_conv - 1):]
    return out, {"conv": raw_state, "ssm": final_state}


def _ssd_with_state(x, dt, A, B, C, D, chunk):
    """Same as mamba2.ssd_chunked but also returns the final SSM state."""
    import repro.models.mamba2 as m2
    b, S, H, P = x.shape
    N = B.shape[-1]
    y = m2.ssd_chunked(x, dt, A, B, C, D, chunk)
    # recompute final state directly (cheap linear pass)
    dA = dt * A[None, None, :]                               # (b,S,H)
    dA_cum_total = jnp.cumsum(dA, axis=1)
    decay_to_end = jnp.exp(dA_cum_total[:, -1:, :] - dA_cum_total)
    state = jnp.einsum("bsn,bsh,bshp->bhnp", B.astype(jnp.float32),
                       decay_to_end * dt, x.astype(jnp.float32))
    return y, state


# Bytes of one chunk's float32 logits in the cross-entropy: bounds the
# loss's activation memory, whatever the vocabulary.
XENT_CHUNK_BYTES = 2 ** 27


def xent_chunks(rows: int, seq: int, vocab: int,
                budget: int) -> Tuple[int, int, int]:
    """The cross-entropy's chunk plan over ``rows`` sequences of ``seq``
    tokens: ``(c, n_chunks, pad)``.  A chunk takes ``c`` consecutive
    positions of every row; ``c`` is the largest power of two whose
    float32 logits ``rows * c * vocab * 4`` fit ``budget`` (at least 1),
    capped at ``seq`` rounded up to a power of two.  Each row is padded
    by ``pad`` positions to ``n_chunks * c``."""
    c = 1 << max(0, (budget // (4 * rows * vocab)).bit_length() - 1)
    c = min(c, 1 << (seq - 1).bit_length())
    n_chunks = -(-seq // c)
    return c, n_chunks, n_chunks * c - seq


@jax.named_scope(S.XENT)
def _chunked_xent(h, w, labels):
    """Cross-entropy in chunks of ``c`` positions of every row
    (``xent_chunks``), so one chunk's float32 (B,c,V) logits, at most
    ``XENT_CHUNK_BYTES``, and not (B,S,V), bound activation memory.
    Where ``c`` does not divide S the rows are padded with zero
    positions labelled -1, which add no loss and no gradient.  The chunk
    body is rematerialized so the backward pass does not retain
    per-chunk softmax.

    The chunks stay (B,c) blocks rather than runs of the flattened
    tokens: on the TPU the flattened form made XLA copy a tied head into
    the transposed layout for the forward loop (77 MB of temporaries for
    mamba2-130m's head, at every chunk size).

    h: (B,S,D); w: (D,V); labels: (B,S) int32, -1 = ignore.
    Returns (sum_xent, n_tokens) both f32 scalars.
    """
    B, S, Dm = h.shape
    V = w.shape[-1]
    c, n, pad = xent_chunks(B, S, V, XENT_CHUNK_BYTES)
    log.info("xent: %d x %d tokens, vocab %d: %d chunks of %d x %d "
             "(%d padded a row)", B, S, V, n, B, c, pad)
    hc = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    hc = hc.reshape(B, n, c, Dm).transpose(1, 0, 2, 3)
    lc = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    lc = lc.reshape(B, n, c).transpose(1, 0, 2)

    @jax.checkpoint
    def body(carry, xs):
        xent_sum, tok_sum = carry
        hb, lb = xs
        logits = (hb @ w).astype(jnp.float32)                # (B,c,V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.clip(lb, 0, V - 1)
        gold = jnp.take_along_axis(logits, lab[..., None],
                                   axis=-1)[..., 0]
        valid = (lb >= 0).astype(jnp.float32)
        xent = ((lse - gold) * valid).sum()
        return (xent_sum + xent, tok_sum + valid.sum()), None

    with jax.named_scope(f"xent_chunks_{n}x{B}x{c}"):
        (xent, n_tok), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (hc, lc))
    return xent, n_tok


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
