"""Math-level correctness of the model building blocks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import layers as L
from repro.models import mamba2 as M


def naive_attention(q, k, v, causal=True, window=0):
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    qr = q.reshape(B, Sq, KH, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k) / np.sqrt(D)
    i = jnp.arange(Sq)[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= i >= j
    if window:
        mask &= i - j < window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, v.shape[-1])


@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("kv_heads", [4, 1])
def test_blockwise_attention_matches_naive(window, kv_heads):
    B, S, H, D = 2, 128, 4, 16
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, kv_heads, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, kv_heads, D))
    out = L.blockwise_attention(q, k, v, causal=True, window=window)
    ref = naive_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_rope_preserves_norm_and_relativity():
    B, S, H, D = 1, 16, 2, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    pos = jnp.arange(S)
    y = L.apply_rope(x, pos, 10000.0)
    # rotation: per-position norms preserved
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(y, axis=-1)),
        np.asarray(jnp.linalg.norm(x, axis=-1)), rtol=1e-5)
    # relativity: <rope(q,i), rope(k,j)> depends only on i-j
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, D))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, D))
    def dot(i, j):
        qi = L.apply_rope(q, jnp.array([i]), 10000.0)
        kj = L.apply_rope(k, jnp.array([j]), 10000.0)
        return float(jnp.sum(qi * kj))
    assert abs(dot(5, 3) - dot(10, 8)) < 1e-4
    assert abs(dot(5, 3) - dot(6, 3)) > 1e-6   # actually depends on offset


def test_ssd_chunked_matches_sequential_recurrence():
    """SSD chunked scan == token-by-token linear SSM recurrence."""
    b, S, H, P, N = 2, 64, 3, 8, 4
    rng = [jax.random.normal(jax.random.PRNGKey(i), s) * 0.5
           for i, s in enumerate([(b, S, H, P), (b, S, H), (H,),
                                  (b, S, N), (b, S, N), (H,)])]
    x, dt_raw, A_raw, B, C, D = rng
    dt = jax.nn.softplus(dt_raw)
    A = -jnp.exp(A_raw)

    out = M.ssd_chunked(x, dt, A, B, C, D, chunk=16)

    # sequential reference
    state = jnp.zeros((b, H, N, P))
    ys = []
    for t in range(S):
        dA = jnp.exp(dt[:, t] * A[None, :])                      # (b,H)
        dBx = jnp.einsum("bn,bh,bhp->bhnp", B[:, t], dt[:, t], x[:, t])
        state = state * dA[..., None, None] + dBx
        y = jnp.einsum("bn,bhnp->bhp", C[:, t], state)
        ys.append(y + x[:, t] * D[None, :, None])
    ref = jnp.stack(ys, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def _direct_xent(h, w, labels):
    """The loss in one piece: log_softmax over (B,S,V), -1 labels
    masked."""
    logp = jax.nn.log_softmax(h @ w)
    mask = labels >= 0
    return -jnp.sum(jnp.take_along_axis(
        logp, jnp.clip(labels, 0)[..., None], -1)[..., 0] * mask)


def _xent_inputs(B, S, V, masked=0):
    h = jax.random.normal(jax.random.PRNGKey(0), (B, S, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, V)) * 0.1
    labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, V)
    return h, w, labels.at[:, :masked].set(-1)


# (B, S, V, labels set to -1 at the start of every row); the budget
# gives chunks of 8 positions a row, so every case runs several
XENT_CASES = {
    "round": (2, 64, 128, 5),
    "seq_not_multiple_of_chunk": (2, 63, 128, 5),     # the MTP head's S-1
    "vocab_not_round": (2, 64, 97, 5),
    "chunk_all_masked": (2, 64, 97, 8),
}


def _xent_budget(monkeypatch, model, B, V):
    monkeypatch.setattr(model, "XENT_CHUNK_BYTES", B * 8 * V * 4)


@pytest.mark.parametrize("shape", XENT_CASES.values(), ids=XENT_CASES.keys())
def test_chunked_xent_matches_direct(shape, monkeypatch):
    from repro.models import model
    B, S, V, masked = shape
    _xent_budget(monkeypatch, model, B, V)
    c, n, pad = model.xent_chunks(B, S, V, model.XENT_CHUNK_BYTES)
    assert c == 8 and n > 1 and (pad > 0) == (S % 8 > 0)
    h, w, labels = _xent_inputs(B, S, V, masked)
    xent, n_tok = model._chunked_xent(h, w, labels)
    np.testing.assert_allclose(float(xent),
                               float(_direct_xent(h, w, labels)), rtol=1e-5)
    assert int(n_tok) == B * (S - masked) == int((labels >= 0).sum())


@pytest.mark.parametrize("shape", XENT_CASES.values(), ids=XENT_CASES.keys())
def test_chunked_xent_grad_matches_direct(shape, monkeypatch):
    """Padding and masked chunks add no gradient to h or to the head."""
    from repro.models import model
    B, S, V, masked = shape
    _xent_budget(monkeypatch, model, B, V)
    h, w, labels = _xent_inputs(B, S, V, masked)
    got = jax.grad(lambda h, w: model._chunked_xent(h, w, labels)[0],
                   argnums=(0, 1))(h, w)
    want = jax.grad(_direct_xent, argnums=(0, 1))(h, w, labels)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-6)
    assert not np.asarray(got[0][:, :masked]).any()


@pytest.mark.parametrize("rows,seq,vocab", [
    (8, 2048, 151936), (8, 2048, 50288), (8, 2047, 151936), (8, 2047, 50288),
    (1, 1, 97), (2, 63, 97), (2, 64, 97), (4, 4096, 32000), (8, 2047, 2 ** 27)])
def test_xent_chunks_plan(rows, seq, vocab):
    from repro.models.model import XENT_CHUNK_BYTES, xent_chunks
    c, n, pad = xent_chunks(rows, seq, vocab, XENT_CHUNK_BYTES)
    assert c & (c - 1) == 0
    assert rows * c * vocab * 4 <= XENT_CHUNK_BYTES or c == 1
    # the largest such power of two, unless the sequence is shorter
    assert rows * 2 * c * vocab * 4 > XENT_CHUNK_BYTES or c >= seq
    assert n * c - pad == seq and 0 <= pad < c


@pytest.mark.parametrize("name,vocab,plan", [
    ("qwen2-1.5b", 151936, (16, 128, 0)),
    ("mamba2-130m", 50288, (64, 32, 0))])
def test_xent_chunks_for_the_cells(name, vocab, plan):
    """8 x 2,048 tokens a chip; the MTP head's 8 x 2,047 pads a position
    a row."""
    from repro.models.model import XENT_CHUNK_BYTES, xent_chunks
    assert xent_chunks(8, 2048, vocab, XENT_CHUNK_BYTES) == plan
    assert xent_chunks(8, 2047, vocab, XENT_CHUNK_BYTES) == (*plan[:2], 1)


def test_moe_dropless_processes_all_assignments():
    """With dropless dispatch every top-k assignment is honored: MoE output
    equals the explicit per-token dense mixture."""
    cfg = get_arch("arctic-480b").reduced()
    p = L.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model)) * 0.5
    y, aux = L.moe_fwd(p, cfg, x, dropless=True)

    # dense reference: run every expert on every token
    h = L.rmsnorm(p["norm"], x, cfg.rms_norm_eps)
    T = 2 * 8
    hf = h.reshape(T, -1)
    logits = hf @ p["router"]["w"]
    probs = jax.nn.softmax(logits, -1)
    topk_p, topk_i = jax.lax.top_k(probs, cfg.moe.top_k)
    topk_p = topk_p / topk_p.sum(-1, keepdims=True)
    outs = []
    for t in range(T):
        acc = jnp.zeros((cfg.d_model,))
        for j in range(cfg.moe.top_k):
            e = int(topk_i[t, j])
            act = (jax.nn.silu(hf[t] @ p["w_gate"][e])
                   * (hf[t] @ p["w_up"][e]))
            acc = acc + topk_p[t, j] * (act @ p["w_down"][e])
        outs.append(acc)
    ref = jnp.stack(outs).reshape(2, 8, -1)
    if "shared" in p:
        ref = ref + L.swiglu_fwd(p["shared"], hf, residual=False).reshape(
            2, 8, -1)
    if "dense_residual" in p:
        ref = ref + L.swiglu_fwd(p["dense_residual"], hf,
                                 residual=False).reshape(2, 8, -1)
    np.testing.assert_allclose(np.asarray(y - x), np.asarray(ref),
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-130m",
                                  "deepseek-v3-671b", "jamba-v0.1-52b",
                                  "llama-3.2-vision-90b", "qwen2-1.5b"])
def test_decode_matches_prefill(arch):
    """Decode from cache reproduces the full-forward last-token logits."""
    from repro.models import build_model
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.num_encoder_tokens:
        batch["encoder_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (B, cfg.num_encoder_tokens, cfg.encoder_dim), jnp.float32)
    full_logits, _ = model.prefill(params, batch)
    b2 = dict(batch)
    b2["tokens"] = toks[:, : S - 1]
    _, cache = model.prefill(params, b2, cache_len=S)
    dec_logits, _ = model.decode_step(params, cache, toks[:, S - 1 : S],
                                      S - 1)
    np.testing.assert_allclose(np.asarray(full_logits[:, -1]),
                               np.asarray(dec_logits[:, 0]),
                               rtol=1e-3, atol=2e-3)


def test_sliding_window_decode_ring_buffer():
    """Windowed decode with a ring cache == full-cache windowed attention."""
    cfg = get_arch("llama3.2-1b").reduced(sliding_window=16)
    from repro.models import build_model
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 48
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    full_logits, _ = model.prefill(params, {"tokens": toks})
    _, cache = model.prefill(params, {"tokens": toks[:, : S - 1]},
                             cache_len=S)
    # ring cache has length == window
    assert cache["p0"]["k"].shape[2] == 16
    dec_logits, _ = model.decode_step(params, cache, toks[:, S - 1 : S],
                                      S - 1)
    np.testing.assert_allclose(np.asarray(full_logits[:, -1]),
                               np.asarray(dec_logits[:, 0]),
                               rtol=1e-3, atol=2e-3)
