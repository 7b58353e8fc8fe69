"""The LGC gradient-compression autoencoders (paper Section IV, Tables I/II).

Encoder ``E_c`` (Table I): five 1-D convolutions with LeakyReLU, strides
(2,2,2,2,1), filters (64,128,256,64,4).  A length-L single-channel gradient
vector becomes an (L/16, 4) *compressed common representation* — 4× fewer
floats, and in the parameter-server pattern only ONE node transmits it.

Decoder ``D_c`` (Table II): five 1-D transposed convolutions with filters
(4,32,64,128,32) followed by a 1×1 conv back to one channel.  The paper's
table lists stride 2 for all five deconvs, which would upsample by 32 and
not invert the ×16 encoder; we set deconv1 stride 1 and deconv2–5 stride 2
(×16 total) so that decode(encode(x)) is shape-preserving — recorded as a
paper-table inconsistency in DESIGN.md.

Two decode heads (Section IV-A / IV-B):
  * RAR (aggregation):  g_rec = D_c(mean_k E_c(g_k))   — eq. (9)-(10)
  * PS  (decoupling):   g_rec_k = D_c^k(g_c, g_I_k)    — eq. (4); the
    innovation vector is concatenated as an extra channel before the final
    1×1 conv (Fig. 5a).

Losses: reconstruction (eq. 6/11) and encoder-similarity (eq. 5).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# (filters, kernel, stride) per Table I
ENCODER_SPEC = ((64, 3, 2), (128, 3, 2), (256, 3, 2), (64, 3, 2), (4, 1, 1))
# (filters, kernel, stride) per Table II (stride of deconv1 adjusted, see doc)
DECODER_SPEC = ((4, 3, 1), (32, 3, 2), (64, 3, 2), (128, 3, 2), (32, 3, 2))

LEAKY_SLOPE = 0.01
ENC_FACTOR = 16          # total length downsampling of the encoder
BOTTLENECK_CH = 4


def _conv_init(key, k, c_in, c_out):
    fan_in = k * c_in
    return (jax.random.normal(key, (k, c_in, c_out), jnp.float32)
            * np.sqrt(2.0 / fan_in))


def init_lgc_autoencoder(key, num_decoders: int = 1,
                         ps_innovation: bool = False) -> Dict:
    """AE params. num_decoders=K for the PS pattern (one decoder per node,
    Section IV-A); 1 for RAR.  ps_innovation adds the innovation channel to
    the final conv of each decoder, and the PS decoders are always
    stacked along a leading K axis — also at K = 1."""
    keys = jax.random.split(key, 16)
    enc, c_in = [], 1
    for i, (c_out, k, _s) in enumerate(ENCODER_SPEC):
        enc.append({"w": _conv_init(keys[i], k, c_in, c_out),
                    "b": jnp.zeros((c_out,))})
        c_in = c_out

    def one_decoder(key):
        dkeys = jax.random.split(key, len(DECODER_SPEC) + 1)
        dec, ci = [], BOTTLENECK_CH
        for i, (c_out, k, _s) in enumerate(DECODER_SPEC):
            dec.append({"w": _conv_init(dkeys[i], k, ci, c_out),
                        "b": jnp.zeros((c_out,))})
            ci = c_out
        final_in = ci + (1 if ps_innovation else 0)
        dec.append({"w": _conv_init(dkeys[-1], 1, final_in, 1),
                    "b": jnp.zeros((1,))})
        return dec

    if num_decoders == 1 and not ps_innovation:
        decoders = one_decoder(keys[10])
    else:
        decoders = jax.vmap(one_decoder)(
            jax.random.split(keys[10], num_decoders))
    return {"encoder": enc, "decoder": decoders}


def _conv1d(p, x, stride):
    """x: (B, L, C) -> (B, L/stride, C_out), SAME padding."""
    return jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride,), padding="SAME",
        dimension_numbers=("NWC", "WIO", "NWC")) + p["b"]


def _deconv1d(p, x, stride):
    return jax.lax.conv_transpose(
        x, p["w"], strides=(stride,), padding="SAME",
        dimension_numbers=("NWC", "WIO", "NWC")) + p["b"]


def lgc_encode(ae_params, g: jnp.ndarray) -> jnp.ndarray:
    """g: (L,) or (B, L) -> compressed rep (B, L/16, 4).  L % 16 == 0."""
    if g.ndim == 1:
        g = g[None]
    x = g[..., None].astype(jnp.float32)                  # (B, L, 1)
    for p, (_c, _k, s) in zip(ae_params["encoder"], ENCODER_SPEC):
        x = _conv1d(p, x, s)
        x = jax.nn.leaky_relu(x, LEAKY_SLOPE)
    return x                                              # (B, L/16, 4)


def _decode_stack(dec_params, z, innovation=None):
    x = z
    for i, (_c, _k, s) in enumerate(DECODER_SPEC):
        x = _deconv1d(dec_params[i], x, s)
        x = jax.nn.leaky_relu(x, LEAKY_SLOPE)
    if innovation is not None:
        x = jnp.concatenate([x, innovation[..., None]], axis=-1)
    x = _conv1d(dec_params[-1], x, 1)                     # 1x1 conv, linear
    return x[..., 0]                                      # (B, L)


def lgc_decode_rar(ae_params, z_avg: jnp.ndarray) -> jnp.ndarray:
    """Aggregation decoder (eq. 10): z_avg (B, L/16, 4) -> (B, L)."""
    return _decode_stack(ae_params["decoder"], z_avg)


def lgc_decode_ps(ae_params, z_common: jnp.ndarray,
                  innovations: jnp.ndarray) -> jnp.ndarray:
    """Decoupling decoders (eq. 4): K per-node decoders share one common
    representation; each concatenates its node's innovation vector.

    z_common: (L/16, 4); innovations: (K, L) -> reconstructions (K, L).
    """
    K = innovations.shape[0]
    z = jnp.broadcast_to(z_common[None], (K,) + z_common.shape)

    def dec_one(dec_params, zi, inno):
        return _decode_stack(dec_params, zi[None], inno[None])[0]

    return jax.vmap(dec_one)(ae_params["decoder"], z, innovations)


# ---------------------------------------------------------------------------
# losses (Section IV)


def ae_loss_rar(ae_params, g_nodes: jnp.ndarray) -> jnp.ndarray:
    """eq. (11): || D_c(mean_k E_c(g_k)) - mean_k g_k ||^2.

    Normalized per element (the paper's unnormalized sum only rescales the
    learning rate; the mean keeps AE training stable across vector lengths).
    """
    z = lgc_encode(ae_params, g_nodes)                    # (K, L/16, 4)
    g_rec = lgc_decode_rar(ae_params, z.mean(0, keepdims=True))[0]
    target = g_nodes.mean(0)
    return jnp.mean((g_rec - target) ** 2)


def ae_loss_ps(ae_params, g_nodes: jnp.ndarray, innovations: jnp.ndarray,
               common_idx: jnp.ndarray, lambda_rec: float = 1.0,
               lambda_sim: float = 0.5) -> Tuple[jnp.ndarray, Dict]:
    """eq. (5)-(7).  One (randomly rotating) node's encoding is the common
    representation; every decoder reconstructs its own node's gradient from
    it plus that node's innovation.

    g_nodes: (K, L); innovations: (K, L); common_idx: scalar int in [0, K).
    """
    K = g_nodes.shape[0]
    z = lgc_encode(ae_params, g_nodes)                    # (K, L/16, 4)
    # similarity loss: sum_{k != m} ||E(g_k) - E(g_m)||^2  (eq. 5),
    # per-element normalized (see ae_loss_rar docstring)
    diff = z[:, None] - z[None, :]                        # (K, K, ...)
    l_sim = jnp.sum(jnp.mean(diff ** 2, axis=tuple(range(2, diff.ndim)))) \
        / max(K * (K - 1), 1)
    z_common = z[common_idx]
    g_rec = lgc_decode_ps(ae_params, z_common, innovations)   # (K, L)
    l_rec = jnp.mean((g_nodes - g_rec) ** 2)              # eq. (6)
    loss = lambda_rec * l_rec + lambda_sim * l_sim        # eq. (7)
    return loss, {"l_rec": l_rec, "l_sim": l_sim}


def compressed_length(mu: int) -> int:
    """Number of floats in the transmitted representation for input len mu."""
    assert mu % ENC_FACTOR == 0
    return mu // ENC_FACTOR * BOTTLENECK_CH
