"""Plain float32 reference of the benchmark's training step.

Straightforward ``jax.numpy`` at HIGHEST matmul precision.  It imports
nothing of the program and takes nothing the program made: the weights
come from :mod:`weights` (the benchmark's own seeded init) and the
batches from :mod:`tokens`.  It follows the published descriptions:

* the model kind's layers (``bench/kinds/<kind>.py``: ``hidden`` up to
  the final norm, ``head`` the tied or untied LM head), built from
  :mod:`primitives`;
* mean cross-entropy over every token, the head applied in slices of
  tokens;
* LGC (arXiv:2103.08870) on the concatenated gradient vector: DGC
  momentum-corrected error feedback, per-layer top-k at the configured
  sparsity, the embedding (first layer) exempt and reduced dense, an
  untied LM head (last layer) sent as plain top-k, the rest through the
  autoencoder.  The leader's top-k index set is the shared support
  (CLT-k, leader = step mod K); PS: the leader's encoding plus one
  innovation decoder per node, reconstructions averaged; RAR: the mean
  of the nodes' encodings through one decoder.
* AdamW with decoupled weight decay under a cosine schedule with linear
  warm-up.

Departure from the published models, shared with the configuration:
the residual stream is not kept in float32 by the program (the
reference computes everything in float32).

``Numerics`` (:mod:`primitives`) sets the precision of every matrix
product and convolution and of the residual stream between layers:
:data:`F32` is the reference, :func:`fp8_numerics` the control that has
to come out as not correct.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from functools import partial
from pathlib import Path
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from lgcbench import spec
from lgcbench.primitives import (F32, HIGHEST, Numerics,  # noqa: F401
                                 einsum, fp8_numerics)


# ---------------------------------------------------------------------------
# parameter trees: nested dicts, leaves in sorted-key order


def leaf_paths(tree, prefix: str = "") -> List[Tuple[str, object]]:
    """``[(path, leaf)]`` in sorted key order at every level (the order
    in which a gradient vector concatenates the leaves)."""
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out += leaf_paths(tree[k], f"{prefix}/{k}" if prefix else k)
        return out
    if isinstance(tree, (list, tuple)):
        out = []
        for i, v in enumerate(tree):
            out += leaf_paths(v, f"{prefix}/{i}" if prefix else str(i))
        return out
    return [(prefix, tree)]


def tree_from_paths(items: Dict[str, object]) -> dict:
    root: dict = {}
    for path, leaf in items.items():
        node = root
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return root


# ---------------------------------------------------------------------------
# model


HEAD_CHUNK = 256          # tokens per slice of the (tokens, vocab) logits


def row_xent(nm, m, params, tokens, labels, kind):
    """Summed cross-entropy of one sequence.  params: the nested dict of
    float32 leaves; ``blocks/p{i}`` stacks position i of the superblock
    on a leading axis.  ``kind``: the model kind's module."""
    h = kind.hidden(nm, m, params, tokens)
    w = kind.head(m, params)
    S = h.shape[0]
    c = min(S, HEAD_CHUNK)

    @jax.checkpoint
    def chunk_xent(hl):
        hc, lc = hl
        logits = einsum(nm, "sd,vd->sv", hc, w)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)
    per = jax.lax.map(chunk_xent, (h.reshape(S // c, c, -1),
                                   labels.reshape(S // c, c)))
    return jnp.sum(per)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _row_grad(nm, m_json, bench_dir, params, tokens, labels):
    """``m_json``: the model as JSON text, hashable as a static argument
    whatever lists or groups its configuration holds."""
    m = json.loads(m_json)
    kind = spec.kind_module(m["kind"], bench_dir)
    return jax.value_and_grad(lambda p: row_xent(nm, m, p, tokens, labels,
                                                 kind))(params)


@jax.jit
def _acc(acc, g):
    return jax.tree_util.tree_map(jnp.add, acc, g)


def node_grads(nm, m, params, tokens, labels, K, half=False,
               bench_dir=spec.BENCH_DIR):
    """Per-node mean gradients and the mean loss.  Rows are split over K
    nodes in order; each node's gradient is the mean over its tokens.
    Runs one row at a time so the float32 activations fit.  ``half``
    plants a fault: each node leaves out the second half of its rows.
    The model kind is found in ``bench_dir``."""
    B, S = tokens.shape
    rows = B // K // 2 if half else B // K
    mi = json.dumps(m, sort_keys=True)
    total, grads = 0.0, []
    for node in range(K):
        acc = None
        first = node * (B // K)
        for r in range(first, first + rows):
            loss, g = _row_grad(nm, mi, bench_dir, params, tokens[r],
                                labels[r])
            total += float(loss)
            acc = g if acc is None else _acc(acc, g)
        grads.append(jax.tree_util.tree_map(lambda x: x / (rows * S), acc))
    return grads, total / (K * rows * S)


# ---------------------------------------------------------------------------
# LGC compressor


@dataclass(frozen=True)
class Leaf:
    path: str
    offset: int
    size: int
    role: str                 # "dense" | "topk_only" | "compressed"
    k: int


def layout(shapes: List[Tuple[str, tuple]], sparsity: float):
    """Leaves in concatenation order with their role and top-k count:
    the embedding (first layer) dense, an untied LM head (last layer)
    top-k without the AE, everything else compressed."""
    out, off = [], 0
    for path, shape in shapes:
        size = int(np.prod(shape)) if shape else 1
        parts = path.split("/")
        role = ("dense" if "embed" in parts else
                "topk_only" if "lm_head" in parts else "compressed")
        k = 0 if role == "dense" else max(1, int(round(size * sparsity)))
        out.append(Leaf(path, off, size, role, k))
        off += size
    return out


AE_ALIGN = 16
# (filters, kernel, stride): encoder per the paper's Table I, decoder per
# Table II with deconv1 at stride 1 so the x16 encoder is inverted
ENCODER = ((64, 3, 2), (128, 3, 2), (256, 3, 2), (64, 3, 2), (4, 1, 1))
DECODER = ((4, 3, 1), (32, 3, 2), (64, 3, 2), (128, 3, 2), (32, 3, 2))
LEAKY = 0.01


def _conv(nm, x, w, b, stride):
    return jax.lax.conv_general_dilated(
        nm.quant(x), nm.quant(w), (stride,), "SAME",
        dimension_numbers=("NWC", "WIO", "NWC"), precision=HIGHEST) + b


def _deconv(nm, x, w, b, stride):
    return jax.lax.conv_transpose(
        nm.quant(x), nm.quant(w), (stride,), "SAME",
        dimension_numbers=("NWC", "WIO", "NWC"), precision=HIGHEST) + b


def ae_encode(nm, ae, vals):
    """vals (L,) -> (L/16, 4)."""
    x = vals[None, :, None]
    for layer, (_c, _k, s) in zip(ae["encoder"], ENCODER):
        x = jax.nn.leaky_relu(_conv(nm, x, layer["w"], layer["b"], s), LEAKY)
    return x[0]


def ae_decode(nm, dec, z, innovation=None):
    """One decoder: z (L/16, 4) [+ innovation (L,)] -> (L,)."""
    x = z[None]
    for layer, (_c, _k, s) in zip(dec[:-1], DECODER):
        x = jax.nn.leaky_relu(_deconv(nm, x, layer["w"], layer["b"], s),
                              LEAKY)
    if innovation is not None:
        x = jnp.concatenate([x, innovation[None, :, None]], axis=-1)
    return _conv(nm, x, dec[-1]["w"], dec[-1]["b"], 1)[0, :, 0]


def _topk_leaf(v, leaf):
    seg = jax.lax.dynamic_slice_in_dim(v, leaf.offset, leaf.size)
    _, idx = jax.lax.top_k(jnp.abs(seg), leaf.k)
    return idx + leaf.offset


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6),
         donate_argnums=(8, 9, 10))
def _compress(nm, leaves, method, K, momentum, innovation_frac, mu_pad,
              ae, us, vs, gs, step):
    """One compressed-phase round over K nodes.

    us, vs, gs: (K, n).  Returns (global gradient (n,), us, vs)."""
    n = gs.shape[1]
    us = momentum * us + gs
    vs = vs + us
    comp = [l for l in leaves if l.role == "compressed"]
    last = [l for l in leaves if l.role == "topk_only"]
    dense = [l for l in leaves if l.role == "dense"]
    mu = sum(l.k for l in comp)

    def support_of(v):
        idx = jnp.concatenate([_topk_leaf(v, l) for l in comp]
                              + [jnp.full((mu_pad - mu,), n, jnp.int32)])
        return jnp.sort(idx.astype(jnp.int32))

    leader = step % K
    supports = jax.vmap(support_of)(vs)                          # (K, mu_pad)
    support = supports[leader]
    safe = jnp.minimum(support, n - 1)
    vals = jnp.where(support < n, vs[:, safe], 0.0)              # (K, mu_pad)
    if method == "lgc_ps":
        k_inv = max(1, int(round(mu_pad * innovation_frac)))

        def innovation(x):
            _, ii = jax.lax.top_k(jnp.abs(x), k_inv)
            return jnp.zeros_like(x).at[ii].set(x[ii])
        innos = jax.vmap(innovation)(vals)
        z = ae_encode(nm, ae, vals[leader])
        recs = [ae_decode(nm, jax.tree_util.tree_map(lambda a: a[i],
                                                     ae["decoder"]),
                          z, innos[i]) for i in range(K)]
        rec = sum(recs) / K
    elif method == "lgc_rar":
        z = sum(ae_encode(nm, ae, vals[i]) for i in range(K)) / K
        rec = ae_decode(nm, ae["decoder"], z)
    else:
        raise ValueError(method)
    g = jnp.zeros((n,), jnp.float32).at[support].add(rec, mode="drop")
    for l in dense:
        seg = jax.lax.dynamic_slice_in_dim(gs, l.offset, l.size, axis=1)
        g = jax.lax.dynamic_update_slice_in_dim(g, seg.mean(0), l.offset, 0)
    clear = [support]
    if last:
        def last_of(v):
            idx = jnp.concatenate([_topk_leaf(v, l) for l in last])
            return idx.astype(jnp.int32)
        lidx = jax.vmap(last_of)(vs)                            # (K, k_last)
        lvals = jnp.take_along_axis(vs, lidx, axis=1)
        g = g.at[lidx.reshape(-1)].add(lvals.reshape(-1) / K)
        us = jax.vmap(lambda u, i: u.at[i].set(0.0))(us, lidx)
        vs = jax.vmap(lambda v, i: v.at[i].set(0.0))(vs, lidx)
    us = us.at[:, support].set(0.0, mode="drop")
    vs = vs.at[:, support].set(0.0, mode="drop")
    return g, us, vs


# ---------------------------------------------------------------------------
# optimizer


def lr_at(opt: dict, step: int) -> float:
    """Cosine schedule with linear warm-up over ``opt["steps"]``."""
    base, total, warm = opt["lr"], opt["steps"], opt["warmup_steps"]
    if step < warm:
        return base * min(step / max(warm, 1), 1.0)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * 0.5 * (1.0 + math.cos(math.pi * t))


def _round_to(x, dtype: str):
    """Round float32 ``x`` to ``dtype``'s precision, keeping float32.
    ``reduce_precision`` is used because XLA may drop a
    convert-to-narrower-and-back pair on the TPU."""
    fi = jnp.finfo(jnp.dtype(dtype))
    if fi.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3, 4))
def _adamw(hyper, stores, params, m, v, g, t, lr):
    """One AdamW step in float32; each weight is then stored at its
    configured dtype (``stores``, in leaf order), as the configuration
    states: there is no float32 master copy."""
    b1, b2, eps, wd = hyper
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    leaves = jax.tree_util.tree_leaves
    new_p, new_m, new_v = [], [], []
    for p, mm, vv, gg, store in zip(leaves(params), leaves(m), leaves(v),
                                    leaves(g), stores):
        mm = b1 * mm + (1 - b1) * gg
        vv = b2 * vv + (1 - b2) * gg * gg
        d = (mm / c1) / (jnp.sqrt(vv / c2) + eps) + wd * p
        new_p.append(_round_to(p - lr * d, store))
        new_m.append(mm)
        new_v.append(vv)
    tdef = jax.tree_util.tree_structure(params)
    return tuple(tdef.unflatten(x) for x in (new_p, new_m, new_v))


# ---------------------------------------------------------------------------
# the reference run


def _flat(tree_paths_leaves):
    return jnp.concatenate([jnp.ravel(x) for _, x in tree_paths_leaves])


def _unflat(vec, shapes):
    out, off = {}, 0
    for path, shape in shapes:
        size = int(np.prod(shape)) if shape else 1
        out[path] = vec[off:off + size].reshape(shape)
        off += size
    return tree_from_paths(out)


def leaf_norms(tree) -> np.ndarray:
    return np.array([float(jnp.linalg.norm(jnp.ravel(x)))
                     for _, x in leaf_paths(tree)])


def segment_norms(vec, leaves) -> np.ndarray:
    return np.array([float(jnp.linalg.norm(
        jax.lax.dynamic_slice_in_dim(vec, l.offset, l.size)))
        for l in leaves])


FAULTS = (None, "half_batch", "no_exchange")


def run(nm: Numerics, model: dict, train: dict, params0: dict, ae,
        batches, start_step: int, fault=None, detail: bool = False,
        bench_dir: Path = spec.BENCH_DIR) -> dict:
    """One training step of the reference per batch, from ``params0``.

    ``train``: {"method", "nodes", "sparsity", "momentum",
    "innovation_sparsity", "optimizer": {...}}.  ``batches``: host arrays
    {"tokens", "labels"} (B, S), one per step.  Returns the readings the
    check compares: each step's loss, the first step's global gradient
    per leaf, the parameters' change after the last step per leaf, and
    the error-feedback state per leaf after the last step.

    ``fault`` plants one of the faults the check has to catch:
    "half_batch" (each node's gradient and loss over half of its rows)
    or "no_exchange" (node 0 applies its own gradient alone; the readings
    are node 0's).

    ``detail`` adds, for a look at where a gap comes from: node 0's
    zeroed error-feedback elements after each step (``v_zero``, the
    elements sent), how many elements of each leaf changed (``moved``)
    and the weights after the last step (``final``, host arrays in leaf
    order).

    ``bench_dir`` holds the model kinds (``kinds/<kind>.py``)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    stores = tuple(str(np.asarray(x).dtype)
                   for x in jax.tree_util.tree_leaves(params0))
    params = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32),
                                    params0)
    shapes = [(p, tuple(x.shape)) for p, x in leaf_paths(params)]
    K = train["nodes"]
    method = train["method"]
    opt = train["optimizer"]
    hyper = (opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"])
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    lay = tuple(layout(shapes, train["sparsity"]))
    n = sum(l.size for l in lay)
    mu = sum(l.k for l in lay if l.role == "compressed")
    mu_pad = -(-mu // AE_ALIGN) * AE_ALIGN
    us = vs = None
    K_ex = 1 if fault == "no_exchange" else K
    if method != "none":
        us = jnp.zeros((K_ex, n), jnp.float32)
        vs = jnp.zeros((K_ex, n), jnp.float32)
    losses, first_grad, v_zero = [], None, []
    for i, b in enumerate(batches):
        step = start_step + i
        grads, loss = node_grads(nm, model, params, jnp.asarray(b["tokens"]),
                                 jnp.asarray(b["labels"]), K,
                                 half=fault == "half_batch",
                                 bench_dir=bench_dir)
        losses.append(loss)
        if fault == "no_exchange":
            grads = grads[:1]
        if method == "none":
            g = grads[0]
            for extra in grads[1:]:
                g = _acc(g, extra)
            g = jax.tree_util.tree_map(lambda x: x / len(grads), g)
        else:
            gs = jnp.stack([_flat(leaf_paths(gg)) for gg in grads])
            del grads
            gflat, us, vs = _compress(
                nm, lay, method, K_ex, train["momentum"],
                train["innovation_sparsity"] / train["sparsity"], mu_pad,
                ae, us, vs, gs, step)
            del gs
            if detail:
                v_zero.append(np.asarray(vs[0] == 0))
            g = _unflat(gflat, shapes)
        if i == 0:
            first_grad = leaf_norms(g)
        params, m, v = _adamw(hyper, stores, params, m, v, g,
                              float(step + 1), lr_at(opt, step))
    change = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - jnp.asarray(b, jnp.float32), params, params0))
    # the leaves whose global gradient is the exchanged gradient itself,
    # not a reconstruction through the autoencoder
    raw = np.array([method == "none" or l.role != "compressed"
                    for l in lay])
    # top-k picks per step of each leaf (0: every element is sent)
    picks = np.array([0 if method == "none" or l.role == "dense" else l.k
                      for l in lay])
    out = {"loss": np.array(losses), "grad": first_grad, "change": change,
           "raw": raw, "picks": picks, "paths": [p for p, _ in shapes]}
    if method != "none":
        out["ef_u"] = np.stack([segment_norms(us[i], lay)
                                for i in range(K_ex)])
        out["ef_v"] = np.stack([segment_norms(vs[i], lay)
                                for i in range(K_ex)])
    if detail:
        out["v_zero"] = v_zero
        out["moved"] = np.array([int(x) for _, x in leaf_paths(
            jax.tree_util.tree_map(
                lambda a, b: jnp.sum(a != jnp.asarray(b, jnp.float32)),
                params, params0))])
        out["final"] = [np.asarray(x) for _, x in leaf_paths(params)]
    return out
