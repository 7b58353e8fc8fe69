"""Explicit collectives with per-collective wire-byte accounting.

``ring_allreduce`` implements the paper's ring-allreduce (Section V /
Fig. 8) as an explicit chunked schedule over ``lax.ppermute``: a
(K-1)-step reduce-scatter followed by a (K-1)-step all-gather, each step
moving one 1/K-sized chunk to the ring neighbour.  Unlike ``lax.psum``
(whose lowering XLA may or may not implement as a ring), the wire traffic
here is *structural*: exactly ``2*(K-1)/K * nbytes`` leaves each node per
reduction, and the module records it.

The ring family (all recorded at their real payload sizes):

  ring_allreduce            f32 wire, one ring per axis
  ring_allreduce_q8         int8 wire: payloads are int8 values + one f32
                            scale per ``scale_block`` values (quantize
                            before send, dequantize-accumulate after
                            receive, requantize to forward) — the
                            transport that makes ``lgc_rar_q8``'s 1-byte
                            rate claim real
  hierarchical_ring_allreduce  intra-pod reduce-scatter → inter-pod
                            ring(s) of the owned 1/K_intra shard →
                            intra-pod all-gather; the inter stage moves
                            K_intra× fewer bytes than chaining full rings
  all_gather_packed         ring circulation of a packed sparse payload
                            (bit-packed index words + int8 values +
                            per-block f32 scales): the sparse top-k
                            exchanges at their real packed size instead
                            of raw f32 values + int32 indices
  broadcast / ring_broadcast  accounted one-to-all at (K-1)/K·nbytes —
                            the leader's index-set exchange is a
                            broadcast, NOT a 2(K-1)/K allreduce
  ring_broadcast_packed     the same one-to-all forwarding for a packed
                            multi-array payload (the leader index set as
                            bucket counts + bit-packed low-bit words),
                            accounted at (K-1)/K of the packed bytes

Accounting semantics: shapes are static, so byte counts are recorded at
*trace* time into a module-level tally.  Each jit specialization records
its per-step bytes once; call :func:`reset_wire_tally` before building a
step and :func:`wire_report` after to read "bytes on the wire per
executed step".  Re-tracing without a reset double-counts — the launchers
reset per phase build.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import quantize as Q

AxisName = Union[str, Sequence[str]]

_tally = threading.local()


def _tally_dict() -> Dict[str, float]:
    if not hasattr(_tally, "d"):
        _tally.d = {}
    return _tally.d


def _tally_ops() -> Dict[str, Dict[str, float]]:
    if not hasattr(_tally, "ops"):
        _tally.ops = {}
    return _tally.ops


@contextlib.contextmanager
def wire_op(label: str):
    """Attribute wire bytes recorded inside the block to plan op
    ``label`` (the executor wraps each transport call in one of these,
    which is what feeds ``wire_report(by_op=True)``).  The executor
    enters the device scope ``exchange/<label>`` beside it, so the same
    label names the op's bytes and its device operations in a trace."""
    prev = getattr(_tally, "label", None)
    _tally.label = label
    try:
        yield
    finally:
        _tally.label = prev


def current_wire_op() -> Union[str, None]:
    """The plan-op label of the innermost active :func:`wire_op` block,
    or None outside the executor (the chaos layer reads this to target
    and tally faults per op)."""
    return getattr(_tally, "label", None)


def record_wire_bytes(kind: str, nbytes: float) -> None:
    if not nbytes:          # zero-length payloads create no tally entry
        return
    d = _tally_dict()
    d[kind] = d.get(kind, 0.0) + float(nbytes)
    label = getattr(_tally, "label", None)
    if label is not None:
        per_op = _tally_ops().setdefault(label, {})
        per_op[kind] = per_op.get(kind, 0.0) + float(nbytes)


def reset_wire_tally() -> None:
    _tally_dict().clear()
    _tally_ops().clear()


def wire_report(by_op: bool = False):
    """Per-node wire bytes recorded since the last reset.

    Default: ``{collective kind: bytes}`` (the historical report, key
    set unchanged).  ``by_op=True``: ``{plan op label: {kind: bytes}}``
    — only bytes recorded under :func:`wire_op` appear, so a byte
    regression names the exchange op that drifted."""
    if by_op:
        return {label: dict(kinds) for label, kinds in _tally_ops().items()}
    return dict(_tally_dict())


def _axes_tuple(axis: AxisName) -> tuple:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _nbytes(x) -> int:
    return int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize if x.shape \
        else jnp.dtype(x.dtype).itemsize


# ---------------------------------------------------------------------------
# accounted wrappers around lax collectives (MeshTransport uses these)


def psum(x, axis: AxisName):
    K = jax.lax.axis_size(_axes_tuple(axis))
    # bandwidth-optimal allreduce moves 2*(K-1)/K of the buffer per node
    record_wire_bytes("all_reduce", 2 * (K - 1) / max(K, 1) * _nbytes(x))
    return jax.lax.psum(x, _axes_tuple(axis))


def pmean(x, axis: AxisName):
    K = jax.lax.axis_size(_axes_tuple(axis))
    record_wire_bytes("all_reduce", 2 * (K - 1) / max(K, 1) * _nbytes(x))
    return jax.lax.pmean(x, _axes_tuple(axis))


def all_gather(x, axis: AxisName, K: Optional[int] = None):
    """all_gather with a collapsed (K, ...) leading axis and accounting."""
    axes = _axes_tuple(axis)
    size = K if K is not None else jax.lax.axis_size(axes)
    record_wire_bytes("all_gather", (size - 1) * _nbytes(x))
    g = jax.lax.all_gather(x, axes, tiled=False)
    return g.reshape((size,) + x.shape)


# ---------------------------------------------------------------------------
# explicit ring allreduce (f32 wire)


def _ring_fwd(K):
    return [(s, (s + 1) % K) for s in range(K)]


def _to_chunks(x: jnp.ndarray, K: int):
    """Flatten + zero-pad to a multiple of K -> ((K, chunk), n_orig)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % K
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(K, -1), n


def _ppermute_chunked(x, axis, perm, max_elems: Optional[int] = None):
    """``lax.ppermute`` of a 1-D payload, optionally split into
    ceil(size/max_elems) messages — the pipelining-granularity knob the
    hierarchical transport tunes per ring level.  Bytes and numerics are
    unchanged; only the message count differs."""
    if not max_elems or x.shape[0] <= max_elems:
        return jax.lax.ppermute(x, axis, perm)
    pieces = []
    for s in range(0, x.shape[0], max_elems):
        e = min(s + max_elems, x.shape[0])
        pieces.append(jax.lax.ppermute(
            jax.lax.slice_in_dim(x, s, e), axis, perm))
    return jnp.concatenate(pieces)


def bucket_widths(c: int, n_buckets: int):
    """The bucket split rule shared by the executor and the pricers:
    ``c`` payload columns under a requested ``n_buckets`` -> (B, cb) —
    ``B`` equal buckets of ``cb`` columns each (the payload is padded to
    ``B*cb``).  ``cb = ceil(c / min(n_buckets, c))`` and then
    ``B = ceil(c / cb)`` drops all-padding buckets, so every bucket
    carries at least one real column and the shapes stay static/equal
    (the ``lax.fori`` pipeline requirement).  B == 1 means "don't
    bucket" — callers take the historical unbucketed path bit for bit."""
    if c <= 0:
        return 1, c
    B0 = max(1, min(int(n_buckets), c))
    cb = -(-c // B0)
    return -(-c // cb), cb


def _record_bucket_bytes(kind: str, nbytes: float, bucket: int) -> None:
    """Per-bucket trace-time recording: bytes land in the global
    per-kind tally as usual, but the per-op row is the active op label
    suffixed ``#b<bucket>`` — the labels ``wire_terms_by_op`` predicts
    for a bucketed plan.  Recording happens HOST-side, outside the
    ``lax.fori`` pipeline body (whose trace runs once, not once per
    bucket), which is why every bucketed collective records its B
    buckets in a plain Python loop before issuing the pipeline."""
    label = current_wire_op()
    if label is None:
        record_wire_bytes(kind, nbytes)
        return
    with wire_op(f"{label}#b{bucket}"):
        record_wire_bytes(kind, nbytes)


def _sw_pipeline(B: int, prep, move, out_shapes):
    """The software pipeline driving every bucketed collective: one
    ``lax.fori_loop`` over buckets in which iteration ``b`` issues
    ``move(staged_b)`` (the bucket's ppermute hop chain) alongside
    ``nxt = prep(b+1)`` (the NEXT bucket's encode / reduce-scatter) —
    the two are data-independent inside the body, which is exactly the
    freedom XLA needs to overlap compression compute with wire time.

    prologue   staged = prep(0)
    body b     nxt = prep(b+1); out[b] = move(staged); staged = nxt
    epilogue   out[B-1] = move(staged)

    Every prep and every move runs exactly once (no wasted hops).
    ``prep(b)`` takes a (possibly traced) bucket index; ``move`` maps
    the staged pytree to a result pytree shaped like ``out_shapes``
    (a pytree of ShapeDtypeStruct for ONE bucket).  Returns the results
    stacked on a new leading (B,) axis."""
    tmap = jax.tree_util.tree_map
    if B == 1:
        return tmap(lambda a: a[None], move(prep(0)))
    bufs = tmap(lambda s: jnp.zeros((B,) + s.shape, s.dtype), out_shapes)

    def body(b, carry):
        staged, bufs = carry
        nxt = prep(b + 1)
        res = move(staged)
        bufs = tmap(lambda buf, r: jax.lax.dynamic_update_index_in_dim(
            buf, r, b, 0), bufs, res)
        return nxt, bufs

    staged, bufs = jax.lax.fori_loop(0, B - 1, body, (prep(0), bufs))
    res = move(staged)
    return tmap(lambda buf, r: jax.lax.dynamic_update_index_in_dim(
        buf, r, B - 1, 0), bufs, res)


def _ring_reduce_scatter(chunks, axis, i, K, max_chunk_elems=None):
    """(K-1) forward hops; returns this node's fully-reduced chunk —
    node i ends up owning chunk (i+1) mod K."""
    fwd = _ring_fwd(K)

    def chunk_at(j):
        return jax.lax.dynamic_index_in_dim(chunks, j % K, 0, keepdims=False)

    send = chunk_at(i)
    for t in range(K - 1):
        recv = _ppermute_chunked(send, axis, fwd, max_chunk_elems)
        send = recv + chunk_at(i - t - 1)
    return send


def _ring_all_gather(send, axis, i, K, max_chunk_elems=None):
    """Circulate the completed chunks; returns the full (K, chunk) table
    (slot j = reduced chunk j, identical on every node)."""
    fwd = _ring_fwd(K)
    out = jnp.zeros((K,) + send.shape, send.dtype)
    out = jax.lax.dynamic_update_index_in_dim(out, send, (i + 1) % K, 0)
    for t in range(K - 1):
        send = _ppermute_chunked(send, axis, fwd, max_chunk_elems)
        out = jax.lax.dynamic_update_index_in_dim(out, send, (i - t) % K, 0)
    return out


def ring_allreduce(x: jnp.ndarray, axis: str, op: str = "add",
                   max_chunk_elems: Optional[int] = None,
                   kind: Optional[str] = "ring_allreduce",
                   n_buckets: int = 1) -> jnp.ndarray:
    """Chunked ring allreduce of ``x`` over manual mesh axis ``axis``.

    Must run inside a shard_map that binds ``axis`` manually.  Works for
    any shape (flattened internally, zero-padded to a multiple of K).
    ``op``: "add" or "mean".  ``max_chunk_elems`` splits each hop's
    payload into multiple ppermute messages (bytes unchanged); ``kind``
    is the wire-tally key (the hierarchical ring relabels its stages;
    ``None`` suppresses recording — a pipelined caller that already
    recorded the bytes host-side).  ``n_buckets`` > 1 splits the (K, c)
    chunk matrix into :func:`bucket_widths` column buckets and software-
    pipelines them (:func:`_sw_pipeline`): bucket b's all-gather hops
    issue while bucket b+1 reduce-scatters.  Columns keep their row
    (node-accumulation order), so the result is BIT-identical to the
    unbucketed schedule at any bucket count — given identical input
    bits reaching the ring.  (One backend caveat: when the input is a
    bare multiply fused into this jit — in practice only the q8
    fake-dequant — the CPU backend FMA-contracts it into the first
    reduce-scatter add differently across program shapes, a ~1 ULP
    effect outside the schedule; see DESIGN.md "The overlapped
    exchange".)  The only byte cost is the bucket-pad columns (priced
    per bucket, see ``plan.padding_overhead_terms``).
    """
    assert op in ("add", "mean"), op
    K = jax.lax.axis_size(axis)
    if K == 1:
        return x
    i = jax.lax.axis_index(axis)
    chunks, n = _to_chunks(x, K)
    c = chunks.shape[1]
    isz = jnp.dtype(x.dtype).itemsize
    B, cb = bucket_widths(c, n_buckets)
    if B == 1:
        if kind is not None:
            record_wire_bytes(kind, 2 * (K - 1) * c * isz)
        send = _ring_reduce_scatter(chunks, axis, i, K, max_chunk_elems)
        out = _ring_all_gather(send, axis, i, K, max_chunk_elems)
        res = out.reshape(-1)[:n].reshape(x.shape)
        return res / K if op == "mean" else res
    if B * cb > c:
        chunks = jnp.pad(chunks, ((0, 0), (0, B * cb - c)))
    if kind is not None:
        for b in range(B):
            _record_bucket_bytes(kind, 2 * (K - 1) * cb * isz, b)

    def prep(b):
        blk = jax.lax.dynamic_slice_in_dim(chunks, b * cb, cb, axis=1)
        return _ring_reduce_scatter(blk, axis, i, K, max_chunk_elems)

    def move(send):
        return _ring_all_gather(send, axis, i, K, max_chunk_elems)

    tables = _sw_pipeline(B, prep, move,
                          jax.ShapeDtypeStruct((K, cb), chunks.dtype))
    # (B, K, cb) bucket-major -> (K, B*cb) column order, drop the pad
    out = jnp.moveaxis(tables, 0, 1).reshape(K, B * cb)
    res = out[:, :c].reshape(-1)[:n].reshape(x.shape)
    return res / K if op == "mean" else res


def ring_allreduce_multi(x: jnp.ndarray, axes: Sequence[str],
                         op: str = "add", n_buckets: int = 1) -> jnp.ndarray:
    """Ring allreduce over several mesh axes by chaining one full-length
    ring per axis.  See :func:`hierarchical_ring_allreduce` for the
    cheaper intra/inter-pod form."""
    out = x
    for ax in axes:
        out = ring_allreduce(out, ax, op="add", n_buckets=n_buckets)
    if op == "mean":
        K = jax.lax.axis_size(tuple(axes))
        out = out / K
    return out


# ---------------------------------------------------------------------------
# int8-wire ring allreduce


def ring_allreduce_q8(x: jnp.ndarray, axis: str, op: str = "add",
                      scale_block: int = Q.SCALE_BLOCK,
                      n_buckets: int = 1) -> jnp.ndarray:
    """Ring allreduce whose ``ppermute`` payloads are int8 values + one
    f32 scale per ``scale_block`` values — the wire really moves ~1
    byte/value (+ scale overhead), and the tally records exactly that.

    Reduce-scatter hops quantize the partial sum before each send and
    dequantize-accumulate after each receive (quantize-forward), so the
    error compounds over the K-1 hops; the completed chunk is then
    quantized ONCE and the same int8 payload circulates through the
    all-gather, so every node — the owner included — decodes the
    identical value and the result stays exactly replicated.  Worst-case
    per-value error after ``op="mean"`` is bounded by
    ``K/(2·127) · max_block|partial sums|`` (K-1 requantizations + 1
    all-gather quantization, each ≤ scale/2, all divided by K).

    With K == 1 no bytes move, but the value still passes through one
    quantize→dequantize roundtrip so the "consumers see a quantized
    value" contract is K-independent (matching the float-wire
    transports' fake quantization).

    ``n_buckets`` > 1 pipelines :func:`bucket_widths` column buckets of
    the chunk matrix: bucket b+1's reduce-scatter (each hop a real
    quantize — the encode compute) runs while bucket b's quantize-once
    int8 payload circulates through the all-gather.  The scale blocks
    re-group per bucket, so the bucketed result differs from the
    unbucketed one only within the documented q8 bound.
    """
    assert op in ("add", "mean"), op
    assert jnp.issubdtype(x.dtype, jnp.floating), x.dtype
    K = jax.lax.axis_size(axis)
    if K == 1:
        return Q.fake_quantize(x, scale_block)
    i = jax.lax.axis_index(axis)
    chunks, n = _to_chunks(x.astype(jnp.float32), K)
    c = chunks.shape[1]
    fwd = _ring_fwd(K)
    B, cb = bucket_widths(c, n_buckets)

    def _rs_quantized(blk, width):
        """Quantize-forward reduce-scatter of one (K, width) chunk
        matrix -> the completed chunk quantized ONCE: the staged int8
        wire payload the all-gather circulates."""
        def chunk_at(j):
            return jax.lax.dynamic_index_in_dim(blk, j % K, 0,
                                                keepdims=False)
        send = chunk_at(i)
        for t in range(K - 1):
            q, s = Q.quantize_i8(send, scale_block)
            q = jax.lax.ppermute(q, axis, fwd)
            s = jax.lax.ppermute(s, axis, fwd)
            send = Q.dequantize_i8(q, s, width) + chunk_at(i - t - 1)
        return Q.quantize_i8(send, scale_block)

    def _ag_quantized(qs, width):
        """Circulate the staged int8 payload unchanged; every node —
        the owner included — decodes identically, so the result stays
        exactly replicated."""
        q, s = qs
        out = jnp.zeros((K, width), jnp.float32)
        out = jax.lax.dynamic_update_index_in_dim(
            out, Q.dequantize_i8(q, s, width), (i + 1) % K, 0)
        for t in range(K - 1):
            q = jax.lax.ppermute(q, axis, fwd)
            s = jax.lax.ppermute(s, axis, fwd)
            out = jax.lax.dynamic_update_index_in_dim(
                out, Q.dequantize_i8(q, s, width), (i - t) % K, 0)
        return out

    if B == 1:
        record_wire_bytes("ring_allreduce_q8",
                          2 * (K - 1) * Q.wire_nbytes(c, scale_block))
        out = _ag_quantized(_rs_quantized(chunks, c), c)
        res = out.reshape(-1)[:n].reshape(x.shape)
        return res / K if op == "mean" else res

    if B * cb > c:
        chunks = jnp.pad(chunks, ((0, 0), (0, B * cb - c)))
    for b in range(B):
        _record_bucket_bytes("ring_allreduce_q8",
                             2 * (K - 1) * Q.wire_nbytes(cb, scale_block), b)

    def prep(b):
        blk = jax.lax.dynamic_slice_in_dim(chunks, b * cb, cb, axis=1)
        return _rs_quantized(blk, cb)

    def move(qs):
        return _ag_quantized(qs, cb)

    tables = _sw_pipeline(
        B, prep, move, jax.ShapeDtypeStruct((K, cb), jnp.float32))
    out = jnp.moveaxis(tables, 0, 1).reshape(K, B * cb)
    res = out[:, :c].reshape(-1)[:n].reshape(x.shape)
    return res / K if op == "mean" else res


def ring_allreduce_q8_multi(x: jnp.ndarray, axes: Sequence[str],
                            op: str = "add",
                            scale_block: int = Q.SCALE_BLOCK,
                            n_buckets: int = 1) -> jnp.ndarray:
    """Chained per-axis int8 rings (mean divides once at the end so the
    intermediate sums keep full int8 range)."""
    out = x
    for ax in axes:
        out = ring_allreduce_q8(out, ax, op="add", scale_block=scale_block,
                                n_buckets=n_buckets)
    if op == "mean":
        out = out / jax.lax.axis_size(tuple(axes))
    return out


# ---------------------------------------------------------------------------
# hierarchical (intra-pod / inter-pod) ring allreduce


def hierarchical_ring_allreduce(x: jnp.ndarray, axes: Sequence[str],
                                op: str = "add",
                                intra_chunk_elems: Optional[int] = None,
                                inter_chunk_elems: Optional[int] = None,
                                n_buckets: int = 1) -> jnp.ndarray:
    """Hierarchical allreduce over multi-axis dp meshes: reduce-scatter
    on the *intra-pod* axis (the LAST of ``axes`` — the fastest-varying,
    highest-bandwidth one), ring-allreduce the owned 1/K_intra shard over
    the remaining (inter-pod) axes, then all-gather intra-pod.

    vs chaining full-length rings per axis (``ring_allreduce_multi``)
    the inter-pod stage moves K_intra× fewer bytes:

        chained:      Σ_a 2(K_a-1)/K_a · nbytes
        hierarchical: 2(K₁-1)/K₁ · nbytes  +  Σ_inter 2(K_a-1)/K_a · nbytes/K₁

    ``intra_chunk_elems`` / ``inter_chunk_elems`` independently cap the
    per-message payload of each ring level (pipelining granularity; bytes
    unchanged).  With a single axis this IS ``ring_allreduce`` — same
    schedule, bit-identical result.  Wire bytes are recorded under
    ``ring_hier_intra`` / ``ring_hier_inter``.

    ``n_buckets`` > 1 on a two-axis mesh software-pipelines the three
    stages per bucket: bucket b+1's intra reduce-scatter runs while
    bucket b moves through the inter ring + intra all-gather.  A bucket
    is a column range of the INTER chunk matrix (the finest level that
    re-chunks), gathered out of the intra chunk matrix so every
    element keeps its chunk row at BOTH levels — which is what keeps the
    bucketed result bit-identical to the unbucketed schedule.  With
    three or more axes the chained inter rings re-chunk the full shard
    per axis and no bucket-compatible column partition exists, so the
    exchange runs unbucketed (documented fallback).
    """
    assert op in ("add", "mean"), op
    axes = tuple(axes)
    if not axes:
        return x
    if len(axes) == 1:
        return ring_allreduce(x, axes[0], op=op,
                              max_chunk_elems=intra_chunk_elems,
                              n_buckets=n_buckets)
    intra = axes[-1]
    K1 = jax.lax.axis_size(intra)
    i1 = jax.lax.axis_index(intra)
    chunks, n = _to_chunks(x, K1)
    c = chunks.shape[1]
    isz = jnp.dtype(x.dtype).itemsize
    B = 1
    if len(axes) == 2:
        Ka = jax.lax.axis_size(axes[0])
        ca = -(-c // Ka)
        B, cab = bucket_widths(ca, n_buckets)
    if B == 1:
        if K1 > 1:
            record_wire_bytes("ring_hier_intra", 2 * (K1 - 1) * c * isz)
        shard = _ring_reduce_scatter(chunks, intra, i1, K1,
                                     intra_chunk_elems)
        for ax in axes[:-1]:
            shard = ring_allreduce(shard, ax, op="add",
                                   max_chunk_elems=inter_chunk_elems,
                                   kind="ring_hier_inter")
        out = _ring_all_gather(shard, intra, i1, K1, intra_chunk_elems)
        res = out.reshape(-1)[:n].reshape(x.shape)
        if op == "mean":
            res = res / jax.lax.axis_size(axes)
        return res

    # two-level bucketed pipeline: bucket b = inter columns
    # [b*cab, (b+1)*cab), i.e. shard positions {ra*ca + col} — gathered
    # so element -> chunk-row is preserved at both ring levels
    ia = jax.lax.axis_index(axes[0])
    for b in range(B):
        if K1 > 1:
            _record_bucket_bytes("ring_hier_intra",
                                 2 * (K1 - 1) * Ka * cab * isz, b)
        if Ka > 1:
            _record_bucket_bytes("ring_hier_inter",
                                 2 * (Ka - 1) * cab * isz, b)
    # pad to the full (Ka, ca) shard grid + one dummy zero column that
    # absorbs the bucket-pad gathers of the last (short) bucket
    grid = jnp.pad(chunks, ((0, 0), (0, Ka * ca + 1 - c)))
    rows = jnp.arange(Ka, dtype=jnp.int32)[:, None]

    def prep(b):
        cols = b * cab + jnp.arange(cab, dtype=jnp.int32)[None, :]
        gid = jnp.where(cols < ca, rows * ca + cols, Ka * ca)
        blk = jnp.take(grid, gid.reshape(-1), axis=1)   # (K1, Ka*cab)
        return _ring_reduce_scatter(blk, intra, i1, K1, intra_chunk_elems)

    def move(piece):
        blk = piece.reshape(Ka, cab)                    # inter chunk rows
        red = _ring_reduce_scatter(blk, axes[0], ia, Ka,
                                   inter_chunk_elems)
        full = _ring_all_gather(red, axes[0], ia, Ka, inter_chunk_elems)
        return _ring_all_gather(full.reshape(-1), intra, i1, K1,
                                intra_chunk_elems)      # (K1, Ka*cab)

    tables = _sw_pipeline(
        B, prep, move, jax.ShapeDtypeStruct((K1, Ka * cab), chunks.dtype))
    # (B, K1, Ka, cab) -> (K1, Ka, B*cab); the bucket-pad columns are
    # exactly the tail >= ca of each inter row
    out = jnp.transpose(tables.reshape(B, K1, Ka, cab), (1, 2, 0, 3))
    out = out.reshape(K1, Ka, B * cab)[:, :, :ca].reshape(K1, Ka * ca)
    res = out[:, :c].reshape(-1)[:n].reshape(x.shape)
    if op == "mean":
        res = res / jax.lax.axis_size(axes)
    return res


# ---------------------------------------------------------------------------
# packed sparse all-gather (ring circulation of an opaque payload)


def _circulate_packed(payload, axes: AxisName, record) -> tuple:
    """One full multi-axis ring circulation of a packed payload tuple
    -> (K_total, ...) arrays in linear node order.  ``record(K, nbytes)``
    is called per gathering axis (None = the caller already recorded)."""
    out = tuple(payload)
    for ax in reversed(_axes_tuple(axes)):
        K = jax.lax.axis_size(ax)
        if K == 1:
            out = tuple(p[None] for p in out)
            continue
        if record is not None:
            record(K, (K - 1) * sum(_nbytes(p) for p in out))
        i = jax.lax.axis_index(ax)
        fwd = _ring_fwd(K)
        stacks = [jax.lax.dynamic_update_index_in_dim(
            jnp.zeros((K,) + p.shape, p.dtype), p, i, 0) for p in out]
        send = list(out)
        for t in range(K - 1):
            send = [jax.lax.ppermute(p, ax, fwd) for p in send]
            src = (i - t - 1) % K          # whose payload just arrived
            stacks = [jax.lax.dynamic_update_index_in_dim(s, p, src, 0)
                      for s, p in zip(stacks, send)]
        out = tuple(stacks)
    # collapse the per-axis leading dims to one linear node axis
    lead = len(_axes_tuple(axes))
    return tuple(p.reshape((-1,) + p.shape[lead:]) for p in out)


def all_gather_packed(payload, axes: AxisName,
                      kind: str = "all_gather_packed", *,
                      encode_fn=None, n_buckets: int = 1):
    """Ring all-gather of a multi-array *packed* payload: every node's
    tuple of arrays (bit-packed index words, int8 values, f32 scales, …)
    circulates over K-1 ``ppermute`` hops per axis, and the tally
    records exactly the packed bytes that move — the collective that
    makes the sparse exchanges' ceil(log2 n)-bit + 1-byte/value
    accounting real (vs ``all_gather``'s raw f32+int32).

    Returns a tuple of (K, ...) arrays stacked in linear node order
    (row-major over ``axes``, matching :func:`all_gather`'s layout).
    Multi-axis meshes chain one circulation per axis, gathering the
    innermost (last) axis first; the summed bytes telescope to exactly
    ``(K-1) * payload_nbytes`` per node, same as a single-axis ring.

    Pipelined form: ``encode_fn(b) -> payload tuple`` (equal shapes for
    every bucket) with ``n_buckets`` > 1 ignores ``payload`` and runs
    the bucketed double-buffered schedule instead — bucket b+1's encode
    (quantize + bit-plane pack) runs while bucket b's payload circulates
    (:func:`_sw_pipeline`).  Returns (n_buckets, K, ...) arrays; bytes
    are recorded per bucket under ``<op label>#b<i>`` sub-labels."""
    if encode_fn is None or n_buckets <= 1:
        if encode_fn is not None:
            payload = encode_fn(0)
        return _circulate_packed(
            payload, axes, lambda K, nb: record_wire_bytes(kind, nb))
    B = int(n_buckets)
    staged0 = encode_fn(0)
    K_total = jax.lax.axis_size(_axes_tuple(axes))
    nbytes0 = sum(_nbytes(p) for p in staged0)
    # host-side per-bucket recording: per gathering axis, the payload
    # grows by the product of the already-gathered axis sizes
    mult = 1
    for ax in reversed(_axes_tuple(axes)):
        K = jax.lax.axis_size(ax)
        if K > 1:
            for b in range(B):
                _record_bucket_bytes(kind, (K - 1) * mult * nbytes0, b)
        mult *= K
    out_shapes = tuple(
        jax.ShapeDtypeStruct((K_total,) + p.shape, p.dtype)
        for p in staged0)

    def prep(b):
        return encode_fn(b)

    def move(staged):
        return _circulate_packed(staged, axes, None)

    return _sw_pipeline(B, prep, move, out_shapes)


# ---------------------------------------------------------------------------
# accounted one-to-all broadcast


def _bcast_bytes(x, axes) -> float:
    K = jax.lax.axis_size(_axes_tuple(axes))
    # a chain/tree broadcast sends K-1 copies total: (K-1)/K·nbytes per
    # node — NOT the 2(K-1)/K allreduce bytes a masked psum suggests
    return (K - 1) / max(K, 1) * _nbytes(x)


def broadcast(x, axes: AxisName, is_leader) -> jnp.ndarray:
    """Leader's ``x`` → all nodes, via the mesh idiom (lax has no
    broadcast primitive): psum of the one-hot-masked value.  Accounted at
    the broadcast cost (K-1)/K·nbytes — the wrapper exists so the index
    exchange is *named and priced* as a broadcast in the wire tally
    instead of masquerading as an all_reduce."""
    record_wire_bytes("broadcast", _bcast_bytes(x, axes))
    zero = jnp.zeros_like(x)
    return jax.lax.psum(jnp.where(is_leader, x, zero), _axes_tuple(axes))


def ring_broadcast(x, axes: AxisName, is_leader) -> jnp.ndarray:
    """Leader's ``x`` → all nodes over explicit ``ppermute`` forwarding:
    per axis, K-1 hops in which a node adopts the payload the first time
    it arrives from a holder.  SPMD makes every node send each hop, but
    only the holder-chain payloads carry information — a real broadcast
    sends K-1 messages total, which is what the tally records
    ((K-1)/K·nbytes per node, same price as :func:`broadcast`)."""
    axes_t = _axes_tuple(axes)
    record_wire_bytes("broadcast", _bcast_bytes(x, axes_t))
    buf = jnp.where(is_leader, x, jnp.zeros_like(x))
    have = jnp.asarray(is_leader).astype(jnp.int32)
    for ax in axes_t:
        K = jax.lax.axis_size(ax)
        fwd = _ring_fwd(K)
        for _ in range(K - 1):
            recv = jax.lax.ppermute(buf, ax, fwd)
            recv_have = jax.lax.ppermute(have, ax, fwd)
            take = (recv_have > 0) & (have == 0)
            buf = jnp.where(take, recv, buf)
            have = jnp.maximum(have, recv_have)
    return buf


def ring_broadcast_packed(payload: Sequence[jnp.ndarray], axes: AxisName,
                          is_leader, kind: str = "broadcast_packed"):
    """:func:`ring_broadcast` of a multi-array *packed* payload: the
    leader's tuple of arrays (index bucket counts + bit-packed low-bit
    words, or the raw-fallback indices) reaches every node over the same
    adopt-first-arrival ``ppermute`` forwarding, all arrays moving
    together so a node adopts a *consistent* payload.  The tally records
    the packed bytes at broadcast cost — (K-1)/K · Σ nbytes per node —
    under ``kind``: the collective that makes the leader index set's
    ceil(log2 n)-bit accounting real (vs :func:`ring_broadcast`'s raw
    int32)."""
    axes_t = _axes_tuple(axes)
    K_total = jax.lax.axis_size(axes_t)
    record_wire_bytes(kind, (K_total - 1) / max(K_total, 1)
                      * sum(_nbytes(p) for p in payload))
    bufs = [jnp.where(is_leader, p, jnp.zeros_like(p)) for p in payload]
    have = jnp.asarray(is_leader).astype(jnp.int32)
    for ax in axes_t:
        K = jax.lax.axis_size(ax)
        fwd = _ring_fwd(K)
        for _ in range(K - 1):
            recvs = [jax.lax.ppermute(b, ax, fwd) for b in bufs]
            recv_have = jax.lax.ppermute(have, ax, fwd)
            take = (recv_have > 0) & (have == 0)
            bufs = [jnp.where(take, r, b) for r, b in zip(recvs, bufs)]
            have = jnp.maximum(have, recv_have)
    return tuple(bufs)
