"""The Transport abstraction: *how bytes move between LGC nodes*.

The paper's two instantiations (parameter-server Fig. 5, ring-allreduce
Fig. 8) differ only in the communication substrate, never in the
compression math.  ``GradientCompressor`` is therefore written once
against this protocol, and the substrate is swapped per run:

  MeshTransport  lax collectives inside a fully-manual shard_map — the
                 production path (XLA chooses the allreduce algorithm).
  RingTransport  same execution context, but every cross-node reduction
                 routes through the explicit chunked ring schedule in
                 repro.dist.collectives, so the paper's ring pattern is
                 actually exercised and its wire bytes are *measured*
                 (see collectives.wire_report), not estimated.
  RingQ8Transport  RingTransport whose compressed-payload reductions
                 (``mean_q8``) ride a REAL int8 wire: quantize before
                 each ppermute hop, dequantize-accumulate after — the
                 transport that makes ``lgc_rar_q8``'s 1-byte/value rate
                 claim true in measured bytes.
  RingHierTransport  hierarchical intra-pod/inter-pod rings on
                 multi-axis dp meshes (last mesh axis = intra-pod), with
                 independently tunable per-level message chunking.
  RingPackedTransport  RingTransport whose sparse exchanges
                 (``sparse_mean_packed``/``sparse_gather_packed``) ride
                 the REAL packed wire: bit-packed indices (high bits as
                 a bucket histogram, low bits through the Pallas
                 bit-plane kernel) + int8 values + per-block f32 scales,
                 circulated over ppermute — the transport that makes the
                 sparse methods' ceil(log2 n)-bit + 1-byte/value rate
                 claim true in measured bytes.
  SimTransport   stacked (K, n) single-host arrays — the paper's own
                 several-nodes-per-GPU emulation; collectives become
                 axis-0 reductions and per-node compute becomes vmap.

Value convention: a *per-node* value is this node's shard under
Mesh/Ring and carries a leading K axis under Sim; a *global* value is
replicated under Mesh/Ring and unbatched under Sim.  ``pernode`` maps a
per-node function (in_axes marks which args are per-node, vmap-style);
``mean``/``sum``/``all_gather``/``from_leader``/``mean_q8``/
``sparse_mean_packed``/``sparse_gather_packed`` cross the node boundary
and return global values.  ``mean_q8`` reduces a value whose *wire
representation* is int8 + per-block f32 scales: real on
RingQ8Transport, fake-quantized (through the same
``repro.dist.quantize`` module) then reduced in f32 everywhere else — so
Sim(fake) == RingQ8(real) up to the wire's bounded requantization error.
The packed sparse pair keeps the *methods* exact instead: float-wire
transports ship the pairs untouched (f32 + int32, the pre-packed
behaviour, bit-exact reproductions of sparse_gd/dgc/lgc_ps), and ONLY
RingPackedTransport encodes through ``repro.dist.packed`` — indices
bit-exact, values paying the one documented q8 quantization.  Choosing
``ring_packed`` is what opts a run into that bounded error.  A
transport-equivalence test asserts all substrates produce identical
global gradients for all five methods (RingQ8/RingPacked within their
bounds).

Adding a transport = implementing these nine methods (see DESIGN.md).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Protocol, Sequence, Tuple, runtime_checkable

import jax
import jax.numpy as jnp

from repro.dist import chaos as CH
from repro.dist import collectives as C
from repro.dist import packed as PK
from repro.dist import quantize as Q

Axis = Sequence[str]


@runtime_checkable
class Transport(Protocol):
    kind: str                  # the make_transport key — what pricing keys on
    K: int
    ae_axes: Tuple[str, ...]

    def pernode(self, fn: Callable, in_axes=0) -> Callable: ...
    def mean(self, x): ...
    def sum(self, x): ...
    def all_gather(self, x): ...
    def from_leader(self, x, leader): ...
    def broadcast_packed(self, idx, leader, n: int, plan=None): ...
    def sparse_mean(self, vals, idx, n: int): ...
    def mean_q8(self, x): ...
    def sparse_gather_packed(self, vals, idx, n: int, plan=None): ...
    def sparse_mean_packed(self, vals, idx, n: int, plan=None): ...


def _scatter(vals, idx, n):
    return jnp.zeros((n,), vals.dtype).at[idx].add(vals, mode="drop")


# ===========================================================================


@dataclass(frozen=True)
class MeshTransport:
    """Per-node code runs as-is on this shard; cross-node ops are lax
    collectives over the (fully manually bound) ``axes``."""
    axes: Tuple[str, ...]
    K: int
    ae_axes: Tuple[str, ...] = ()
    node_index: Optional[jnp.ndarray] = None   # override for exotic callers
    scale_block: int = Q.SCALE_BLOCK           # int8-wire scale granularity
    interpret: bool | None = None              # None: from the platform
    guard: str = "off"                         # executor guard policy
    # bucketed-exchange schedule (ring family only): buckets per
    # exchange for the software-pipelined rotate-while-encode schedule;
    # 1 = the historical unbucketed path.  Mesh ignores it — the lax
    # collectives' lowering is opaque, there is no schedule to pipeline.
    wire_buckets: int = 1

    kind = "mesh"              # class attr, not a field: the pricing key

    def _index(self):
        if self.node_index is not None:
            return self.node_index
        idx = jnp.zeros((), jnp.int32)
        for ax in self.axes:
            idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        return idx

    def pernode(self, fn, in_axes=0):
        return fn

    def mean(self, x):
        return C.pmean(x, self.axes) if self.axes else x

    def sum(self, x):
        return C.psum(x, self.axes) if self.axes else x

    def all_gather(self, x):
        return C.all_gather(x, self.axes, self.K) if self.axes else x[None]

    def from_leader(self, x, leader):
        if not self.axes:
            return x
        return C.broadcast(x, self.axes, self._index() == leader)

    def broadcast_packed(self, idx, leader, n, plan=None):
        """Leader's *sorted* index set (k,) over [0, n] → all nodes.
        Here (and on every float wire) the set moves as the raw int32
        broadcast ``from_leader`` already prices; only
        RingPackedTransport re-routes it onto the packed index wire
        (bucket counts + bit-packed low bits) — which decodes bit-exact,
        so unlike the value-carrying packed exchanges this re-route
        changes bytes only, never numerics.  ``plan`` (an exchange-plan
        PackPlan) is the packed wire format to use; float wires ignore
        it."""
        return self.from_leader(idx, leader)

    def mean_q8(self, x):
        """Fake int8: quantize→dequantize per node through the shared
        quantize module, then the f32 reduction — the mesh wire still
        moves 4 bytes/value (and rate.py accounts it as such); only
        RingQ8Transport makes the int8 bytes real."""
        return self.mean(Q.fake_quantize(x, self.scale_block))

    def _sparse_gather(self, vals, idx, n):
        """(K, n) per-node dense scatters of the pairs over the raw
        f32 + int32 all_gather wire — the shared body of ``sparse_mean``
        and the base ``sparse_gather_packed`` (which only
        RingPackedTransport re-routes onto the packed wire)."""
        if not self.axes:
            if vals.shape[0] == 0:
                return jnp.zeros((1, n), vals.dtype)
            return _scatter(vals, idx, n)[None]
        if vals.shape[0] == 0:
            return jnp.zeros((self.K, n), vals.dtype)
        vals_g = self.all_gather(vals)
        idx_g = self.all_gather(idx)
        return jax.vmap(lambda vv, ii: _scatter(vv, ii, n))(vals_g, idx_g)

    def sparse_mean(self, vals, idx, n):
        """Mean of per-node sparse (vals, idx) as a dense (n,) vector,
        moving only K*k values+indices over the wire, not n.  Always the
        raw f32 wire — deliberately NOT routed through
        ``sparse_gather_packed``, so the packed transport's override
        never touches exchanges the compressor wants exact.  All K
        nodes' pairs scatter-add into ONE (n,) vector: the (K, n) stack
        of per-node scatters would hold K*n floats on every device."""
        if not self.axes:
            return _scatter(vals, idx, n)
        if vals.shape[0] == 0:
            return jnp.zeros((n,), vals.dtype)
        vals_g = self.all_gather(vals)
        idx_g = self.all_gather(idx)
        return _scatter(vals_g.reshape(-1), idx_g.reshape(-1), n) / self.K

    def sparse_gather_packed(self, vals, idx, n, plan=None):
        """Per-node dense scatters (K, n) of sparse pairs whose *wire
        representation* is packed (bit-packed indices + int8 values) on
        the packed transport.  Here the wire is f32 values + raw int32
        indices — EXACT, and the tally says so; only RingPackedTransport
        ships the packed bytes, whose values pay the documented q8
        bound.  Choosing the transport is what opts a run into that
        bounded error — the sparse methods stay bit-exact reproductions
        everywhere else."""
        return self._sparse_gather(vals, idx, n)

    def sparse_mean_packed(self, vals, idx, n, plan=None):
        """sparse_mean over the packed wire representation (here the
        raw wire: the same exchange as ``sparse_mean``)."""
        return self.sparse_mean(vals, idx, n)


@dataclass(frozen=True)
class RingTransport(MeshTransport):
    """MeshTransport with every cross-node reduction routed through the
    explicit chunked ring in repro.dist.collectives (chained per-axis
    rings on multi-axis dp meshes) and the leader exchange through the
    explicit ppermute-forwarding broadcast."""

    kind = "ring"

    def mean(self, x):
        return C.ring_allreduce_multi(x, self.axes, op="mean",
                                      n_buckets=self.wire_buckets) \
            if self.axes else x

    def sum(self, x):
        return C.ring_allreduce_multi(x, self.axes, op="add",
                                      n_buckets=self.wire_buckets) \
            if self.axes else x

    def from_leader(self, x, leader):
        if not self.axes:
            return x
        return C.ring_broadcast(x, self.axes, self._index() == leader)


@dataclass(frozen=True)
class RingQ8Transport(RingTransport):
    """RingTransport whose ``mean_q8`` rides the REAL int8 wire
    (collectives.ring_allreduce_q8: int8 payloads + one f32 scale per
    ``scale_block`` values, quantize-forward through the ring).  All
    other traffic — exempt-dense, exempt-last, index broadcast,
    all_gather — stays f32, matching rate.py, which only prices the
    encoding reduction at ~1 byte/value."""

    kind = "ring_q8"

    def mean_q8(self, x):
        if not self.axes:
            return Q.fake_quantize(x, self.scale_block)
        return C.ring_allreduce_q8_multi(x, self.axes, op="mean",
                                         scale_block=self.scale_block,
                                         n_buckets=self.wire_buckets)


@dataclass(frozen=True)
class RingHierTransport(RingTransport):
    """Hierarchical intra-pod/inter-pod rings: reduce-scatter on the LAST
    mesh axis (intra-pod), ring-allreduce the owned shard over the
    remaining axes, all-gather intra-pod — the inter stage moves
    K_intra× fewer bytes than RingTransport's chained full rings.
    ``intra_chunk``/``inter_chunk`` independently cap each level's
    per-message payload (0/None = one message per hop).  On a single dp
    axis this degenerates to exactly RingTransport's schedule."""
    intra_chunk: Optional[int] = None
    inter_chunk: Optional[int] = None

    kind = "ring_hier"

    def mean(self, x):
        return C.hierarchical_ring_allreduce(
            x, self.axes, op="mean", intra_chunk_elems=self.intra_chunk,
            inter_chunk_elems=self.inter_chunk,
            n_buckets=self.wire_buckets) if self.axes else x

    def sum(self, x):
        return C.hierarchical_ring_allreduce(
            x, self.axes, op="add", intra_chunk_elems=self.intra_chunk,
            inter_chunk_elems=self.inter_chunk,
            n_buckets=self.wire_buckets) if self.axes else x


@dataclass(frozen=True)
class RingPackedTransport(RingTransport):
    """RingTransport whose sparse exchanges ride the REAL packed wire:
    ``sparse_gather_packed`` encodes each node's (vals, idx) through
    ``repro.dist.packed`` (high index bits as a bucket histogram, low
    bits through the bit-plane pack kernel, values as int8 + per-block
    f32 scales) and circulates exactly that payload over
    ``collectives.all_gather_packed`` — measured at ~0.33x of the raw
    f32+int32 exchange at 1M params (CI-gated).  Indices decode
    bit-exact; values pay the wire's single quantization (error <= half
    the per-block scale — the transport gate's documented q8 bound vs
    the exact Sim oracle).  The lgc family's leader index set also rides
    the packed index wire (``broadcast_packed``: bucket counts +
    bit-packed low bits over ``ring_broadcast_packed``) — bit-exact, so
    it changes measured bytes only.  Dense reductions and plain
    all_gathers stay f32, matching rate.py, which re-prices exactly the
    packed exchanges on this wire."""

    kind = "ring_packed"

    def _decode_contrib(self, pj, plan, dtype, n):
        """Decode + scatter one received payload, masked out entirely
        when a guard policy is on and its structural validation fails
        (checksum, histogram sum, index bounds/monotonicity, finite
        scales) — the contribution stays in the sender's EF residual —
        with the bad count landing on the executing op's fault tally
        through the structural sink."""
        vj, ij = PK.decode_sparse(pj, plan, interpret=self.interpret)
        out = _scatter(vj.astype(dtype), ij, n)
        if self.guard != "off":
            ok, bad = PK.validate_payload(pj, plan,
                                          interpret=self.interpret)
            CH.report_structural(bad)
            out = jnp.where(ok, out, jnp.zeros_like(out))
        return out

    def sparse_mean_packed(self, vals, idx, n, plan=None):
        return self.sparse_gather_packed(vals, idx, n, plan=plan).mean(0)

    def sparse_gather_packed(self, vals, idx, n, plan=None):
        if not self.axes or vals.shape[0] == 0:
            return super().sparse_gather_packed(vals, idx, n)
        if plan is None:
            plan = PK.make_plan(n, vals.shape[0], self.scale_block)
        # an exchange-plan-supplied format must describe THIS exchange —
        # the same (n, k) the pricers priced
        assert plan.n == n and plan.k == vals.shape[0], (plan, n,
                                                         vals.shape)
        B = 1
        if not plan.raw_index:
            B, kb = C.bucket_widths(plan.k, self.wire_buckets)
        if B == 1:
            payload = PK.encode_sparse_fused(vals, idx, plan,
                                             interpret=self.interpret)
            gathered = C.all_gather_packed(payload, self.axes)
            return jnp.stack([
                self._decode_contrib(tuple(a[j] for a in gathered),
                                     plan, vals.dtype, n)
                for j in range(self.K)])   # K static; one decode/node
        # bucketed double-buffered schedule: sort ONCE, sentinel-pad to
        # B*kb pairs, and ship each bucket as a self-contained payload
        # (own histogram/scales/checksum — the priced bucket overhead)
        # so bucket b+1's fused encode runs under bucket b's hops
        sub = PK.bucket_plan(plan, kb)
        vals_s, idx_s = PK._sort_pairs(vals, idx)
        pad = B * kb - plan.k
        if pad:
            vals_s = jnp.concatenate(
                [vals_s, jnp.zeros((pad,), vals_s.dtype)])
            idx_s = jnp.concatenate(
                [idx_s, jnp.full((pad,), n, jnp.int32)])

        if CH.structural_sink_active():
            # guarded runs encode eagerly (host loop): the composed
            # encoder's non-finite reports cannot cross the fori-loop
            # pipeline boundary, and fault events must not be lost.
            # Circulation still pipelines; only the encode overlap is
            # given up under guard (documented in DESIGN.md).
            payloads = [PK.encode_sparse(
                jax.lax.dynamic_slice_in_dim(vals_s, b * kb, kb),
                jax.lax.dynamic_slice_in_dim(idx_s, b * kb, kb),
                sub, interpret=self.interpret) for b in range(B)]
            stacked = tuple(jnp.stack(parts)
                            for parts in zip(*payloads))

            def encode_fn(b):
                return tuple(jax.lax.dynamic_index_in_dim(
                    s, b, 0, keepdims=False) for s in stacked)
        else:
            def encode_fn(b):
                return PK.encode_sparse_fused(
                    jax.lax.dynamic_slice_in_dim(vals_s, b * kb, kb),
                    jax.lax.dynamic_slice_in_dim(idx_s, b * kb, kb),
                    sub, interpret=self.interpret)

        gathered = C.all_gather_packed(None, self.axes,
                                       encode_fn=encode_fn, n_buckets=B)
        outs = []
        for j in range(self.K):
            # per-bucket supports are disjoint slices of one sorted
            # index set, so summing the scatters is exact (each index
            # receives from exactly one bucket; sentinels drop)
            out = jnp.zeros((n,), vals.dtype)
            for b in range(B):
                pj = tuple(a[b][j] for a in gathered)
                out = out + self._decode_contrib(pj, sub, vals.dtype, n)
            outs.append(out)
        return jnp.stack(outs)

    def broadcast_packed(self, idx, leader, n, plan=None):
        """The leader index set over the REAL packed index wire: encode
        the (sorted) set through ``packed.encode_indices`` (high bits as
        a bucket histogram, low bits through the bit-plane kernel),
        forward exactly that payload over
        ``collectives.ring_broadcast_packed``, decode on arrival —
        bit-exact for any sorted indices in [0, n], so numerics are
        identical to the raw int32 broadcast and only the measured bytes
        change (~2.5x fewer on the lgc index term at 1M params).  SPMD
        makes every node encode, but only the leader's payload is ever
        adopted."""
        if not self.axes or idx.shape[0] == 0:
            return self.from_leader(idx, leader)
        if plan is None:
            plan = PK.make_plan(n, idx.shape[0], self.scale_block)
        assert plan.n == n and plan.k == idx.shape[0], (plan, n, idx.shape)
        payload = PK.encode_indices(idx, plan, interpret=self.interpret)
        got = C.ring_broadcast_packed(payload, self.axes,
                                      self._index() == leader)
        if self.guard != "off":
            # report structural damage (checksum/histogram/bounds) on
            # the received index payload; the *repair* happens at the
            # executor, which scrubs the decoded set back into a valid
            # sorted support (an index set has no zero-contribution
            # fallback the way a value payload does)
            ok, bad = PK.validate_payload(got, plan, values=False,
                                          interpret=self.interpret)
            CH.report_structural(bad)
        return PK.decode_indices(got, plan, interpret=self.interpret)


# ===========================================================================


@dataclass(frozen=True)
class SimTransport:
    """Single-host emulation on stacked (K, ...) node arrays."""
    K: int
    ae_axes: Tuple[str, ...] = ()
    scale_block: int = Q.SCALE_BLOCK
    interpret: bool | None = None
    guard: str = "off"

    kind = "sim"

    def pernode(self, fn, in_axes=0):
        return jax.vmap(fn, in_axes=in_axes)

    def mean(self, x):
        return x.mean(0)

    def sum(self, x):
        return x.sum(0)

    def all_gather(self, x):
        return x

    def from_leader(self, x, leader):
        return jax.lax.dynamic_index_in_dim(x, leader, 0, keepdims=False)

    def broadcast_packed(self, idx, leader, n, plan=None):
        """Wire-free emulation: the leader row, untouched — the exact
        oracle the packed index wire must match bit-for-bit."""
        return self.from_leader(idx, leader)

    def mean_q8(self, x):
        """The fake-quant oracle: per-node quantize→dequantize through
        the shared module, then the axis-0 mean."""
        fq = jax.vmap(lambda xx: Q.fake_quantize(xx, self.scale_block))
        return fq(x).mean(0)

    def _sparse_gather(self, vals, idx, n):
        if vals.shape[-1] == 0:
            return jnp.zeros((self.K, n), vals.dtype)
        return jax.vmap(lambda vv, ii: _scatter(vv, ii, n))(vals, idx)

    def sparse_mean(self, vals, idx, n):
        return self._sparse_gather(vals, idx, n).mean(0)

    def sparse_gather_packed(self, vals, idx, n, plan=None):
        """The exact oracle: per-node scatter of the untouched pairs.
        RingPackedTransport must match it with bit-exact indices and
        values within the documented q8 bound (its single value
        quantization) — asserted by the transport gate."""
        return self._sparse_gather(vals, idx, n)

    def sparse_mean_packed(self, vals, idx, n, plan=None):
        return self.sparse_gather_packed(vals, idx, n, plan=plan).mean(0)


# ===========================================================================


TRANSPORTS = ("mesh", "ring", "ring_q8", "ring_hier", "ring_packed", "sim")

# bounds on |global gradient - SimTransport oracle|: the float wires are
# exact up to summation order; ring_q8's compressed phase pays the
# wire's bounded requantization error and ring_packed's sparse
# exchanges their single int8 value quantization
EXACT_TOL = 1e-5
Q8_TOL = 2e-3

# the ring family: manual-shard_map transports with structurally measured
# wire bytes (everything but mesh's XLA-chosen lowering and sim's
# wire-free emulation)
RING_TRANSPORTS = ("ring", "ring_q8", "ring_hier", "ring_packed")


def make_transport(kind: str, K: int, axes: Axis = (),
                   ae_axes: Axis = (), node_index=None, *,
                   scale_block: int = 0,
                   intra_chunk: Optional[int] = None,
                   inter_chunk: Optional[int] = None,
                   interpret: bool | None = None,
                   guard: str = "off",
                   wire_buckets: int = 1,
                   fault: Optional[CH.FaultSpec] = None):
    """Factory keyed by CompressionConfig.transport.  ``scale_block``
    (0 = default) sets the int8-wire scale granularity; ``intra_chunk``/
    ``inter_chunk`` tune the hierarchical ring's per-level message size;
    ``interpret`` overrides where the packed wire's Pallas pack kernels
    run (None: compiled on TPU, interpreted elsewhere).  ``guard``
    (one of ``chaos.GUARD_POLICIES``) arms per-contribution structural
    validation inside the transport; the executor reads the same field
    to decide its own result validation.  ``kind`` may be prefixed
    ``chaos:<base>`` to wrap the base substrate in a
    :class:`~repro.dist.chaos.ChaosTransport` injecting ``fault``'s
    seeded corruption — identical fault positions on every base, which
    is what lets the equivalence gates run under faults."""
    spec = None
    if kind.startswith("chaos:"):
        kind = kind[len("chaos:"):]
        spec = fault if fault is not None else CH.FaultSpec()
    elif fault is not None and fault.active:
        spec = fault
    sb = scale_block or Q.SCALE_BLOCK
    if guard not in CH.GUARD_POLICIES:
        raise ValueError(f"unknown guard {guard!r}; "
                         f"known: {CH.GUARD_POLICIES}")
    wb = max(int(wire_buckets or 1), 1)
    args = (tuple(axes), K, tuple(ae_axes), node_index, sb, interpret)
    base = None
    if kind == "mesh":
        base = MeshTransport(*args, guard=guard)
    elif kind == "ring":
        base = RingTransport(*args, guard=guard, wire_buckets=wb)
    elif kind == "ring_q8":
        base = RingQ8Transport(*args, guard=guard, wire_buckets=wb)
    elif kind == "ring_hier":
        base = RingHierTransport(*args, guard=guard, wire_buckets=wb,
                                 intra_chunk=intra_chunk or None,
                                 inter_chunk=inter_chunk or None)
    elif kind == "ring_packed":
        base = RingPackedTransport(*args, guard=guard, wire_buckets=wb)
    elif kind == "sim":
        base = SimTransport(K, tuple(ae_axes), sb, interpret, guard)
    if base is None:
        raise ValueError(f"unknown transport {kind!r}; known: "
                         f"{TRANSPORTS} (optionally chaos:<base>)")
    return CH.ChaosTransport(base, spec) if spec is not None else base
