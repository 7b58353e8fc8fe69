"""The exchange-plan IR: ONE declarative wire plan per (method, phase).

The paper's headline is a *rate* claim — what each method actually puts
on the wire (Tables IV/VI) — so the bytes a step moves and the bytes the
accounting reports must never drift apart.  Before this module they were
kept equal by assertion (``tests/test_wire_accounting.py``): the step
logic in ``core/compressors.py`` and the pricing if-ladder in
``core/rate.py`` were two hand-mirrored copies of the same exchange
sequence.  This module makes them equal *by construction*:

  * :func:`build_plan` — a host-side compiler from
    ``(CompressionConfig, GradientLayout, K, transport, phase)`` to a
    :class:`Plan`: an ordered tuple of typed exchange ops, each carrying
    a static payload descriptor (element counts, vector length, shipped
    vs rate-counted pair counts, the :class:`~repro.dist.packed.PackPlan`
    for packed exchanges).  The op list is transport-*independent* —
    every substrate executes the same exchanges, which is exactly the
    transport-equivalence contract — while the *pricing* of each op is
    transport-aware.
  * :func:`execute` — THE executor: walks ``plan.ops`` in order against
    any :class:`~repro.dist.transport.Transport`, wiring the method's
    per-node compute (accumulate/select/encode/…) in as feed callbacks.
    Each transport call runs under :func:`collectives.wire_op
    <repro.dist.collectives.wire_op>`, so the trace-time tally records
    every byte against the op label that shipped it (the per-op wire
    trace in ``wire_report(by_op=True)``).
  * :func:`wire_terms` / :func:`wire_terms_by_op` — the wire pricer:
    walks the *same op objects* and predicts the trace-time tally per
    collective kind (and per op label), per transport / dp-mesh shape.
  * :func:`rate_terms` — the rate pricer: walks the same ops again and
    produces the paper-style per-node one-send payload split into
    (leader, other) bytes — DEFLATE index estimates, /K leader
    amortization and the PS leader/other asymmetry included.

``core/rate.py``'s ``rate_report``/``wire_payload_terms`` are thin
wrappers over the pricers; neither contains a per-method exchange
dispatch of its own anymore.  A new exchange = a new op here, priced
once, executed once, tallied once.

The overlapped exchange (``Plan.wire_buckets``, from
``CompressionConfig.wire_buckets`` / ``--wire-buckets``): every
bucketable ring exchange splits into ``collectives.bucket_widths``
column buckets and software-pipelines them — bucket b's ppermute hops
run while bucket b+1 encodes (reduce-scatter / quantize / fused packed
encode).  The pricer mirrors the executor bucket for bucket:

  * :func:`bucket_plan` — splits ONE op into its per-bucket
    sub-exchanges, labelled ``<op.label>#b<i>``, whose descriptors sum
    to the unbucketed tally plus the explicitly priced bucket padding.
    ``wire_terms_by_op`` emits exactly the rows the bucketed executor
    records (zero slack, gated in ``tests/test_overlap.py``).
  * :func:`padding_overhead_terms` — per op, the accounted bytes minus
    the pad-free ideal payload: the ``_to_chunks`` ceil-pad plus the
    bucket pad.  ``accounted == ideal + overhead`` holds at every
    bucket count, so raising ``wire_buckets`` changes an op's bytes by
    exactly its padding delta.

``mesh`` never buckets (the lax lowering is opaque) but is priced as a
first-class substrate: DenseReduce/Reduce -> ``all_reduce``, gathers ->
``all_gather``, leader exchanges -> ``broadcast``, with zero padding
overhead.  See DESIGN.md "The overlapped bucketed exchange".

Op catalogue (wire semantics per transport family):

  ==================  =====================================================
  op                  wire payload
  ==================  =====================================================
  DenseReduce         f32 ring/hier/lax allreduce of ``n_vals`` floats
  Reduce              as DenseReduce; ``wire="q8"`` rides the int8 ring
                      (1 byte/value + per-block scales) on ``ring_q8``
                      and costs full f32 elsewhere (fake quantization
                      saves nothing on the wire)
  AllGather           (K-1) x ``n_vals`` f32 per node
  SparseExchange      k (value, index) pairs over a length-``n_vec``
                      vector: f32 values + raw int32 indices on every
                      wire (the exact path — never packed)
  PackedSparseExchange same pairs, but on ``ring_packed`` the payload is
                      ``pack``'s real bytes: bucket counts + bit-packed
                      low index bits + int8 values + per-block scales
                      (indices bit-exact, values pay the one documented
                      q8 quantization); exact f32+int32 elsewhere
  IndexBroadcast      the rotating leader's sorted index set: packed
                      index bytes on ``ring_packed`` (bit-exact), raw
                      int32 broadcast elsewhere; rate amortizes it /K
  LeaderBroadcast     the leader's ``n_vals`` f32 to all nodes at
                      (K-1)/K wire cost; rate: the leader alone pays
  ==================  =====================================================
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CompressionConfig
from repro.core import autoencoder as AE
from repro.core.phases import PHASE_TOPK_AE, PHASE_WARMUP
from repro.core.sparsify import (GradientLayout, innovation_frac,
                                 innovation_k)
from repro.dist import chaos as CH
from repro.dist import collectives as C
from repro.dist import packed as PK
from repro.dist import quantize as Q
from repro.utils import scopes as S

BYTES_F32 = 4
BYTES_I32 = 4

METHODS = ("none", "sparse_gd", "dgc", "lgc_ps", "lgc_rar", "lgc_rar_q8")


# ---------------------------------------------------------------------------
# the ops


@dataclass(frozen=True)
class Op:
    label: str


@dataclass(frozen=True)
class DenseReduce(Op):
    """f32 allreduce of ``n_vals`` values.  ``exempt=True`` marks the
    exempt-layer dense traffic that ``rate_report(count_exempt=False)``
    — the paper's own accounting — leaves out of the transmitted rate."""
    n_vals: int
    exempt: bool = False


@dataclass(frozen=True)
class Reduce(Op):
    """Allreduce of ``n_vals`` values whose wire dtype is an op property:
    ``wire="q8"`` ships int8 + per-block f32 scales on the int8 ring
    (``ring_q8``) and full f32 on every float wire."""
    n_vals: int
    wire: str = "f32"              # "f32" | "q8"


@dataclass(frozen=True)
class AllGather(Op):
    n_vals: int


@dataclass(frozen=True)
class SparseExchange(Op):
    """k (value, index) pairs over a length-``n_vec`` vector, always on
    the exact f32 + raw-int32 wire.  ``k`` is the shipped pair count
    (sentinel padding included); ``k_rate`` the pairs the paper's rate
    accounting counts (``mu`` vs the shipped ``mu_pad``)."""
    n_vec: int
    k: int
    k_rate: int


@dataclass(frozen=True)
class PackedSparseExchange(Op):
    """A SparseExchange that rides the packed wire on ``ring_packed``:
    ``pack`` is THE :class:`~repro.dist.packed.PackPlan` — built once
    here, shipped by the transport, priced by both pricers (no second
    ``make_plan`` call can disagree).  ``mode="mean"`` averages the
    scattered pairs; ``"gather"`` returns the (K, n_vec) per-node
    scatters (the PS innovation exchange)."""
    n_vec: int
    k: int
    k_rate: int
    pack: Optional[PK.PackPlan]    # None when k == 0
    mode: str = "mean"             # "mean" | "gather"


@dataclass(frozen=True)
class IndexBroadcast(Op):
    """The rotating leader's sorted index set (k entries over [0, n_vec])
    to all nodes: the packed index payload (``pack``, bit-exact) on
    ``ring_packed``, a raw int32 broadcast elsewhere.  The rate amortizes
    the leader's send across the K nodes (Section V-A)."""
    n_vec: int
    k: int
    k_rate: int
    pack: Optional[PK.PackPlan]


@dataclass(frozen=True)
class LeaderBroadcast(Op):
    """The leader's ``n_vals`` f32 values to all nodes (the PS common
    encoding): wire cost (K-1)/K·nbytes, rate cost on the leader only."""
    n_vals: int


@dataclass(frozen=True)
class Plan:
    """The compiled exchange plan: ordered ops + the static context they
    were compiled for.  ``transport`` is the default pricing substrate;
    the op *list* is transport-independent by construction."""
    method: str
    phase: str
    transport: str
    K: int
    scale_block: int
    ops: Tuple[Op, ...]
    # bucketed-exchange schedule: how many software-pipeline buckets the
    # ring-family transports split each exchange into (1 = unbucketed —
    # the historical schedule).  Part of the plan because the pricers
    # must predict the per-bucket tally rows the executor records.
    wire_buckets: int = 1

    @property
    def labels(self) -> Tuple[str, ...]:
        return tuple(op.label for op in self.ops)

    def op(self, label: str) -> Op:
        for op in self.ops:
            if op.label == label:
                return op
        raise KeyError(label)


# ---------------------------------------------------------------------------
# the compiler


def steady_phase(method: str) -> str:
    """The phase a method spends training in — what the rate tables and
    the wire contract price."""
    from repro.core.phases import PHASE_COMPRESSED
    if method == "none":
        return PHASE_WARMUP
    if method in ("sparse_gd", "dgc"):
        return PHASE_TOPK_AE
    return PHASE_COMPRESSED


def build_plan(cc: CompressionConfig, layout: GradientLayout, K: int,
               transport: Optional[str] = None,
               phase: Optional[str] = None) -> Plan:
    """Compile the exchange plan for one compressor step.  All inputs
    are static (host-side), so this runs at trace time; the op order IS
    the transport-call order :func:`execute` performs and both pricers
    price."""
    method = cc.method
    assert method in METHODS, method
    tkind = transport if transport is not None else (cc.transport or "mesh")
    phase = phase if phase is not None else steady_phase(method)
    sb = cc.q8_scale_block or Q.SCALE_BLOCK
    n = layout.n_total

    def _plan(ops) -> Plan:
        return Plan(method=method, phase=phase, transport=tkind, K=K,
                    scale_block=sb, ops=tuple(ops),
                    wire_buckets=getattr(cc, "wire_buckets", 1) or 1)

    if phase == PHASE_WARMUP or method == "none":
        return _plan([DenseReduce("grad", n_vals=n)])

    packed = method in PK.PACKED_METHODS
    ops = [DenseReduce("exempt_dense",
                       n_vals=sum(l.size for l in layout.dense),
                       exempt=True)]

    chk = cc.guard_checksum

    def sparse(label, n_vec, k, k_rate, mode="mean"):
        if packed:
            pack = PK.make_plan(n_vec, k, sb, checksum=chk) if k else None
            return PackedSparseExchange(label, n_vec=n_vec, k=k,
                                        k_rate=k_rate, pack=pack,
                                        mode=mode)
        assert mode == "mean", mode   # float-exact gathers aren't needed
        return SparseExchange(label, n_vec=n_vec, k=k, k_rate=k_rate)

    ops.append(sparse("exempt_last", n, layout.k_last, layout.k_last))
    mp = layout.mu_pad

    if method in ("sparse_gd", "dgc"):
        # the whole cross-node exchange: mu_pad shipped pairs, mu counted
        ops.append(sparse("topk", n, mp, layout.mu))
        return _plan(ops)

    # lgc family: CLT-k rotating-leader support, then the phase payload
    ops.append(IndexBroadcast("support", n_vec=n, k=mp, k_rate=layout.mu,
                              pack=PK.make_plan(n, mp, sb, checksum=chk)))
    zl = AE.compressed_length(mp)
    if phase == PHASE_TOPK_AE:
        ops.append(Reduce("support_vals", n_vals=mp))
        ops.append(AllGather("gather_vals", n_vals=mp))
        if method == "lgc_ps":
            ops.append(AllGather("gather_inno", n_vals=mp))
    elif method == "lgc_ps":
        k_inv = innovation_k(mp, innovation_frac(cc.innovation_sparsity,
                                                 cc.sparsity))
        ops.append(LeaderBroadcast("z_common", n_vals=zl))
        ops.append(PackedSparseExchange(
            "innovations", n_vec=mp, k=k_inv, k_rate=k_inv,
            pack=PK.make_plan(mp, k_inv, sb, checksum=chk)
            if k_inv else None,
            mode="gather"))
    else:
        ops.append(Reduce("encoding", n_vals=zl,
                          wire="q8" if method == "lgc_rar_q8" else "f32"))
    return _plan(ops)


# ---------------------------------------------------------------------------
# THE executor


def _run_op(op: Op, t, args: tuple):
    if isinstance(op, DenseReduce):
        (x,) = args
        return t.mean(x)
    if isinstance(op, Reduce):
        (x,) = args
        return t.mean_q8(x) if op.wire == "q8" else t.mean(x)
    if isinstance(op, AllGather):
        (x,) = args
        return t.all_gather(x)
    if isinstance(op, SparseExchange):
        vals, idx = args
        return t.sparse_mean(vals, idx, op.n_vec)
    if isinstance(op, PackedSparseExchange):
        vals, idx = args
        if op.mode == "gather":
            return t.sparse_gather_packed(vals, idx, op.n_vec,
                                          plan=op.pack)
        return t.sparse_mean_packed(vals, idx, op.n_vec, plan=op.pack)
    if isinstance(op, IndexBroadcast):
        idx, leader = args
        return t.broadcast_packed(idx, leader, op.n_vec, plan=op.pack)
    if isinstance(op, LeaderBroadcast):
        x, leader = args
        return t.from_leader(x, leader)
    raise TypeError(op)


def _guard_result(op: Op, res):
    """Validate one op's result under a guard policy -> (scrubbed
    result, traced int32 bad-element count).

    Float payloads: every non-finite element, and every finite element
    with ``|x| > chaos.GUARD_MAX`` (a flipped exponent bit usually lands
    ~1e38 — corrupt but isfinite), is zeroed.  Zeroing IS the
    EF-retention contract: the compressor only clears ``u``/``v`` at
    coordinates the exchange delivered, so a scrubbed contribution stays
    in the residual and re-ships next round instead of being lost.

    An IndexBroadcast result is repaired structurally: out-of-bound
    entries clip into [0, n_vec] (n_vec = the select_topk sentinel) and
    the set re-sorts, restoring the codec's monotone-sorted contract so
    downstream gathers stay well-defined."""
    if isinstance(op, IndexBroadcast):
        idx = res
        bad = jnp.sum(((idx < 0) | (idx > op.n_vec)).astype(jnp.int32))
        if idx.shape[0] > 1:
            bad = bad + jnp.sum((idx[1:] < idx[:-1]).astype(jnp.int32))
        fixed = jnp.sort(jnp.clip(idx, 0, op.n_vec))
        return jnp.where(bad > 0, fixed, idx), bad
    if jnp.issubdtype(res.dtype, jnp.inexact):
        mask = ~jnp.isfinite(res) | (jnp.abs(res) > CH.GUARD_MAX)
        return jnp.where(mask, jnp.zeros_like(res), res), \
            jnp.sum(mask.astype(jnp.int32))
    return res, jnp.zeros((), jnp.int32)


def execute(plan: Plan, t, feeds: Dict[str, Callable],
            guard: Optional[str] = None) -> Dict[str, Any]:
    """Run ``plan.ops`` in order against transport ``t``.

    ``feeds[label](env) -> args tuple`` produces each op's transport
    arguments; ``env`` maps already-executed labels to their results (so
    a feed can consume an earlier op's output, e.g. gather at the
    broadcast support) and feeds may memoize shared per-node compute
    into underscore-prefixed keys.  Every op label must have exactly one
    feed and vice versa — a step cannot silently skip or invent an
    exchange the plan (and therefore the pricing) doesn't know about.
    Each transport call runs under ``collectives.wire_op(label)`` and
    ``jax.named_scope("exchange/<label>")``: the op label names both
    the trace-time byte tally and the device operations of that call.
    The feeds run outside both, so their per-node work is not counted
    as the exchange's.

    ``guard`` (default: the transport's own ``guard`` field; one of
    ``chaos.GUARD_POLICIES``) arms per-op result validation: each
    result is scrubbed through :func:`_guard_result`, structural bad
    counts reported by the transport (packed payload validation, the
    quantizer's non-finite mask) drain into the same per-op tally via
    ``chaos.structural_sink``, and the returned env carries
    ``env["__guard__"] = {"policy", "bad": {label: int32}, "ok"}`` for
    the compressor's round gating (``skip_round``) and the driver's
    fail_fast check.  ``guard="off"`` is byte-for-byte the historical
    executor — zero added trace."""
    labels = set(plan.labels)
    missing = labels - set(feeds)
    extra = set(feeds) - labels
    assert not missing and not extra, (
        f"plan/feeds mismatch for {plan.method}/{plan.phase}: "
        f"missing feeds {sorted(missing)}, unplanned feeds {sorted(extra)}")
    guard = guard if guard is not None else getattr(t, "guard", "off")
    assert guard in CH.GUARD_POLICIES, guard
    env: Dict[str, Any] = {}
    bad_by_op: Dict[str, Any] = {}
    for op in plan.ops:
        args = feeds[op.label](env)
        if not isinstance(args, tuple):
            args = (args,)
        scope = jax.named_scope(f"{S.EXCHANGE}/{op.label}")
        if guard == "off":
            with C.wire_op(op.label), scope:
                env[op.label] = _run_op(op, t, args)
            continue
        sink: list = []
        with C.wire_op(op.label), scope, CH.structural_sink(sink):
            res = _run_op(op, t, args)
        res, bad = _guard_result(op, res)
        for extra_bad in sink:
            bad = bad + extra_bad
        env[op.label] = res
        bad_by_op[op.label] = bad
    if guard != "off":
        total = sum(bad_by_op.values())
        env["__guard__"] = {"policy": guard, "bad": bad_by_op,
                            "ok": total == 0}
    return env


# ---------------------------------------------------------------------------
# the wire pricer: predicted trace-time tally per op, per collective kind


def bucket_plan(op: Op, n_buckets: int, tkind: str, Ks: Tuple[int, ...],
                K: int, sb: int) -> Dict[str, Dict[str, float]]:
    """The per-op bucketizer/pricer: split one exchange op into its
    per-bucket sub-exchanges and return their exact wire descriptors —
    ``{sub-label: {collective kind: bytes}}``, where an unbucketed
    exchange keeps the op's own label and a bucketed one emits one
    ``label#b<i>`` row per pipeline bucket, mirroring the executor's
    :func:`collectives._record_bucket_bytes` labels byte for byte.

    The bucket split rule is :func:`collectives.bucket_widths` applied
    exactly where the executing collective applies it — per-axis chunk
    columns for the f32/q8 rings, inter-level columns for the two-axis
    hierarchical ring (three or more axes run unbucketed — the
    executor's documented fallback), sorted pairs for the packed
    gather (per-bucket sub-format from ``packed.bucket_plan``).  The
    per-bucket rows sum to the unbucketed tally plus the explicitly
    priced bucket padding (:func:`padding_overhead_terms`).

    ``mesh`` prices the lax-collective tally kinds (``all_reduce`` /
    ``all_gather`` / ``broadcast``) and never buckets — the lax
    lowering is opaque, so there is no schedule to pipeline."""
    out: Dict[str, Dict[str, float]] = {}

    def add(bucket: Optional[int], kind: str, b: float) -> None:
        if not b:
            return
        lbl = op.label if bucket is None else f"{op.label}#b{bucket}"
        terms = out.setdefault(lbl, {})
        terms[kind] = terms.get(kind, 0.0) + float(b)

    mesh = tkind == "mesh"
    WB = 1 if mesh else max(int(n_buckets), 1)

    def reduce_f32(n_vals: int, itemsize: int = BYTES_F32) -> None:
        if n_vals <= 0:
            return
        if mesh:
            add(None, "all_reduce", 2 * (K - 1) / K * n_vals * itemsize)
        elif tkind == "ring_hier" and len(Ks) > 1:
            K1 = Ks[-1]
            c = -(-n_vals // K1)
            B = 1
            if len(Ks) == 2:
                Ka = Ks[0]
                ca = -(-c // Ka)
                B, cab = C.bucket_widths(ca, WB)
            if B == 1:
                if K1 > 1:
                    add(None, "ring_hier_intra", 2 * (K1 - 1) * c * itemsize)
                for Ka in Ks[:-1]:
                    if Ka > 1:
                        add(None, "ring_hier_inter",
                            2 * (Ka - 1) * (-(-c // Ka)) * itemsize)
            else:
                Ka = Ks[0]
                for b in range(B):
                    if K1 > 1:
                        add(b, "ring_hier_intra",
                            2 * (K1 - 1) * Ka * cab * itemsize)
                    if Ka > 1:
                        add(b, "ring_hier_inter",
                            2 * (Ka - 1) * cab * itemsize)
        else:
            for Ka in Ks:
                if Ka > 1:
                    c = -(-n_vals // Ka)
                    B, cb = C.bucket_widths(c, WB)
                    if B == 1:
                        add(None, "ring_allreduce",
                            2 * (Ka - 1) * c * itemsize)
                    else:
                        for b in range(B):
                            add(b, "ring_allreduce",
                                2 * (Ka - 1) * cb * itemsize)

    if isinstance(op, DenseReduce):
        reduce_f32(op.n_vals)
    elif isinstance(op, Reduce):
        if op.wire == "q8" and tkind == "ring_q8":
            for Ka in Ks:
                if Ka > 1:
                    c = -(-op.n_vals // Ka)
                    B, cb = C.bucket_widths(c, WB)
                    if B == 1:
                        add(None, "ring_allreduce_q8",
                            2 * (Ka - 1) * Q.wire_nbytes(c, sb))
                    else:
                        for b in range(B):
                            add(b, "ring_allreduce_q8",
                                2 * (Ka - 1) * Q.wire_nbytes(cb, sb))
        else:
            reduce_f32(op.n_vals)
    elif isinstance(op, AllGather):
        add(None, "all_gather", (K - 1) * op.n_vals * BYTES_F32)
    elif isinstance(op, PackedSparseExchange):
        if op.k > 0:
            if tkind == "ring_packed":
                B = 1
                if not op.pack.raw_index:
                    B, kb = C.bucket_widths(op.k, WB)
                if B == 1:
                    add(None, "all_gather_packed",
                        (K - 1) * PK.wire_nbytes(op.pack))
                else:
                    sub = PK.bucket_plan(op.pack, kb)
                    for b in range(B):
                        add(b, "all_gather_packed",
                            (K - 1) * PK.wire_nbytes(sub))
            else:
                add(None, "all_gather",
                    (K - 1) * op.k * (BYTES_F32 + BYTES_I32))
    elif isinstance(op, SparseExchange):
        if op.k > 0:
            add(None, "all_gather",
                (K - 1) * op.k * (BYTES_F32 + BYTES_I32))
    elif isinstance(op, IndexBroadcast):
        # method-blind packing: the index wire carries no values, so
        # ring_packed re-routes it for every method
        if tkind == "ring_packed":
            add(None, "broadcast_packed",
                (K - 1) / K * PK.index_nbytes(op.pack))
        else:
            add(None, "broadcast", (K - 1) / K * op.k * BYTES_I32)
    elif isinstance(op, LeaderBroadcast):
        add(None, "broadcast", (K - 1) / K * op.n_vals * BYTES_F32)
    else:
        raise TypeError(op)
    return out


def _op_wire_terms(op: Op, tkind: str, Ks: Tuple[int, ...], K: int,
                   sb: int) -> Dict[str, float]:
    """Unbucketed per-op pricing, aggregated by collective kind — the
    pre-bucketing interface, kept for callers that only need the op's
    total (the bucketed rows sum to it plus the priced bucket pad)."""
    terms: Dict[str, float] = {}
    for sub in bucket_plan(op, 1, tkind, Ks, K, sb).values():
        for kind, b in sub.items():
            terms[kind] = terms.get(kind, 0.0) + b
    return terms


def _wire_ctx(plan: Plan, transport: Optional[str],
              axis_sizes: Optional[Sequence[int]]):
    tkind = transport if transport is not None else plan.transport
    assert tkind in ("mesh", "ring", "ring_q8", "ring_hier",
                     "ring_packed"), tkind
    Ks = tuple(axis_sizes) if axis_sizes else (plan.K,)
    assert int(np.prod(Ks)) == plan.K, (Ks, plan.K)
    return tkind, Ks


def wire_terms_by_op(plan: Plan, transport: Optional[str] = None,
                     axis_sizes: Optional[Sequence[int]] = None,
                     wire_buckets: Optional[int] = None,
                     ) -> Dict[str, Dict[str, float]]:
    """{op label: {collective kind: bytes}} — the per-op prediction of
    ``collectives.wire_report(by_op=True)`` for one executed plan (ops
    that move no bytes are omitted, matching the tally).  A bucketed
    plan (``plan.wire_buckets`` > 1, overridable per call) prices one
    ``label#b<i>`` row per pipeline bucket — the exact labels the
    executor's per-bucket host-side recording emits."""
    tkind, Ks = _wire_ctx(plan, transport, axis_sizes)
    WB = wire_buckets if wire_buckets is not None else plan.wire_buckets
    out: Dict[str, Dict[str, float]] = {}
    for op in plan.ops:
        for lbl, terms in bucket_plan(op, WB, tkind, Ks, plan.K,
                                      plan.scale_block).items():
            dst = out.setdefault(lbl, {})
            for kind, b in terms.items():
                dst[kind] = dst.get(kind, 0.0) + b
    return out


def wire_terms(plan: Plan, transport: Optional[str] = None,
               axis_sizes: Optional[Sequence[int]] = None,
               wire_buckets: Optional[int] = None) -> Dict[str, float]:
    """Aggregate of :func:`wire_terms_by_op` by collective kind — the
    prediction of plain ``collectives.wire_report()`` for one step."""
    out: Dict[str, float] = {}
    for terms in wire_terms_by_op(plan, transport, axis_sizes,
                                  wire_buckets).values():
        for kind, b in terms.items():
            out[kind] = out.get(kind, 0.0) + b
    return out


def padding_overhead_terms(plan: Plan, transport: Optional[str] = None,
                           axis_sizes: Optional[Sequence[int]] = None,
                           wire_buckets: Optional[int] = None,
                           ) -> Dict[str, float]:
    """{op label: zero-pad bytes} — the part of each op's *accounted*
    wire bytes that carries padding rather than payload, priced
    explicitly: the ``_to_chunks`` ceil-pad every ring hop ships (a
    non-multiple-of-K vector pads its last chunk), the bucket-pad
    columns a pipelined schedule adds on top, and the packed wire's
    per-bucket duplicated histograms + sentinel pad pairs.  The ideal
    (pad-free) payload divides exactly: ``2(Ka-1)/Ka · nbytes`` per ring
    axis, ``(K-1) · wire_nbytes(parent pack)`` for the packed gather.
    By construction ``accounted == ideal + overhead`` per op, so the
    bucketed-vs-unbucketed byte delta of a plan is exactly the delta of
    these overheads (property-tested).  Ops with no padding are
    omitted; mesh moves exactly-sized lax buffers and never pads."""
    tkind, Ks = _wire_ctx(plan, transport, axis_sizes)
    WB = wire_buckets if wire_buckets is not None else plan.wire_buckets
    out: Dict[str, float] = {}
    for op in plan.ops:
        accounted = 0.0
        for terms in bucket_plan(op, WB, tkind, Ks, plan.K,
                                 plan.scale_block).values():
            accounted += sum(terms.values())
        ideal = _op_ideal_bytes(op, tkind, Ks, plan.K, plan.scale_block)
        pad = accounted - ideal
        if pad > 1e-9:
            out[op.label] = pad
    return out


def _op_ideal_bytes(op: Op, tkind: str, Ks: Tuple[int, ...], K: int,
                    sb: int) -> float:
    """The pad-free wire bytes of one op: what the exchange would move
    if every chunk split divided exactly (fractional chunks allowed) —
    the baseline :func:`padding_overhead_terms` subtracts."""
    if tkind == "mesh":
        # lax collectives move exactly-sized buffers: ideal == accounted
        return sum(sum(t.values()) for t in
                   bucket_plan(op, 1, tkind, Ks, K, sb).values())

    def ring_ideal(n_vals: float, bytes_per_elem: float) -> float:
        if n_vals <= 0:
            return 0.0
        if tkind == "ring_hier" and len(Ks) > 1:
            K1 = Ks[-1]
            total = 2 * (K1 - 1) / K1 * n_vals * bytes_per_elem
            shard = n_vals / K1     # each inter axis allreduces the shard
            for Ka in Ks[:-1]:
                total += 2 * (Ka - 1) / Ka * shard * bytes_per_elem
            return total
        return sum(2 * (Ka - 1) / Ka * n_vals * bytes_per_elem
                   for Ka in Ks if Ka > 1)

    if isinstance(op, DenseReduce):
        return ring_ideal(op.n_vals, BYTES_F32)
    if isinstance(op, Reduce):
        if op.wire == "q8" and tkind == "ring_q8":
            # 1 byte/value + 4/scale_block bytes/value of f32 scales
            return ring_ideal(op.n_vals, 1.0 + 4.0 / sb)
        return ring_ideal(op.n_vals, BYTES_F32)
    if isinstance(op, PackedSparseExchange) and op.k > 0 \
            and tkind == "ring_packed":
        return float((K - 1) * PK.wire_nbytes(op.pack))
    # gathers/broadcasts ship exactly-sized payloads: no padding
    return sum(sum(t.values()) for t in
               bucket_plan(op, 1, tkind, Ks, K, sb).values())


# ---------------------------------------------------------------------------
# the rate pricer: the paper's per-node one-send payload, (leader, other)


def _op_rate_bytes(op: Op, tkind: str, K: int, sb: int,
                   idx_arrays: Dict[str, Optional[np.ndarray]],
                   count_exempt: bool,
                   deflate) -> Tuple[float, float]:
    """(leader_bytes, other_bytes) one op contributes to a node's
    per-iteration transmitted payload.  Reductions and gathers count one
    send of the payload per node; an IndexBroadcast/LeaderBroadcast is
    paid by the leader alone (the /K amortization falls out of the
    (leader + (K-1)·other)/K average)."""
    idx = idx_arrays.get(op.label)
    if isinstance(op, DenseReduce):
        b = 0.0 if (op.exempt and not count_exempt) \
            else op.n_vals * BYTES_F32
        return b, b
    if isinstance(op, Reduce):
        if op.wire == "q8" and tkind == "ring_q8":
            b = Q.wire_nbytes(op.n_vals, sb)
        else:
            b = op.n_vals * BYTES_F32
        return b, b
    if isinstance(op, AllGather):
        b = op.n_vals * BYTES_F32
        return b, b
    if isinstance(op, (SparseExchange, PackedSparseExchange)):
        if op.k <= 0:
            return 0.0, 0.0
        if isinstance(op, PackedSparseExchange) and tkind == "ring_packed":
            # the REAL packed payload, from the op's own PackPlan — no
            # deflate estimate (the wire structurally realizes the
            # ceil(log2 n)-bit index cost)
            b = float(PK.wire_nbytes(op.pack))
        else:
            b = (op.k_rate * BYTES_F32
                 + deflate(idx, op.k_rate, op.n_vec))
        return b, b
    if isinstance(op, IndexBroadcast):
        if tkind == "ring_packed":
            b = float(PK.index_nbytes(op.pack))
        else:
            b = float(deflate(idx, op.k_rate, op.n_vec))
        return b, 0.0
    if isinstance(op, LeaderBroadcast):
        return op.n_vals * BYTES_F32, 0.0
    raise TypeError(op)


def rate_terms(plan: Plan, *,
               indices: Optional[np.ndarray] = None,
               inno_indices: Optional[np.ndarray] = None,
               count_exempt: bool = True,
               transport: Optional[str] = None,
               deflate=None) -> Tuple[float, float]:
    """(leader_bytes, other_bytes) per iteration for one plan — the
    paper-style rate accounting derived from the op list.  ``indices``
    prices the top-k/support index set with an exact DEFLATE size on the
    float wires; ``inno_indices`` the PS innovation set.  ``deflate`` is
    injected by ``core.rate`` (kept there so the estimate stays beside
    the paper's accounting discussion)."""
    if deflate is None:
        from repro.core.rate import deflate_bytes as deflate
    tkind = transport if transport is not None else plan.transport
    idx_arrays = {"topk": indices, "support": indices,
                  "innovations": inno_indices}
    leader = other = 0.0
    for op in plan.ops:
        lb, ob = _op_rate_bytes(op, tkind, plan.K, plan.scale_block,
                                idx_arrays, count_exempt, deflate)
        leader += lb
        other += ob
    return leader, other
