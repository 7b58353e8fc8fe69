"""Gradient compressors (paper Section V) as first-class trainer components.

Five methods, matching the paper's experimental comparison (Tables IV/VI):
  none        baseline distributed training, dense all-reduce
  sparse_gd   Sparse GD [19]: top-k + plain residual accumulation (no
              momentum correction)
  dgc         Deep Gradient Compression [20]: top-k + momentum correction
  lgc_ps      LGC, parameter-server pattern (common rep + innovations)
  lgc_rar     LGC, ring-allreduce pattern (encode -> average -> decode)
  lgc_rar_q8  beyond-paper: lgc_rar with int8-quantized encodings

Every method is written ONCE, in :meth:`GradientCompressor.step`, against
the :class:`repro.dist.transport.Transport` protocol.  ``step`` does not
call the transport directly: it compiles the method's exchange sequence
with ``repro.dist.plan.build_plan`` (the exchange-plan IR) and supplies
per-op feed callbacks to ``plan.execute`` — the SAME op objects price
``rate.rate_report``/``wire_payload_terms``, so the bytes a step moves
and the bytes the accounting reports agree by construction, and the
trace-time tally attributes every byte to the op label that shipped it
(``collectives.wire_report(by_op=True)``).  The substrate —
*how bytes move between nodes* — is injected:

  * ``MeshTransport``  lax collectives inside a fully-manual shard_map on
    the production mesh (the trainer and multi-pod dry-run): the
    all-reduce *carries the compressed representation*, which is the
    paper's claim expressed in collective bytes.
  * ``RingTransport``  same context, but reductions take the explicit
    chunked ring schedule in repro.dist.collectives — the paper's
    ring-allreduce pattern with measured wire bytes.
  * ``RingQ8Transport``  the int8 wire: ``lgc_rar_q8``'s encoding
    reduction ships int8 values + per-block f32 scales through the ring
    (quantize-forward), so the 1-byte/value rate claim is measured, not
    fake.
  * ``RingHierTransport``  hierarchical intra-pod/inter-pod rings on
    multi-axis dp meshes.
  * ``RingPackedTransport``  the packed sparse wire: the top-k exchanges
    of sparse_gd/dgc/lgc_ps (and their exempt-last traffic) ship
    bit-packed indices + int8 values + per-block f32 scales through a
    ppermute ring, so the ceil(log2 n)-bit + 1-byte/value rate claim is
    measured, not fake.  Indices stay bit-exact; values pay the wire's
    one documented q8 quantization — ONLY on this transport.  On every
    other transport the same exchanges move exact f32 pairs, so the
    sparse methods remain bit-exact reproductions by default.
  * ``SimTransport``   stacked (K, n) single-host arrays (the paper's own
    experiments emulate several nodes per GPU the same way).  Used by the
    convergence benchmarks; tests assert sim == mesh == ring == ring_hier
    (ring_q8 / ring_packed within their quantization bounds).

``dist_step`` / ``sim_step`` are thin wrappers that build the transport
and call ``step`` — kept as the public API the launchers and tests use.

Residual top-k selection dispatches on ``CompressionConfig.topk_backend``
("jnp" reference, the per-leaf Pallas ``global_topk`` kernel, or "fused"
— the single-sweep segmented kernel that folds the EF accumulate and the
per-leaf selection of *all* exempt+compressed leaves into ONE launch),
so the kernels in repro.kernels serve the training hot path, not just
benchmarks.  Phase-3 encoding dispatches on ``ae_backend`` ("jnp" convs
vs the MXU-backed ``ops.lgc_encode_fast``).

State is a PyTree carried in the train state; all shapes static.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CompressionConfig
from repro.core import autoencoder as AE
from repro.core import sparsify as SP
from repro.core.phases import (PHASE_COMPRESSED, PHASE_TOPK_AE, PHASE_WARMUP)
# PACKED_METHODS: the methods whose sparse exchanges ride the packed
# wire (real packed bytes + the one q8 value quantization on
# RingPackedTransport; exact f32 pairs everywhere else); the lgc_rar
# family's cross-node exchange is the dense encoding reduction, which
# the int8 ring (mean_q8) already covers.  Defined beside the codec so
# rate.py prices exactly the set dispatched here.
from repro.dist.packed import PACKED_METHODS  # noqa: F401  (re-export)
# the exchange-plan IR: build_plan compiles the method's exchanges into
# typed ops; execute runs them against the transport; the SAME op
# objects drive rate.py's byte accounting.  Imported after the core.*
# imports above so the plan module's own repro.core imports resolve
# against the already-initialized submodules.
from repro.dist import chaos as CH
from repro.dist import plan as XP
from repro.dist.transport import Transport, make_transport
from repro.utils import scopes as S

Axis = Sequence[str]



@dataclass(frozen=True)
class GradientCompressor:
    """Facade bundling config, layout and method dispatch."""
    cc: CompressionConfig
    layout: SP.GradientLayout
    K: int                        # number of nodes (data-parallel shards)

    # -- state ----------------------------------------------------------------

    def init_state(self, key) -> Dict[str, Any]:
        n = self.layout.n_total
        state: Dict[str, Any] = {
            "u": jnp.zeros((n,), jnp.float32),      # momentum accumulator
            "v": jnp.zeros((n,), jnp.float32),      # residual accumulator
        }
        if self.cc.method.startswith("lgc"):
            ps = self.cc.method == "lgc_ps"
            state["ae"] = AE.init_lgc_autoencoder(
                key, num_decoders=self.K if ps else 1, ps_innovation=ps)
            state["ae_mom"] = jax.tree_util.tree_map(
                jnp.zeros_like, state["ae"])
        return state

    def init_sim_states(self, key):
        """Stacked per-node state for sim_step (AE stored once)."""
        base = self.init_state(key)
        out = {
            "u": jnp.zeros((self.K,) + base["u"].shape, jnp.float32),
            "v": jnp.zeros((self.K,) + base["v"].shape, jnp.float32),
        }
        for k in ("ae", "ae_mom"):
            if k in base:
                out[k] = base[k]
        return out

    # -- per-node pieces -------------------------------------------------------

    @property
    def _use_momentum(self) -> bool:
        # sparse_gd is plain residual accumulation, no momentum correction
        return self.cc.method != "sparse_gd"

    @jax.named_scope(S.SELECT)
    def _accumulate(self, u, v, g):
        if not self._use_momentum:
            return u, v + g
        return SP.momentum_correct(u, v, g, self.cc.momentum_correction)

    @jax.named_scope(S.SELECT)
    def _select(self, v):
        return SP.select_topk(v, self.layout,
                              backend=self.cc.topk_backend,
                              extract=self.cc.extract_backend)

    @jax.named_scope(S.SELECT)
    def _select_last(self, v):
        return SP.select_topk_last(v, self.layout,
                                   backend=self.cc.topk_backend,
                                   extract=self.cc.extract_backend)

    @jax.named_scope(S.SELECT)
    def _fused_sweep(self, u, v, g):
        """One-launch accumulate + select over compressed AND exempt-last
        leaves (topk_backend="fused")."""
        return SP.fused_accumulate_select(
            g, u, v, self.layout, self.cc.momentum_correction,
            use_momentum=self._use_momentum,
            extract=self.cc.extract_backend)

    @jax.named_scope(S.AE)
    def _encode(self, ae, x):
        assert self.cc.ae_backend in ("jnp", "pallas"), self.cc.ae_backend
        if self.cc.ae_backend == "pallas":
            from repro.kernels import ops as K_ops
            return K_ops.lgc_encode_fast(ae, x)
        return AE.lgc_encode(ae, x)[0]                   # (mu/16, 4)

    # -- AE online training (phase 2, Section V-B) -----------------------------

    @jax.named_scope(S.AE)
    def _ae_update(self, state, g_nodes, inno_nodes, step, ae_axes=()):
        """One SGD step on the AE params.  g_nodes: (K, mu_pad) — a global
        (replicated) value, so the update is identical on every node.
        Under tensor parallelism each model shard compresses its own slice
        of the gradient: ``ae_axes`` names the model axes to pmean the AE
        grads over so the shared AE stays replicated."""
        cc = self.cc
        if cc.method == "lgc_ps":
            common_idx = step % self.K
            def loss_fn(ae):
                l, _ = AE.ae_loss_ps(ae, g_nodes, inno_nodes, common_idx,
                                     cc.lambda_rec, cc.lambda_sim)
                return l
        else:
            def loss_fn(ae):
                return AE.ae_loss_rar(ae, g_nodes)
        ae_loss, grads = jax.value_and_grad(loss_fn)(state["ae"])
        if ae_axes:
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, tuple(ae_axes)), grads)
            ae_loss = jax.lax.pmean(ae_loss, tuple(ae_axes))
        # global-norm clip keeps early AE steps stable regardless of the
        # magnitude of the incoming gradient statistics
        gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                             for g in jax.tree_util.tree_leaves(grads)))
        scale = jnp.minimum(1.0, 1.0 / jnp.maximum(gnorm, 1e-12))
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        # SGD with momentum 0.9 (paper Section VI-A: lr=1e-3, batch 1)
        mom = jax.tree_util.tree_map(lambda m, g: 0.9 * m + g,
                                     state["ae_mom"], grads)
        ae = jax.tree_util.tree_map(lambda p, m: p - cc.ae_lr * m,
                                    state["ae"], mom)
        return ae, mom, ae_loss

    # -- guard plumbing --------------------------------------------------------

    @staticmethod
    def _guard_gate(env, stats):
        """Surface the executor's per-op guard tally into the step stats
        (``fault/<label>`` per op + ``guard_ok``) and return
        ``(ok, policy)`` for round gating — or None when the executor
        ran unguarded (the historical path, untouched)."""
        g = env.get("__guard__")
        if g is None:
            return None
        for lbl, bad in g["bad"].items():
            stats[f"fault/{lbl}"] = bad
        ok = jnp.asarray(g["ok"])
        stats["guard_ok"] = ok.astype(jnp.int32)
        return ok, g["policy"]

    @staticmethod
    def _gate_clear(gate, cleared, raw):
        """EF retention under a guard: when the round saw any fault, the
        accumulators stay UNCLEARED — the scrubbed/skipped contribution
        re-ships from ``u``/``v`` next round instead of being lost
        (the executor's scrub zeroes bad wire elements; this is the
        matching sender-side half of the contract)."""
        if gate is None:
            return cleared
        ok, _ = gate
        return tuple(jnp.where(ok, c, r) for c, r in zip(cleared, raw))

    @staticmethod
    def _gate_round(gate, global_g):
        """skip_round: a faulty round contributes NO gradient at all —
        the optimizer sees zeros (and, with _gate_clear, the full
        gradient stays in the residual for the next round)."""
        if gate is None or gate[1] != "skip_round":
            return global_g
        ok, _ = gate
        return jnp.where(ok, global_g, jnp.zeros_like(global_g))

    @staticmethod
    def _gate_tree(gate, new, old):
        """Freeze an auxiliary state update (the AE and its momentum)
        when the round saw any fault — training the autoencoder on a
        scrubbed gradient vector would be training it on zeros."""
        if gate is None:
            return new
        ok, _ = gate
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(ok, a, b), new, old)

    # ==========================================================================
    # THE step: every method, once, against a Transport
    # ==========================================================================

    def step(self, t: Transport, state, g, step, phase: str):
        """Compress per-node gradients and return the *global* (aggregated)
        gradient vector plus the new compressor state.

        The cross-node exchanges are NOT dispatched here: ``build_plan``
        compiles this (method, phase, transport) into the exchange-plan
        IR (repro.dist.plan) and ``execute`` runs the ops in plan order
        against ``t`` — this method only supplies the per-node compute
        (accumulate/select/encode/decode) as feed callbacks between ops.
        Because rate.py prices the SAME op objects, a step cannot ship
        an exchange the accounting doesn't know about (and vice versa —
        the executor asserts feeds == plan labels both ways).

        Value convention (see repro.dist.transport): ``g`` and
        ``state["u"]/state["v"]`` are per-node; ``state["ae"]`` and the
        returned global gradient are global.  Under SimTransport per-node
        values carry a leading K axis; under Mesh/Ring they are this
        shard's local arrays inside a fully-manual shard_map.
        """
        cc, layout, n = self.cc, self.layout, self.layout.n_total
        stats: Dict[str, jnp.ndarray] = {}
        plan = XP.build_plan(cc, layout, self.K, transport=t.kind,
                             phase=phase)

        if phase == PHASE_WARMUP or cc.method == "none":
            env = XP.execute(plan, t, {"grad": lambda env: g})
            gate = self._guard_gate(env, stats)
            return self._gate_round(gate, env["grad"]), state, stats

        fused = cc.topk_backend == "fused"
        if fused:
            # ONE kernel sweep: EF accumulate + segmented selection over
            # compressed AND exempt-last leaves (one HBM read/write pass
            # instead of three, one launch instead of one per leaf)
            u, v, f_vals, f_idx, last_vals, last_idx = t.pernode(
                self._fused_sweep, in_axes=(0, 0, 0))(
                    state["u"], state["v"], g)
        else:
            u, v = t.pernode(self._accumulate, in_axes=(0, 0, 0))(
                state["u"], state["v"], g)
            # exempt last layer: top-k values+indices exchanged sparsely
            last_vals, last_idx = t.pernode(self._select_last)(v)

        # exempt-dense part: reduce ONLY the dense segments (not an
        # n-length mostly-zero vector — that would put dense-gradient
        # traffic back on the wire).  Which wire the exempt-last (and
        # every other sparse) exchange rides is the PLAN's decision:
        # PackedSparseExchange ops carry the PackPlan the packed
        # transport ships, SparseExchange ops stay on the exact f32 wire.
        dense_seg = t.pernode(lambda gg: SP.dense_segments(gg, layout))(g)
        feeds = {
            "exempt_dense": lambda env: dense_seg,
            "exempt_last": lambda env: (last_vals, last_idx),
        }

        # combined clear: compressed + exempt-last index sets zeroed in a
        # single scatter pass over each accumulator (2 passes, not 4)
        @jax.named_scope(S.SELECT)
        def clear2(uu, vv, ii, jj):
            return SP.clear_sent_merged(uu, vv, ii, jj, n)
        clear_own = t.pernode(clear2, in_axes=(0, 0, 0, 0))
        clear_shared = t.pernode(clear2, in_axes=(0, 0, None, 0))

        def g_dense_of(env):
            return SP.scatter_dense_segments(env["exempt_dense"],
                                             layout, n)

        if cc.method in ("sparse_gd", "dgc"):
            vals, idx = (f_vals, f_idx) if fused \
                else t.pernode(self._select)(v)
            feeds["topk"] = lambda env: (vals, idx)
            env = XP.execute(plan, t, feeds)
            gate = self._guard_gate(env, stats)
            global_g = env["topk"] + g_dense_of(env) + env["exempt_last"]
            global_g = self._gate_round(gate, global_g)
            u, v = self._gate_clear(gate, clear_own(u, v, idx, last_idx),
                                    (u, v))
            return global_g, {**state, "u": u, "v": v}, stats

        # ---- LGC ----
        # cyclic leader top-k (CLT-k): the rotating leader's index set is
        # shared by every node — for RAR this makes the mu-length values
        # reduction the whole cross-node exchange; for PS it is the
        # index-support reading under which the paper's Table IV/VI rates
        # (0.012MB per non-leader node) close: non-leaders do NOT ship
        # their own index sets, and each node's innovation is indexed
        # locally within the support (log2(mu) bits).  Recorded in
        # DESIGN.md.
        if cc.method not in ("lgc_rar", "lgc_rar_q8", "lgc_ps"):
            raise ValueError(f"unknown method {cc.method}")

        leader = step % self.K
        own_idx = f_idx if fused else t.pernode(self._select)(v)[1]
        # canonical (sorted) support on EVERY transport: the packed index
        # broadcast's histogram codec requires monotone indices, and the
        # support must be ordered identically everywhere for the
        # transport-equivalence gates to stay bitwise (a set in a
        # different order would reorder the AE's input vector)
        own_idx = jnp.sort(own_idx, axis=-1)
        feeds["support"] = lambda env: (own_idx, leader)

        def vals_of(env):
            # per-node gather at the broadcast support — memoized in env
            # so every feed past "support" shares one gather
            if "_vals" not in env:
                env["_vals"] = t.pernode(SP.gather_at, in_axes=(0, None))(
                    v, env["support"])
            return env["_vals"]

        is_ps = cc.method == "lgc_ps"
        if is_ps:
            frac = SP.innovation_frac(cc.innovation_sparsity, cc.sparsity)

            def _innovation(x):
                vec, ii = SP.select_innovation(x, frac)
                return vec, x[ii], ii          # in-place vec + sparse pair

            def inno_of(env):
                if "_inno" not in env:
                    env["_inno"] = t.pernode(_innovation)(vals_of(env))
                return env["_inno"]

        if phase == PHASE_TOPK_AE:
            # top-k updates + online AE training on the gathered vectors.
            # indices are shared (CLT-k) so reducing the mu-length values
            # vector IS the whole cross-node exchange.
            feeds["support_vals"] = vals_of
            feeds["gather_vals"] = vals_of
            if is_ps:
                feeds["gather_inno"] = lambda env: inno_of(env)[0]
            env = XP.execute(plan, t, feeds)
            gate = self._guard_gate(env, stats)
            idx = env["support"]                             # (mu_pad,)
            sent = SP.scatter_to_dense(env["support_vals"], idx, n)
            global_g = sent + g_dense_of(env) + env["exempt_last"]
            global_g = self._gate_round(gate, global_g)
            g_nodes = env["gather_vals"]                     # (K, mu_pad)
            inno_nodes = env["gather_inno"] if is_ps else None
            ae, ae_mom, ae_loss = self._ae_update(state, g_nodes,
                                                  inno_nodes, step,
                                                  t.ae_axes)
            ae = self._gate_tree(gate, ae, state["ae"])
            ae_mom = self._gate_tree(gate, ae_mom, state["ae_mom"])
            stats["ae_loss"] = ae_loss
            u, v = self._gate_clear(gate,
                                    clear_shared(u, v, idx, last_idx),
                                    (u, v))
            return global_g, {**state, "u": u, "v": v, "ae": ae,
                              "ae_mom": ae_mom}, stats

        # phase 3 (compressed): encode -> move -> decode
        def encode(x):
            return self._encode(state["ae"], x)              # (mu/16, 4)

        if is_ps:
            # Fig. 8: the leader worker ships E_c(g~); every worker ships
            # its innovation; the master decodes per node and averages the
            # reconstructions (eqs. 12-13) over the shared index support.
            # The innovation exchange is sparse (k_inv values + local
            # indices within the mu_pad support) and rides the packed
            # wire — NOT a mu_pad-length in-place f32 all_gather.
            feeds["z_common"] = lambda env: (
                t.pernode(encode)(vals_of(env)), leader)
            feeds["innovations"] = lambda env: (inno_of(env)[1],
                                                inno_of(env)[2])
            env = XP.execute(plan, t, feeds)
            gate = self._guard_gate(env, stats)
            idx = env["support"]
            with jax.named_scope(S.AE):
                recs = AE.lgc_decode_ps(state["ae"], env["z_common"],
                                        env["innovations"])  # (K, mu_pad)
            rec_dense = SP.scatter_to_dense(recs.mean(0), idx, n)
        else:
            # RAR (eq. 17-19): encode -> average (THE all-reduce) -> decode.
            # lgc_rar_q8's encoding reduction is a Reduce op with
            # wire="q8": REAL int8 on RingQ8Transport (quantize-forward
            # ring, ~1 byte/value measured), fake-quantized through the
            # same repro.dist.quantize module then reduced in f32
            # everywhere else — so Sim/Mesh/Ring == RingQ8 up to the
            # wire's bounded requantization error.
            feeds["encoding"] = lambda env: t.pernode(encode)(vals_of(env))
            env = XP.execute(plan, t, feeds)
            gate = self._guard_gate(env, stats)
            idx = env["support"]
            with jax.named_scope(S.AE):
                rec = AE.lgc_decode_rar(state["ae"],
                                        env["encoding"][None])[0]
            rec_dense = SP.scatter_to_dense(rec, idx, n)

        global_g = rec_dense + g_dense_of(env) + env["exempt_last"]
        global_g = self._gate_round(gate, global_g)
        u, v = self._gate_clear(gate, clear_shared(u, v, idx, last_idx),
                                (u, v))
        return global_g, {**state, "u": u, "v": v}, stats

    # ==========================================================================
    # public wrappers (transport construction)
    # ==========================================================================

    def dist_step(self, state, g: jnp.ndarray, step: jnp.ndarray, phase: str,
                  axes: Axis, ae_axes: Axis = (), node_index=None,
                  transport: Optional[str] = None):
        """Distributed step for THIS shard's flat gradient, inside a
        fully-manual shard_map over ``axes`` (+ the model axes).

        ``node_index`` overrides the shard's linear index over ``axes``
        (pass it when the caller already computed it).  ``transport``
        overrides ``CompressionConfig.transport`` ("mesh", "ring",
        "ring_q8", "ring_hier" or "ring_packed", optionally prefixed
        "chaos:" for fault injection).  When the config carries an
        active FaultSpec (any ``fault_*`` set) the transport is
        auto-wrapped in chaos:<base>; ``cc.guard`` arms the executor's
        per-op validation either way."""
        kind = transport if transport is not None else \
            (self.cc.transport or "mesh")
        if kind.split(":", 1)[-1] == "sim":
            raise ValueError(
                "transport='sim' is not a distributed transport (stacked "
                "(K, n) arrays, no mesh axes) — call sim_step instead")
        spec = CH.spec_from_config(self.cc)
        if spec is not None and not kind.startswith("chaos:"):
            kind = "chaos:" + kind
        t = make_transport(kind, self.K, axes, ae_axes, node_index,
                           scale_block=self.cc.q8_scale_block,
                           intra_chunk=self.cc.ring_intra_chunk,
                           inter_chunk=self.cc.ring_inter_chunk,
                           guard=self.cc.guard,
                           wire_buckets=self.cc.wire_buckets, fault=spec)
        return self.step(t, state, g, step, phase)

    def sim_step(self, states, g_nodes: jnp.ndarray, step, phase: str):
        """Single-host emulation on stacked (K, n) node gradients.
        states: PyTree stacked over K (u, v per node; ae stored once).
        Returns (global_g (n,), states, stats)."""
        spec = CH.spec_from_config(self.cc)
        kind = "chaos:sim" if spec is not None else "sim"
        t = make_transport(kind, self.K,
                           scale_block=self.cc.q8_scale_block,
                           guard=self.cc.guard, fault=spec)
        return self.step(t, states, g_nodes, step, phase)


# ---------------------------------------------------------------------------


def build_compressor(cc: CompressionConfig, params_template,
                     K: int) -> GradientCompressor:
    layout = SP.build_layout(params_template, cc.sparsity)
    return GradientCompressor(cc=cc, layout=layout, K=K)
