"""Distributed step builders.

Two training paths and two serving paths:

* ``make_auto_train_step``  — baseline: one jit, GSPMD auto-partitioning
  from parameter/batch shardings (TP over ``model``, FSDP over ``data``,
  DP over ``pod``+``data``).  The gradient all-reduce is implicit.  Used
  for every (arch x shape) dry-run baseline and for the big configs.

* ``make_lgc_train_step``   — the paper: two sequential regions inside
  one jit (a layout kept from jax 0.4, which could not lower collectives
  over outer-bound manual axes from a nested shard_map; merging the
  regions is a separate, measured change).  Region 1 computes per-node
  gradients with a vmap over the node axis under GSPMD auto partitioning
  (node axis sharded over dp, model axis auto for TP — keeping the node
  axis means no gradient all-reduce is ever emitted); region 2 is a
  ``shard_map`` fully manual over ALL mesh axes running the gradient
  compressor per (node x model-shard), so the cross-node reduction
  carries top-k values (phase 2) or autoencoder encodings (phase 3)
  instead of the dense gradient — over lax collectives
  (``transport="mesh"``) or the explicit ring family in
  repro.dist.collectives (``transport="ring"``, ``"ring_q8"`` — int8
  wire — or ``"ring_hier"``; wire bytes measured in all three).
  EF/momentum state lives per (node x model-shard) as a (DP, MP,
  n_local) array.  Params stay replicated across dp shards (paper
  semantics: every node holds the model).

* ``make_prefill_step`` / ``make_decode_step`` — serving, plain jit auto;
  decode shards the KV cache batch over dp axes, or the sequence dim when
  batch is too small (long_500k), letting XLA derive flash-style
  partial-softmax collectives.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig, TrainConfig
from repro.core.compressors import GradientCompressor, build_compressor
from repro.dist.sharding import (batch_pspec, cache_pspecs, local_shape,
                                 param_pspecs)
from repro.launch.input_specs import batch_specs, cache_specs, params_specs
from repro.launch.mesh import (dp_axes_of, dp_size_of, model_size_of)
from repro.models.model import Model
from repro.optim.optimizers import Optimizer, build_optimizer
from repro.utils import scopes as S
from repro.utils.tree import tree_flatten_vector, tree_unflatten_vector


def _shard(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _batch_pspecs(batch_tree, dp_axes):
    bp = batch_pspec(dp_axes)
    def spec(path, leaf):
        extra = (None,) * (len(leaf.shape) - 1)
        return P(*(tuple(bp) + extra))
    return jax.tree_util.tree_map_with_path(spec, batch_tree)


# ===========================================================================
# baseline (auto) training step
# ===========================================================================


@dataclass
class AutoTrainStep:
    step_fn: Callable
    params_sharding: Any
    opt_sharding: Any
    batch_sharding: Any
    optimizer: Optimizer

    def init(self, rng, model: Model):
        params = jax.jit(model.init, out_shardings=self.params_sharding)(rng)
        opt_state = jax.jit(self.optimizer.init,
                            out_shardings=self.opt_sharding)(params)
        return params, opt_state


def make_auto_train_step(model: Model, tc: TrainConfig, mesh,
                         fsdp: bool = True, remat: Optional[bool] = None,
                         ) -> AutoTrainStep:
    optimizer = build_optimizer(tc)
    mp = model_size_of(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    fsdp_axes = ("data",) if (fsdp and "data" in sizes) else ()
    fsdp_size = sizes.get("data", 1) if fsdp else 1
    dp_axes = dp_axes_of(mesh)

    p_shapes = params_specs(model)
    pspecs = param_pspecs(p_shapes, model_size=mp, fsdp_axes=fsdp_axes,
                          fsdp_size=fsdp_size)
    o_shapes = jax.eval_shape(optimizer.init, p_shapes)
    ospecs = param_pspecs(o_shapes, model_size=mp, fsdp_axes=fsdp_axes,
                          fsdp_size=fsdp_size)

    def train_step(params, opt_state, batch, step):
        def loss_fn(p):
            return model.loss(p, batch, remat=remat)
        with jax.named_scope(S.FWD_BWD):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
        with jax.named_scope(S.OPTIMIZER):
            new_params, new_opt = optimizer.update(grads, opt_state,
                                                   params, step)
        return new_params, new_opt, metrics

    ps = _shard(mesh, pspecs)
    os_ = _shard(mesh, ospecs)

    def make_jit(batch_tree):
        bs = _shard(mesh, _batch_pspecs(batch_tree, dp_axes))
        return jax.jit(train_step,
                       in_shardings=(ps, os_, bs, None),
                       out_shardings=(ps, os_, None),
                       donate_argnums=(0, 1))

    return AutoTrainStep(make_jit, ps, os_, None, optimizer)


# ===========================================================================
# LGC (paper) training step
# ===========================================================================


@dataclass
class LGCTrainStep:
    make_step: Callable[[str], Callable]       # phase -> jitted step fn
    compressor: GradientCompressor
    params_sharding: Any
    opt_sharding: Any
    comp_sharding: Any
    optimizer: Optimizer
    n_local: int
    dp_size: int
    mp_size: int

    def init(self, rng, model: Model, mesh):
        params = jax.jit(model.init, out_shardings=self.params_sharding)(rng)
        opt_state = jax.jit(self.optimizer.init,
                            out_shardings=self.opt_sharding)(params)

        def comp_init(key):
            base = self.compressor.init_state(key)
            out = {"u": jnp.zeros((self.dp_size, self.mp_size, self.n_local),
                                  jnp.float32),
                   "v": jnp.zeros((self.dp_size, self.mp_size, self.n_local),
                                  jnp.float32)}
            for k in ("ae", "ae_mom"):
                if k in base:
                    out[k] = base[k]
            return out

        comp_state = jax.jit(comp_init,
                             out_shardings=self.comp_sharding)(rng)
        return params, opt_state, comp_state


def make_lgc_train_step(model: Model, tc: TrainConfig, mesh,
                        remat: Optional[bool] = None) -> LGCTrainStep:
    """Build the paper's distributed training step on ``mesh``.

    Requirements: global batch divisible by the dp axes product; params
    replicated across dp shards (no FSDP — EF state is O(params)/node,
    which bounds the applicable model scale exactly as in the paper).
    """
    cc = tc.compression
    optimizer = build_optimizer(tc)
    mp = model_size_of(mesh)
    dp_axes = dp_axes_of(mesh)
    dp = dp_size_of(mesh)

    p_shapes = params_specs(model)
    # model-axis-only specs (params replicated over dp in LGC mode)
    pspecs = param_pspecs(p_shapes, model_size=mp)
    o_shapes = jax.eval_shape(optimizer.init, p_shapes)
    ospecs = param_pspecs(o_shapes, model_size=mp)

    # local (per-model-shard) template drives the compressor layout
    flat, treedef = jax.tree_util.tree_flatten_with_path(p_shapes)
    flat_specs = jax.tree_util.tree_leaves(
        pspecs, is_leaf=lambda x: isinstance(x, P))
    local_leaves = []
    for (path, leaf), spec in zip(flat, flat_specs):
        shp = local_shape(tuple(leaf.shape), spec, {"model": mp})
        local_leaves.append(jax.ShapeDtypeStruct(shp, leaf.dtype))
    local_template = jax.tree_util.tree_unflatten(treedef, local_leaves)

    compressor = build_compressor(cc, local_template, dp)
    n_local = compressor.layout.n_total

    dp_tuple = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    comp_specs: Dict[str, Any] = {
        "u": P(dp_tuple, "model", None),
        "v": P(dp_tuple, "model", None),
    }
    has_ae = cc.method.startswith("lgc")
    if has_ae:
        comp_specs["ae"] = P()
        comp_specs["ae_mom"] = P()

    # all mesh axes, bound manually by the compression region
    all_axes = set(mesh.axis_names)
    model_axes = ("model",) if mp > 1 else ()

    def _prepend(spec_tree, lead):
        return jax.tree_util.tree_map(
            lambda s: P(*((lead,) + tuple(s))), spec_tree,
            is_leaf=lambda x: isinstance(x, P))

    # stacked per-node gradients: (DP, *leaf) — node axis over dp, model
    # dims per the parameter specs (the compression region binds both
    # manually)
    grads_stack_specs = _prepend(pspecs, dp_tuple)

    def build_phase(phase: str, batch_tree):
        # ---- region 1: per-node gradients --------------------------------
        # vmap over the node axis under GSPMD auto partitioning: the batch
        # is reshaped (B,) -> (DP, B/DP) with the node axis sharded over
        # dp, so each device computes ITS node's gradient and — crucially
        # — no gradient all-reduce is ever emitted (the node axis is kept,
        # not summed).  The model axis stays auto for TP.  A vmap is used
        # instead of a dp-manual shard_map because jax 0.4's partial-auto
        # shard_map could not return auto-sharded (TP) gradients when
        # model > 1 (XLA manual-subgroup check); the vmap stays until a
        # one-region step is measured against it.
        @jax.named_scope(S.FWD_BWD)
        def grad_region(params, batch):
            def node_loss(b):
                def loss_fn(p):
                    return model.loss(p, b, remat=remat)
                (_loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                return grads, metrics

            batch_nodes = jax.tree_util.tree_map(
                lambda x: x.reshape((dp, x.shape[0] // dp) + x.shape[1:]),
                batch)
            grads_stack, metrics = jax.vmap(node_loss)(batch_nodes)
            grads_stack = jax.lax.with_sharding_constraint(
                grads_stack, _shard(mesh, grads_stack_specs))
            return grads_stack, metrics

        # ---- region 2: compression + aggregation -------------------------
        # fully manual over every mesh axis: each (node x model-shard)
        # device flattens its local gradient block and the cross-node
        # reduction moves compressed payloads via the configured transport.
        @jax.named_scope(S.COMPRESS)
        def compress_region(grads_stack, u3, v3, ae_part, step):
            grads_local = jax.tree_util.tree_map(lambda g: g[0],
                                                 grads_stack)
            st = {"u": u3[0, 0], "v": v3[0, 0], **ae_part}
            flat_g = tree_flatten_vector(grads_local)
            gflat, new_st, stats = compressor.dist_step(
                st, flat_g, step, phase, dp_axes, ae_axes=model_axes)
            g_global = tree_unflatten_vector(gflat, local_template)
            new_ae = {k: new_st[k] for k in ae_part}
            return (g_global, new_st["u"][None, None],
                    new_st["v"][None, None], new_ae, stats)

        compress_sm = jax.shard_map(
            compress_region, mesh=mesh,
            in_specs=(grads_stack_specs, P(dp_tuple, "model", None),
                      P(dp_tuple, "model", None), P(), P()),
            out_specs=(pspecs, P(dp_tuple, "model", None),
                       P(dp_tuple, "model", None), P(), P()),
            axis_names=all_axes, check_vma=False)

        # ---- whole step (jit): grads -> compress -> optimizer ------------
        def step_fn(params, opt_state, comp_state, batch, step):
            grads_stack, metrics = grad_region(params, batch)
            ae_part = {k: comp_state[k] for k in ("ae", "ae_mom")
                       if k in comp_state}
            g_global, u3, v3, ae_part, stats = compress_sm(
                grads_stack, comp_state["u"], comp_state["v"], ae_part,
                step)
            with jax.named_scope(S.OPTIMIZER):
                new_params, new_opt = optimizer.update(g_global, opt_state,
                                                       params, step)
            metrics = jax.tree_util.tree_map(
                lambda x: jnp.mean(x, axis=0), metrics)
            for k, val in stats.items():
                metrics[k] = val
            new_comp = {"u": u3, "v": v3, **ae_part}
            return new_params, new_opt, new_comp, metrics

        return jax.jit(
            step_fn,
            in_shardings=(_shard(mesh, pspecs), _shard(mesh, ospecs),
                          _shard(mesh, comp_specs),
                          _shard(mesh, _batch_pspecs(batch_tree, dp_axes)),
                          None),
            out_shardings=(_shard(mesh, pspecs), _shard(mesh, ospecs),
                           _shard(mesh, comp_specs), None),
            donate_argnums=(0, 1, 2),
        )

    return LGCTrainStep(build_phase, compressor, _shard(mesh, pspecs),
                        _shard(mesh, ospecs), _shard(mesh, comp_specs),
                        optimizer, n_local, dp, mp)


# ===========================================================================
# serving steps
# ===========================================================================


def _serve_pspecs(model: Model, mesh):
    """Serving weight shardings: TP over `model`; additionally shard over
    `data` (weight-sharded inference, per-layer all-gathers) when the
    per-model-shard weights exceed half a v5e HBM — a 671B-class MoE
    cannot serve with data-replicated weights."""
    from repro.utils.tree import tree_size_bytes
    mp = model_size_of(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    p_shapes = params_specs(model)
    per_shard = tree_size_bytes(p_shapes) / max(mp, 1)
    if per_shard > 8e9 and "data" in sizes:
        return param_pspecs(p_shapes, model_size=mp, fsdp_axes=("data",),
                            fsdp_size=sizes["data"])
    return param_pspecs(p_shapes, model_size=mp)


def make_prefill_step(model: Model, mesh, shape: InputShape):
    mp = model_size_of(mesh)
    dp_axes = dp_axes_of(mesh)
    dp = dp_size_of(mesh)
    pspecs = _serve_pspecs(model, mesh)
    batch_tree = batch_specs(model.cfg, shape)
    cache_tree = cache_specs(model, shape)
    cspecs = cache_pspecs(cache_tree, dp_axes=dp_axes, dp_size=dp,
                          model_size=mp,
                          seq_shard_axis="data" if dp > 1 else None)

    def prefill(params, batch):
        return model.prefill(params, batch, cache_len=shape.seq_len)

    return jax.jit(
        prefill,
        in_shardings=(_shard(mesh, pspecs),
                      _shard(mesh, _batch_pspecs(batch_tree, dp_axes))),
        out_shardings=(NamedSharding(mesh, P()), _shard(mesh, cspecs)),
    )


def make_decode_step(model: Model, mesh, shape: InputShape):
    mp = model_size_of(mesh)
    dp_axes = dp_axes_of(mesh)
    dp = dp_size_of(mesh)
    pspecs = _serve_pspecs(model, mesh)
    cache_tree = cache_specs(model, shape)
    cspecs = cache_pspecs(cache_tree, dp_axes=dp_axes, dp_size=dp,
                          model_size=mp,
                          seq_shard_axis="data" if dp > 1 else None)
    B = shape.global_batch
    tok_spec = P(batch_pspec(dp_axes)[0] if B % dp == 0 and B > 1 else None)

    def decode(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    return jax.jit(
        decode,
        in_shardings=(_shard(mesh, pspecs), _shard(mesh, cspecs),
                      NamedSharding(mesh, P(*tok_spec, None)), None),
        out_shardings=(NamedSharding(mesh, P()), _shard(mesh, cspecs)),
        donate_argnums=(1,),
    )
