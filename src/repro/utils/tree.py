"""PyTree helpers used across the framework.

The LGC compressors (repro.core) operate on the *flattened gradient vector*
exactly as the paper does (gradients of all layers concatenated into one
1-D vector, Section V of the paper). These utilities provide a cheap,
jit-compatible bijection between a PyTree of arrays and that vector.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def keystr_path(path) -> str:
    """'/'-joined key path, e.g. ``blocks/0/w``."""
    return jax.tree_util.keystr(path, simple=True, separator="/")


def tree_count_params(tree: Any) -> int:
    """Total number of scalar parameters in a PyTree."""
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(np.prod(l.shape) for l in leaves))


def tree_size_bytes(tree: Any) -> int:
    """Total size in bytes of a PyTree of arrays (or ShapeDtypeStructs)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(np.prod(l.shape) * jnp.dtype(l.dtype).itemsize for l in leaves))


def tree_zeros_like(tree: Any) -> Any:
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def tree_flatten_vector(tree: Any, dtype=jnp.float32) -> jnp.ndarray:
    """Concatenate every leaf (raveled) into a single 1-D vector.

    This is the paper's ``concatenate(g_l)`` (Algorithm 1/2): the per-layer
    gradient tensors unfolded and joined into one vector per node.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((0,), dtype)
    return jnp.concatenate([jnp.ravel(l).astype(dtype) for l in leaves])


def tree_unflatten_vector(vector: jnp.ndarray, like: Any) -> Any:
    """Inverse of :func:`tree_flatten_vector` given a structural template."""
    leaves, treedef = jax.tree_util.tree_flatten(like)
    out = []
    offset = 0
    for leaf in leaves:
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        seg = jax.lax.dynamic_slice_in_dim(vector, offset, n)
        out.append(seg.reshape(leaf.shape).astype(leaf.dtype))
        offset += n
    return jax.tree_util.tree_unflatten(treedef, out)


@functools.lru_cache(maxsize=None)
def _segment_offsets(shapes: tuple) -> tuple:
    offs, off = [], 0
    for s in shapes:
        offs.append(off)
        off += int(np.prod(s)) if s else 1
    return tuple(offs), off


def tree_vector_size(tree: Any) -> int:
    """Length of the vector :func:`tree_flatten_vector` would produce."""
    return tree_count_params(tree)
