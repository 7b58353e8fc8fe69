"""The benchmark's own seeded weights.

Every leaf is drawn from the run's seed by its path, so the program and
the reference start from the same numbers without either making them.
The rules follow the published initialisations: linear and embedding
weights normal with standard deviation 1/sqrt(fan-in) (0.02 for the
embedding), RMSNorm scales one, biases zero, Mamba2's ``A_log`` as
log(U[1, 16]), its ``dt_bias`` as the inverse softplus of a step size
log-uniform in [1e-3, 1e-1], ``D`` one, and its depthwise conv uniform
in +-1/sqrt(kernel) (PyTorch's default).  Autoencoder kernels are He
normal, their biases zero.  A model kind may give a leaf these rules do
not cover its own published initialisation (``leaf_init`` of its
module, ``bench/kinds/<kind>.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds may exceed 32 bits): the
    seed is hashed to 31 bits first."""
    word = np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def _leaf(key, path: str, shape, dtype, leaf_init=None):
    x = None if leaf_init is None else leaf_init(path, shape, key)
    if x is None:
        x = _shared(key, path, shape)
    return x.astype(dtype)


def _shared(key, path: str, shape):
    parts = path.split("/")
    name = parts[-1]
    f32 = jnp.float32
    if name == "scale":
        x = jnp.ones(shape, f32)
    elif name == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, np.log(1e-3),
                                        np.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)
    elif name == "D":
        x = jnp.ones(shape, f32)
    elif name == "conv_w":
        bound = 1.0 / np.sqrt(shape[-2])
        x = jax.random.uniform(key, shape, f32, -bound, bound)
    elif name == "conv_b":
        x = jax.random.uniform(key, shape, f32, -0.5, 0.5)
    elif name == "b":
        x = jnp.zeros(shape, f32)
    elif "encoder" in parts or "decoder" in parts:          # AE kernel
        fan_in = shape[-3] * shape[-2]
        x = jax.random.normal(key, shape, f32) * np.sqrt(2.0 / fan_in)
    elif "embed" in parts:
        x = jax.random.normal(key, shape, f32) * 0.02
    else:
        fan_in = shape[-2]
        x = jax.random.normal(key, shape, f32) / np.sqrt(fan_in)
    return x


def make(specs, key, leaf_init=None):
    """``specs``: [(path, shape, dtype)] -> {path: array}.  Meant to be
    called under ``jax.jit`` with the key as an argument, so one program
    serves every seed and makes every leaf on the device.  ``leaf_init``
    (path, shape, key) -> float32 array or None: a model kind's own
    rule, tried before the shared ones."""
    return {path: _leaf(jax.random.fold_in(key, i), path, shape, dtype,
                        leaf_init)
            for i, (path, shape, dtype) in enumerate(specs)}
