"""Reproduction framework for "Learned Gradient Compression for
Distributed Deep Learning" on the jax/pallas stack."""
