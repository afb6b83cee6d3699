"""Seeded random weights of a Llama-family decoder, drawn on the device.

Every tensor is a pure function of ``(seed, layer, name, expert)``. The
harness draws them to build the program's parameters, and each plain
reference draws them again on its own, so nothing that the program made
reaches the reference. ``name`` is the parameter's path inside one layer
(``"attn/q"``, ``"mlp/gate"``, ``"ln1"``); embedding and head use layer
-1. Matrices have a fan-in scale of their own (std ``1/sqrt(K)`` for a
``(K, N)`` matrix, each expert included), so every group of the integer
scales sees the spread a trained layer of that width would give it.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def base_key(seed: int) -> jax.Array:
    """A key from a seed of any size (driver seeds exceed 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def stream(key, layer, name: str, expert=0) -> jax.Array:
    """The key of one tensor. ``layer``/``expert`` may be traced."""
    k = jax.random.fold_in(key, layer + 1)
    k = jax.random.fold_in(k, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(k, expert)


def matrix(key, layer, name: str, K: int, N: int, expert=0) -> jax.Array:
    """f32 ``(K, N)``, std ``1/sqrt(K)``."""
    return jax.random.normal(stream(key, layer, name, expert), (K, N),
                             jnp.float32) / math.sqrt(K)


def gain(key, layer, name: str, d: int) -> jax.Array:
    """f32 ``(d,)`` RMSNorm gain near 1."""
    return 1.0 + 0.1 * jax.random.normal(stream(key, layer, name), (d,),
                                         jnp.float32)


def bias(key, layer, name: str, n: int) -> jax.Array:
    """bf16 ``(n,)`` projection bias (served in bf16)."""
    return (0.1 * jax.random.normal(stream(key, layer, name), (n,),
                                    jnp.float32)).astype(jnp.bfloat16)


def embed(key, vocab: int, d: int) -> jax.Array:
    """bf16 ``(vocab, d)``, std 1 (served in bf16)."""
    return jax.random.normal(stream(key, -1, "embed"), (vocab, d),
                             jnp.bfloat16)


def head(key, d: int, vocab: int) -> jax.Array:
    """bf16 ``(d, vocab)``, std ``1/sqrt(d)`` (served in bf16)."""
    w = jax.random.normal(stream(key, -1, "head"), (d, vocab), jnp.bfloat16)
    return w * jnp.bfloat16(1.0 / math.sqrt(d))
