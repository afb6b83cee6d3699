"""Plain float32 forward of Mixtral (hf:mistralai/Mixtral-8x7B-v0.1).

A Mistral decoder whose MLP is a sparse mixture of experts: a float32
router scores every expert, the top ``num_experts_per_tok`` probabilities
of its softmax are renormalized to sum to 1, and the token's output is
their weighted sum of SwiGLU experts. No expert drops a token. Every
expert is computed for every token and weighted by its gate (zero where
the token was not routed): the plainest form, at the cost of
``num_local_experts / num_experts_per_tok`` times the expert work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights
from bench.references import _plain


def logits(cfg: dict, seed: int, tokens, rows, *, bits: int = 8):
    d, E = cfg["hidden_size"], cfg["num_local_experts"]
    top = cfg["num_experts_per_tok"]

    @jax.jit
    def gates(h, key, layer):
        p = jax.nn.softmax(_plain.mm(h, weights.matrix(
            key, layer, "mlp/router", d, E)), axis=-1)
        val, idx = jax.lax.top_k(p, top)
        val = val / jnp.sum(val, -1, keepdims=True)
        return jnp.sum(jax.nn.one_hot(idx, E) * val[..., None], axis=-2)

    @functools.partial(jax.jit, donate_argnums=0)
    def expert(x, h, g, key, layer, e):
        y = _plain.swiglu(cfg, h, key, layer, bits, expert=e)
        return x + jnp.take(g, e, axis=-1)[..., None] * y

    def mlp(x, h, key, layer):
        g = gates(h, key, layer)
        for e in range(E):
            x = expert(x, h, g, key, layer, jnp.int32(e))
        return x

    return _plain.decoder_logits(cfg, seed, tokens, rows, bits=bits,
                                 attn_bias=False, mlp=mlp)
