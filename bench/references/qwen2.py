"""Plain float32 forward of Qwen2 (arXiv:2407.10671; hf:Qwen/Qwen2-72B).

A dense decoder: grouped-query attention whose query, key and value
projections carry a bias (the output projection has none), RMSNorm,
rotate-half RoPE, and a SwiGLU MLP.
"""
from __future__ import annotations

import functools

import jax

from bench.references import _plain


def logits(cfg: dict, seed: int, tokens, rows, *, bits: int = 8):
    @functools.partial(jax.jit, donate_argnums=0)
    def mlp(x, h, key, layer):
        return x + _plain.swiglu(cfg, h, key, layer, bits)

    return _plain.decoder_logits(cfg, seed, tokens, rows, bits=bits,
                                 attn_bias=True, mlp=mlp)
