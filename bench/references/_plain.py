"""Plain float32 pieces of a Llama-family decoder under W4A8.

Written from the published descriptions (RMSNorm, rotate-half RoPE,
grouped-query causal attention, SwiGLU) and from the paper's W4A8
integer-scale scheme, in straightforward ``jax.numpy`` at
``Precision.HIGHEST``. Nothing here imports the program: weights are
drawn again from the seed (``bench.weights``) and quantized here, by
round-to-nearest into int4 codes with one scale per group of 128 input
rows, the scale turned into an integer by the amplifier, and dequantized
back to float32. Activations entering each quantized matrix are rounded
per token to ``a_bits`` (8 in the configuration, 4 for the control).

The forward runs layer by layer over all sampled sequences at once,
padded to one length (causal attention makes the padding inert), so one
compiled program serves every layer and every run of a cell.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import weights

HI = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def dequant_w(w, q: dict):
    """RTN int4 group codes with integer scales, back in f32."""
    K, N = w.shape
    gs, qw = q["group_size"], 2 ** (q["w_bits"] - 1) - 1
    wg = w.reshape(K // gs, gs, N)
    s = jnp.maximum(jnp.max(jnp.abs(wg), axis=1, keepdims=True), 1e-8) / qw
    codes = jnp.clip(jnp.round(wg / s), -qw, qw)
    alpha = float(q["amplifier"])
    s_int = jnp.clip(jnp.round(s * alpha), 1, 2 ** 31 - 1)
    return (codes * (s_int / alpha)).reshape(K, N)


def fake_quant_act(x, bits: int):
    """Per-token symmetric rounding of the last axis to ``bits``."""
    qa = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / qa
    return jnp.clip(jnp.round(x / s), -qa, qa) * s


def qlinear(x, w_deq, bits: int):
    return mm(fake_quant_act(x, bits), w_deq)


def rmsnorm(x, g, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta: float):
    """x (T, H, D), positions 0..T-1, rotate-half convention."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def causal_attention(q, k, v):
    """One sequence: q (T, Hq, D), k/v (T, Hkv, D) -> (T, Hq, D)."""
    T, Hq, D = q.shape
    G = Hq // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(D)
    mask = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)


def attention_block(cfg: dict, x, key, layer, bits: int, *, bias: bool):
    """x (n, T, d) residual stream -> x + attention(rmsnorm(x))."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = cfg["quantization"]
    n, T, _ = x.shape
    h = rmsnorm(x, weights.gain(key, layer, "ln1", d), cfg["rms_norm_eps"])

    def proj(name, N, inp, K):
        y = qlinear(inp, dequant_w(weights.matrix(key, layer, name, K, N),
                                   q), bits)
        if bias:
            y = y + weights.bias(key, layer, name + "/b", N).astype(
                jnp.float32)
        return y

    qh = proj("attn/q", Hq * hd, h, d).reshape(n, T, Hq, hd)
    kh = proj("attn/k", Hkv * hd, h, d).reshape(n, T, Hkv, hd)
    vh = proj("attn/v", Hkv * hd, h, d).reshape(n, T, Hkv, hd)
    theta = float(cfg["rope_theta"])
    o = jax.lax.map(lambda a: causal_attention(
        rope(a[0], theta), rope(a[1], theta), a[2]), (qh, kh, vh))
    o = o.reshape(n, T, Hq * hd)
    w_o = dequant_w(weights.matrix(key, layer, "attn/o", Hq * hd, d), q)
    return x + qlinear(o, w_o, bits)


def swiglu(cfg: dict, h, key, layer, bits: int, expert=0):
    """SwiGLU of one (expert) MLP: down(silu(gate h) * up h)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["quantization"]

    def w(name, K, N):
        return dequant_w(weights.matrix(key, layer, name, K, N, expert), q)

    g = qlinear(h, w("mlp/gate", d, f), bits)
    u = qlinear(h, w("mlp/up", d, f), bits)
    return qlinear(jax.nn.silu(g) * u, w("mlp/down", f, d), bits)


def _vocab_chunks(V: int, limit: int = 16384) -> int:
    for c in range(1, V + 1):
        if V % c == 0 and V // c <= limit:
            return c
    return V


def decoder_logits(cfg: dict, seed: int, tokens, rows, *, bits: int,
                   attn_bias: bool, mlp) -> np.ndarray:
    """Logits at chosen positions of a plain decoder forward.

    ``tokens`` (n, T) int32, ``rows`` (n, P) int32 positions per sequence;
    ``mlp(x, h, key, layer) -> x + mlp(h)`` is the family's MLP block.
    Returns (n, P, vocab) float32 on the device.
    """
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    key = weights.base_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    rows = jnp.asarray(rows, jnp.int32)
    x = jax.jit(lambda key, t: weights.embed(key, V, d)[t].astype(
        jnp.float32))(key, tokens)

    attn = jax.jit(functools.partial(attention_block, cfg, bits=bits,
                                     bias=attn_bias))
    norm2 = jax.jit(lambda x, key, layer: rmsnorm(
        x, weights.gain(key, layer, "ln2", d), cfg["rms_norm_eps"]))
    for layer in range(cfg["num_hidden_layers"]):
        i = jnp.int32(layer)
        x = attn(x, key, i)
        x = mlp(x, norm2(x, key, i), key, i)

    c = _vocab_chunks(V)

    @jax.jit
    def head(x, rows, key):
        xs = jnp.take_along_axis(x, rows[..., None], axis=1)  # (n, P, d)
        xs = rmsnorm(xs, weights.gain(key, -1, "final_norm", d),
                     cfg["rms_norm_eps"])
        w = weights.head(key, d, V).reshape(d, c, V // c)
        out = jax.lax.map(lambda j: mm(xs, jnp.take(w, j, axis=1).astype(
            jnp.float32)), jnp.arange(c))  # (c, n, P, V/c)
        return jnp.moveaxis(out, 0, 2).reshape(*xs.shape[:2], V)

    return head(x, rows, key)
