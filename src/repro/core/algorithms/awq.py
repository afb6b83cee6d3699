"""AWQ (Lin et al., arXiv:2306.00978): activation-aware weight scaling.

Salient input channels (large mean |x|) get their weights scaled UP before
quantization (finer effective resolution) and the activations scaled DOWN
correspondingly at runtime (the ``pre_scale`` in qlinear). The exponent
alpha is grid-searched per layer to minimize the quantized output MSE —
exactly AWQ's search, with the scale realized online instead of folded
into the previous layer (equivalent math).
"""
from __future__ import annotations

import numpy as np

from repro.core.quant import qmax


def _rtn(w: np.ndarray, bits: int, gs: int):
    K, N = w.shape
    G = K // gs
    qm = qmax(bits)
    w3 = w.reshape(G, gs, N)
    s = np.maximum(np.abs(w3).max(axis=1), 1e-8) / qm  # (G, N)
    q = np.clip(np.round(w3 / s[:, None, :]), -qm, qm)
    return q.reshape(K, N).astype(np.int8), s.astype(np.float32)


def awq_quantize(
    w: np.ndarray,   # (K, N)
    x: np.ndarray,   # (n, K)
    bits: int,
    group_size: int,
    grid: int = 8,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Returns (codes, scales, pre_scale (K,))."""
    K, N = w.shape
    gs = group_size if group_size > 0 else K
    x = x.astype(np.float32)
    act_mag = np.maximum(np.abs(x).mean(axis=0), 1e-6)  # (K,)
    ref = x @ w
    best = (None, None, None, np.inf)
    for j in range(grid + 1):
        alpha = j / grid
        s = act_mag ** alpha
        s = s / (np.sqrt(s.max() * s.min()) + 1e-12)  # normalize (AWQ)
        s = np.maximum(s, 1e-4)
        codes, scales = _rtn(w * s[:, None], bits, gs)
        deq = codes.astype(np.float32).reshape(K // gs, gs, N) \
            * scales[:, None, :]
        out = (x / s[None, :]) @ deq.reshape(K, N)
        mse = float(((ref - out) ** 2).mean())
        if mse < best[3]:
            best = (codes, scales, s.astype(np.float32), mse)
    return best[0], best[1], best[2]
