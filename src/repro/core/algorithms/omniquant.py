"""Omniquant-lite (Shao et al., arXiv:2308.13137): weight clipping search.

The full Omniquant learns clipping + smoothing by gradient descent; this
lite version grid-searches the clip ratio per layer against the calibrated
output MSE — the same "learnable weight clipping" degree of freedom,
optimized by direct search (adequate at this model scale).
"""
from __future__ import annotations

import numpy as np

from repro.core.quant import qmax


def omniquant_quantize(
    w: np.ndarray,   # (K, N)
    x: np.ndarray,   # (n, K)
    bits: int,
    group_size: int,
    grid=(1.0, 0.95, 0.9, 0.85, 0.8, 0.75, 0.7),
) -> tuple[np.ndarray, np.ndarray]:
    K, N = w.shape
    gs = group_size if group_size > 0 else K
    G = K // gs
    qm = qmax(bits)
    x = x.astype(np.float32)
    ref = x @ w
    w3 = w.reshape(G, gs, N)
    best = (None, None, np.inf)
    for clip in grid:
        s = np.maximum(np.abs(w3).max(axis=1) * clip, 1e-8) / qm
        q = np.clip(np.round(w3 / s[:, None, :]), -qm, qm)
        deq = (q * s[:, None, :]).reshape(K, N)
        mse = float(((ref - x @ deq) ** 2).mean())
        if mse < best[2]:
            best = (q.reshape(K, N).astype(np.int8), s.astype(np.float32),
                    mse)
    return best[0], best[1]
