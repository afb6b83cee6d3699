"""Fused per-token activation quantization kernel (producer for the qGEMMs).

Per-token symmetric absmax int8 quantization of the last axis — the
activation-side half of W4A8/W8A8 (paper §5.1 "per-token activation
quantization"). Fusing this into a single VMEM pass (read bf16 row, write
int8 row + f32 scale) is part of the FastGEMM-style fusion the paper
borrows from OdysseyLLM (§4.2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .w4a8_gemm import _round_up


def _chunk(K: int) -> int:
    for c in (512, 256, 128):
        if K % c == 0:
            return c
    return K


def quantize_rows(x_ref, q_ref, *, qm: float, lead: tuple = ()):
    """Shared block body: per-row symmetric absmax int8 quantization of the
    ``(rows, K)`` block ``x_ref[lead]`` into ``q_ref``; returns the
    ``(rows, 1)`` f32 scales.

    Used by the standalone ``act_quant`` kernel below AND by the ragged
    grouped MoE GEMM (``moe_gemm``), which folds this into its first
    k-group pass — both paths run the exact same f32 ops, so fused and
    unfused activation quantization stay bit-identical. The row is walked
    in lane chunks (amax pass, then quantize pass) so the f32 temporaries
    are ``(rows, chunk)`` and not ``(rows, K)``: at K=14336 a whole-row
    upcast alone is 7 MiB of VMEM per 128 rows. ``max`` is exact, so the
    chunking changes no bit. The ``1e-8`` amax floor keeps all-zero
    (capacity-padded) rows finite; their codes are still exactly zero.
    """
    K = q_ref.shape[-1]
    c = _chunk(K)

    def xf(c0):
        return x_ref[(*lead, slice(None), slice(c0, c0 + c))].astype(
            jnp.float32)

    amax = None
    for c0 in range(0, K, c):
        m = jnp.max(jnp.abs(xf(c0)), axis=-1, keepdims=True)
        amax = m if amax is None else jnp.maximum(amax, m)
    scale = jnp.maximum(amax, 1e-8) / qm
    for c0 in range(0, K, c):
        q_ref[:, c0:c0 + c] = jnp.clip(
            jnp.round(xf(c0) / scale), -qm, qm).astype(jnp.int8)
    return scale


def _kernel(x_ref, q_ref, s_ref, *, qm: float):
    s_ref[...] = quantize_rows(x_ref, q_ref, qm=qm)


# VMEM for the double-buffered (bm, K) input and int8 output blocks: half
# of the 16 MiB default scoped VMEM, leaving the rest to the compiler.
_BLOCK_BUDGET = 8 << 20


def _row_block(M: int, K: int, itemsize: int, bm: int) -> int:
    """Rows per block: ``bm`` (capped by M), halved while the whole-row
    blocks would overrun ``_BLOCK_BUDGET`` — at K=14336 in bf16, 256 rows
    need 21 MiB and the compiler refuses the kernel."""
    bm = min(bm, _round_up(M, 8))
    while bm > 32 and 2 * bm * K * (itemsize + 1) > _BLOCK_BUDGET:
        bm //= 2
    return bm


@functools.partial(jax.jit, static_argnames=("bits", "bm", "interpret"))
def act_quant(
    x: jax.Array,  # (M, K) bf16/f32
    *,
    bits: int = 8,
    bm: int = 256,
    interpret: bool = False,
):
    """Returns (q int8 (M,K), scale f32 (M,1))."""
    M, K = x.shape
    qm = float(2 ** (bits - 1) - 1)
    bm = _row_block(M, K, x.dtype.itemsize, bm)
    Mp = _round_up(M, bm)
    if Mp != M:
        # pad with ones (not zeros) so padded rows have a sane nonzero amax
        x = jnp.pad(x, ((0, Mp - M), (0, 0)), constant_values=1)
    q, s = pl.pallas_call(
        functools.partial(_kernel, qm=qm),
        grid=(Mp // bm,),
        in_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, K), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, K), jnp.int8),
            jax.ShapeDtypeStruct((Mp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q[:M], s[:M]
