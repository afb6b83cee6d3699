"""Baseline Pallas kernel: fine-grained W4A8 GEMM with FLOAT scales (Eq. 1).

Identical structure to ``w4a8_gemm.py`` except the inner loop — which is the
whole point. Per group it must
    1. convert the int32 MXU partial to f32            (I32->F32, VPU)
    2. FMA with the group's float scale into an f32 accumulator.
That is ``K/group`` converts + f32 FMAs per output tile (paper Fig. 2b,
Table 2 "Atom" column) versus ONE convert total for Integer Scale. Keeping
the two kernels diff-minimal isolates the paper's claim structurally.

Also serves coarse-grained W4A8/W8A8 (group_size=-1): the single per-channel
scale is applied per K-block (mathematically identical since it is constant
across groups) — this is the OdysseyLLM-style baseline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .w4a8_gemm import _group_accumulate, dense_call, scale_rows, snap_blocks


def _kernel(_lr_ref, x_ref, wp_ref, s_ref, sa_ref, o_ref, facc_ref, *,
            nk: int, gs: int, groups_per_blk: int, w_bits: int,
            coarse: bool, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        facc_ref[...] = jnp.zeros_like(facc_ref)

    facc_ref[...] = _group_accumulate(
        x_ref[...], wp_ref[...],
        scale_rows(s_ref, k, groups_per_blk, coarse=coarse), facc_ref[...],
        gs=gs, groups_per_blk=groups_per_blk, w_bits=w_bits, integer=False)

    @pl.when(k == nk - 1)
    def _epilogue():
        o_ref[...] = (facc_ref[...] * sa_ref[...]).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "w_bits", "bm", "bn", "bk", "interpret",
                     "out_dtype"),
)
def fg_gemm_float_scale(
    xq: jax.Array,     # int8 (M, K)
    sa: jax.Array,     # f32 (M, 1)
    qvalue: jax.Array, # int8 ([R,] K/2, N) packed (w4) | ([R,] K, N) (w8)
    scale: jax.Array,  # f32 ([R,] K/g, N) fine | ([R,] 1, N) coarse
    layer=None,        # int32 scalar: layer of a stacked qvalue/scale
    *,
    group_size: int = 128,  # -1 => coarse
    w_bits: int = 4,
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    M, K = xq.shape
    N = qvalue.shape[-1]
    coarse = group_size <= 0
    gs = K if coarse else group_size
    bm, bn, bk = snap_blocks(M, K, N, bm=bm, bn=bn, bk=bk,
                             group_size=group_size)
    if coarse:
        gs = bk  # treat each K-block as one "group" with the constant scale
    nk = K // bk
    groups_per_blk = bk // gs

    pack = 2 if w_bits == 4 else 1
    return dense_call(
        functools.partial(
            _kernel, nk=nk, gs=gs, groups_per_blk=groups_per_blk,
            w_bits=w_bits, coarse=coarse, out_dtype=out_dtype,
        ),
        xq, sa, qvalue, scale, layer, bm=bm, bn=bn, bk=bk, pack=pack,
        acc_dtype=jnp.float32, out_dtype=out_dtype, interpret=interpret)
