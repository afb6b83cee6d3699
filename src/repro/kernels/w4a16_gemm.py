"""Marlin-analog Pallas kernel: fine-grained W4A16 weight-only GEMM.

The paper benchmarks against Marlin's W4A16 (Fig. 1/5, Table 6). Marlin's
CUDA tricks (async copy, ldmatrix interleave, stream-K) don't transfer;
the TPU-idiomatic equivalent is: nibble-packed int4 weights streamed
HBM->VMEM (4x less weight bandwidth than bf16 — the entire point of
weight-only quant in the memory-bound decode regime), dequantized in-VMEM
to bf16 with the per-group float scale, then bf16 MXU matmul with f32
accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .w4a8_gemm import _unpack_wblock, dense_call, scale_rows, snap_blocks


def _dequant_group_accumulate(x, wp, srows, facc, *, gs: int,
                              groups_per_blk: int):
    """Shared weight-only block body (also used by the grouped MoE kernel):
    unpack int4, dequant each group to bf16 with its float scale row (from
    ``w4a8_gemm.scale_rows``), bf16 MXU matmul with f32 accumulation."""
    wfull = _unpack_wblock(wp, gs * groups_per_blk)
    for gi in range(groups_per_blk):
        xg = x[:, gi * gs:(gi + 1) * gs]  # (bm, gs) bf16
        wg = wfull[gi * gs:(gi + 1) * gs, :]  # (gs, bn) int8
        wd = (wg.astype(jnp.float32) * srows[gi]).astype(
            jnp.bfloat16
        )
        facc = facc + jax.lax.dot_general(
            xg, wd, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    return facc


def _kernel(_lr_ref, x_ref, wp_ref, s_ref, o_ref, facc_ref, *,
            nk: int, gs: int, groups_per_blk: int, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        facc_ref[...] = jnp.zeros_like(facc_ref)

    facc_ref[...] = _dequant_group_accumulate(
        x_ref[...], wp_ref[...], scale_rows(s_ref, k, groups_per_blk),
        facc_ref[...], gs=gs, groups_per_blk=groups_per_blk)

    @pl.when(k == nk - 1)
    def _epilogue():
        o_ref[...] = facc_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "bm", "bn", "bk", "interpret", "out_dtype"),
)
def w4a16_gemm(
    x: jax.Array,      # bf16 (M, K)
    qvalue: jax.Array, # int8 ([R,] K/2, N) packed
    scale: jax.Array,  # f32 ([R,] K/g, N)
    layer=None,        # int32 scalar: layer of a stacked qvalue/scale
    *,
    group_size: int = 128,
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    M, K = x.shape
    N = qvalue.shape[-1]
    gs = group_size
    bm, bn, bk = snap_blocks(M, K, N, bm=bm, bn=bn, bk=bk, group_size=gs)
    nk = K // bk
    groups_per_blk = bk // gs

    return dense_call(
        functools.partial(_kernel, nk=nk, gs=gs,
                          groups_per_blk=groups_per_blk, out_dtype=out_dtype),
        x.astype(jnp.bfloat16), None, qvalue, scale, layer, bm=bm, bn=bn,
        bk=bk, pack=2, acc_dtype=jnp.float32, out_dtype=out_dtype,
        interpret=interpret)
