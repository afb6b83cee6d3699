"""Flagship Pallas TPU kernel: fine-grained W4A8 GEMM with Integer Scale.

Implements paper Eq. 2 / Table 2 "Ours":

    C_g = A_g * W_g * s_g^INT + C_{g-1}     (all INT32, MXU + VPU)
    O   = FLOAT(C_G) * s_a / alpha          (ONE convert per output tile)

TPU adaptation:
  * per-group int8 x int8 -> int32 matmuls run on the MXU
    (``preferred_element_type=int32``), iterated over the K grid dimension;
  * the per-group *integer* scale multiply + add stays on VPU int32 lanes —
    no I32->F32 convert inside the loop (that is the float-scale
    bottleneck this kernel removes);
  * int4 weights are nibble-packed along K with a group-local (lo, hi)
    layout (``repro.core.packing``) so unpack = 2 shift pairs + one
    sublane-dim concat; no gathers/lane shuffles;
  * int32 accumulator lives in VMEM scratch across the K grid;
  * BlockSpec tiles are chosen from the call's shape (:func:`dense_blocks`):
    (bm=128, bn=256, bk=512) above one m-tile of rows, and at decode,
    where every weight byte is read once, blocks of up to 2 MiB of packed
    weight that fit the VMEM budget (:func:`stream_blocks`, which the
    ragged grouped kernel of ``moe_gemm`` shares); the ``(K/g, bn)``
    scale column stays resident across k (:func:`scale_spec`), since a
    ``(bk/g, bn)`` block per k-step is one Mosaic refuses whenever bk/g
    is not a multiple of 8.
    Interpret mode does not apply the TPU compiler's tiling and VMEM rules;
    ``tests/test_tpu_compile.py`` compiles the kernels for a v5e to do so.
  * weights and scales may be the whole layer stack ``(R, ...)`` of a
    scanned model plus a traced ``layer`` index, a scalar-prefetch operand
    that the weight and scale index maps lead with (:func:`layer_operands`).
    A Pallas call cannot take a slice fused into it, so slicing the layer
    out first would copy every layer's weights each step.

Weight-bit generality: the same kernel body serves W8A8 (``w_bits=8``,
no unpack) — Integer Scale is bit-width agnostic (paper §7).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LAYOUT_UNIT = 128  # must match repro.core.packing.LAYOUT_UNIT


def _unpack_wblock(wp: jax.Array, bk: int) -> jax.Array:
    """(bk/2, bn) packed int8 -> (bk, bn) int8, natural k-order.

    The packing layout (repro.core.packing) stores, per 128-row unit, byte b
    = (k=b | k=64+b << 4); unpack per unit is two shift pairs + one
    sublane-dim concat — no permutation. Static unroll over units.
    """
    unit = LAYOUT_UNIT if bk % LAYOUT_UNIT == 0 else bk
    h = unit // 2
    parts = []
    for u in range(bk // unit):
        w32 = wp[u * h:(u + 1) * h, :].astype(jnp.int32)
        lo = (w32 << 28) >> 28
        hi = (w32 << 24) >> 28
        parts.append(jnp.concatenate([lo, hi], axis=0))
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return out.astype(jnp.int8)


def layer_operands(qvalue: jax.Array, scale: jax.Array, layer):
    """A weight and its scale with a leading layer axis, and the layer
    index as the ``(1,)`` int32 scalar-prefetch operand that
    :func:`weight_spec` and :func:`scale_spec` read.

    A layer-stacked ``(R, ...)`` pair is read in place at ``layer``
    (clamped to ``[0, R)``, as XLA clamps a dynamic slice), so no copy of
    one layer's weights is ever made; an unstacked pair (``layer=None``)
    gains a unit axis, a bitcast, and index 0.
    """
    if layer is None:
        return qvalue[None], scale[None], jnp.zeros((1,), jnp.int32)
    idx = jnp.clip(jnp.asarray(layer, jnp.int32), 0, qvalue.shape[0] - 1)
    return qvalue, scale, idx.reshape(1)


def weight_spec(rows: int, bn: int):
    """BlockSpec of a layer-stacked ``(R, K/pack, N)`` weight on the dense
    grid ``(i, j, k)``: the ``(rows, bn)`` block of layer ``lr[0]`` (the
    scalar-prefetch ref of :func:`layer_operands`), layer axis squeezed."""
    return pl.BlockSpec((None, rows, bn), lambda i, j, k, lr: (lr[0], k, j))


def scale_spec(n_groups: int, bn: int, *, expert: bool = False):
    """BlockSpec of a layer-stacked ``(R, K/g, N)`` scale operand (or of an
    ``(E, K/g, N)`` one on the expert grid): the whole ``(K/g, bn)``
    column of the current n-tile, resident across the k axis (its block
    index ignores ``k``, so it is fetched once per output tile). A
    ``(bk/g, bn)`` block per k-step would be refused by Mosaic whenever
    ``bk/g`` is neither a multiple of 8 (the sublane tile) nor ``K/g`` —
    e.g. bk=512, g=128 gives 4. Kernels read their groups' rows out of the
    column with :func:`scale_rows`.
    """
    if expert:
        return pl.BlockSpec((1, n_groups, bn), lambda e, i, j, k: (e, 0, j))
    return pl.BlockSpec((None, n_groups, bn),
                        lambda i, j, k, lr: (lr[0], 0, j))


def dense_call(kernel, x: jax.Array, sa, qvalue: jax.Array,
               scale: jax.Array, layer, *, bm: int, bn: int, bk: int,
               pack: int, acc_dtype, out_dtype, interpret: bool):
    """Launch a dense GEMM body over the grid ``(M/bm, N/bn, K/bk)``.

    Operands, after the layer-index ref: the ``(bm, bk)`` activation
    block, the packed weight (:func:`weight_spec`), the scale column
    (:func:`scale_spec`) and, unless ``sa`` is None (weight-only), the
    ``(bm, 1)`` per-token scales. ``qvalue``/``scale`` are one layer's, or
    a layer stack read at ``layer`` (:func:`layer_operands`). Rows are
    padded to a multiple of ``bm`` and cut back after.
    """
    M, K = x.shape
    Mp = _round_up(M, bm)
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
        if sa is not None:
            sa = jnp.pad(sa, ((0, Mp - M), (0, 0)))
    qvalue, scale, lr = layer_operands(qvalue, scale, layer)
    N = qvalue.shape[-1]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k, lr: (i, k)),
        weight_spec(bk // pack, bn),
        scale_spec(scale.shape[1], bn),
    ]
    operands = [x, qvalue, scale]
    if sa is not None:
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, j, k, lr: (i, 0)))
        operands.append(sa)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Mp // bm, N // bn, K // bk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, lr: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        interpret=interpret,
    )(lr, *operands)
    return out[:M]


def scale_rows(s_ref, k, groups_per_blk: int, *, coarse: bool = False,
               expert: bool = False) -> list:
    """The k-th K-block's per-group scale rows, each ``(1, bn)``, read from
    the resident column of :func:`scale_spec`. Rows are loaded one at a
    time: Mosaic accepts a dynamic single-row load at any offset, but a
    multi-row one only at offsets it can prove are multiples of 8.
    ``coarse`` (one per-channel row) reuses row 0 for every group."""
    lead = (0,) if expert else ()

    def row(r):
        return s_ref[(*lead, pl.ds(r, 1), slice(None))]

    if coarse:
        return [row(0)] * groups_per_blk
    return [row(k * groups_per_blk + gi) for gi in range(groups_per_blk)]


def _group_accumulate(x, wp, srows, acc, *, gs: int, groups_per_blk: int,
                      w_bits: int, integer: bool):
    """Shared block body for every fine-grained W{4,8}A8 kernel: unpack the
    packed weight block, run one MXU int8 matmul per group, scale-accumulate
    with that group's ``(1, bn)`` row from :func:`scale_rows`.

    ``integer=True`` keeps the accumulation in int32 (Eq. 2 — the
    integer-scale step, no convert in the loop); ``integer=False`` converts
    each group partial to f32 and FMAs with the float scale (Eq. 1 — the
    bottleneck the paper removes).
    """
    wfull = _unpack_wblock(wp, gs * groups_per_blk) if w_bits == 4 else wp
    for gi in range(groups_per_blk):  # static unroll over groups in block
        xg = x[:, gi * gs:(gi + 1) * gs]  # (bm, gs) int8
        wg = wfull[gi * gs:(gi + 1) * gs, :]
        part = jax.lax.dot_general(  # MXU int8 matmul, int32 out
            xg, wg, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        if integer:
            # THE integer-scale step: stays in int32 — no convert in loop.
            acc = acc + part * srows[gi]
        else:
            # THE float-scale bottleneck: per-group convert + f32 FMA.
            acc = acc + part.astype(jnp.float32) * srows[gi]
    return acc


def _kernel(_lr_ref, x_ref, wp_ref, s_ref, sa_ref, o_ref, acc_ref, *,
            nk: int, gs: int, groups_per_blk: int, w_bits: int,
            alpha: float, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = _group_accumulate(
        x_ref[...], wp_ref[...], scale_rows(s_ref, k, groups_per_blk),
        acc_ref[...], gs=gs, groups_per_blk=groups_per_blk, w_bits=w_bits,
        integer=True)

    @pl.when(k == nk - 1)
    def _epilogue():
        # ONE I32->F32 convert per output tile; /alpha folded into s_a.
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * (sa_ref[...] * (1.0 / alpha))
        ).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "alpha", "w_bits", "bm", "bn", "bk",
                     "interpret", "out_dtype"),
)
def fg_gemm_integer_scale(
    xq: jax.Array,        # int8 (M, K)
    sa: jax.Array,        # f32 (M, 1) per-token scales
    qvalue: jax.Array,    # int8 ([R,] K/2, N) packed (w4) | ([R,] K, N) (w8)
    int_scale: jax.Array, # int32 ([R,] K/g, N)
    layer=None,           # int32 scalar: layer of a stacked qvalue/scale
    *,
    group_size: int = 128,
    alpha: float = 1024.0,
    w_bits: int = 4,
    bm: int = 128,
    bn: int | None = None,  # None (with bk): chosen by shape, dense_blocks
    bk: int | None = None,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    M, K = xq.shape
    N = qvalue.shape[-1]
    gs = group_size
    if K % gs:
        raise ValueError(f"K={K} % group={gs}")
    bm, bn, bk = dense_blocks(M, K, N, group_size=gs, w_bits=w_bits, bm=bm,
                              bn=bn, bk=bk)
    nk = K // bk
    groups_per_blk = bk // gs

    pack = 2 if w_bits == 4 else 1
    return dense_call(
        functools.partial(
            _kernel, nk=nk, gs=gs, groups_per_blk=groups_per_blk,
            w_bits=w_bits, alpha=alpha, out_dtype=out_dtype,
        ),
        xq, sa, qvalue, int_scale, layer, bm=bm, bn=bn, bk=bk, pack=pack,
        acc_dtype=jnp.int32, out_dtype=out_dtype, interpret=interpret)


#: Blocks of every kernel's default launch above one m-tile of rows.
DEFAULT_BN, DEFAULT_BK = 256, 512
#: Rows of one m-tile: up to here a call streams each weight byte once.
STREAM_ROWS = 128
#: Caps of :func:`stream_blocks`: the widest n-block, the deepest k-block,
#: and the packed weight bytes of one grid step.
STREAM_BN, STREAM_BK, STREAM_BYTES = 2048, 2048, 2 << 20
#: VMEM that one grid step's blocks and scratch (:func:`step_vmem`) may
#: take under :func:`stream_blocks`: three quarters of Mosaic's default
#: scoped limit on a v5e (16 MiB), which no kernel here raises. The rest
#: is left to the kernel body's temporaries, which are not blocks; at
#: Mixtral's expert down projection (bm = 128, bn = 2048) they took
#: 1.9 MiB (compiles for a v5e).
VMEM_BUDGET = 12 << 20


def step_vmem(rows: int, K: int, bn: int, bk: int, *, group_size: int,
              w_bits: int, slab_itemsize: int = 0) -> int:
    """Bytes of VMEM one grid step of an integer-scale GEMM holds in
    blocks and scratch, at ``bm = rows``: the packed ``(bk/pack, bn)``
    weight block and the ``(K/g, bn)`` int32 scale column
    (:func:`scale_spec`), both double-buffered; the int32 accumulator and
    the double-buffered f32 output tile; the per-token scales, a lane
    tile a row; and the activations. The dense kernel streams
    ``(rows, bk)`` int8 blocks, double-buffered (``slab_itemsize=0``);
    the ragged grouped kernels keep an m-tile's raw ``(rows, K)`` slab,
    single-buffered, at ``slab_itemsize`` bytes a value, and its int8
    codes (``moe_gemm``)."""
    pack = 2 if w_bits == 4 else 1
    if slab_itemsize:
        acts = rows * K * (slab_itemsize + 1)
    else:
        acts = 2 * rows * bk
    scale = _round_up(K // group_size, 8) * bn * 4
    return (acts + 2 * rows * 128 * 4 + 2 * (bk // pack) * bn
            + 2 * scale + 3 * rows * bn * 4)


def stream_blocks(rows: int, K: int, N: int, *, group_size: int,
                  w_bits: int, slab_itemsize: int = 0) -> tuple[int, int]:
    """``(bn, bk)`` of an integer-scale call whose rows fit one m-tile:
    a dense call at decode (M = the engine's slots), or a ragged grouped
    call whose per-expert capacity C fits one (``moe_gemm``; the grid is
    then ``(E, 1, N/bn, K/bk)``).

    Every weight byte of the call (of each routed expert) is then read
    once, so the bytes do not depend on the blocks; the number of grid
    steps does. Each step costs ≈ 0.35 us of its own, while a
    ``(512, 256)`` int4 block moves 64 KB, 0.08 us at 819 GB/s: small
    blocks leave such calls bound by steps, not bytes. So take the widest
    ``bn`` (a multiple of 128 dividing N, up to ``STREAM_BN``), then the
    deepest ``bk`` (whole groups and packing units, dividing K, up to
    ``STREAM_BK``) that keeps the packed block within ``STREAM_BYTES``
    and the step within ``VMEM_BUDGET`` (:func:`step_vmem`, with the
    call's ``rows`` and ``slab_itemsize``); a narrower ``bn`` only where
    no ``bk`` fits. Where no such divisor exists, the defaults, snapped
    as ever. On a v5e at M = 32, Qwen2-72B's gate projection
    (8192 -> 29568) takes 1295 us a call at ``(bn, bk) = (128, 512)`` and
    225 us at the ``(1408, 2048)`` chosen here. Mixtral's expert
    projections take ``(2048, 2048)`` at C = 32; its down projection
    (K = 14336) at C = 128 with an f32 slab, whose 9 MiB leave no room
    for a 2 MiB block, takes ``(1024, 512)``.
    """
    pack = 2 if w_bits == 4 else 1
    align = math.lcm(group_size, LAYOUT_UNIT)
    for bn in _divisors(N, STREAM_BN, LAYOUT_UNIT) or [DEFAULT_BN]:
        cap = min(STREAM_BK, STREAM_BYTES * pack // bn)
        for bk in _divisors(K, cap, align) or [DEFAULT_BK]:
            if step_vmem(rows, K, bn, bk, group_size=group_size,
                         w_bits=w_bits,
                         slab_itemsize=slab_itemsize) <= VMEM_BUDGET:
                return bn, bk
    return DEFAULT_BN, DEFAULT_BK


def dense_blocks(M: int, K: int, N: int, *, group_size: int, w_bits: int,
                 bm: int = 128, bn: int | None = None,
                 bk: int | None = None,
                 slab_itemsize: int = 0) -> tuple[int, int, int]:
    """The ``(bm, bn, bk)`` an integer-scale call launches with: the
    dense GEMM (``M`` rows) or the ragged grouped one (``M`` = the
    per-expert capacity C, ``slab_itemsize`` its dispatch buffer's).

    Blocks the caller gives are honoured, the other at its default
    (``DEFAULT_BN``/``DEFAULT_BK``). With neither given, a call of at
    most ``STREAM_ROWS`` rows takes :func:`stream_blocks`, a larger one
    the defaults. Either way :func:`snap_blocks` makes them tile the call.
    """
    if bn is None and bk is None and _round_up(M, 8) <= STREAM_ROWS:
        bn, bk = stream_blocks(min(bm, _round_up(M, 8)), K, N,
                               group_size=group_size, w_bits=w_bits,
                               slab_itemsize=slab_itemsize)
    return snap_blocks(M, K, N, bm=bm, bn=bn or DEFAULT_BN,
                       bk=bk or DEFAULT_BK, group_size=group_size)


def snap_blocks(rows: int, K: int, N: int, *, bm: int, bn: int, bk: int,
                group_size: int) -> tuple[int, int, int]:
    """Snap ``(bm, bn, bk)`` to tile an ``(rows, K) x (K, N)`` call
    exactly: ``bm`` to the rows rounded up to 8, ``bn`` and ``bk`` down to
    divisors of N and K (lane-aligned where one exists), ``bk`` holding
    whole groups (``group_size <= 0``: one per-channel scale, no groups).
    Every GEMM wrapper launches what this returns."""
    coarse = group_size <= 0
    bm = min(bm, _round_up(rows, 8))
    bn = _snap_block(N, bn, 128)
    bk = _snap_block(K, min(bk, K), 1 if coarse else group_size)
    if not coarse and bk % group_size:
        bk = group_size  # block must hold whole groups
    return bm, bn, bk


def _divisors(dim: int, cap: int, align: int) -> list[int]:
    """Multiples of ``align`` that divide ``dim`` and are at most ``cap``,
    largest first."""
    return [cand for cand in range(min(cap, dim) // align * align, 0, -align)
            if dim % cand == 0]


def _snap_block(dim: int, blk: int, align: int) -> int:
    """Largest divisor of ``dim`` that is <= blk and a multiple of
    ``align`` (falling back to any divisor) — grids must tile exactly."""
    blk = min(blk, dim)
    if dim % blk == 0:
        return blk
    for cand in range(blk, 0, -1):
        if dim % cand == 0 and cand % align == 0:
            return cand
    for cand in range(blk, 0, -1):
        if dim % cand == 0:
            return cand
    return dim


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, b: int) -> int:
    return _cdiv(a, b) * b
