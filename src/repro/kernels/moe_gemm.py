"""Grouped (batched-expert) Pallas TPU kernels for MoE FFNs (paper §5.5).

The Mixtral headline result needs every expert's fine-grained W4A8 GEMM to
run through the integer-scale fast path. ``jax.vmap`` over the reference
GEMM materializes E independent XLA dots with per-group float bookkeeping;
instead these kernels run ONE ``pallas_call`` whose grid iterates
``(experts, m-tiles, n-tiles, k-groups)`` over the dense dispatch buffer —
the Marlin/FPTQ-style batched-expert GEMM, with the expert index just an
extra (outermost) grid dimension selecting the weight/scale slabs.

All three quantization schemes ride the same structure:

  * ``fg_grouped_gemm_integer_scale_ragged`` — Eq. 2 per expert: int32
    group accumulation, ONE convert per output tile. Per-expert amplifiers
    (heuristic recipes give each expert its own alpha) arrive as an
    ``(E, 1)`` SMEM operand that the epilogue divides the per-token scale
    by.
  * ``fg_grouped_gemm_float_scale_ragged`` — Eq. 1 baseline (per-group
    converts), also serves coarse per-channel scales (``group_size=-1``).
  * ``grouped_w4a16_gemm_ragged`` — weight-only Marlin-analog (in-VMEM
    dequant to bf16, fp MXU matmul).

The block bodies are the SAME helpers the dense kernels use
(``w4a8_gemm._group_accumulate`` / ``w4a16_gemm._dequant_group_accumulate``)
— the grouped kernels add only the expert grid dimension, the ragged
skipping and the fused activation quantization.

Ragged scalar-prefetch contract
-------------------------------

The kernels take the per-expert routed row counts as a scalar-prefetch
operand (``pltpu.PrefetchScalarGridSpec``) and skip every m-tile that
starts at or past its expert's count:

  * ``row_counts`` is int32 ``(E,)``; rows ``[0, row_counts[e])`` of expert
    ``e``'s capacity slab are routed tokens, every row at or past
    ``row_counts[e]`` MUST be zero-filled (exactly what the sort-based
    dispatch in ``models.moe`` produces). Counts are clamped to ``C``;
    ``None`` takes every capacity slot as routed.
  * the grid still statically covers ``(E, C/bm, N/bn, K/bk)``, but for an
    inactive m-tile the block index maps clamp every operand to an
    already-resident block (no DMA is issued for a revisited block) and
    ``pl.when`` skips the quant/MXU body, so inactive grid steps cost only
    grid bookkeeping; the epilogue writes exact zeros for them. Executed
    m-tile work drops from ``E * ceil(C/bm)`` to
    ``sum_e ceil(row_counts[e]/bm)`` (see :func:`ragged_tile_stats`).
  * activation quantization is FUSED: the ragged W4A8 kernels consume the
    raw bf16/f32 dispatch buffer and quantize each (bm, K) row-block once
    into VMEM scratch on the tile's first (j==0, k==0) pass, reusing the
    codes for every n-tile/k-group — ``ops.qgemm_grouped`` no longer runs
    the dense ``act_quant`` kernel over the full ``(E*C, K)`` buffer, so
    the padded slots are never even quantized. The in-kernel math is
    ``act_quant.quantize_rows`` verbatim, which keeps fused and unfused
    paths bit-identical.
  * bit-exactness invariant: for any zero-filled-past-count input, the
    output equals the per-expert reference of ``kernels/ref.py`` on the
    ``act_quant`` codes on every row below ``row_counts[e]`` (element for
    element on the integer-scale path, including per-expert alphas: the
    epilogue divides by alpha with the reference's op order), and is
    exact zeros past it.
  * the weights and scales may be a scanned model's whole layer stack
    ``(R, E, ...)`` with a traced ``layer`` index, a second scalar-prefetch
    operand that their index maps lead with (``w4a8_gemm.layer_operands``):
    no copy of one layer's experts is made. The output equals the call on
    ``qvalue[layer]``, bit for bit.

VMEM at full K. The fused-quant kernels hold an m-tile's whole raw
``(bm, K)`` activation slab plus its ``(bm, K)`` int8 codes. At Mixtral's
expert down projection (K=14336, bm=128) the compiler refused them for
VMEM at every capacity that is a multiple of bm: a whole-row f32 upcast
inside the quantization (7 MiB per temporary) on top of a double-buffered
slab (7 MiB in bf16, 14 MiB in f32) against the 16 MiB default scoped
limit. Two changes fix it without shrinking the tiles: the slab is
quantized in lane chunks (``act_quant.quantize_rows``; temporaries are
``(bm, 512)``), and it is single-buffered (``pl.Buffered(1)``). Its block
index changes once per m-tile, every ``N/bn * K/bk`` grid steps, so a
second buffer would only prefetch the next tile's slab at the cost of a
whole slab of VMEM. Capping bm by K would halve the MXU rows of prefill;
raising ``vmem_limit_bytes`` would tie the kernel to one chip's VMEM and
grow with K. ``tests/test_tpu_compile.py`` compiles every capacity.

Blocks. ``bm`` is C rounded up to 8, at most 128. The integer-scale
kernel, given no ``bn``/``bk``, takes them by call shape
(``w4a8_gemm.dense_blocks``). Where C fits one m-tile (decode) the grid
is ``(E, 1, N/bn, K/bk)`` and each routed expert's weights are read once,
so the blocks set only the number of grid steps: up to 2 MiB of packed
weight a step (``w4a8_gemm.stream_blocks``; Mixtral's expert projections
at C = 32 run 112 steps a call, not 3584). The slab and its codes stay
resident beside those blocks, so the rule charges them, with the
double-buffered scale column, to the same VMEM budget
(``w4a8_gemm.step_vmem``) and narrows the blocks where they would not
fit (down at C = 128 with an f32 slab: ``(1024, 512)``). Above one
m-tile (prefill), and in the float-scale and weight-only kernels,
``(bn, bk) = (256, 512)``, snapped to tile the call.

With ragged skipping in place the ``act_quant`` ``maximum(amax, 1e-8)``
floor is no longer what keeps padded slots sane on the grouped path (they
are skipped outright, and partial-tile zero rows quantize to zero codes
regardless of the floor); the floor still protects genuinely all-zero
*routed* rows and the dense/standalone users, so it stays — but it can now
be revisited independently of MoE capacity padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .act_quant import quantize_rows
from .w4a8_gemm import (_cdiv, _group_accumulate, _round_up, dense_blocks,
                        layer_operands, scale_rows, snap_blocks)
from .w4a16_gemm import _dequant_group_accumulate


def ragged_tile_stats(row_counts, C: int, bm: int = 128) -> dict:
    """Executed-m-tile accounting for a ragged launch (python ints).

    ``dense_m_tiles`` is every m-tile of the grid, as if none were
    skipped; per m-tile the full (N/bn, K/bk) inner grid does MXU work, so
    the ratio is the MAC-savings of ragged skipping.
    """
    bm = min(bm, _round_up(C, 8))
    Cp = _round_up(C, bm)
    counts = [min(int(c), C) for c in row_counts]
    dense = len(counts) * (Cp // bm)
    ragged = sum(_cdiv(c, bm) for c in counts)
    return {"bm": bm, "dense_m_tiles": dense, "ragged_m_tiles": ragged}


def _ragged_specs(E, Cp, K, N, bm, bn, bk, *, pack, s_rows,
                  fused_quant, n_extra=0):
    """Grid + BlockSpecs for the ragged kernels.

    Index maps receive the scalar-prefetch ``row_counts`` ref and the
    layer-index ref (``w4a8_gemm.layer_operands``) as trailing args; the
    weight and scale operands are layer stacks ``(R, E, ...)`` whose
    squeezed leading block index is that layer. Inactive m-tiles clamp
    every input block index to one that is (or was just) resident so the
    pipeline issues no DMA for them; the output map is NOT clamped
    (inactive tiles must write their zeros).
    """

    def _last_tile(rc, e):
        # index of the last active m-tile (0 when the expert is empty)
        return jnp.maximum(pl.cdiv(rc[e], bm) - 1, 0)

    if fused_quant:
        # raw activations: one full-K row slab per (e, m-tile); quantized
        # into scratch at (j==0, k==0) and reused across every (j, k).
        def x_map(e, i, j, k, rc, lr):
            return (e, jnp.minimum(i, _last_tile(rc, e)), 0)

        x_spec = pl.BlockSpec((1, bm, K), x_map,
                              pipeline_mode=pl.Buffered(1))
    else:
        def x_map(e, i, j, k, rc, lr):
            act = i * bm < rc[e]
            return (e, jnp.minimum(i, _last_tile(rc, e)),
                    jnp.where(act, k, 0))

        x_spec = pl.BlockSpec((1, bm, bk), x_map)

    def w_map(e, i, j, k, rc, lr):
        act = i * bm < rc[e]
        return (lr[0], e, jnp.where(act, k, 0), jnp.where(act, j, 0))

    def s_map(e, i, j, k, rc, lr):
        # the whole (s_rows, bn) scale column, as in w4a8_gemm.scale_spec
        return (lr[0], e, 0, jnp.where(i * bm < rc[e], j, 0))

    nk = K // bk
    grid = (E, Cp // bm, N // bn, nk)
    in_specs = [
        x_spec,
        pl.BlockSpec((None, 1, bk // pack, bn), w_map),
        pl.BlockSpec((None, 1, s_rows, bn), s_map),
    ]
    if n_extra:  # per-expert alpha: the whole (E, 1) f32 array, in SMEM
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    out_spec = pl.BlockSpec((1, bm, bn),
                            lambda e, i, j, k, rc, lr: (e, i, j))
    return grid, in_specs, out_spec, nk


def _ragged_kernel(rc_ref, _lr_ref, x_ref, wp_ref, s_ref, a_ref, o_ref,
                   xq_s, sa_s, acc_ref, *,
                   nk: int, gs: int, groups_per_blk: int, w_bits: int,
                   integer: bool, coarse: bool, bm: int, bk: int,
                   qm: float, out_dtype):
    """Ragged W{4,8}A8 tile with FUSED activation quantization.

    Quantizes the (bm, K) row slab once per m-tile (first j/k pass) into
    int8+scale VMEM scratch via the exact ``act_quant`` block body, then
    accumulates k-groups from the scratch codes. Inactive tiles skip all
    of it and write zeros.
    """
    e = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    k = pl.program_id(3)
    active = i * bm < rc_ref[e]

    @pl.when(active & (j == 0) & (k == 0))
    def _quant():
        sa_s[...] = quantize_rows(x_ref, xq_s, qm=qm, lead=(0,))

    @pl.when(active)
    def _body():
        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        xblk = xq_s[:, pl.ds(k * bk, bk)]
        acc_ref[...] = _group_accumulate(
            xblk, wp_ref[0],
            scale_rows(s_ref, k, groups_per_blk, coarse=coarse, expert=True),
            acc_ref[...], gs=gs, groups_per_blk=groups_per_blk,
            w_bits=w_bits, integer=integer)

    @pl.when(k == nk - 1)
    def _epilogue():
        @pl.when(active)
        def _write():
            # same op order as ``ref.fg_gemm_is_ref`` (sa / alpha, then
            # ONE multiply) so the output equals the reference bitwise.
            sa = sa_s[...] / a_ref[e, 0]
            if integer:
                o_ref[0] = (acc_ref[...].astype(jnp.float32)
                            * sa).astype(out_dtype)
            else:
                o_ref[0] = (acc_ref[...] * sa).astype(out_dtype)

        @pl.when(jnp.logical_not(active))
        def _zeros():
            o_ref[0] = jnp.zeros_like(o_ref[0])


def _ragged_a8_call(x, row_counts, qvalue, scale, alpha, layer, *,
                    integer: bool, group_size: int, a_bits: int, w_bits: int,
                    bm: int, bn: int | None, bk: int | None, interpret: bool,
                    out_dtype):
    """Shared wrapper for the ragged integer-/float-scale W{4,8}A8 kernels.
    ``bn = bk = None``: blocks by shape (``w4a8_gemm.dense_blocks``)."""
    E, C, K = x.shape
    N = qvalue.shape[-1]
    coarse = group_size <= 0
    gs = K if coarse else group_size
    if not coarse and K % gs:
        raise ValueError(f"K={K} % group={gs}")
    bm, bn, bk = dense_blocks(C, K, N, group_size=group_size, w_bits=w_bits,
                              bm=bm, bn=bn, bk=bk,
                              slab_itemsize=x.dtype.itemsize)
    if coarse:
        gs = bk  # each K-block is one "group" with the constant scale
    groups_per_blk = bk // gs
    qm = float(2 ** (a_bits - 1) - 1)

    if row_counts is None:
        rc = jnp.full((E,), C, jnp.int32)
    else:
        rc = jnp.minimum(jnp.asarray(row_counts, jnp.int32), C)

    # per-expert amplifier as an (E, 1) operand (1.0 on the float path)
    a = jnp.broadcast_to(
        jnp.asarray(alpha, jnp.float32).reshape(-1)[:, None], (E, 1))

    Cp = _round_up(C, bm)
    if Cp != C:
        x = jnp.pad(x, ((0, 0), (0, Cp - C), (0, 0)))

    qvalue, scale, lr = layer_operands(qvalue, scale, layer)
    pack = 2 if w_bits == 4 else 1
    grid, in_specs, out_spec, nk = _ragged_specs(
        E, Cp, K, N, bm, bn, bk, pack=pack, s_rows=scale.shape[2],
        fused_quant=True, n_extra=1)
    acc_dtype = jnp.int32 if integer else jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((bm, K), jnp.int8),     # quantized row slab
            pltpu.VMEM((bm, 1), jnp.float32),  # per-token scales
            pltpu.VMEM((bm, bn), acc_dtype),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _ragged_kernel, nk=nk, gs=gs, groups_per_blk=groups_per_blk,
            w_bits=w_bits, integer=integer, coarse=coarse, bm=bm, bk=bk,
            qm=qm, out_dtype=out_dtype,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, Cp, N), out_dtype),
        interpret=interpret,
    )(rc, lr, x, qvalue, scale, a)
    return out[:, :C]


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "a_bits", "w_bits", "bm", "bn", "bk",
                     "interpret", "out_dtype"),
)
def fg_grouped_gemm_integer_scale_ragged(
    x: jax.Array,          # bf16/f32 (E, C, K) RAW dispatch buffer
    row_counts,            # int32 (E,) routed rows per expert, or None
    qvalue: jax.Array,     # int8 ([R,] E, K/2 | K, N): w4 packed | w8
    int_scale: jax.Array,  # int32 ([R,] E, K/g, N)
    layer=None,            # int32 scalar: layer of a stacked qvalue/scale
    *,
    group_size: int = 128,
    alpha=1024.0,          # python float, or f32 (E,) per-expert amplifiers
    a_bits: int = 8,
    w_bits: int = 4,
    bm: int = 128,
    bn: int | None = None,  # None (with bk): chosen by shape, dense_blocks
    bk: int | None = None,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Ragged batched-expert Eq. 2 GEMM with fused act-quant."""
    return _ragged_a8_call(
        x, row_counts, qvalue, int_scale, alpha, layer, integer=True,
        group_size=group_size, a_bits=a_bits, w_bits=w_bits,
        bm=bm, bn=bn, bk=bk, interpret=interpret, out_dtype=out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "a_bits", "w_bits", "bm", "bn", "bk",
                     "interpret", "out_dtype"),
)
def fg_grouped_gemm_float_scale_ragged(
    x: jax.Array,      # bf16/f32 (E, C, K) RAW dispatch buffer
    row_counts,        # int32 (E,) routed rows per expert, or None
    qvalue: jax.Array, # int8 ([R,] E, K/2 | K, N): w4 packed | w8
    scale: jax.Array,  # f32 ([R,] E, K/g, N) fine | ([R,] E, 1, N) coarse
    layer=None,        # int32 scalar: layer of a stacked qvalue/scale
    *,
    group_size: int = 128,  # -1 => coarse
    a_bits: int = 8,
    w_bits: int = 4,
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Ragged batched-expert Eq. 1 baseline with fused act-quant."""
    return _ragged_a8_call(
        x, row_counts, qvalue, scale, 1.0, layer, integer=False,
        group_size=group_size, a_bits=a_bits, w_bits=w_bits,
        bm=bm, bn=bn, bk=bk, interpret=interpret, out_dtype=out_dtype)


def _ragged_wo_kernel(rc_ref, _lr_ref, x_ref, wp_ref, s_ref, o_ref,
                      facc_ref, *, nk: int, gs: int, groups_per_blk: int,
                      bm: int, out_dtype):
    e = pl.program_id(0)
    i = pl.program_id(1)
    k = pl.program_id(3)
    active = i * bm < rc_ref[e]

    @pl.when(active)
    def _body():
        @pl.when(k == 0)
        def _init():
            facc_ref[...] = jnp.zeros_like(facc_ref)

        facc_ref[...] = _dequant_group_accumulate(
            x_ref[0], wp_ref[0],
            scale_rows(s_ref, k, groups_per_blk, expert=True),
            facc_ref[...], gs=gs, groups_per_blk=groups_per_blk)

    @pl.when(k == nk - 1)
    def _epilogue():
        @pl.when(active)
        def _write():
            o_ref[0] = facc_ref[...].astype(out_dtype)

        @pl.when(jnp.logical_not(active))
        def _zeros():
            o_ref[0] = jnp.zeros_like(o_ref[0])


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "bm", "bn", "bk", "interpret",
                     "out_dtype"),
)
def grouped_w4a16_gemm_ragged(
    x: jax.Array,      # bf16 (E, C, K)
    row_counts,        # int32 (E,) routed rows per expert, or None
    qvalue: jax.Array, # int8 ([R,] E, K/2, N) packed
    scale: jax.Array,  # f32 ([R,] E, K/g, N)
    layer=None,        # int32 scalar: layer of a stacked qvalue/scale
    *,
    group_size: int = 128,
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Ragged batched-expert weight-only Marlin-analog (no act-quant)."""
    E, C, K = x.shape
    N = qvalue.shape[-1]
    gs = group_size
    bm, bn, bk = snap_blocks(C, K, N, bm=bm, bn=bn, bk=bk,
                             group_size=group_size)
    groups_per_blk = bk // gs

    if row_counts is None:
        rc = jnp.full((E,), C, jnp.int32)
    else:
        rc = jnp.minimum(jnp.asarray(row_counts, jnp.int32), C)

    Cp = _round_up(C, bm)
    if Cp != C:
        x = jnp.pad(x, ((0, 0), (0, Cp - C), (0, 0)))
    qvalue, scale, lr = layer_operands(qvalue, scale, layer)
    grid, in_specs, out_spec, nk = _ragged_specs(
        E, Cp, K, N, bm, bn, bk, pack=2, s_rows=K // gs,
        fused_quant=False)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_wo_kernel, nk=nk, gs=gs,
                          groups_per_blk=groups_per_blk, bm=bm,
                          out_dtype=out_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, Cp, N), out_dtype),
        interpret=interpret,
    )(rc, lr, x.astype(jnp.bfloat16), qvalue, scale)
    return out[:, :C]
