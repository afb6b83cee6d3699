"""Kernel/config registry walked by ``python -m repro.analysis.qlint``.

Every Pallas kernel in ``repro.kernels`` is registered here with a
representative config: deterministic synthetic weights/scales (static
operands are seeded tight from their concrete values) and contract
ranges for the data-dependent operands (activations from the a_bits
range, ragged row counts from the wrapper's [0, C] clamp contract).

How to register a new kernel
----------------------------
Append a :class:`KernelEntry` in :func:`entries`:

* ``build`` returns ``(fn, args, input_ranges)`` — ``fn(*args)`` must be
  traceable by ``jax.make_jaxpr`` (the jitted wrappers are fine);
  ``input_ranges`` maps arg positions to :class:`Interval` contract
  ranges (or ``interp.DATA``) for operands whose concrete values are
  placeholders.
* set ``integer_scale=True`` (and ``alpha``) iff the kernel carries the
  Eq. 2 INT32 accumulation — it then gets an overflow certificate and
  the single-convert lint rule.
* kernels with scalar-prefetch operands set ``prefetch_ranges`` so the
  index-map bounds rule can seed those refs: the ragged row counts, and
  the layer index that every GEMM's weight and scale maps lead with
  (``w4a8_gemm.layer_operands``: 0 for one layer's operands, the clamped
  ``[0, R)`` for a stack of R layers, which :func:`_stacked` registers).

Shapes are kept small (tracing + interval interpretation run in CI on
every push) but structurally faithful: multiple k-steps (nk=2) so the
accumulator carry across the minor grid axis is analyzed, multiple
groups per block, packed int4 weights, ragged expert slabs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import numpy as np

from .interp import DATA
from .intervals import Interval

# synthetic shapes — small but multi-tile in every dimension that matters
M, K, N, GS, BK = 8, 512, 256, 128, 256
E, C = 2, 64
G = K // GS
# engine decode shapes: the continuous-batching decode tick routes at most
# max_slots * top_k tokens, so per-expert capacity snaps to the 8-row floor
# — the grouped call the serving path issues every tick is certified at
# this small-M config (incl. a zero-routed expert in the rep row counts).
E_DEC, C_DEC = 4, 8
# layers of the stacked variants (a scanned model's layer stack)
R_STACK = 3
# decode-sized call given no blocks, dense or ragged grouped: the shape
# rule (``w4a8_gemm.stream_blocks``) takes bk = 2048, 16 groups a block,
# nk = 2
K_STREAM = 4096


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    name: str
    config: str
    build: Callable[[], tuple]  # -> (fn, args, input_ranges)
    integer_scale: bool = False
    alpha: float | None = None
    a_bits: int = 8
    prefetch_ranges: tuple = ()
    meta: Any = None


def _codes(rng, k, n, bits):
    q = 2 ** (bits - 1) - 1
    return rng.integers(-q, q + 1, size=(k, n)).astype(np.int8)


def _packed(codes4):
    import jax.numpy as jnp

    from repro.core import packing

    return np.asarray(packing.pack_int4(jnp.asarray(codes4)))


def _w4_operands(rng, k=K, n=N, alpha=1024):
    packed = _packed(_codes(rng, k, n, 4))
    scales = rng.uniform(0.005, 0.02, (k // GS, n)).astype(np.float32)
    ints = np.clip(np.round(scales * alpha), 1, 2**31 - 1).astype(np.int32)
    return packed, scales, ints


def _w8_operands(rng, k=K, n=N):
    """W8 scales are ~18x smaller; amplifier follows the shipped
    heuristic+6 spec (recipe.W8A8_FG)."""
    import jax.numpy as jnp

    from repro.core import integer_scale as isc

    codes = _codes(rng, k, n, 8)
    scales = rng.uniform(8e-4, 1.2e-3, (k // GS, n)).astype(np.float32)
    exp = int(isc.heuristic_amplifier_exp(jnp.asarray(scales))) + 6
    alpha = int(2 ** min(exp, isc.MAX_AMPLIFIER_EXP))
    ints = np.clip(np.round(scales * alpha), 1, 2**31 - 1).astype(np.int32)
    return codes, scales, ints, alpha


def _sa(rng, *lead):
    return rng.uniform(1e-3, 0.05, (*lead, 1)).astype(np.float32)


def _j(x):
    import jax.numpy as jnp

    return jnp.asarray(x)


def _build_dense_is(w_bits: int, a_bits: int, k: int = K,
                    bk: int | None = BK):
    """``bk=None``: the launch the serving path makes, blocks by shape."""
    def build():
        from repro.kernels import w4a8_gemm as W

        rng = np.random.default_rng(0)
        if w_bits == 4:
            wq, _, ints = _w4_operands(rng, k=k)
            alpha = 1024.0
        else:
            wq, _, ints, alpha = _w8_operands(rng)
        qa = 2 ** (a_bits - 1) - 1
        fn = functools.partial(
            W.fg_gemm_integer_scale, group_size=GS, alpha=float(alpha),
            w_bits=w_bits, bk=bk)
        args = (_j(np.zeros((M, k), np.int8)), _j(_sa(rng, M)),
                _j(wq), _j(ints))
        return fn, args, {0: Interval(-qa, qa)}
    return build


def _build_dense_fs(group_size: int):
    def build():
        from repro.kernels import w4a8_gemm_fscale as W

        rng = np.random.default_rng(1)
        wq, scales, _ = _w4_operands(rng)
        if group_size <= 0:
            scales = scales.max(axis=0, keepdims=True)  # (1, N) coarse
        fn = functools.partial(
            W.fg_gemm_float_scale, group_size=group_size, w_bits=4, bk=BK)
        args = (_j(np.zeros((M, K), np.int8)), _j(_sa(rng, M)),
                _j(wq), _j(scales))
        return fn, args, {0: Interval(-127, 127)}
    return build


def _build_w4a16():
    from repro.kernels import w4a16_gemm as W

    rng = np.random.default_rng(2)
    wq, scales, _ = _w4_operands(rng)
    fn = functools.partial(W.w4a16_gemm, group_size=GS, bk=BK)
    args = (_j(np.zeros((M, K), np.float32)), _j(wq), _j(scales))
    return fn, args, {0: DATA}


def _build_act_quant():
    from repro.kernels import act_quant as A

    fn = functools.partial(A.act_quant, bits=8)
    return fn, (_j(np.zeros((64, 256), np.float32)),), {0: DATA}


def _build_flash():
    from repro.kernels import flash_attention as F

    rng = np.random.default_rng(3)
    q = rng.normal(size=(1, 256, 2, 64)).astype(np.float32)
    k = rng.normal(size=(1, 256, 1, 64)).astype(np.float32)
    v = rng.normal(size=(1, 256, 1, 64)).astype(np.float32)
    fn = functools.partial(F.flash_attention_tpu, causal=True, bk=128)
    return fn, (_j(q), _j(k), _j(v)), {0: DATA, 1: DATA, 2: DATA}


def _moe_w4(rng, alpha=1024):
    packed, ints = [], []
    for _ in range(E):
        p, _, i = _w4_operands(rng, alpha=alpha)
        packed.append(p)
        ints.append(i)
    return np.stack(packed), np.stack(ints)


def _build_moe_ragged(integer: bool):
    def build():
        from repro.kernels import moe_gemm as MG

        rng = np.random.default_rng(5)
        packed, ints = _moe_w4(rng)
        rc = np.asarray([37, C], np.int32)
        if integer:
            fn = functools.partial(
                MG.fg_grouped_gemm_integer_scale_ragged, group_size=GS,
                alpha=1024.0, a_bits=8, w_bits=4, bk=BK)
            scale_arg = ints
        else:
            fn = functools.partial(
                MG.fg_grouped_gemm_float_scale_ragged, group_size=GS,
                a_bits=8, w_bits=4, bk=BK)
            scale_arg = (ints / 1024.0).astype(np.float32)
        args = (_j(np.zeros((E, C, K), np.float32)), _j(rc),
                _j(packed), _j(scale_arg))
        return fn, args, {0: DATA, 1: Interval(0, C)}
    return build


def _build_moe_ragged_decode(integer: bool, k: int = K):
    """The integer-scale call takes the launch the serving path makes,
    blocks by shape; the float-scale one a fixed ``bk``."""
    def build():
        from repro.kernels import moe_gemm as MG

        rng = np.random.default_rng(7)
        packed, ints = [], []
        for _ in range(E_DEC):
            p, _, i = _w4_operands(rng, k=k)
            packed.append(p)
            ints.append(i)
        rc = np.asarray([0, 3, C_DEC, 5], np.int32)  # incl. idle expert
        if integer:
            fn = functools.partial(
                MG.fg_grouped_gemm_integer_scale_ragged, group_size=GS,
                alpha=1024.0, a_bits=8, w_bits=4)
            scale_arg = np.stack(ints)
        else:
            fn = functools.partial(
                MG.fg_grouped_gemm_float_scale_ragged, group_size=GS,
                a_bits=8, w_bits=4, bk=BK)
            scale_arg = (np.stack(ints) / 1024.0).astype(np.float32)
        args = (_j(np.zeros((E_DEC, C_DEC, k), np.float32)), _j(rc),
                _j(np.stack(packed)), _j(scale_arg))
        return fn, args, {0: DATA, 1: Interval(0, C_DEC)}
    return build


def _qspec_is():
    from repro.core.recipe import QuantSpec

    return QuantSpec(w_bits=4, a_bits=8, group_size=GS,
                     scale_mode="integer", amplifier=1024)


def _build_ops_dense():
    """The instrumented ``kernels.ops.qgemm`` wrapper end-to-end (telemetry
    is host-side python, so the traced jaxpr must stay identical to the
    bare act-quant + integer-scale kernel composition)."""
    from repro.kernels import ops

    rng = np.random.default_rng(8)
    wq, _, ints = _w4_operands(rng)
    params = {"qvalue": _j(wq), "scale": _j(ints), "alpha": 1024.0}
    spec = _qspec_is()

    def fn(x):
        return ops.qgemm(x, params, spec, block=ops.BlockConfig(bk=BK))

    return fn, (_j(np.zeros((M, K), np.float32)),), {0: DATA}


def _build_ops_grouped():
    """The instrumented ``kernels.ops.qgemm_grouped`` wrapper over the
    ragged fused-quant path (row_counts traced, as the engine feeds it)."""
    from repro.kernels import ops

    rng = np.random.default_rng(9)
    packed, ints = _moe_w4(rng)
    params = {"qvalue": _j(packed), "scale": _j(ints), "alpha": 1024.0}
    spec = _qspec_is()

    def fn(x, rc):
        return ops.qgemm_grouped(x, params, spec, row_counts=rc,
                                 block=ops.BlockConfig(bk=BK))

    args = (_j(np.zeros((E, C, K), np.float32)),
            _j(np.asarray([23, C], np.int32)))
    return fn, args, {0: DATA, 1: Interval(0, C)}


def _build_w4a16_ragged():
    from repro.kernels import moe_gemm as MG

    rng = np.random.default_rng(6)
    packed, scales = [], []
    for _ in range(E):
        p, s, _ = _w4_operands(rng)
        packed.append(p)
        scales.append(s)
    rc = np.asarray([17, C], np.int32)
    fn = functools.partial(MG.grouped_w4a16_gemm_ragged, group_size=GS,
                           bk=BK)
    args = (_j(np.zeros((E, C, K), np.float32)), _j(rc),
            _j(np.stack(packed)), _j(np.stack(scales)))
    return fn, args, {0: DATA, 1: Interval(0, C)}


def _stacked(build, w_pos: int):
    """``build``'s kernel reading its weight and scale (args ``w_pos`` and
    ``w_pos + 1``) out of a stack of ``R_STACK`` layers at a traced layer
    index, the kernels' next positional argument."""
    def stacked_build():
        import jax.numpy as jnp

        fn, args, ranges = build()
        args = list(args)
        for p in (w_pos, w_pos + 1):
            args[p] = jnp.stack([args[p]] * R_STACK)
        layer = len(args)
        args.append(_j(np.int32(R_STACK - 1)))
        return fn, tuple(args), {**ranges,
                                 layer: Interval(0.0, R_STACK - 1.0)}
    return stacked_build


_L0 = Interval(0.0, 0.0)  # one layer's operands: layer index 0
_LR = Interval(0.0, R_STACK - 1.0)
_RC = (Interval(0.0, float(C)), _L0)
_RC_DEC = (Interval(0.0, float(C_DEC)), _L0)
_RC_STACK = (Interval(0.0, float(C)), _LR)


def entries() -> list:
    """All shipped kernels x configs, in lint/certify order."""
    return [
        KernelEntry("w4a8-is", f"W4A8 g{GS} K={K} alpha=1024 bk={BK}",
                    _build_dense_is(4, 8), integer_scale=True, alpha=1024,
                    prefetch_ranges=(_L0,)),
        KernelEntry("w4a8-is-stream",
                    f"W4A8 g{GS} M={M} K={K_STREAM} alpha=1024 by shape",
                    _build_dense_is(4, 8, k=K_STREAM, bk=None),
                    integer_scale=True, alpha=1024, prefetch_ranges=(_L0,)),
        KernelEntry("w8a8-is", f"W8A8 g{GS} K={K} alpha=heuristic+6",
                    _build_dense_is(8, 8), integer_scale=True,
                    prefetch_ranges=(_L0,)),
        KernelEntry("w4a4-is", f"W4A4 g{GS} K={K} alpha=1024",
                    _build_dense_is(4, 4), integer_scale=True, alpha=1024,
                    a_bits=4, prefetch_ranges=(_L0,)),
        KernelEntry("w4a8-fs", f"W4A8 float-scale g{GS} K={K}",
                    _build_dense_fs(GS), prefetch_ranges=(_L0,)),
        KernelEntry("w4a8-coarse", f"W4A8 per-channel K={K}",
                    _build_dense_fs(-1), prefetch_ranges=(_L0,)),
        KernelEntry("w4a16", f"W4A16 weight-only g{GS} K={K}",
                    _build_w4a16, prefetch_ranges=(_L0,)),
        KernelEntry("act-quant", "per-token int8, M=64 K=256",
                    _build_act_quant),
        KernelEntry("flash-attention", "causal Sq=Sk=256 bq=256 bk=128",
                    _build_flash),
        KernelEntry("moe-w4a8-is-ragged",
                    f"ragged fused-quant E={E} C={C} K={K} alpha=1024",
                    _build_moe_ragged(True), integer_scale=True, alpha=1024,
                    prefetch_ranges=_RC),
        KernelEntry("moe-w4a8-fs-ragged",
                    f"ragged fused-quant E={E} C={C} K={K} float-scale",
                    _build_moe_ragged(False), prefetch_ranges=_RC),
        KernelEntry("moe-w4a16-ragged",
                    f"ragged weight-only E={E} C={C} K={K}",
                    _build_w4a16_ragged, prefetch_ranges=_RC),
        KernelEntry("moe-w4a8-is-ragged-decode",
                    f"engine decode E={E_DEC} C={C_DEC} K={K_STREAM} "
                    "alpha=1024 by shape",
                    _build_moe_ragged_decode(True, k=K_STREAM),
                    integer_scale=True, alpha=1024,
                    prefetch_ranges=_RC_DEC),
        KernelEntry("moe-w4a8-fs-ragged-decode",
                    f"engine decode E={E_DEC} C={C_DEC} K={K} float-scale",
                    _build_moe_ragged_decode(False),
                    prefetch_ranges=_RC_DEC),
        # instrumented dispatch wrappers (telemetry must not perturb jaxprs)
        KernelEntry("ops-qgemm-is",
                    f"ops.qgemm W4A8-IS g{GS} K={K} alpha=1024",
                    _build_ops_dense, integer_scale=True, alpha=1024,
                    prefetch_ranges=(_L0,)),
        KernelEntry("ops-qgemm-grouped-is",
                    f"ops.qgemm_grouped ragged E={E} C={C} K={K} alpha=1024",
                    _build_ops_grouped, integer_scale=True, alpha=1024,
                    prefetch_ranges=_RC),
        # the same kernels reading a stack of R_STACK layers in place
        KernelEntry("w4a8-is-stacked",
                    f"W4A8 g{GS} K={K} alpha=1024 R={R_STACK}",
                    _stacked(_build_dense_is(4, 8), 2), integer_scale=True,
                    alpha=1024, prefetch_ranges=(_LR,)),
        KernelEntry("w4a8-fs-stacked",
                    f"W4A8 float-scale g{GS} K={K} R={R_STACK}",
                    _stacked(_build_dense_fs(GS), 2),
                    prefetch_ranges=(_LR,)),
        KernelEntry("w4a16-stacked",
                    f"W4A16 weight-only g{GS} K={K} R={R_STACK}",
                    _stacked(_build_w4a16, 1), prefetch_ranges=(_LR,)),
        KernelEntry("moe-w4a8-is-ragged-stacked",
                    f"ragged E={E} C={C} K={K} alpha=1024 R={R_STACK}",
                    _stacked(_build_moe_ragged(True), 2),
                    integer_scale=True, alpha=1024,
                    prefetch_ranges=_RC_STACK),
        KernelEntry("moe-w4a8-fs-ragged-stacked",
                    f"ragged E={E} C={C} K={K} float-scale R={R_STACK}",
                    _stacked(_build_moe_ragged(False), 2),
                    prefetch_ranges=_RC_STACK),
        KernelEntry("moe-w4a16-ragged-stacked",
                    f"ragged weight-only E={E} C={C} K={K} R={R_STACK}",
                    _stacked(_build_w4a16_ragged, 2),
                    prefetch_ranges=_RC_STACK),
    ]
