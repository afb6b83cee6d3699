"""Public jit'd wrappers over the Pallas kernels, plus scheme dispatch.

Call convention (v2)
--------------------
Two scheme-dispatched entry points, both consuming a qlinear param dict
directly (the dict ``qlinear.finish_quant`` / ``quantize_linear`` build:
``{"qvalue", "scale", "alpha"?}``) plus a :class:`BlockConfig`:

* ``qgemm(x, params, qspec, block=...)`` — dense (M, K) x (K, N), the
  entry point ``repro.core.qlinear.linear_apply`` uses under kernel mode
  "pallas" / "pallas_interpret".
* ``qgemm_grouped(x, params, qspec, row_counts=..., block=...)`` — the
  batched-expert MoE path: stacked (E, ...) operands, ONE fused ragged
  grouped kernel instead of a vmap over experts. ``row_counts`` (int32
  ``(E,)``, traced or concrete) lets the scalar-prefetch kernels skip
  m-tiles past each expert's routed token count — the continuous-batching
  decode path threads the live per-tick dispatch counts here.

``params["alpha"]`` (the integer-scale amplifier) may be a python float
(static, baked into the kernel epilogue) or a traced f32 scalar / (E,)
array (the per-layer / per-expert values stored by quantization) — traced
values are folded into the per-token activation scale, which is exact for
the power-of-two amplifiers Listing 1 produces. When absent, the fallback
is derived from ``qspec.amplifier``; heuristic amplifiers have no static
value and raise instead of silently rescaling by a wrong constant (the
stored alpha is what the PR-3 overflow certificates cover).

Without a TPU only ``BlockConfig(interpret=True)`` executes. Interpret
mode runs the same BlockSpecs and grids but not the TPU compiler's tiling
and VMEM checks: ``tests/test_tpu_compile.py`` compiles the kernels for a
described v5e, and ``chip_smoke.py`` runs them on one.

The v1 shims (``*_from_params``, positional ``qvalue, scale``,
``block=dict``, ``interpret=``) completed their one-release deprecation
window and are GONE; legacy forms now raise ``TypeError``. The kernel mode
itself ("reference" vs "pallas"[_interpret]) is NOT chosen here — callers
pass it explicitly to ``qlinear.linear_apply`` / ``grouped_linear_apply``
(see ``qlinear.kernel_mode`` for the script shim).

Telemetry (repro.obs)
---------------------
Every wrapper call increments ``qgemm_calls_total{scheme,kind,shape,
block}`` on the current registry. These are host/python-side counts: in
eager code they count executions; inside jit they count TRACES (a useful
retrace detector — steady-state serving holds them constant). Executed
vs total m-tiles of the ragged grouped calls are counted by the serving
engine from the routed counts each step outputs
(``engine_moe_m_tiles_total``). Per the repro.obs rule, nothing below
reads or writes metrics from inside a kernel body.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.recipe import QuantSpec

from .act_quant import act_quant
from .moe_gemm import (fg_grouped_gemm_float_scale_ragged,
                       fg_grouped_gemm_integer_scale_ragged,
                       grouped_w4a16_gemm_ragged)
from .w4a8_gemm import fg_gemm_integer_scale
from .w4a8_gemm_fscale import fg_gemm_float_scale
from .w4a16_gemm import w4a16_gemm


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """Kernel launch configuration: BlockSpec tile sizes + interpret mode.

    Divisibility is validated at construction (not at the first traced
    call): ``bm`` must be a multiple of 8 (the f32 sublane tile — the
    kernels snap it down to ``round_up(C, 8)`` for small decode batches),
    ``bn``/``bk`` multiples of 128 (the lane tile; ``bk`` must also hold
    whole quantization groups, which the kernels enforce against the
    qspec's ``group_size`` since that is a property of the weights, not of
    the launch). The defaults mirror every GEMM kernel's own defaults.
    """

    bm: int = 128
    bn: int = 256
    bk: int = 512
    interpret: bool = False

    def __post_init__(self):
        for name, val, mult in (("bm", self.bm, 8), ("bn", self.bn, 128),
                                ("bk", self.bk, 128)):
            if not isinstance(val, int) or val <= 0 or val % mult:
                raise ValueError(
                    f"BlockConfig.{name}={val!r}: must be a positive "
                    f"multiple of {mult} (BlockSpec tile divisibility)")

    def kernel_kwargs(self) -> dict:
        """Splat into the underlying Pallas wrapper call."""
        return {"bm": self.bm, "bn": self.bn, "bk": self.bk,
                "interpret": self.interpret}


#: Default launch config for CPU-validated kernels (tests/benchmarks).
INTERPRET = BlockConfig(interpret=True)


def _as_block(block) -> BlockConfig:
    if block is None:
        return BlockConfig()
    if isinstance(block, BlockConfig):
        return block
    raise TypeError(f"block must be BlockConfig or None, got "
                    f"{type(block).__name__} (the dict form was removed "
                    "with the v1 shims)")


def _resolve_alpha(alpha, qspec: QuantSpec):
    """Amplifier for the integer-scale epilogue.

    The stored per-layer/per-expert ``params["alpha"]`` always wins — it is
    the value the PR-3 overflow certificate covers (possibly capped below
    the qspec's request). Without it, a static integer ``qspec.amplifier``
    is an exact fallback; heuristic amplifiers resolve per layer at
    quantization time, so silently substituting a constant would rescale
    the output by an arbitrary factor AND bypass certification — raise.
    """
    if alpha is not None:
        return alpha
    if isinstance(qspec.amplifier, int):
        return float(qspec.amplifier)
    raise ValueError(
        f"qspec.amplifier={qspec.amplifier!r} is resolved per layer at "
        "quantization time; pass the stored per-layer alpha "
        "(params['alpha']) — no static fallback exists for heuristic "
        "amplifiers")


def _scheme_of(qspec: QuantSpec) -> str:
    if qspec.weight_only:
        return f"w{qspec.w_bits}a16"
    s = "is" if (qspec.scale_mode == "integer" and qspec.fine_grained) \
        else "fs"
    return f"w{qspec.w_bits}a{qspec.a_bits}-{s}"


def _record_call(scheme: str, kind: str, shape: tuple,
                 blk: BlockConfig) -> None:
    obs.current_registry().counter(
        "qgemm_calls_total",
        "kernels.ops wrapper calls (trace-time under jit)",
        ("scheme", "kind", "shape", "block"),
    ).inc(scheme=scheme, kind=kind,
          shape="x".join(str(d) for d in shape),
          block=f"{blk.bm}x{blk.bn}x{blk.bk}")


def qgemm(
    x: jax.Array,         # (M, K) bf16/f32 activations
    params: dict,         # qlinear param dict: qvalue, scale, alpha?
    qspec: QuantSpec = None,
    *,
    block: BlockConfig | None = None,
) -> jax.Array:
    """Quantized GEMM honoring ``qspec``; returns f32 (M, N).

    Scheme dispatch (weight-only W4A16 / fine-grained integer scale /
    float scale) comes from the qspec; operands from the param dict.
    """
    if not isinstance(params, dict):
        raise TypeError(
            "qgemm takes the qlinear param dict as its second argument "
            "(the v1 positional qvalue/scale form was removed)")
    blk = _as_block(block)
    kw = blk.kernel_kwargs()
    N = params["qvalue"].shape[-1]
    _record_call(_scheme_of(qspec), "dense", (*x.shape, N), blk)

    if qspec.weight_only:
        if qspec.w_bits != 4:
            raise NotImplementedError("weight-only kernel is W4A16")
        return w4a16_gemm(x, params["qvalue"], params["scale"],
                          group_size=qspec.group_size, **kw)

    xq, sa = act_quant(x, bits=qspec.a_bits, interpret=blk.interpret)
    if qspec.scale_mode == "integer" and qspec.fine_grained:
        a = _resolve_alpha(params.get("alpha"), qspec)
        if not isinstance(a, (int, float)):
            # traced per-layer amplifier: fold 1/alpha into sa (exact for
            # the power-of-two alphas the heuristic emits)
            sa = sa / jnp.asarray(a, jnp.float32)
            a = 1.0
        return fg_gemm_integer_scale(
            xq, sa, params["qvalue"], params["scale"],
            group_size=qspec.group_size, alpha=float(a),
            w_bits=qspec.w_bits, **kw)
    return fg_gemm_float_scale(
        xq, sa, params["qvalue"], params["scale"],
        group_size=qspec.group_size, w_bits=qspec.w_bits, **kw)


def qgemm_grouped(
    x: jax.Array,         # (E, C, K) bf16/f32 dispatch buffer
    params: dict,         # stacked per-expert param dict
    qspec: QuantSpec = None,
    *,
    row_counts=None,      # int32 (E,) routed rows per expert | None=all C
    block: BlockConfig | None = None,
) -> jax.Array:
    """Batched-expert quantized GEMM; returns f32 (E, C, N).

    Always routes through the ragged scalar-prefetch kernels
    (``kernels.moe_gemm``): activation quantization happens INSIDE the
    grouped kernel's first k-group pass (no dense ``act_quant`` sweep over
    the ``(E*C, K)`` buffer), and when ``row_counts`` is given, m-tiles
    entirely past an expert's routed row count are skipped. ``row_counts``
    is a data operand (traced under jit — the serving engine feeds the
    live per-tick dispatch counts without retracing). Rows at or past
    ``row_counts[e]`` must be zero-filled (the MoE dispatch guarantees
    this); ``row_counts=None`` treats every capacity slot as routed.
    """
    if not isinstance(params, dict):
        raise TypeError(
            "qgemm_grouped takes the stacked qlinear param dict as its "
            "second argument (the v1 positional qvalue/scale form was "
            "removed)")
    blk = _as_block(block)
    kw = blk.kernel_kwargs()
    N = params["qvalue"].shape[-1]
    _record_call(_scheme_of(qspec), "grouped", (*x.shape, N), blk)

    if qspec.weight_only:
        if qspec.w_bits != 4:
            raise NotImplementedError("weight-only kernel is W4A16")
        return grouped_w4a16_gemm_ragged(
            x, row_counts, params["qvalue"], params["scale"],
            group_size=qspec.group_size, **kw)

    if qspec.scale_mode == "integer" and qspec.fine_grained:
        a = _resolve_alpha(params.get("alpha"), qspec)
        return fg_grouped_gemm_integer_scale_ragged(
            x, row_counts, params["qvalue"], params["scale"],
            group_size=qspec.group_size, alpha=a,
            a_bits=qspec.a_bits, w_bits=qspec.w_bits, **kw)
    return fg_grouped_gemm_float_scale_ragged(
        x, row_counts, params["qvalue"], params["scale"],
        group_size=qspec.group_size, a_bits=qspec.a_bits,
        w_bits=qspec.w_bits, **kw)
