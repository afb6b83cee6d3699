"""Quantized linear layer: spec declaration, offline quantization, apply.

This is the model-facing integration point of Integer Scale. A linear layer
in any architecture is declared through :func:`linear_specs`; depending on
the :class:`~repro.core.recipe.QuantSpec` attached to its path it becomes

  * FP (bf16) linear                          (spec is None)
  * fine/coarse W{4,8}A{4,8,16} quantized     (storage: packed int4 / int8)

Apply dispatches between the pure-jnp reference path (always available; used
for dry-run lowering and CPU tests) and the Pallas TPU kernels in
``repro.kernels`` (used on real TPUs; validated via interpret mode).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Literal

import jax
import jax.numpy as jnp

from repro import obs
from repro.nn import spec as S
from . import packing
from .integer_scale import integerize
from .quant import QWeight, quantize_activation, quantize_weight
from .recipe import QuantSpec

KernelMode = Literal["reference", "pallas", "pallas_interpret"]

# The mode is threaded explicitly: ModelConfig.kernel_mode -> apply_linear /
# expert_linear_apply -> here, and the serving engine sets it on its
# ServeConfig. ``kernel_mode`` below is the scoped default for scripts
# that don't thread a ``mode=`` kwarg.
_MODE_STACK: list[KernelMode] = []


@contextlib.contextmanager
def kernel_mode(mode: KernelMode):
    """Scoped default kernel mode for call sites that don't pass ``mode``.

    Prefer threading the mode explicitly (ModelConfig.kernel_mode /
    ServeConfig.kernel_mode / the ``mode=`` kwarg); this context manager
    exists so scripts keep a one-liner.
    """
    if mode not in ("reference", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown kernel mode {mode!r}")
    _MODE_STACK.append(mode)
    try:
        yield
    finally:
        _MODE_STACK.pop()


def current_kernel_mode() -> KernelMode:
    """Mode used when a call site passes ``mode=None``."""
    return _MODE_STACK[-1] if _MODE_STACK else "reference"


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


def _num_groups(K: int, group_size: int) -> int:
    return 1 if group_size <= 0 else K // group_size


def linear_specs(
    K: int,
    N: int,
    qspec: QuantSpec | None,
    axes: tuple[str | None, str | None],
    *,
    bias: bool = False,
    dtype=jnp.bfloat16,
) -> dict[str, S.ParamSpec]:
    """Parameter specs for one (possibly quantized) linear of shape (K, N).

    ``axes`` are the logical axes of (K, N) e.g. ("embed", "mlp").
    """
    ax_in, ax_out = axes
    out: dict[str, S.ParamSpec] = {}
    if qspec is None:
        out["w"] = S.w((K, N), (ax_in, ax_out), dtype=dtype)
    else:
        G = _num_groups(K, qspec.group_size)
        if qspec.w_bits == 4:
            out["qvalue"] = S.zeros((K // 2, N), (ax_in, ax_out), dtype=jnp.int8)
        elif qspec.w_bits == 8:
            out["qvalue"] = S.zeros((K, N), (ax_in, ax_out), dtype=jnp.int8)
        else:
            raise ValueError(f"unsupported w_bits={qspec.w_bits}")
        if (qspec.scale_mode == "integer" and not qspec.weight_only
                and qspec.fine_grained):
            out["scale"] = S.ones((G, N), (ax_in, ax_out), dtype=jnp.int32)
            # per-layer amplifier (supports the heuristic search, Listing 1)
            out["alpha"] = S.ones((), (), dtype=jnp.float32)
        else:
            out["scale"] = S.ones((G, N), (ax_in, ax_out), dtype=jnp.float32)
        if qspec.algo in ("awq", "smoothquant"):
            # per-in-channel activation compensation (x / pre_scale)
            out["pre_scale"] = S.ones((K,), (ax_in,), dtype=jnp.float32)
        if qspec.rotate:
            # QuaRot-style orthogonal rotation applied online to x
            out["rot"] = S.w((K, K), (ax_in, None), dtype=dtype)
    if bias:
        out["b"] = S.zeros((N,), (ax_out,), dtype=dtype)
    return out


# ---------------------------------------------------------------------------
# Offline quantization of a trained fp weight -> param arrays
# ---------------------------------------------------------------------------


def _certify_amplifier(scales, alpha: int, qspec: QuantSpec):
    """Static INT32-overflow certificate for this layer's amplifier.

    Returns the Certificate (also appended to repro.analysis.certify's
    log), or None when the scales are traced (inside jit/vmap the
    concrete values don't exist; certification then happens at the
    recipe/registry level instead).
    """
    import numpy as np

    try:
        s = np.asarray(scales)
    except Exception:  # traced values (TracerArrayConversionError etc.)
        return None
    from repro.analysis import certify

    return certify.resolve_amplifier(
        s, alpha=int(alpha), group_size=qspec.group_size,
        w_bits=qspec.w_bits, a_bits=qspec.a_bits)


def finish_quant(
    codes: jax.Array,   # int8 (K, N) quantized codes
    scales: jax.Array,  # f32 (G, N) (G=1 for coarse)
    qspec: QuantSpec,
    *,
    bias: jax.Array | None = None,
    pre_scale: jax.Array | None = None,
    rot: jax.Array | None = None,
) -> dict[str, jax.Array]:
    """Shared finishing step for every algorithm: pack int4, integerize the
    scales (the paper's free lunch), assemble the param dict.

    Quantization-health telemetry lands here: one ``quantized_layers_total``
    tick per finished layer, and ``alpha_cap_events_total`` whenever the
    overflow certificate forces the amplifier below the requested value.
    ``alpha_cap_events_total`` is created unconditionally so an explicit
    zero appears in snapshots even on runs that never cap.
    """
    reg = obs.current_registry()
    caps = reg.counter(
        "alpha_cap_events_total",
        "layers whose amplifier was capped below request by the "
        "INT32-overflow certificate")
    caps.inc(0)  # materialize the series: snapshots show an explicit 0
    qvalue = packing.pack_int4(codes) if qspec.w_bits == 4 else codes
    out: dict[str, jax.Array] = {"qvalue": qvalue}
    if (qspec.scale_mode == "integer" and not qspec.weight_only
            and qspec.fine_grained):
        # Integer Scale applies to fine-grained group scales (paper §4);
        # coarse specs keep the single float scale (nothing to amortize).
        qw = QWeight(codes, scales, qspec.w_bits, qspec.group_size)
        isw = integerize(qw, qspec.amplifier)
        cert = _certify_amplifier(scales, isw.alpha, qspec)
        if cert is not None and cert.resolved_alpha != isw.alpha:
            # statically unsafe amplifier: rebuild at the certified cap
            caps.inc()
            isw = integerize(qw, cert.resolved_alpha)
        scheme = f"w{qspec.w_bits}a{qspec.a_bits}-is"
        out["scale"] = isw.int_scale
        out["alpha"] = jnp.float32(isw.alpha)
    else:
        scheme = (f"w{qspec.w_bits}a16" if qspec.weight_only
                  else f"w{qspec.w_bits}a{qspec.a_bits}-fs")
        out["scale"] = scales
    reg.counter("quantized_layers_total",
                "linear layers finished by finish_quant",
                ("scheme",)).inc(scheme=scheme)
    if bias is not None:
        out["b"] = bias
    if pre_scale is not None:
        out["pre_scale"] = jnp.asarray(pre_scale, jnp.float32)
    if rot is not None:
        out["rot"] = rot
    return out


def quantize_linear(
    w: jax.Array,
    qspec: QuantSpec,
    *,
    bias: jax.Array | None = None,
) -> dict[str, jax.Array]:
    """RTN path (algorithms/ provide GPTQ/AWQ/... on top of finish_quant)."""
    K, N = w.shape
    qw = quantize_weight(w, qspec.w_bits, qspec.group_size, qspec.clip_ratio)
    scales = qw.scale if qspec.fine_grained else qw.scale[None, :]
    return finish_quant(qw.qvalue, scales, qspec, bias=bias)


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerRef:
    """Layer ``layer`` (traced) of a layer-stacked linear's params, left
    unsliced. :func:`linear_apply` / :func:`grouped_linear_apply` take it
    for their ``params`` and forward the stack and the index to the Pallas
    kernels, which read the weights in place; any other reader gets a leaf
    at ``layer`` from ``ref[key]``."""

    params: dict
    layer: Any

    def __getitem__(self, key):
        return self.params[key][self.layer]

    def __contains__(self, key) -> bool:
        return key in self.params


def _layer_params(params, layer, qspec, mode):
    """-> (params, layer) for an apply: a stacked linear's params at
    ``layer`` — except the operands the Pallas kernels index themselves,
    which stay stacked and keep ``layer`` beside them."""
    if isinstance(params, LayerRef):
        params, layer = params.params, params.layer
    if layer is None:
        return params, None
    kernel = qspec is not None and mode in ("pallas", "pallas_interpret")
    keep = ("qvalue", "scale", "alpha") if kernel else ()
    params = {k: v if k in keep else v[layer] for k, v in params.items()}
    return params, (layer if kernel else None)


def _unpack(params: dict, qspec: QuantSpec, K: int) -> jax.Array:
    if qspec.w_bits == 4:
        return packing.unpack_int4(params["qvalue"])
    return params["qvalue"]


def linear_apply(
    params: dict[str, jax.Array],
    x: jax.Array,
    qspec: QuantSpec | None,
    *,
    mode: KernelMode | None = None,
    layer=None,
) -> jax.Array:
    """y = x @ W (+ b), honoring the quantization spec.

    x: (..., K) activation (bf16/f32). Returns same float dtype as x.
    With ``layer`` (or a :class:`LayerRef` for ``params``) the params are
    a layer stack: the Pallas kernels read its weights in place at
    ``layer``; the reference path slices them (XLA fuses the slice into
    the dot).
    """
    mode = mode or current_kernel_mode()
    params, layer = _layer_params(params, layer, qspec, mode)
    if qspec is None:
        y = x @ params["w"].astype(x.dtype)
        if "b" in params:
            y = y + params["b"].astype(y.dtype)
        return y

    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    out_dtype = x.dtype

    if "pre_scale" in params:  # AWQ/SmoothQuant activation compensation
        x2 = x2 / params["pre_scale"].astype(x2.dtype)
    if "rot" in params:  # QuaRot-style online rotation
        x2 = x2 @ params["rot"].astype(x2.dtype)

    if mode in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops

        # the param dict carries the stored per-layer ``alpha`` — qgemm
        # forwards it, so heuristic-amplifier layers use their certified
        # value rather than any static qspec fallback.
        y2 = kops.qgemm(
            x2, params, qspec, layer=layer,
            block=kops.BlockConfig(interpret=(mode == "pallas_interpret")),
        )
    else:
        y2 = _reference_qgemm(x2, params, qspec, K)

    y = y2.reshape(*lead, -1).astype(out_dtype)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


def _reference_qgemm(x2, params, qspec: QuantSpec, K: int) -> jax.Array:
    """Pure-jnp semantics of every supported scheme (also the dry-run path —
    int8 dot_generals appear in HLO so the roofline sees integer compute)."""
    wq = _unpack(params, qspec, K)  # int8 (K, N)
    N = wq.shape[1]
    gs = qspec.group_size if qspec.group_size > 0 else K
    G = K // gs
    scale = params["scale"]

    if qspec.weight_only:
        # W4A16 Marlin-analog: dequant to activation dtype, fp GEMM.
        w = wq.reshape(G, gs, N).astype(jnp.float32) * scale[:, None, :]
        return x2 @ w.reshape(K, N).astype(x2.dtype)

    xq, sa = quantize_activation(x2, qspec.a_bits)  # int8, (M,1) f32
    x3 = xq.reshape(-1, G, gs)
    w3 = wq.reshape(G, gs, N)
    part = jax.lax.dot_general(
        x3, w3,
        dimension_numbers=(((2,), (1,)), ((1,), (0,))),
        preferred_element_type=jnp.int32,
    )  # (G, M, N)
    if qspec.scale_mode == "integer" and qspec.fine_grained:
        acc = jnp.sum(part * scale[:, None, :], axis=0)  # int32
        return acc.astype(jnp.float32) * (sa / params["alpha"])
    acc = jnp.sum(part.astype(jnp.float32) * scale[:, None, :], axis=0)
    return acc * sa


def grouped_linear_apply(
    params: dict[str, jax.Array],
    x: jax.Array,
    qspec: QuantSpec | None,
    *,
    row_counts: jax.Array | None = None,
    mode: KernelMode | None = None,
    layer=None,
) -> jax.Array:
    """Batched-expert linear: x (E, C, K) -> (E, C, N), params stacked with
    a leading expert dim (the MoE dispatch-buffer path).

    Under "pallas"/"pallas_interpret" every expert runs in ONE grouped
    ragged Pallas kernel (``repro.kernels.moe_gemm``) with activation
    quantization fused into its first k-group pass — per-expert ``alpha``
    values from heuristic amplifiers are forwarded and folded into the
    activation scales. ``row_counts`` (int32 ``(E,)``, rows past it must be
    zero-filled) lets the kernel skip capacity-padding m-tiles; the
    reference branch ignores it (zero rows already produce zero outputs
    there), so both branches keep identical semantics. Activation
    compensation (``pre_scale``), rotation (``rot``) and bias are applied
    once here so both branches share the exact same semantics. ``layer``
    (or a :class:`LayerRef`) reads a layer stack as :func:`linear_apply`
    does.
    """
    mode = mode or current_kernel_mode()
    params, layer = _layer_params(params, layer, qspec, mode)
    if qspec is None:
        y = jnp.einsum("eck,ekn->ecn", x, params["w"].astype(x.dtype))
        if "b" in params:
            y = y + params["b"][:, None, :].astype(y.dtype)
        return y

    out_dtype = x.dtype
    x2 = x
    if "pre_scale" in params:  # (E, K) per-expert compensation
        x2 = x2 / params["pre_scale"][:, None, :].astype(x2.dtype)
    if "rot" in params:  # (E, K, K) per-expert rotation
        x2 = jnp.einsum("eck,ekj->ecj", x2, params["rot"].astype(x2.dtype))

    core = {k: v for k, v in params.items()
            if k in ("qvalue", "scale", "alpha")}
    if mode in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops

        y = kops.qgemm_grouped(
            x2, core, qspec, row_counts=row_counts, layer=layer,
            block=kops.BlockConfig(interpret=(mode == "pallas_interpret")))
    else:
        K = x.shape[-1]
        y = jax.vmap(
            lambda p, xe: _reference_qgemm(xe, p, qspec, K))(core, x2)

    y = y.astype(out_dtype)
    if "b" in params:
        y = y + params["b"][:, None, :].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Whole-tree quantization: fp params -> quantized params per recipe
# ---------------------------------------------------------------------------


def quantize_tree(
    fp_params: Any,
    fp_specs: Any,
    recipe,
    *,
    adjusted: dict[str, jax.Array] | None = None,
) -> Any:
    """Walk a param tree; each dict node shaped like a linear ({"w": (K,N)})
    whose path matches the recipe is replaced by quantized arrays.

    ``adjusted``: optional path->weight overrides produced by calibration
    algorithms (GPTQ/AWQ/...) — quantization then uses the adjusted weight.
    """

    def walk(node, path):
        if isinstance(node, dict) and "w" in node and not isinstance(node["w"], dict):
            w = node["w"]
            if hasattr(w, "ndim") and w.ndim == 2:
                qspec = recipe.spec_for(path)
                if qspec is not None:
                    src = adjusted.get(path, w) if adjusted else w
                    return quantize_linear(
                        jnp.asarray(src, jnp.float32), qspec,
                        bias=node.get("b"),
                    )
            return node
        if isinstance(node, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in node.items()}
        return node

    return walk(fp_params, "")
