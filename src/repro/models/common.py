"""Shared model-building helpers: recipe-aware linears, norms, stacking."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core import qlinear
from repro.core.recipe import QuantRecipe
from repro.nn import spec as S


# ---------------------------------------------------------------------------
# Recipe-aware linear declaration/apply (paths must match between the two)
# ---------------------------------------------------------------------------


def linear(recipe: QuantRecipe | None, path: str, K: int, N: int,
           axes, *, bias: bool = False, dtype=jnp.bfloat16):
    qspec = recipe.spec_for(path) if recipe is not None else None
    return qlinear.linear_specs(K, N, qspec, axes, bias=bias, dtype=dtype)


# Calibration capture: when enabled (and running EAGERLY with
# cfg.scan_layers=False), every linear's input activations are recorded per
# path in call order — GPTQ/AWQ/SmoothQuant read these (core/ptq.py).
_CAPTURE: dict | None = None
_CAPTURE_SAMPLES = 256


def start_capture() -> None:
    global _CAPTURE
    _CAPTURE = {}


def end_capture() -> dict:
    global _CAPTURE
    out, _CAPTURE = _CAPTURE, None
    return out or {}


def apply_linear(recipe: QuantRecipe | None, path: str,
                 params: dict, x: jax.Array, *,
                 mode: str | None = None) -> jax.Array:
    qspec = recipe.spec_for(path) if recipe is not None else None
    if _CAPTURE is not None and not isinstance(
            x, jax.core.Tracer):
        import numpy as np

        x2 = np.asarray(x, dtype=np.float32).reshape(-1, x.shape[-1])
        step = max(1, x2.shape[0] // _CAPTURE_SAMPLES)
        _CAPTURE.setdefault(path, []).append(x2[::step][:_CAPTURE_SAMPLES])
    # params are one layer's, or a qlinear.LayerRef into the layer stack
    # (the Pallas serving scan, :func:`layer_view`). ``mode`` is
    # cfg.kernel_mode threaded from the model block; None defers to the
    # ambient default inside qlinear.
    return qlinear.linear_apply(params, x, qspec, mode=mode)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_spec(d: int) -> dict:
    return {"g": S.ones((d,), ("embed",))}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * params["g"].astype(jnp.float32)
            ).astype(dt)


def layernorm_spec(d: int) -> dict:
    return {"g": S.ones((d,), ("embed",)), "b": S.zeros((d,), ("embed",))}


def layernorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["g"].astype(jnp.float32)
            + params["b"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Spec stacking for scan-over-layers
# ---------------------------------------------------------------------------


def stack_specs(tree: Any, n: int, axis_name: str = "layers") -> Any:
    """Prepend a stacked leading dim (scanned layers) to every ParamSpec."""

    def one(s: S.ParamSpec) -> S.ParamSpec:
        return S.ParamSpec(
            (n, *s.shape), s.dtype, s.init,
            (axis_name, *s.logical_axes), s.init_scale,
        )

    return jax.tree.map(one, tree, is_leaf=S.is_spec)


def take_layer(stacked: Any, i) -> Any:
    """Slice layer i out of a stacked param tree (for unscanned access)."""
    return jax.tree.map(lambda a: a[i], stacked)


def layer_view(stacked: Any, layer) -> Any:
    """Layer ``layer`` (traced) of a stacked param tree without copying its
    quantized weights: each quantized linear (a dict holding ``qvalue``)
    becomes a :class:`qlinear.LayerRef`, which the Pallas kernels read in
    place by the index; every other leaf (norms, router, unquantized
    weights) is sliced here."""
    if isinstance(stacked, dict):
        if "qvalue" in stacked:
            return qlinear.LayerRef(stacked, layer)
        return {k: layer_view(v, layer) for k, v in stacked.items()}
    return stacked[layer]
