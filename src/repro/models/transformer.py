"""Decoder-only transformer LM covering dense / MoE / VLM families.

Layers are grouped into (unrolled prefix + repeating pattern x R); the
repeating pattern is scanned with ``lax.scan`` over stacked params so 100-
layer configs lower to a compact HLO, with optional per-block remat for
training. Heterogeneous stacks (DeepSeek's leading dense layer, the vision
model's every-5th cross-attention layer) fall out of the same mechanism.

A forward on the Pallas kernels that is not differentiated (it serves: it
is not ``mode="train"``, or it fills a cache) scans the layer index
instead of the weights. The quantized weights then stay stacked and the
kernels read them in place (``common.layer_view``); slicing them out would
copy every layer's weights each step, since a Pallas call cannot take a
fused slice.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core import qlinear
from repro.nn import spec as S
from . import attention as A
from .common import layer_view, rmsnorm, rmsnorm_spec, stack_specs
from .config import ModelConfig
from .mlp import mlp_apply, mlp_specs
from .moe import moe_apply, moe_specs


# ---------------------------------------------------------------------------
# Layer layout: kinds, prefix/pattern split
# ---------------------------------------------------------------------------


def layer_kinds(cfg: ModelConfig) -> list[str]:
    L = cfg.num_layers
    if cfg.family == "vlm" and cfg.cross_attn_every:
        return ["cross" if (i + 1) % cfg.cross_attn_every == 0 else "self"
                for i in range(L)]
    if cfg.num_experts:
        return ["self"] * cfg.first_dense_layers + \
               ["moe"] * (L - cfg.first_dense_layers)
    return ["self"] * L


def split_layers(kinds: list[str], max_period: int = 8):
    """-> (prefix_kinds, pattern_kinds, repeats) minimizing prefix then
    period, so scan covers as much as possible."""
    n = len(kinds)
    for p in range(0, n):
        rest = kinds[p:]
        for period in range(1, max_period + 1):
            if len(rest) % period:
                continue
            pat = rest[:period]
            if pat * (len(rest) // period) == rest:
                return kinds[:p], pat, len(rest) // period
    return kinds, [], 0


# ---------------------------------------------------------------------------
# One block
# ---------------------------------------------------------------------------


def _attn_specs(cfg: ModelConfig, recipe, base: str) -> dict:
    if cfg.attention == "mla":
        return A.mla_specs(cfg, recipe, base)
    return A.gqa_specs(cfg, recipe, base)


def _block_specs(cfg: ModelConfig, recipe, kind: str, base: str) -> dict:
    d = cfg.d_model
    out: dict = {"ln1": rmsnorm_spec(d), "ln2": rmsnorm_spec(d)}
    if kind == "cross":
        out["attn"] = A.cross_attn_specs(cfg, recipe, f"{base}/xattn")
        out["mlp"] = mlp_specs(cfg, recipe, f"{base}/mlp")
        out["gate_attn"] = S.zeros((), ())
        out["gate_mlp"] = S.zeros((), ())
    else:
        out["attn"] = _attn_specs(cfg, recipe, f"{base}/attn")
        if kind == "moe":
            out["mlp"] = moe_specs(cfg, recipe, f"{base}/mlp")
        else:
            out["mlp"] = mlp_specs(cfg, recipe, f"{base}/mlp")
    return out


def _block_cache_specs(cfg: ModelConfig, kind: str, batch: int,
                       max_seq: int) -> dict:
    if kind == "cross":
        mem = cfg.num_image_tokens or cfg.encoder_seq
        return A.cross_attn_cache_specs(cfg, batch, mem)
    if cfg.attention == "mla":
        return A.mla_cache_specs(cfg, batch, max_seq)
    return A.gqa_cache_specs(cfg, batch, max_seq)


def _block_apply(params, x, cfg: ModelConfig, recipe, kind: str, base: str,
                 *, mode, cache, pos, memory):
    """-> (x, cache, aux, routed counts (G, E) of a MoE block | None)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(params["ln1"], x, cfg.norm_eps)
    if kind == "cross":
        h, cache = A.cross_attn_apply(
            params["attn"], h, cfg, recipe, f"{base}/xattn",
            memory=memory, cache=cache, mode=mode)
        x = x + jnp.tanh(params["gate_attn"]).astype(x.dtype) * h
        h2 = rmsnorm(params["ln2"], x, cfg.norm_eps)
        h2 = mlp_apply(params["mlp"], h2, cfg, recipe, f"{base}/mlp")
        x = x + jnp.tanh(params["gate_mlp"]).astype(x.dtype) * h2
        return x, cache, aux, None
    if cfg.attention == "mla":
        h, cache = A.mla_apply(params["attn"], h, cfg, recipe,
                               f"{base}/attn", mode=mode, cache=cache,
                               pos=pos)
    else:
        h, cache = A.gqa_apply(params["attn"], h, cfg, recipe,
                               f"{base}/attn", mode=mode, cache=cache,
                               pos=pos)
    x = x + h
    h2 = rmsnorm(params["ln2"], x, cfg.norm_eps)
    counts = None
    if kind == "moe":
        h2, aux, counts = moe_apply(params["mlp"], h2, cfg, recipe,
                                    f"{base}/mlp")
    else:
        h2 = mlp_apply(params["mlp"], h2, cfg, recipe, f"{base}/mlp")
    x = x + h2
    return x, cache, aux, counts


# ---------------------------------------------------------------------------
# Whole-model specs
# ---------------------------------------------------------------------------


def param_specs(cfg: ModelConfig, recipe=None) -> dict:
    d, V = cfg.d_model, cfg.vocab_size
    dt = cfg.activation_dtype
    prefix, pattern, R = split_layers(layer_kinds(cfg))
    specs: dict = {
        "embed": S.w((V, d), ("vocab", "embed"), dtype=dt, init="embed"),
        "final_norm": rmsnorm_spec(d),
    }
    if not cfg.tie_embeddings:
        specs["head"] = {"w": S.w((d, V), ("embed", "vocab"), dtype=dt)}
    if prefix:
        specs["prefix"] = {
            str(i): _block_specs(cfg, recipe, k, f"prefix/{i}")
            for i, k in enumerate(prefix)
        }
    if R:
        pat = {f"s{j}": _block_specs(cfg, recipe, k, f"blocks/s{j}")
               for j, k in enumerate(pattern)}
        specs["blocks"] = stack_specs(pat, R)
    return specs


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    prefix, pattern, R = split_layers(layer_kinds(cfg))
    out: dict = {}
    if prefix:
        out["prefix"] = {
            str(i): _block_cache_specs(cfg, k, batch, max_seq)
            for i, k in enumerate(prefix)
        }
    if R:
        pat = {f"s{j}": _block_cache_specs(cfg, k, batch, max_seq)
               for j, k in enumerate(pattern)}
        out["blocks"] = stack_specs(pat, R, axis_name="layers")
    return out


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------


def apply(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B, S) int32
    *,
    recipe=None,
    mode: str = "train",
    cache: dict | None = None,
    pos=0,
    memory: jax.Array | None = None,  # (B, Sm, d) image/frame embeddings
    routing: bool = False,
):
    """Returns (logits f32 (B, S, V), new_cache, aux_loss).

    With ``routing=True`` a fourth result follows: the routed
    (capacity-clipped) per-expert counts of every MoE layer, int32
    ``(L_moe, G, E)`` in layer order (None without MoE layers). They
    leave the step as an output so that compiled code needs no host
    callback to observe the live dispatch (``models.moe`` routing sinks).

    ``cfg.kernel_mode`` (when set) selects the qlinear backend for every
    quantized linear in the forward. It is established here, inside the
    (possibly jitted) function body, so retraces re-apply it.
    """
    if cfg.kernel_mode:
        with qlinear.kernel_mode(cfg.kernel_mode):
            out = _apply(params, cfg, tokens, recipe=recipe, mode=mode,
                         cache=cache, pos=pos, memory=memory)
    else:
        out = _apply(params, cfg, tokens, recipe=recipe, mode=mode,
                     cache=cache, pos=pos, memory=memory)
    return out if routing else out[:3]


def _apply(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,
    *,
    recipe=None,
    mode: str = "train",
    cache: dict | None = None,
    pos=0,
    memory: jax.Array | None = None,
):
    prefix, pattern, R = split_layers(layer_kinds(cfg))
    x = params["embed"].astype(cfg.activation_dtype)[tokens]
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {} if cache is not None else None
    routed: list = []  # (n, G, E) routed counts, in layer order

    if prefix:
        if cache is not None:
            new_cache["prefix"] = {}
        for i, kind in enumerate(prefix):
            c = cache["prefix"][str(i)] if cache is not None else None
            x, c, a, n = _block_apply(
                params["prefix"][str(i)], x, cfg, recipe, kind,
                f"prefix/{i}", mode=mode, cache=c, pos=pos, memory=memory)
            aux = aux + a
            if n is not None:
                routed.append(n[None])
            if cache is not None:
                new_cache["prefix"][str(i)] = c

    if R:
        # Scan the layer index rather than the weights where nothing is
        # differentiated: a training scan that closed over the stack would
        # accumulate a full-size gradient of it at every step.
        indexed = (cfg.scan_layers
                   and qlinear.current_kernel_mode() != "reference"
                   and (mode != "train" or cache is not None))

        def body(carry, inp):
            xc, auxc = carry
            if cache is not None:
                p_l, c_l = inp
            else:
                p_l, c_l = inp, None
            if indexed:
                p_l = layer_view(params["blocks"], p_l)
            c_out, n_out = {}, []
            for j, kind in enumerate(pattern):
                cj = c_l[f"s{j}"] if c_l is not None else None
                xc, cj, a, n = _block_apply(
                    p_l[f"s{j}"], xc, cfg, recipe, kind, f"blocks/s{j}",
                    mode=mode, cache=cj, pos=pos, memory=memory)
                auxc = auxc + a
                if n is not None:
                    n_out.append(n)
                if cache is not None:
                    c_out[f"s{j}"] = cj
            # one repeat's MoE counts (n, G, E), in pattern order
            n_out = jnp.stack(n_out) if n_out else None
            return (xc, auxc), (c_out if cache is not None else None, n_out)

        if not cfg.scan_layers:
            # unrolled python loop — required for eager calibration capture
            from .common import take_layer

            caches = []
            for r in range(R):
                p_r = take_layer(params["blocks"], r)
                c_r = take_layer(cache["blocks"], r) if cache is not None \
                    else None
                (x, aux), (c_r, n_r) = body((x, aux), (p_r, c_r)
                                            if cache is not None else p_r)
                caches.append(c_r)
                if n_r is not None:
                    routed.append(n_r)
            if cache is not None:  # restack, as the scan returns it
                new_cache["blocks"] = jax.tree.map(
                    lambda *a: jnp.stack(a), *caches)
        else:
            if cfg.remat and mode == "train":
                body = jax.checkpoint(body, prevent_cse=False)
            layers = jnp.arange(R) if indexed else params["blocks"]
            xs = (layers, cache["blocks"]) if cache is not None else layers
            (x, aux), (scanned_cache, n_s) = jax.lax.scan(body, (x, aux),
                                                          xs)
            if n_s is not None:  # (R, n, G, E) -> (R * n, G, E)
                routed.append(n_s.reshape(-1, *n_s.shape[2:]))
            if cache is not None:
                new_cache["blocks"] = scanned_cache

    if mode == "prefill":
        # serving semantics: only the last position's logits are needed —
        # slicing before the head avoids a (B, S, V) logits tensor.
        x = x[:, -1:]
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x.astype(jnp.float32) @ params["embed"].astype(
            jnp.float32).T
    else:
        logits = x.astype(jnp.float32) @ params["head"]["w"].astype(
            jnp.float32)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    routed = jnp.concatenate(routed) if routed else None
    return logits, new_cache, aux, routed
