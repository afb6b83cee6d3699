"""Mixture-of-Experts with sort-based capacity dispatch + expert parallelism.

Design:
  router (fp32) -> top-k -> stable sort by expert -> gather into a dense
  (groups, E, C, d) dispatch buffer -> batched expert GEMMs with the expert
  axis sharded on the ``model`` mesh axis (EP; GSPMD inserts the all-to-all)
  -> weighted scatter-combine. Tokens beyond capacity are dropped (GShard).

Expert FFN weights may be quantized (paper §5.5 — Mixtral): the batched
expert GEMM runs the fused grouped integer-scale Pallas kernel
(``repro.kernels.moe_gemm``) under kernel mode "pallas"/"pallas_interpret",
and a vmapped fine-grained reference GEMM otherwise — either way the HLO
contains int8 dot_generals per expert.

Shared experts (DeepSeek-V2) are a plain always-on MLP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import qlinear
from repro.nn import spec as S
from .config import ModelConfig
from .mlp import mlp_apply, mlp_specs


# ---------------------------------------------------------------------------
# Expert-stacked linears (leading E dim), recipe-aware
# ---------------------------------------------------------------------------


def expert_linear_specs(E: int, K: int, N: int, qspec, axes, dtype) -> dict:
    base = qlinear.linear_specs(K, N, qspec, axes[1:], dtype=dtype)

    def stack(s: S.ParamSpec) -> S.ParamSpec:
        return S.ParamSpec((E, *s.shape), s.dtype, s.init,
                           (axes[0], *s.logical_axes), s.init_scale)

    return jax.tree.map(stack, base, is_leaf=S.is_spec)


def expert_linear_apply(params: dict, x: jax.Array, qspec,
                        row_counts: jax.Array | None = None, *,
                        mode: str | None = None) -> jax.Array:
    """x: (E, C, K) -> (E, C, N), all experts in one call.

    Quantized experts route through ``qlinear.grouped_linear_apply``: under
    kernel mode "pallas"/"pallas_interpret" that is ONE fused grouped
    Pallas GEMM over the (experts, m, n, k-groups) grid (kernels/moe_gemm)
    rather than a vmap of the per-expert reference GEMM. ``row_counts``
    (int32 (E,), routed rows per expert; rows past it are zero-filled by
    the dispatch) lets the ragged kernel skip capacity-padding m-tiles —
    it is a data operand (traced under jit), so the serving engine's
    decode step feeds fresh per-tick counts without retracing. ``mode`` is
    cfg.kernel_mode threaded from moe_apply.
    """
    return qlinear.grouped_linear_apply(params, x, qspec,
                                        row_counts=row_counts, mode=mode)


# ---------------------------------------------------------------------------
# MoE layer
# ---------------------------------------------------------------------------


# -- int8 dispatch compression (DeepSeek-V3-style) ---------------------------
# The dispatch buffer crosses the data->expert resharding boundary (the
# all-to-all). Quantizing per token to int8 (+ f32 scale) halves the wire
# bytes vs bf16. The sharding constraint below is what forces GSPMD to run
# the all-to-all ON the int8 tensor (dequant lands on the expert side);
# without it XLA would transport the dequantized bf16. Gradients pass
# straight through (custom_vjp): under W4A8 the expert GEMMs re-quantize
# activations anyway, so the forward effect is one extra rounding.

_DISPATCH_SHARDING = None  # optional (q8_sharding, scale_sharding) pair


def set_dispatch_sharding(q8_sharding, scale_sharding) -> None:
    global _DISPATCH_SHARDING
    _DISPATCH_SHARDING = (q8_sharding, scale_sharding)


@jax.custom_vjp
def _int8_transport(buf: jax.Array) -> jax.Array:
    amax = jnp.max(jnp.abs(buf.astype(jnp.float32)), axis=-1, keepdims=True)
    scl = jnp.maximum(amax, 1e-8) / 127.0
    q8 = jnp.clip(jnp.round(buf.astype(jnp.float32) / scl),
                  -127, 127).astype(jnp.int8)
    if _DISPATCH_SHARDING is not None:
        q8 = jax.lax.with_sharding_constraint(q8, _DISPATCH_SHARDING[0])
        scl = jax.lax.with_sharding_constraint(scl, _DISPATCH_SHARDING[1])
    return (q8.astype(jnp.float32) * scl).astype(buf.dtype)


def _int8_transport_fwd(buf):
    return _int8_transport(buf), None


def _int8_transport_bwd(_, g):
    return (g,)  # straight-through


_int8_transport.defvjp(_int8_transport_fwd, _int8_transport_bwd)


# -- routing sinks ----------------------------------------------------------
# Observability hook for the serving engine. The routed
# (capacity-clipped) per-expert counts of every MoE layer leave the
# compiled step as an OUTPUT (``moe_apply``'s third result, stacked per
# layer by ``transformer.apply(..., routing=True)``); compiled code never
# carries a host callback. The serving engine fetches them with the data
# it already syncs after each step and hands them to every registered sink
# as {"counts": np (G, E), "capacity": int} records, one per MoE layer in
# layer order (:func:`publish_routing`). A sink registered at any time
# receives every later step's records. Sinks may be plain callables or
# weakref-wrapped methods (``weakref.WeakMethod``) — dead weakrefs are
# pruned on delivery.

_ROUTING_SINKS: list = []


def add_routing_sink(sink) -> None:
    """Register ``sink(record: dict)`` (or a weakref to one)."""
    _ROUTING_SINKS.append(sink)


def remove_routing_sink(sink) -> None:
    if sink in _ROUTING_SINKS:
        _ROUTING_SINKS.remove(sink)


def start_routing_trace() -> list:
    """Begin recording {"counts": np (G,E), "capacity": int} per MoE call.

    Convenience wrapper over the sink API: returns the live list records
    append to; pass it to :func:`stop_routing_trace` when done.
    """
    records: list = []
    add_routing_sink(records.append)
    return records


def stop_routing_trace(records: list | None = None) -> list:
    """Detach the list-sink ``start_routing_trace`` installed.

    With no argument (legacy form) every list-append sink is detached —
    callers that interleave traces should pass their own list back.
    """
    if records is not None:
        remove_routing_sink(records.append)
        return records
    out: list = []
    for s in list(_ROUTING_SINKS):
        if getattr(s, "__self__", None).__class__ is list:
            out = s.__self__
            remove_routing_sink(s)
    return out


def routing_records(counts, cfg: ModelConfig, tokens: int) -> list[dict]:
    """One step's routed counts ``(L, G, E)`` (the MoE layers of a forward
    over ``tokens`` tokens, in layer order) as per-layer sink records."""
    C = _capacity(cfg, tokens // counts.shape[1])
    return [{"counts": c, "capacity": C} for c in counts]


def publish_routing(records: list[dict]) -> None:
    """Fan routing records out to every live sink."""
    import weakref

    for rec in records:
        for s in list(_ROUTING_SINKS):
            if isinstance(s, weakref.ref):
                live = s()
                if live is None:
                    remove_routing_sink(s)
                    continue
                live(rec)
            else:
                s(rec)


def moe_specs(cfg: ModelConfig, recipe, base: str) -> dict:
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    dt = cfg.activation_dtype
    out = {
        "router": S.w((d, E), ("embed", None), dtype=jnp.float32),
    }
    for name in ("gate", "up"):
        qspec = recipe.spec_for(f"{base}/{name}") if recipe else None
        out[name] = expert_linear_specs(
            E, d, f, qspec, ("experts", "embed", "moe_mlp"), dt)
    qspec = recipe.spec_for(f"{base}/down") if recipe else None
    out["down"] = expert_linear_specs(
        E, f, d, qspec, ("experts", "moe_mlp", "embed"), dt)
    if cfg.num_shared_experts:
        out["shared"] = mlp_specs(
            cfg, recipe, f"{base}/shared",
            d_ff=cfg.num_shared_experts * cfg.moe_d_ff)
    return out


def capacity(tokens: int, top_k: int, num_experts: int,
             capacity_factor: float) -> int:
    """Per-expert capacity (8-aligned)."""
    c = int(tokens * top_k * capacity_factor / max(num_experts, 1))
    return max(8, -(-c // 8) * 8)


def _capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    return capacity(tokens_per_group, cfg.top_k, cfg.num_experts,
                    cfg.capacity_factor)


def moe_apply(params: dict, x: jax.Array, cfg: ModelConfig, recipe,
              base: str):
    """x: (B, S, d). Returns (y, aux_loss, counts): ``counts`` int32
    (G, E) is the routed (capacity-clipped) rows per expert of each
    dispatch group — what the ragged kernel skips by, and what the
    serving engine's m-tile accounting reads from the step's outputs."""
    B, Sq, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    G = max(1, cfg.dispatch_groups)
    T_all = B * Sq
    if T_all % G:
        G = 1
    T = T_all // G
    C = _capacity(cfg, T)
    xf = x.reshape(G, T, d)

    # --- router (fp32, never quantized) -----------------------------------
    logits = xf.astype(jnp.float32) @ params["router"]  # (G, T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (G, T, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # --- load-balancing aux loss (GShard/Switch) ---------------------------
    me = jnp.mean(probs, axis=1)  # (G, E) mean prob
    one_hot_top1 = jax.nn.one_hot(expert_idx[..., 0], E)
    ce = jnp.mean(one_hot_top1, axis=1)  # (G, E) dispatch fraction
    aux = cfg.router_aux_coef * E * jnp.mean(jnp.sum(me * ce, -1))

    def dispatch_one(xg, eg, gg):
        """xg (T,d), eg (T,k) int, gg (T,k) -> (y (T,d))."""
        Tk = T * k
        e_flat = eg.reshape(Tk)
        g_flat = gg.reshape(Tk)
        t_flat = jnp.repeat(jnp.arange(T), k)
        order = jnp.argsort(e_flat, stable=True)
        e_s, t_s, g_s = e_flat[order], t_flat[order], g_flat[order]
        counts = jnp.bincount(e_s, length=E)
        starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(Tk) - starts[e_s]
        keep = pos < C
        slot = e_s * C + jnp.where(keep, pos, 0)
        # dispatch buffer (E*C, d)
        buf = jnp.zeros((E * C, d), xg.dtype)
        vals = jnp.where(keep[:, None], xg[t_s], 0)
        buf = buf.at[slot].add(vals)  # kept slots unique -> add == set
        return (buf.reshape(E, C, d), (t_s, g_s, e_s, pos, keep),
                jnp.minimum(counts, C).astype(jnp.int32))

    buf, meta, counts = jax.vmap(dispatch_one)(xf, expert_idx, gate_vals)
    # buf: (G, E, C, d) — E sharded on `model` via logical axis "experts"
    # counts: (G, E) routed (capacity-clipped) rows per expert — slots at or
    # past counts[g, e] are zero-filled, which is exactly the ragged grouped
    # kernel's row_counts contract.

    if cfg.moe_int8_dispatch:
        buf = _int8_transport(buf)

    # With one dispatch group the expert slab rows [0, counts[0, e]) are
    # contiguous, so the ragged grouped kernel can skip the padded m-tiles.
    # With G > 1 the (E, G*C, d) reshape interleaves each group's padding
    # into the middle of the slab, so raggedness is not expressible as a
    # single per-expert count — fall back to the dense (exact) behavior.
    row_counts = counts[0] if G == 1 else None

    km = cfg.kernel_mode

    def expert_ffn(b):  # b: (G, E, C, d) -> (G, E, C, d)
        be = jnp.swapaxes(b, 0, 1).reshape(E, G * C, d)
        qs_g = recipe.spec_for(f"{base}/gate") if recipe else None
        qs_u = recipe.spec_for(f"{base}/up") if recipe else None
        qs_d = recipe.spec_for(f"{base}/down") if recipe else None
        g = expert_linear_apply(params["gate"], be, qs_g, row_counts,
                                mode=km)
        u = expert_linear_apply(params["up"], be, qs_u, row_counts,
                                mode=km)
        h = (jax.nn.silu(g.astype(jnp.float32)).astype(be.dtype) * u)
        y = expert_linear_apply(params["down"], h, qs_d, row_counts,
                                mode=km)
        return jnp.swapaxes(y.reshape(E, G, C, d), 0, 1)

    yb = expert_ffn(buf)  # (G, E, C, d)

    def combine_one(yg, m):
        t_s, g_s, e_s, pos, keep = m
        slot = e_s * C + jnp.where(keep, pos, 0)
        vals = yg.reshape(E * C, d)[slot]  # (Tk, d)
        vals = jnp.where(keep[:, None], vals, 0) * g_s[:, None].astype(yg.dtype)
        out = jnp.zeros((T, d), yg.dtype)
        return out.at[t_s].add(vals)

    y = jax.vmap(combine_one)(yb, meta).reshape(B, Sq, d)

    if cfg.num_shared_experts:
        y = y + mlp_apply(params["shared"], x, cfg, recipe, f"{base}/shared")
    return y.astype(x.dtype), aux, counts
