"""xLSTM (sLSTM + mLSTM blocks) — attention-free recurrent LM.

Faithful to arXiv:2405.04517 structure at the block level:
  * mLSTM: matrix memory C (dh x dh per head), exponential input gate,
    sigmoid forget gate, stabilizer state m; q/k from a causal-conv path.
  * sLSTM: scalar memory with per-head block-diagonal recurrent weights,
    exponential gating + stabilizer; followed by a gated FFN (factor 4/3).
  * blocks alternate mLSTM : sLSTM at 7:1 (``slstm_every``).

Temporal mixing runs as a ``lax.scan`` over time (exact recurrence). The
recurrent state is O(1) in sequence length — this is why xlstm-1.3b runs
the ``long_500k`` cell that full-attention archs must skip. Decode carries
{C, n, m} / {c, n, m, h} per block in the cache.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.nn import spec as S
from .common import apply_linear, linear, rmsnorm, rmsnorm_spec, stack_specs
from .config import ModelConfig


def _d_inner(cfg: ModelConfig) -> int:
    return int(cfg.d_model * cfg.mlstm_proj_factor)


def _dh(cfg: ModelConfig) -> int:
    return _d_inner(cfg) // cfg.num_heads


# ---------------------------------------------------------------------------
# Causal depthwise conv (width 4)
# ---------------------------------------------------------------------------


def conv_specs(d: int, width: int) -> dict:
    return {"w": S.w((width, d), (None, "embed")),
            "b": S.zeros((d,), ("embed",))}


def causal_conv(params: dict, x: jax.Array, *, state: jax.Array | None = None):
    """x (B, S, d). state (B, width-1, d) carries the rolling window for
    decode. Returns (y, new_state)."""
    w = params["w"].astype(jnp.float32)
    width, d = w.shape
    xf = x.astype(jnp.float32)
    if state is None:
        pad = jnp.zeros((x.shape[0], width - 1, d), jnp.float32)
    else:
        pad = state.astype(jnp.float32)
    xp = jnp.concatenate([pad, xf], axis=1)  # (B, S+w-1, d)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(width))
    y = y + params["b"].astype(jnp.float32)
    new_state = xp[:, -(width - 1):, :]
    return y.astype(x.dtype), new_state.astype(x.dtype)


# ---------------------------------------------------------------------------
# mLSTM block
# ---------------------------------------------------------------------------


def mlstm_specs(cfg: ModelConfig, recipe, base: str) -> dict:
    d, di = cfg.d_model, _d_inner(cfg)
    H = cfg.num_heads
    dt = cfg.activation_dtype
    return {
        "ln": rmsnorm_spec(d),
        "up": linear(recipe, f"{base}/up", d, 2 * di, ("embed", "mlp"),
                     dtype=dt),
        "conv": conv_specs(di, cfg.conv_width),
        "q": linear(recipe, f"{base}/q", di, di, ("mlp", "heads_q"), dtype=dt),
        "k": linear(recipe, f"{base}/k", di, di, ("mlp", "heads_q"), dtype=dt),
        "v": linear(recipe, f"{base}/v", di, di, ("mlp", "heads_q"), dtype=dt),
        "if_gate": {"w": S.w((di, 2 * H), ("mlp", None), scale=0.3),
                    "b": S.zeros((2 * H,), (None,))},
        "out_norm": rmsnorm_spec(di),
        "down": linear(recipe, f"{base}/down", di, d, ("mlp", "embed"),
                       dtype=dt),
    }


def mlstm_state_specs(cfg: ModelConfig, batch: int) -> dict:
    H, dh = cfg.num_heads, _dh(cfg)
    di = _d_inner(cfg)
    return {
        "C": S.zeros((batch, H, dh, dh), ("cache_batch", "heads_q", None, None),
                     dtype=jnp.float32),
        "n": S.zeros((batch, H, dh), ("cache_batch", "heads_q", None),
                     dtype=jnp.float32),
        "m": S.zeros((batch, H), ("cache_batch", "heads_q"),
                     dtype=jnp.float32),
        "conv": S.zeros((batch, cfg.conv_width - 1, di),
                        ("cache_batch", None, "mlp"),
                        dtype=cfg.activation_dtype),
    }


def _mlstm_cell(state, qkvif):
    """One timestep of the stabilized mLSTM recurrence.

    state: C (B,H,dh,dh), n (B,H,dh), m (B,H)
    qkvif: q,k,v (B,H,dh); i_raw, f_raw (B,H)
    """
    C, n, m = state
    q, k, v, i_raw, f_raw = qkvif
    log_f = jax.nn.log_sigmoid(f_raw)  # (B,H)
    m_new = jnp.maximum(log_f + m, i_raw)
    i_p = jnp.exp(i_raw - m_new)
    f_p = jnp.exp(log_f + m - m_new)
    C_new = f_p[..., None, None] * C + i_p[..., None, None] * (
        v[..., :, None] * k[..., None, :])  # (B,H,dh,dh)
    n_new = f_p[..., None] * n + i_p[..., None] * k
    num = jnp.einsum("bhvk,bhk->bhv", C_new, q)
    den = jnp.abs(jnp.einsum("bhk,bhk->bh", n_new, q))
    # stabilized denominator: max(|n.q|, exp(-m)) keeps the (C, n, m)
    # representation scale-invariant (paper eq. 26) — so a zero-initialized
    # decode state is exactly equivalent to the training init.
    h = num / jnp.maximum(den, jnp.exp(-m_new))[..., None]
    return (C_new, n_new, m_new), h


def _mlstm_chunked(q, k, v, i_raw, f_raw, C0, n0, m0, chunk: int):
    """Chunkwise-PARALLEL mLSTM (beyond-paper §Perf optimization).

    Mathematically identical to scanning `_mlstm_cell` over time (tested
    allclose): the stabilizer admits the closed form
        m_t = F_t + max(m_0, cummax_{s<=t}(li_s - F_s)),
    F_t = cumsum(log sigmoid(f_raw)), so intra-chunk outputs become a
    decay-masked attention matmul and only a LIGHT scan over S/chunk
    summaries remains — sequential depth drops 32768 -> 128 for the
    prefill_32k cell.

    q,k,v: (B,S,H,dh) f32; i_raw,f_raw: (B,S,H) f32.
    Returns (h (B,S,H,dh), (C,n,m) final state).
    """
    B, S, H, dh = q.shape
    c = min(chunk, S)
    nc = S // c
    assert S % c == 0, (S, c)

    def reshape_c(t):
        return jnp.moveaxis(t.reshape(B, nc, c, *t.shape[2:]), 1, 0)

    qc, kc, vc = reshape_c(q), reshape_c(k), reshape_c(v)
    lf = jax.nn.log_sigmoid(reshape_c(f_raw))       # (nc,B,c,H)
    li = reshape_c(i_raw)
    F = jnp.cumsum(lf, axis=2)                      # F_t
    run_max = jax.lax.cummax(li - F, axis=2)        # max_{s<=t}(li_s - F_s)
    mask = jnp.tril(jnp.ones((c, c), bool))

    def chunk_body(carry, inp):
        C_in, n_in, m_in = carry                # (B,H,dh,dh),(B,H,dh),(B,H)
        qb, kb, vb, Fb, lib, rmb = inp          # (B,c,H,dh) / (B,c,H)
        m_t = Fb + jnp.maximum(m_in[:, None, :], rmb)       # (B,c,H)
        g_in = jnp.exp(Fb + m_in[:, None, :] - m_t)         # (B,c,H)
        num_in = jnp.einsum("bhvk,bchk->bchv", C_in, qb)
        den_in = jnp.einsum("bhk,bchk->bch", n_in, qb)
        # intra-chunk: D[t,s] = exp(F_t - F_s + li_s - m_t), s <= t
        logD = (Fb[:, :, None, :] - Fb[:, None, :, :]
                + lib[:, None, :, :] - m_t[:, :, None, :])  # (B,t,s,H)
        D = jnp.where(mask[None, :, :, None], jnp.exp(logD), 0.0)
        scores = jnp.einsum("bthk,bshk->btsh", qb, kb) * D
        num = jnp.einsum("btsh,bshv->bthv", scores, vb) \
            + g_in[..., None] * num_in
        den = jnp.sum(scores, axis=2) + g_in * den_in       # (B,c,H)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]
        # chunk-final summaries (t = c)
        m_c = m_t[:, -1, :]
        decay_s = jnp.exp(Fb[:, -1, None, :] - Fb + lib
                          - m_c[:, None, :])                # (B,c,H)
        carry_g = jnp.exp(Fb[:, -1, :] + m_in - m_c)        # (B,H)
        C_new = (carry_g[..., None, None] * C_in
                 + jnp.einsum("bsh,bshv,bshk->bhvk", decay_s, vb, kb))
        n_new = (carry_g[..., None] * n_in
                 + jnp.einsum("bsh,bshk->bhk", decay_s, kb))
        return (C_new, n_new, m_c), h

    (C, n, m), hs = jax.lax.scan(chunk_body, (C0, n0, m0),
                                 (qc, kc, vc, F, li, run_max))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, H, dh)
    return h, (C, n, m)


def mlstm_apply(params, x, cfg: ModelConfig, recipe, base: str, *,
                state: dict | None = None):
    """x (B,S,d) -> (y, new_state). state=None => fresh zeros (training)."""
    B, Sq, d = x.shape
    H, dh, di = cfg.num_heads, _dh(cfg), _d_inner(cfg)
    h_in = rmsnorm(params["ln"], x, cfg.norm_eps)
    up = apply_linear(recipe, f"{base}/up", params["up"], h_in)
    xm, z = up[..., :di], up[..., di:]
    conv_state = state["conv"] if state is not None else None
    xc, conv_new = causal_conv(params["conv"], xm, state=conv_state)
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(x.dtype)
    q = apply_linear(recipe, f"{base}/q", params["q"], xc)
    k = apply_linear(recipe, f"{base}/k", params["k"], xc) / math.sqrt(dh)
    v = apply_linear(recipe, f"{base}/v", params["v"], xm)
    gates = (xm.astype(jnp.float32) @ params["if_gate"]["w"]
             + params["if_gate"]["b"])  # (B,S,2H)
    i_raw, f_raw = gates[..., :H], gates[..., H:]

    def reshape_heads(t):
        return t.reshape(B, Sq, H, dh).astype(jnp.float32)

    q, k, v = reshape_heads(q), reshape_heads(k), reshape_heads(v)

    if state is None:
        C0 = jnp.zeros((B, H, dh, dh), jnp.float32)
        n0 = jnp.zeros((B, H, dh), jnp.float32)
        m0 = jnp.zeros((B, H), jnp.float32)
    else:
        C0, n0, m0 = (state["C"].astype(jnp.float32),
                      state["n"].astype(jnp.float32),
                      state["m"].astype(jnp.float32))

    if cfg.mlstm_impl == "chunked" and Sq > 1:
        hseq, (C, n, m) = _mlstm_chunked(
            q, k, v, i_raw.astype(jnp.float32),
            f_raw.astype(jnp.float32), C0, n0, m0, cfg.chunk_size)
        h = hseq
    else:
        def step(carry, t_in):
            return _mlstm_cell(carry, t_in)

        xs = (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
              jnp.moveaxis(v, 1, 0), jnp.moveaxis(i_raw, 1, 0),
              jnp.moveaxis(f_raw, 1, 0))
        (C, n, m), hs = jax.lax.scan(step, (C0, n0, m0), xs)
        h = jnp.moveaxis(hs, 0, 1)
    h = h.reshape(B, Sq, di)  # (B,S,di)
    h = rmsnorm(params["out_norm"], h.astype(x.dtype), cfg.norm_eps)
    h = h * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    y = apply_linear(recipe, f"{base}/down", params["down"], h)
    new_state = None
    if state is not None:
        new_state = {"C": C, "n": n, "m": m, "conv": conv_new}
    return x + y, new_state


# ---------------------------------------------------------------------------
# sLSTM block
# ---------------------------------------------------------------------------


def slstm_specs(cfg: ModelConfig, recipe, base: str) -> dict:
    d = cfg.d_model
    H = cfg.num_heads
    dh = d // H
    dt = cfg.activation_dtype
    ff = int(d * 4 / 3)
    ff = -(-ff // 128) * 128  # 128-multiple so group-128 quant applies
    return {
        "ln": rmsnorm_spec(d),
        "wx": linear(recipe, f"{base}/wx", d, 4 * d, ("embed", "mlp"),
                     dtype=dt),
        # block-diagonal recurrent weights: (H, dh, 4*dh)
        "r": S.w((H, dh, 4 * dh), ("heads_q", None, None), scale=1.0),
        "out_norm": rmsnorm_spec(d),
        "ff_gate": linear(recipe, f"{base}/ff_gate", d, ff,
                          ("embed", "mlp"), dtype=dt),
        "ff_up": linear(recipe, f"{base}/ff_up", d, ff, ("embed", "mlp"),
                        dtype=dt),
        "ff_down": linear(recipe, f"{base}/ff_down", ff, d,
                          ("mlp", "embed"), dtype=dt),
    }


def slstm_state_specs(cfg: ModelConfig, batch: int) -> dict:
    H = cfg.num_heads
    dh = cfg.d_model // H
    ax = ("cache_batch", "heads_q", None)
    return {
        "c": S.zeros((batch, H, dh), ax, dtype=jnp.float32),
        "n": S.zeros((batch, H, dh), ax, dtype=jnp.float32),
        "m": S.zeros((batch, H, dh), ax, dtype=jnp.float32),
        "h": S.zeros((batch, H, dh), ax, dtype=jnp.float32),
    }


def slstm_apply(params, x, cfg: ModelConfig, recipe, base: str, *,
                state: dict | None = None):
    B, Sq, d = x.shape
    H = cfg.num_heads
    dh = d // H
    xi = rmsnorm(params["ln"], x, cfg.norm_eps)
    pre = apply_linear(recipe, f"{base}/wx", params["wx"], xi)  # (B,S,4d)
    pre = pre.reshape(B, Sq, H, 4 * dh).astype(jnp.float32)

    if state is None:
        z = jnp.zeros((B, H, dh), jnp.float32)
        st = (z, z, jnp.zeros((B, H, dh), jnp.float32), z)
    else:
        st = (state["c"].astype(jnp.float32), state["n"].astype(jnp.float32),
              state["m"].astype(jnp.float32), state["h"].astype(jnp.float32))

    r = params["r"].astype(jnp.float32)

    def step(carry, pre_t):  # pre_t (B,H,4dh)
        c, n, m, h = carry
        rec = jnp.einsum("bhd,hdk->bhk", h, r)  # (B,H,4dh)
        g = pre_t + rec
        zt, it, ft, ot = jnp.split(g, 4, axis=-1)
        zt = jnp.tanh(zt)
        log_f = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(log_f + m, it)
        i_p = jnp.exp(it - m_new)
        f_p = jnp.exp(log_f + m - m_new)
        c_new = f_p * c + i_p * zt
        n_new = f_p * n + i_p
        h_new = jax.nn.sigmoid(ot) * c_new / jnp.maximum(n_new, jnp.exp(-m_new))
        return (c_new, n_new, m_new, h_new), h_new

    (c, n, m, h_last), hs = jax.lax.scan(step, st, jnp.moveaxis(pre, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, Sq, d).astype(x.dtype)
    h = rmsnorm(params["out_norm"], h, cfg.norm_eps)
    x = x + h
    # gated FFN (factor 4/3)
    g = apply_linear(recipe, f"{base}/ff_gate", params["ff_gate"], x)
    u = apply_linear(recipe, f"{base}/ff_up", params["ff_up"], x)
    ff = apply_linear(recipe, f"{base}/ff_down", params["ff_down"],
                      jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u)
    new_state = None
    if state is not None:
        new_state = {"c": c, "n": n, "m": m, "h": h_last}
    return x + ff, new_state


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------


def layer_kinds(cfg: ModelConfig) -> list[str]:
    return ["slstm" if (i + 1) % cfg.slstm_every == 0 else "mlstm"
            for i in range(cfg.num_layers)]


def _split(cfg: ModelConfig):
    from .transformer import split_layers

    return split_layers(layer_kinds(cfg))


def param_specs(cfg: ModelConfig, recipe=None) -> dict:
    d, V = cfg.d_model, cfg.vocab_size
    dt = cfg.activation_dtype
    prefix, pattern, R = _split(cfg)
    specs: dict = {
        "embed": S.w((V, d), ("vocab", "embed"), dtype=dt, init="embed"),
        "final_norm": rmsnorm_spec(d),
        "head": {"w": S.w((d, V), ("embed", "vocab"), dtype=dt)},
    }

    def block_specs(kind, base):
        if kind == "slstm":
            return slstm_specs(cfg, recipe, base)
        return mlstm_specs(cfg, recipe, base)

    if prefix:
        specs["prefix"] = {str(i): block_specs(k, f"prefix/{i}")
                           for i, k in enumerate(prefix)}
    if R:
        pat = {f"s{j}": block_specs(k, f"blocks/s{j}")
               for j, k in enumerate(pattern)}
        specs["blocks"] = stack_specs(pat, R)
    return specs


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    """max_seq unused — recurrent state is O(1). Kept for API parity."""
    prefix, pattern, R = _split(cfg)

    def block_state(kind):
        if kind == "slstm":
            return slstm_state_specs(cfg, batch)
        return mlstm_state_specs(cfg, batch)

    out: dict = {}
    if prefix:
        out["prefix"] = {str(i): block_state(k)
                         for i, k in enumerate(prefix)}
    if R:
        pat = {f"s{j}": block_state(k) for j, k in enumerate(pattern)}
        out["blocks"] = stack_specs(pat, R)
    return out


def apply(params, cfg: ModelConfig, tokens, *, recipe=None, mode="train",
          cache=None, pos=0, memory=None):
    prefix, pattern, R = _split(cfg)
    x = params["embed"].astype(cfg.activation_dtype)[tokens]
    new_cache: dict | None = {} if cache is not None else None

    def block(p, xc, kind, base, st):
        if kind == "slstm":
            return slstm_apply(p, xc, cfg, recipe, base, state=st)
        return mlstm_apply(p, xc, cfg, recipe, base, state=st)

    if prefix:
        if cache is not None:
            new_cache["prefix"] = {}
        for i, kind in enumerate(prefix):
            st = cache["prefix"][str(i)] if cache is not None else None
            x, st = block(params["prefix"][str(i)], x, kind, f"prefix/{i}", st)
            if cache is not None:
                new_cache["prefix"][str(i)] = st

    if R:
        def body(xc, inp):
            if cache is not None:
                p_l, c_l = inp
            else:
                p_l, c_l = inp, None
            outs = {}
            for j, kind in enumerate(pattern):
                st = c_l[f"s{j}"] if c_l is not None else None
                xc, st = block(p_l[f"s{j}"], xc, kind, f"blocks/s{j}", st)
                if cache is not None:
                    outs[f"s{j}"] = st
            return xc, (outs if cache is not None else None)

        if cfg.remat and mode == "train":
            body = jax.checkpoint(body, prevent_cse=False)
        xs = (params["blocks"], cache["blocks"]) if cache is not None \
            else params["blocks"]
        x, scanned = jax.lax.scan(body, x, xs)
        if cache is not None:
            new_cache["blocks"] = scanned

    if mode == "prefill":
        x = x[:, -1:]
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = x.astype(jnp.float32) @ params["head"]["w"].astype(jnp.float32)
    return logits, new_cache, jnp.zeros((), jnp.float32)
