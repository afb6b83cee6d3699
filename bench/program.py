"""The system under test, built for one configuration file.

It maps a configuration file onto the program's (``src/repro``)
``ModelConfig`` and quantization recipe, builds the program's quantized
parameters on the device from the seed, and makes the serving engine.
Beyond this module the harness touches the program only through the
engine's public API, its metrics registry (``repro.obs``) and the MoE
routing sink (``repro.models.moe``).

Parameters are built one layer at a time inside one jitted call that
donates the stacked buffers: the layer's float weights are drawn from
the seed (``bench.weights``), quantized by the program's own quantizer
(``qlinear.quantize_linear``: RTN, integer group scales), and written
into layer ``i`` of the stack. Float weights never reach the host, and
the device holds one layer's float weights at most. Under ``jit`` the
program skips its overflow certificate, so each layer also returns what
the closed-form Eq. 2 bound needs: the largest column sum of its integer
scales, and how many integer scales equal 1 (the floor of the
integerized scales).
"""
from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp

from bench import weights


def import_program(root) -> None:
    """Put the program's sources on the path; fail when they are absent."""
    src = root / "src"
    if not (src / "repro" / "serving" / "engine.py").is_file():
        raise SystemExit(f"bench: the program's sources are not at {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def model_config(cfg: dict, kernel_mode: str):
    from repro.models.config import ModelConfig

    if cfg.get("tie_word_embeddings"):
        raise ValueError("tied embeddings are not supported by the harness")
    srv = cfg["serving"]
    experts = int(cfg.get("num_local_experts", 0))
    return ModelConfig(
        name=cfg["name"], family="moe" if experts else "dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qkv_bias=bool(cfg.get("attention_bias")),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        num_experts=experts, top_k=int(cfg.get("num_experts_per_tok", 2)),
        moe_d_ff=cfg["intermediate_size"] if experts else 0,
        capacity_factor=float(srv.get("capacity_factor", 1.25)),
        dtype=srv["dtype"], kv_cache_dtype=srv["kv_cache_dtype"],
        kernel_mode=kernel_mode)


def recipe(cfg: dict):
    from repro.core.recipe import QuantRecipe, QuantSpec

    q = cfg["quantization"]
    spec = QuantSpec(w_bits=q["w_bits"], a_bits=q["a_bits"],
                     group_size=q["group_size"], scale_mode=q["scale_mode"],
                     amplifier=q["amplifier"], algo="rtn")
    return QuantRecipe(rules=(("*", spec),), name="bench-" + spec.name)


def build_params(api, mcfg, rcp, key):
    """-> (params, per-layer [(max integer-scale column sum, floor hits)])."""
    from repro.core import qlinear
    from repro.nn import spec as S

    specs = api.param_specs(mcfg, rcp)
    if set(specs) != {"embed", "final_norm", "head", "blocks"}:
        raise ValueError(f"unexpected parameter layout {sorted(specs)}")
    R = mcfg.num_layers
    d, V = mcfg.d_model, mcfg.vocab_size

    def layer(key, i):
        scales = []

        def node(spec, path):
            if isinstance(spec, dict) and "qvalue" in spec:
                qs = rcp.spec_for("blocks/s0/" + path)
                shape = spec["qvalue"].shape  # (R, [E,] K/pack, N)
                K, N = shape[-2] * (8 // qs.w_bits), shape[-1]
                if len(shape) == 4:
                    out = jax.lax.map(
                        lambda e: qlinear.quantize_linear(
                            weights.matrix(key, i, path, K, N, e), qs),
                        jnp.arange(shape[1]))
                else:
                    b = (weights.bias(key, i, path + "/b", N)
                         if "b" in spec else None)
                    out = qlinear.quantize_linear(
                        weights.matrix(key, i, path, K, N), qs, bias=b)
                if set(out) != set(spec):
                    raise ValueError(f"{path}: {sorted(out)} != "
                                     f"{sorted(spec)}")
                scales.append(out["scale"])
                return out
            if isinstance(spec, dict):
                return {k: node(v, f"{path}/{k}" if path else k)
                        for k, v in spec.items()}
            if path.endswith("/g"):
                return weights.gain(key, i, path[:-2], spec.shape[-1])
            if path.endswith("router"):
                return weights.matrix(key, i, path, *spec.shape[-2:])
            raise ValueError(f"no seeded draw for parameter {path!r}")

        tree = {"s0": node(specs["blocks"]["s0"], "")}
        colsum = jnp.max(jnp.stack([jnp.max(jnp.sum(s, axis=-2))
                                    for s in scales]))
        floors = sum(jnp.sum(s <= 1) for s in scales)
        return tree, colsum, floors

    @functools.partial(jax.jit, donate_argnums=0)
    def fill(blocks, key, i):
        tree, colsum, floors = layer(key, i)
        blocks = jax.tree.map(
            lambda B, x: jax.lax.dynamic_update_index_in_dim(
                B, x.astype(B.dtype), i, 0), blocks, tree)
        return blocks, colsum, floors

    blocks = jax.jit(lambda: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), specs["blocks"],
        is_leaf=S.is_spec))()
    stats = []
    for i in range(R):
        blocks, colsum, floors = fill(blocks, key, jnp.int32(i))
        stats.append((colsum, floors))
    outer = jax.jit(lambda key: {
        "embed": weights.embed(key, V, d),
        "final_norm": {"g": weights.gain(key, -1, "final_norm", d)},
        "head": {"w": weights.head(key, d, V)},
    })(key)
    params = {**outer, "blocks": blocks}
    return params, [(int(c), int(f)) for c, f in stats]


def make_engine(api, mcfg, params, rcp, mix: dict):
    from repro.serving.engine import Engine, ServeConfig

    eng = mix["engine"]
    sc = ServeConfig(
        max_slots=eng["max_slots"], max_seq=eng["max_seq"],
        prefill_len=eng["prefill_len"],
        max_new_tokens=int(mix["output"].get("max", mix["output"].get(
            "value", 0))),
        eos_id=-1, temperature=0.0, kernel_mode=mcfg.kernel_mode)
    return Engine(api, mcfg, params, sc, rcp)
