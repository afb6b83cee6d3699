"""Compile rehearsals of the serving path's Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unaligned block shapes, fast-memory (VMEM) overruns.
These tests lower and compile each GEMM kernel, at Mixtral-8x7B,
Qwen2-72B and Llama-3.2-3B widths, for one chip of a *described* v5e
topology (the TPU compiler is installed; no chip is attached, nothing
runs). The topology is described inside a fixture, never at import, so
every pytest-xdist worker collects the same tests; where it cannot be
described the tests skip. The persistent compilation cache is off around
the compiles: their entries could not be read back without a chip.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.recipe import DEFAULT_RECIPE
from repro.kernels.act_quant import act_quant
from repro.kernels.moe_gemm import fg_grouped_gemm_integer_scale_ragged
from repro.kernels.w4a8_gemm import fg_gemm_integer_scale
from repro.kernels.w4a8_gemm_fscale import fg_gemm_float_scale
from repro.kernels.w4a16_gemm import w4a16_gemm
from repro.models.registry import get_arch, get_model
from repro.nn import spec as S

GS = 128
# (K, N) of the dense layers: Llama-3.2-3B fused QKV-ish (4096 -> 6144),
# Mixtral-8x7B FFN up (4096 -> 14336) and down (14336 -> 4096), Qwen2-72B
# MLP gate/up (8192 -> 29568), down (29568 -> 8192) and k/v (8192 -> 1024).
# At these M the integer-scale kernel takes its blocks by shape
# (``w4a8_gemm.stream_blocks``); the others take the (256, 512) defaults,
# snapped: gate/up's bn to 128, down's bk to 384 (three groups a block)
DENSE_KN = [(4096, 6144), (4096, 14336), (14336, 4096),
            (8192, 29568), (29568, 8192), (8192, 1024)]
# Mixtral-8x7B expert projections (K, N); E=8 experts
EXPERT_KN = {"up": (4096, 14336), "down": (14336, 4096)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; the program must hold a
    Mosaic kernel (``tpu_custom_call``), i.e. Pallas really lowered."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("M", [8, 128])
@pytest.mark.parametrize("K,N", DENSE_KN)
@pytest.mark.parametrize("scheme", ["is", "fs", "w4a16"])
def test_dense_gemm_compiles(one_chip, scheme, K, N, M):
    w = ((K // 2, N), jnp.int8)
    if scheme == "w4a16":
        _compile(lambda x, q, s: w4a16_gemm(x, q, s, group_size=GS),
                 one_chip, ((M, K), jnp.bfloat16), w,
                 ((K // GS, N), jnp.float32))
        return
    kernel = fg_gemm_integer_scale if scheme == "is" else fg_gemm_float_scale
    sdt = jnp.int32 if scheme == "is" else jnp.float32
    _compile(lambda x, sa, q, s: kernel(x, sa, q, s, group_size=GS),
             one_chip, ((M, K), jnp.int8), ((M, 1), jnp.float32), w,
             ((K // GS, N), sdt))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("C", [8, 64, 128, 160, 256])
@pytest.mark.parametrize("proj", ["up", "down"])
def test_ragged_grouped_is_compiles(one_chip, proj, C, dtype):
    """Every capacity the engine produces, including the multiples of bm
    (128, 256) that the wrapper does not pad: the full-K activation slab
    and its quantization must fit VMEM at K=14336. Blocks by shape: up to
    one m-tile (C <= 128) up to 2 MiB of packed weight a step, narrowed
    where the slab leaves no room (down at C = 128 in f32), the defaults
    above."""
    K, N = EXPERT_KN[proj]
    E = 8
    _compile(
        lambda x, rc, q, s, a: fg_grouped_gemm_integer_scale_ragged(
            x, rc, q, s, group_size=GS, alpha=a),
        one_chip, ((E, C, K), dtype), ((E,), jnp.int32),
        ((E, K // 2, N), jnp.int8), ((E, K // GS, N), jnp.int32),
        ((E,), jnp.float32))


@pytest.mark.parametrize("M", [8, 512])
@pytest.mark.parametrize("K", [4096, 14336, 29568])
def test_act_quant_compiles(one_chip, K, M):
    _compile(lambda x: act_quant(x), one_chip, ((M, K), jnp.bfloat16))


# Mixtral-8x7B's 8 layers of the benchmark, stacked as the layer scan holds
# them; the kernels read layer ``l`` of the stack in place
R_LAYERS = 8


def _stacked_dense_is(one_chip, R, K, N, M):
    _compile(
        lambda x, sa, q, s, lyr: fg_gemm_integer_scale(
            x, sa, q, s, lyr, group_size=GS),
        one_chip, ((M, K), jnp.int8), ((M, 1), jnp.float32),
        ((R, K // 2, N), jnp.int8),
        ((R, K // GS, N), jnp.int32), ((), jnp.int32))


def test_layer_stacked_dense_is_compiles(one_chip):
    # attention q/o projections at 32 slots
    _stacked_dense_is(one_chip, R_LAYERS, 4096, 4096, 32)


def test_layer_stacked_dense_is_compiles_qwen2_down(one_chip):
    # Qwen2-72B's 10 layers of the benchmark, MLP down at 32 slots: blocks
    # by shape (2048, 1408), eleven groups a block (29568 = 21 * 1408)
    _stacked_dense_is(one_chip, 10, 29568, 8192, 32)


@pytest.mark.parametrize("K,N", [(8192, 29568), (8192, 8192)],
                         ids=["gate_up", "q"])
def test_layer_stacked_dense_is_compiles_qwen2(one_chip, K, N):
    # the same stack's gate/up and q at 32 slots, blocks by shape:
    # (1408, 2048) and (2048, 2048), 1.4 and 2 MiB of packed int4 a step
    _stacked_dense_is(one_chip, 10, K, N, 32)


@pytest.mark.parametrize("proj,C", [("up", 8), ("down", 8), ("up", 32),
                                    ("down", 32)],
                         ids=["up", "down", "up-decode", "down-decode"])
def test_layer_stacked_ragged_is_compiles(one_chip, proj, C):
    # decode: 32 slots x top-2 over 8 experts at capacity_factor 4.0 gives
    # C = 32, one m-tile, blocks by shape (2048, 2048); C = 8 is the
    # dispatch's smallest capacity
    K, N = EXPERT_KN[proj]
    E = 8
    _compile(
        lambda x, rc, q, s, lyr, a: fg_grouped_gemm_integer_scale_ragged(
            x, rc, q, s, lyr, group_size=GS, alpha=a),
        one_chip, ((E, C, K), jnp.bfloat16), ((E,), jnp.int32),
        ((R_LAYERS, E, K // 2, N), jnp.int8),
        ((R_LAYERS, E, K // GS, N), jnp.int32), ((), jnp.int32),
        ((E,), jnp.float32))


def _hlo_types(dtype, shape) -> set:
    dt = {jnp.dtype(jnp.int8): "s8", jnp.dtype(jnp.int32): "s32"}[
        jnp.dtype(dtype)]
    return {f"{dt}[{','.join(str(d) for d in s)}]{{"
            for s in ((1, *shape), shape)}


def test_scanned_decode_reads_stacked_weights_in_place(one_chip):
    """A scanned Pallas decode step at small widths: no ``dynamic-slice``
    (fused or not) in the compiled program takes a stacked quantized
    weight or its scale as operand — the kernels index the stack."""
    cfg = dataclasses.replace(get_arch("mixtral-8x7b", smoke=True),
                              num_layers=4, kernel_mode="pallas")
    api = get_model(cfg)
    B, max_seq = 8, 64

    def abstract(specs):
        return jax.tree.map(
            lambda sp: jax.ShapeDtypeStruct(sp.shape, sp.dtype,
                                            sharding=one_chip),
            specs, is_leaf=S.is_spec)

    params = abstract(api.param_specs(cfg, DEFAULT_RECIPE))
    cache = abstract(api.cache_specs(cfg, B, max_seq))
    toks = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def decode(p, t, c, q):
        return api.apply(p, cfg, t, recipe=DEFAULT_RECIPE, mode="decode",
                         cache=c, pos=q, routing=True)

    text = jax.jit(decode).lower(params, toks, cache, pos).compile().as_text()
    assert "tpu_custom_call" in text
    # one layer of a stacked weight or scale, with its layer axis or without
    layer_types = set().union(*(
        _hlo_types(a.dtype, a.shape[1:])
        for path, a in jax.tree_util.tree_leaves_with_path(params["blocks"])
        if path[-1].key in ("qvalue", "scale")))
    slices = [ln.strip() for ln in text.splitlines()
              if re.search(r"\bdynamic-slice\(", ln)]
    assert slices  # the KV cache still rides the scan
    copies = [ln for ln in slices
              if any(f" = {t}" in ln for t in layer_types)]
    assert not copies, copies[:3]
