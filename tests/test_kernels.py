"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles,
executed in interpret mode (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import integer_scale as isc
from repro.core import packing, quant
from repro.kernels import ref as KR
from repro.kernels.act_quant import act_quant
from repro.kernels import w4a8_gemm as W
from repro.kernels.w4a8_gemm import fg_gemm_integer_scale
from repro.kernels.w4a8_gemm_fscale import fg_gemm_float_scale
from repro.kernels.w4a16_gemm import w4a16_gemm

jax.config.update("jax_platform_name", "cpu")

SHAPES = [  # (M, K, N, group)
    (1, 256, 128, 128),     # decode-like
    (7, 512, 256, 128),     # ragged M
    (48, 1024, 512, 128),
    (16, 512, 384, 256),    # larger group
    (128, 384, 128, 128),   # K not multiple of bk default
]


def _mk(seed, M, K, N, g, w_bits=4):
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (K, N)) * 0.05
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (M, K))
    qw = quant.quantize_weight(w, w_bits, g)
    xq, sa = quant.quantize_activation(x)
    packed = packing.pack_int4(qw.qvalue) if w_bits == 4 else qw.qvalue
    return qw, packed, xq, sa


@pytest.mark.parametrize("M,K,N,g", SHAPES)
def test_is_kernel_bit_exact_vs_oracle(M, K, N, g):
    qw, packed, xq, sa = _mk(0, M, K, N, g)
    isw = isc.integerize(qw, 1024)
    y_k = fg_gemm_integer_scale(xq, sa, packed, isw.int_scale,
                                group_size=g, alpha=1024.0, interpret=True)
    y_r = KR.fg_gemm_is_ref(xq, sa, packed, isw.int_scale,
                            group_size=g, alpha=1024.0)
    # integer path is bit-exact; final f32 epilogue is one multiply
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


@pytest.mark.parametrize("M,K,N,g", SHAPES)
def test_fs_kernel_vs_oracle(M, K, N, g):
    qw, packed, xq, sa = _mk(1, M, K, N, g)
    y_k = fg_gemm_float_scale(xq, sa, packed, qw.scale,
                              group_size=g, interpret=True)
    y_r = KR.fg_gemm_fs_ref(xq, sa, packed, qw.scale, group_size=g)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("M,K,N", [(1, 256, 128), (33, 512, 256)])
def test_coarse_fs_kernel_vs_oracle(M, K, N):
    qw, packed, xq, sa = _mk(2, M, K, N, -1)
    y_k = fg_gemm_float_scale(xq, sa, packed, qw.scale[None, :],
                              group_size=-1, interpret=True)
    y_r = KR.fg_gemm_fs_ref(xq, sa, packed, qw.scale[None, :],
                            group_size=-1)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("M,K,N,g", SHAPES[:3])
def test_w8_is_kernel_vs_oracle(M, K, N, g):
    qw, packed, xq, sa = _mk(3, M, K, N, g, w_bits=8)
    isw = isc.integerize(qw, "heuristic+6")
    y_k = fg_gemm_integer_scale(xq, sa, packed, isw.int_scale,
                                group_size=g, alpha=float(isw.alpha),
                                w_bits=8, interpret=True)
    y_r = KR.fg_gemm_is_ref(xq, sa, packed, isw.int_scale, group_size=g,
                            alpha=float(isw.alpha), w_bits=8)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


@pytest.mark.parametrize("M,K,N,g", SHAPES[:3])
def test_w4a16_kernel_vs_oracle(M, K, N, g):
    key = jax.random.PRNGKey(5)
    w = jax.random.normal(key, (K, N)) * 0.05
    x = jax.random.normal(jax.random.PRNGKey(6), (M, K)).astype(
        jnp.bfloat16)
    qw = quant.quantize_weight(w, 4, g)
    packed = packing.pack_int4(qw.qvalue)
    y_k = w4a16_gemm(x, packed, qw.scale, group_size=g, interpret=True)
    y_r = KR.w4a16_gemm_ref(x, packed, qw.scale, group_size=g)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("M,K", [(1, 128), (5, 384), (64, 1024)])
@pytest.mark.parametrize("bits", [4, 8])
def test_act_quant_kernel_vs_oracle(M, K, bits):
    x = (jax.random.normal(jax.random.PRNGKey(7), (M, K)) * 3).astype(
        jnp.bfloat16)
    q_k, s_k = act_quant(x, bits=bits, interpret=True)
    q_r, s_r = KR.act_quant_ref(x, bits=bits)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=1e-6, atol=1e-9)
    # codes may differ by 1 at exact rounding ties (fusion order)
    diff = np.abs(q_k.astype(np.int32) - q_r.astype(np.int32))
    assert diff.max() <= 1
    assert (diff > 0).mean() < 5e-3  # rare rounding ties


def test_kernel_block_shape_sweep():
    """BlockSpec tiling must not change results."""
    M, K, N, g = 40, 1024, 512, 128
    qw, packed, xq, sa = _mk(8, M, K, N, g)
    isw = isc.integerize(qw, 1024)
    ref = KR.fg_gemm_is_ref(xq, sa, packed, isw.int_scale,
                            group_size=g, alpha=1024.0)
    for bm, bn, bk in [(8, 128, 128), (16, 256, 256), (128, 512, 1024),
                       (32, 128, 512)]:
        y = fg_gemm_integer_scale(
            xq, sa, packed, isw.int_scale, group_size=g, alpha=1024.0,
            bm=bm, bn=bn, bk=bk, interpret=True)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ref),
                                      err_msg=f"blocks={(bm, bn, bk)}")


# Qwen2-72B's divisibility at reduced size, at the engine's 32 decode
# slots: N = 2688 = 21 * 128 as gate/up's 29568 = 231 * 128, and K = 2688
# as down's; the last case reads layer 1 of a stack at a traced index
CHOSEN = [(32, 2048, 2688, None), (32, 2688, 1024, None),
          (32, 2688, 1024, 1)]


@pytest.mark.parametrize("M,K,N,layer", CHOSEN)
def test_is_kernel_chosen_blocks_bit_identical(M, K, N, layer):
    """The blocks chosen by call shape give the old default launch's
    (bn=256, bk=512) output bit for bit: per-group int32 products summed
    in int32 are exact in any blocking, and the epilogue is one convert."""
    g = 128
    qw, packed, xq, sa = _mk(13, M, K, N, g)
    scale = isc.integerize(qw, 1024).int_scale
    if layer is not None:
        qw1, packed1, _, _ = _mk(14, M, K, N, g)
        packed = jnp.stack([packed, packed1])
        scale = jnp.stack([scale, isc.integerize(qw1, 1024).int_scale])
    chosen = W.dense_blocks(M, K, N, group_size=g, w_bits=4)
    old = W.dense_blocks(M, K, N, group_size=g, w_bits=4, bn=256, bk=512)
    assert chosen[1:] != old[1:] and K // chosen[2] < K // old[2]

    def run(lyr, **blocks):
        return jax.jit(lambda lyr: fg_gemm_integer_scale(
            xq, sa, packed, scale, lyr, group_size=g, alpha=1024.0,
            interpret=True, **blocks))(lyr)

    y = run(layer)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(run(layer, bn=256, bk=512)))
    if layer is not None:
        y_r = KR.fg_gemm_is_ref(xq, sa, packed1, scale[layer],
                                group_size=g, alpha=1024.0)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_r))


RULE_KN = [(8192, 29568), (29568, 8192), (8192, 8192), (8192, 1024),
           (4096, 14336), (14336, 4096), (2048, 2688), (2688, 1024),
           (512, 256), (384, 128), (1024, 96)]


@pytest.mark.parametrize("w_bits", [4, 8])
@pytest.mark.parametrize("K,N,g", [(K, N, g) for K, N in RULE_KN
                                   for g in (128, 256) if K % g == 0])
def test_dense_blocks_rule(K, N, g, w_bits):
    """Up to one m-tile of rows the blocks divide N and K, hold whole
    groups and 128-row packing units, and keep the packed block within
    the byte cap; above it, and for any explicit block, the launch is
    the one the defaults or the caller's blocks gave before."""
    old = W.snap_blocks(512, K, N, bm=128, bn=256, bk=512, group_size=g)
    for M in (1, 32, 128):
        bm, bn, bk = W.dense_blocks(M, K, N, group_size=g, w_bits=w_bits)
        assert bm == -(-M // 8) * 8
        assert N % bn == 0 and K % bk == 0 and bk % g == 0
        if N % 128 == 0:
            assert bn % 128 == 0 and bn <= W.STREAM_BN
        if K % 128 == 0:
            assert bk % 128 == 0 and bk <= W.STREAM_BK
            assert bk * bn // (2 if w_bits == 4 else 1) <= W.STREAM_BYTES
        assert (N // bn) * (K // bk) <= (N // old[1]) * (K // old[2])
    for M in (129, 512):
        assert W.dense_blocks(M, K, N, group_size=g, w_bits=w_bits) == old
    for blocks in (dict(bn=256, bk=512), dict(bn=128), dict(bk=256)):
        want = W.snap_blocks(32, K, N, bm=128, bn=blocks.get("bn", 256),
                             bk=blocks.get("bk", 512), group_size=g)
        assert W.dense_blocks(32, K, N, group_size=g, w_bits=w_bits,
                              **blocks) == want


@pytest.mark.parametrize("K,N,bn,bk", [
    (8192, 29568, 1408, 2048),   # gate, up
    (29568, 8192, 2048, 1408),   # down
    (8192, 8192, 2048, 2048),    # q, o
    (8192, 1024, 1024, 2048),    # k, v
])
def test_stream_blocks_qwen2_decode(K, N, bn, bk):
    """The blocks Qwen2-72B's decode launches at 32 slots: 292 grid steps
    a layer, where the (256, 512) defaults snapped to 11,008."""
    assert W.dense_blocks(32, K, N, group_size=128, w_bits=4) == (32, bn, bk)


@pytest.mark.parametrize("C,K,N,itemsize,bm,bn,bk", [
    (32, 4096, 14336, 2, 32, 2048, 2048),    # up (gate): 112 steps a call
    (32, 14336, 4096, 2, 32, 2048, 2048),    # down: 112 steps a call
    (512, 4096, 14336, 2, 128, 256, 512),    # prefill: the defaults
    (512, 14336, 4096, 2, 128, 256, 512),
    (128, 14336, 4096, 4, 128, 1024, 512),   # f32 slab: 9 MiB resident
], ids=["up-decode", "down-decode", "up-prefill", "down-prefill",
        "down-128-f32"])
def test_stream_blocks_mixtral_experts(C, K, N, itemsize, bm, bn, bk):
    """The blocks Mixtral's ragged expert GEMM launches: at decode's
    C = 32 the widest and deepest blocks (112 grid steps a call, where
    the (256, 512) defaults took 3584); above one m-tile the defaults;
    and where the resident (bm, K) slab and its codes leave no room for
    a 2 MiB block, a smaller one that fits the budget."""
    got = W.dense_blocks(C, K, N, group_size=128, w_bits=4,
                         slab_itemsize=itemsize)
    assert got == (bm, bn, bk)
    if C <= W.STREAM_ROWS:
        assert W.step_vmem(bm, K, bn, bk, group_size=128, w_bits=4,
                           slab_itemsize=itemsize) <= W.VMEM_BUDGET


@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("K,N", [(4096, 14336), (14336, 4096),
                                 (2048, 2688), (2688, 1024)])
def test_stream_blocks_fit_vmem(K, N, itemsize):
    """Up to one m-tile of rows every chosen step fits the VMEM budget
    with the ragged kernel's resident slab charged, and takes no more
    grid steps than the defaults."""
    for C in (8, 32, 64, 128):
        bm, bn, bk = W.dense_blocks(C, K, N, group_size=128, w_bits=4,
                                    slab_itemsize=itemsize)
        assert W.step_vmem(bm, K, bn, bk, group_size=128, w_bits=4,
                           slab_itemsize=itemsize) <= W.VMEM_BUDGET
        old = W.snap_blocks(C, K, N, bm=128, bn=256, bk=512, group_size=128)
        assert (N // bn) * (K // bk) <= (N // old[1]) * (K // old[2])


def test_linear_apply_pallas_honors_stored_alpha():
    """Regression: the Pallas branch of linear_apply used to drop the
    stored per-layer ``alpha`` (heuristic amplifiers then rescaled by the
    qspec default 1024 — outputs wrong by alpha/1024)."""
    from repro.core.qlinear import linear_apply, quantize_linear
    from repro.core.recipe import QuantSpec

    K, N, M = 512, 256, 16
    spec = QuantSpec(amplifier="heuristic+6")
    w = jax.random.normal(jax.random.PRNGKey(11), (K, N)) * 0.03
    x = jax.random.normal(jax.random.PRNGKey(12), (M, K))
    params = quantize_linear(w, spec)
    assert float(params["alpha"]) != 1024.0, \
        "test needs a non-default amplifier to catch the fallback"
    y_ref = linear_apply(params, x.astype(jnp.float32), spec,
                         mode="reference")
    y_pal = linear_apply(params, x.astype(jnp.float32), spec,
                         mode="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-2)


def test_qgemm_dispatch_matches_reference_path():
    """kernels.ops.qgemm (pallas interpret) == qlinear reference path."""
    from repro.core.qlinear import linear_apply, quantize_linear
    from repro.core.recipe import QuantSpec
    from repro.kernels.ops import BlockConfig, qgemm

    K, N, M = 512, 256, 24
    spec = QuantSpec()
    w = jax.random.normal(jax.random.PRNGKey(9), (K, N)) * 0.03
    x = jax.random.normal(jax.random.PRNGKey(10), (M, K))
    params = quantize_linear(w, spec)
    y_ref = linear_apply(params, x.astype(jnp.float32), spec,
                         mode="reference")
    y_pal = qgemm(x.astype(jnp.float32), params, spec,
                  block=BlockConfig(interpret=True))
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-2)
