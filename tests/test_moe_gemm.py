"""Grouped (batched-expert) Pallas kernel validation: the ragged kernels
with ``row_counts=None`` (every capacity slot routed) against the
per-expert oracles of ``kernels/ref.py``, zero-filled capacity slots, the
qlinear/MoE entry points against the reference route, and the int32
overflow audit on the grouped accumulator."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import integer_scale as isc
from repro.core import packing, qlinear, quant
from repro.core.recipe import QuantSpec
from repro.kernels import ref as KR
from repro.kernels.act_quant import act_quant
from repro.kernels.moe_gemm import (fg_grouped_gemm_float_scale_ragged,
                                    fg_grouped_gemm_integer_scale_ragged,
                                    grouped_w4a16_gemm_ragged)

jax.config.update("jax_platform_name", "cpu")

SHAPES = [  # (E, C, K, N, g)
    (2, 8, 256, 128, 128),    # minimum-capacity decode-like
    (4, 24, 256, 256, 128),   # phi-3.5-MoE smoke expert dims (d=f=256)
    (3, 16, 512, 384, 128),   # ragged N
    (2, 16, 512, 256, 256),   # larger group
]


def _mk_experts(seed, E, K, N, g, w_bits=4, amplifier=1024):
    """Per-expert quantized weights + stacked kernel operands."""
    keys = jax.random.split(jax.random.PRNGKey(seed), E)
    packed, iscale, fscale, alphas, isws = [], [], [], [], []
    for e in range(E):
        # per-expert magnitude spread so heuristic amplifiers differ
        w = jax.random.normal(keys[e], (K, N)) * 0.05 * (4.0 ** (e % 3))
        qw = quant.quantize_weight(w, w_bits, g)
        isw = isc.integerize(qw, amplifier)
        isws.append(isw)
        packed.append(packing.pack_int4(qw.qvalue) if w_bits == 4
                      else qw.qvalue)
        iscale.append(isw.int_scale)
        fscale.append(qw.scale)
        alphas.append(float(isw.alpha))
    return (jnp.stack(packed), jnp.stack(iscale), jnp.stack(fscale),
            alphas, isws)


def _mk_acts(seed, E, C, K):
    """Raw activations and their int8 codes and per-token scales, from the
    act_quant kernel whose block body the ragged kernels fuse."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (E, C, K))
    xq, sa = act_quant(x.reshape(E * C, K), interpret=True)
    return x, xq.reshape(E, C, K), sa.reshape(E, C, 1)


@pytest.mark.parametrize("E,C,K,N,g", SHAPES)
def test_grouped_is_kernel_bit_exact_vs_vmapped_ref(E, C, K, N, g):
    qv, iscale, _, alphas, _ = _mk_experts(0, E, K, N, g)
    x, xq, sa = _mk_acts(1, E, C, K)
    y_k = fg_grouped_gemm_integer_scale_ragged(
        x, None, qv, iscale, group_size=g, alpha=1024.0, interpret=True)
    y_r = jnp.stack([
        KR.fg_gemm_is_ref(xq[e], sa[e], qv[e], iscale[e],
                          group_size=g, alpha=1024.0) for e in range(E)])
    # integer path is bit-exact; epilogue is one f32 multiply per element
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


def test_grouped_is_kernel_per_expert_alpha():
    """Heuristic amplifiers give each expert its OWN alpha; the grouped
    kernel divides sa by alpha_e in its epilogue and must stay bit-exact
    per expert."""
    E, C, K, N, g = 4, 16, 256, 256, 128
    qv, iscale, _, alphas, _ = _mk_experts(2, E, K, N, g,
                                           amplifier="heuristic+6")
    assert len(set(alphas)) > 1, "want distinct per-expert amplifiers"
    x, xq, sa = _mk_acts(3, E, C, K)
    y_k = fg_grouped_gemm_integer_scale_ragged(
        x, None, qv, iscale, group_size=g,
        alpha=jnp.asarray(alphas, jnp.float32), interpret=True)
    y_r = jnp.stack([
        KR.fg_gemm_is_ref(xq[e], sa[e], qv[e], iscale[e],
                          group_size=g, alpha=alphas[e]) for e in range(E)])
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


@pytest.mark.parametrize("E,C,K,N,g", SHAPES[:2])
def test_grouped_fs_kernel_vs_vmapped_ref(E, C, K, N, g):
    qv, _, fscale, _, _ = _mk_experts(4, E, K, N, g)
    x, xq, sa = _mk_acts(5, E, C, K)
    y_k = fg_grouped_gemm_float_scale_ragged(
        x, None, qv, fscale, group_size=g, interpret=True)
    y_r = jnp.stack([
        KR.fg_gemm_fs_ref(xq[e], sa[e], qv[e], fscale[e], group_size=g)
        for e in range(E)])
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-5, atol=1e-4)


def test_grouped_w8_is_kernel_vs_vmapped_ref():
    E, C, K, N, g = 2, 16, 256, 128, 128
    qv, iscale, _, alphas, _ = _mk_experts(6, E, K, N, g, w_bits=8,
                                           amplifier="heuristic+6")
    x, xq, sa = _mk_acts(7, E, C, K)
    y_k = fg_grouped_gemm_integer_scale_ragged(
        x, None, qv, iscale, group_size=g, w_bits=8,
        alpha=jnp.asarray(alphas, jnp.float32), interpret=True)
    y_r = jnp.stack([
        KR.fg_gemm_is_ref(xq[e], sa[e], qv[e], iscale[e], group_size=g,
                          alpha=alphas[e], w_bits=8) for e in range(E)])
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


def test_grouped_w4a16_kernel_vs_vmapped_ref():
    E, C, K, N, g = 3, 16, 256, 256, 128
    qv, _, fscale, _, _ = _mk_experts(8, E, K, N, g)
    x = jax.random.normal(jax.random.PRNGKey(9), (E, C, K)).astype(
        jnp.bfloat16)
    y_k = grouped_w4a16_gemm_ragged(x, None, qv, fscale, group_size=g,
                                    interpret=True)
    y_r = jnp.stack([
        KR.w4a16_gemm_ref(x[e], qv[e], fscale[e], group_size=g)
        for e in range(E)])
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-2, atol=2e-2)


def test_grouped_is_kernel_ragged_capacity_padding():
    """Dispatch buffers zero-fill capacity slots past each expert's routed
    token count; with every slot taken as routed, the padded rows must
    produce exactly-zero outputs and the valid rows must equal the
    per-expert reference of the unpadded activations."""
    E, C, K, N, g = 3, 24, 256, 128, 128
    qv, iscale, _, _, _ = _mk_experts(10, E, K, N, g)
    x, xq, sa = _mk_acts(11, E, C, K)
    counts = [5, 24, 0]  # ragged per-expert occupancy, incl. empty expert
    rows = jnp.arange(C)[None, :, None]
    mask = rows < jnp.asarray(counts)[:, None, None]
    y = fg_grouped_gemm_integer_scale_ragged(
        jnp.where(mask, x, 0.0), None, qv, iscale, group_size=g,
        alpha=1024.0, interpret=True)
    for e, c in enumerate(counts):
        y_r = KR.fg_gemm_is_ref(xq[e], sa[e], qv[e], iscale[e],
                                group_size=g, alpha=1024.0)
        np.testing.assert_array_equal(np.asarray(y[e, :c]),
                                      np.asarray(y_r[:c]))
        np.testing.assert_array_equal(np.asarray(y[e, c:]),
                                      np.zeros((C - c, N), np.float32))


def test_grouped_accumulator_overflow_audit():
    """The grouped kernel shares the dense kernel's int32 accumulator; per
    expert, the static worst-case bound and the empirical max |accumulator|
    for a batch of dispatch activations must clear 2^31 with the default
    amplifier at MoE expert shapes."""
    E, C, K, N, g = 4, 16, 256, 128, 128
    _, iscale, _, _, isws = _mk_experts(14, E, K, N, g)
    _, xq, _ = _mk_acts(15, E, C, K)
    for e, isw in enumerate(isws):
        assert not isc.would_overflow(isw), (
            f"expert {e}: static bound {isc.overflow_bound(isw):,} >= 2^31")
        emp = isc.empirical_max_accum(xq[e], isw)
        assert emp < 2 ** 31
        assert emp <= isc.overflow_bound(isw)


def test_grouped_linear_apply_pallas_matches_reference():
    """qlinear.grouped_linear_apply: one fused grouped kernel == vmapped
    reference GEMM on identical pre-quantized operands (per-expert alpha
    and stacked bias included)."""
    E, C, K, N, g = 4, 16, 256, 256, 128
    qv, iscale, _, alphas, _ = _mk_experts(16, E, K, N, g,
                                           amplifier="heuristic+6")
    params = {
        "qvalue": qv,
        "scale": iscale,
        "alpha": jnp.asarray(alphas, jnp.float32),
        "b": jax.random.normal(jax.random.PRNGKey(17), (E, N)) * 0.1,
    }
    spec = QuantSpec(amplifier="heuristic+6")
    x = jax.random.normal(jax.random.PRNGKey(18), (E, C, K))
    y_pal = qlinear.grouped_linear_apply(params, x, spec,
                                         mode="pallas_interpret")
    y_ref = qlinear.grouped_linear_apply(params, x, spec, mode="reference")
    # both branches quantize activations identically up to act_quant
    # rounding ties (see test_kernels.test_act_quant_kernel_vs_oracle)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-2)


def test_expert_linear_apply_routes_to_grouped_kernel():
    """models.moe.expert_linear_apply under pallas_interpret must equal the
    reference route (same stacked params, same dispatch buffer)."""
    from repro.models.moe import expert_linear_apply

    E, C, K, N, g = 4, 16, 256, 256, 128
    qv, iscale, _, _, _ = _mk_experts(19, E, K, N, g)
    params = {"qvalue": qv, "scale": iscale,
              "alpha": jnp.full((E,), 1024.0, jnp.float32)}
    spec = QuantSpec()
    x = jax.random.normal(jax.random.PRNGKey(20), (E, C, K)).astype(
        jnp.bfloat16)
    with qlinear.kernel_mode("pallas_interpret"):
        y_pal = expert_linear_apply(params, x, spec)
    y_ref = expert_linear_apply(params, x, spec)
    np.testing.assert_allclose(
        np.asarray(y_pal, dtype=np.float32), np.asarray(y_ref, np.float32),
        rtol=2e-2, atol=2e-2)
