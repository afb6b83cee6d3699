"""Ragged scalar-prefetch grouped kernel validation.

The invariant: for any dispatch buffer whose rows at or past each expert's
``row_counts[e]`` are zero-filled, the ragged kernel (scalar-prefetch
m-tile skipping + fused act-quant) must equal the per-expert oracle of
``kernels/ref.py`` fed the act_quant kernel's codes on every row below
``row_counts[e]`` (bit for bit on the integer-scale path), and write exact
zeros past it — for every variant (integer-scale, float-scale incl.
coarse, W4A16), including per-expert heuristic alphas, for the edge cases
that exercise the grid clamping: an expert with 0 routed tokens, counts
that are not a multiple of the m-block, and all experts exactly at
capacity.
"""
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
                                    grouped_w4a16_gemm_ragged,
                                    ragged_tile_stats)

jax.config.update("jax_platform_name", "cpu")


def _mk_experts(seed, E, K, N, g, w_bits=4, amplifier="heuristic+6"):
    keys = jax.random.split(jax.random.PRNGKey(seed), E)
    packed, iscale, fscale, alphas = [], [], [], []
    for e in range(E):
        # magnitude spread so heuristic amplifiers differ across experts
        w = jax.random.normal(keys[e], (K, N)) * 0.05 * (4.0 ** (e % 3))
        qw = quant.quantize_weight(w, w_bits, g)
        isw = isc.integerize(qw, amplifier)
        packed.append(packing.pack_int4(qw.qvalue) if w_bits == 4
                      else qw.qvalue)
        iscale.append(isw.int_scale)
        fscale.append(qw.scale)
        alphas.append(float(isw.alpha))
    return (jnp.stack(packed), jnp.stack(iscale), jnp.stack(fscale), alphas)


def _ragged_acts(seed, E, C, K, counts):
    """Raw dispatch-style buffer: rows at or past counts[e] zero-filled."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (E, C, K))
    mask = jnp.arange(C)[None, :, None] < jnp.asarray(counts)[:, None, None]
    return jnp.where(mask, x, 0.0)


def _dense_quant(x):
    """One act_quant kernel over (E*C, K): the codes the fused in-kernel
    quantization must reproduce."""
    E, C, K = x.shape
    xq, sa = act_quant(x.reshape(E * C, K), interpret=True)
    return xq.reshape(E, C, K), sa.reshape(E, C, 1)


def _assert_rows(y, y_ref, counts, **tol):
    """Rows below ``counts[e]`` match the per-expert reference (exactly,
    unless a tolerance is given); rows past it are exact zeros."""
    y, y_ref = np.asarray(y), np.asarray(y_ref)
    for e, c in enumerate(counts):
        if tol:
            np.testing.assert_allclose(y[e, :c], y_ref[e, :c], **tol,
                                       err_msg=f"expert {e}")
        else:
            np.testing.assert_array_equal(y[e, :c], y_ref[e, :c],
                                          err_msg=f"expert {e}")
        np.testing.assert_array_equal(y[e, c:], 0.0, err_msg=f"expert {e}")


def _is_ref(xq, sa, qv, iscale, g, alphas, w_bits=4):
    return jnp.stack([
        KR.fg_gemm_is_ref(xq[e], sa[e], qv[e], iscale[e], group_size=g,
                          alpha=alphas[e], w_bits=w_bits)
        for e in range(xq.shape[0])])


# counts exercising: empty expert, non-multiple-of-bm, at-capacity
COUNT_CASES = [
    ([0, 24, 24], "empty expert"),
    ([5, 13, 21], "counts not a multiple of the m-block"),
    ([24, 24, 24], "all experts at capacity"),
]


@pytest.mark.parametrize("counts,label", COUNT_CASES)
def test_ragged_is_bit_exact_vs_reference(counts, label):
    E, C, K, N, g = 3, 24, 256, 128, 128
    qv, iscale, _, alphas = _mk_experts(0, E, K, N, g)
    assert len(set(alphas)) > 1, "want distinct per-expert amplifiers"
    al = jnp.asarray(alphas, jnp.float32)
    x = _ragged_acts(1, E, C, K, counts)
    xq, sa = _dense_quant(x)
    y_rag = fg_grouped_gemm_integer_scale_ragged(
        x, jnp.asarray(counts, jnp.int32), qv, iscale, group_size=g,
        alpha=al, interpret=True)
    _assert_rows(y_rag, _is_ref(xq, sa, qv, iscale, g, alphas), counts)


@pytest.mark.parametrize("counts,label", COUNT_CASES[:2])
def test_ragged_fs_vs_reference(counts, label):
    E, C, K, N, g = 3, 24, 256, 128, 128
    qv, _, fscale, _ = _mk_experts(2, E, K, N, g)
    x = _ragged_acts(3, E, C, K, counts)
    xq, sa = _dense_quant(x)
    y_ref = jnp.stack([
        KR.fg_gemm_fs_ref(xq[e], sa[e], qv[e], fscale[e], group_size=g)
        for e in range(E)])
    y_rag = fg_grouped_gemm_float_scale_ragged(
        x, jnp.asarray(counts, jnp.int32), qv, fscale, group_size=g,
        interpret=True)
    _assert_rows(y_rag, y_ref, counts, rtol=1e-5, atol=1e-4)


def test_ragged_fs_coarse_vs_reference():
    """Coarse per-channel scales (group_size=-1) take a distinct branch
    (one scale row reused for every k-block) — same ragged invariant."""
    E, C, K, N = 3, 24, 256, 128
    packs, scales = [], []
    for e in range(E):
        w = jax.random.normal(jax.random.PRNGKey(40 + e), (K, N)) * 0.05
        qw = quant.quantize_weight(w, 4, -1)
        packs.append(packing.pack_int4(qw.qvalue))
        scales.append(qw.scale[None, :])  # (1, N) coarse
    qv, cscale = jnp.stack(packs), jnp.stack(scales)
    counts = [0, 11, 24]
    x = _ragged_acts(41, E, C, K, counts)
    xq, sa = _dense_quant(x)
    y_ref = jnp.stack([
        KR.fg_gemm_fs_ref(xq[e], sa[e], qv[e], cscale[e], group_size=-1)
        for e in range(E)])
    y_rag = fg_grouped_gemm_float_scale_ragged(
        x, jnp.asarray(counts, jnp.int32), qv, cscale, group_size=-1,
        interpret=True)
    _assert_rows(y_rag, y_ref, counts, rtol=1e-5, atol=1e-4)


def test_ragged_w4a16_vs_reference():
    E, C, K, N, g = 3, 24, 256, 256, 128
    qv, _, fscale, _ = _mk_experts(4, E, K, N, g)
    counts = [0, 7, 24]
    x = _ragged_acts(5, E, C, K, counts).astype(jnp.bfloat16)
    y_ref = jnp.stack([
        KR.w4a16_gemm_ref(x[e], qv[e], fscale[e], group_size=g)
        for e in range(E)])
    y_rag = grouped_w4a16_gemm_ragged(
        x, jnp.asarray(counts, jnp.int32), qv, fscale, group_size=g,
        interpret=True)
    _assert_rows(y_rag, y_ref, counts, rtol=2e-2, atol=2e-2)


def test_ragged_outputs_zero_past_counts():
    """Skipped m-tiles must write exact zeros, not stale garbage."""
    E, C, K, N, g = 2, 32, 256, 128, 128
    qv, iscale, _, _ = _mk_experts(6, E, K, N, g, amplifier=1024)
    counts = [9, 0]
    x = _ragged_acts(7, E, C, K, counts)
    y = fg_grouped_gemm_integer_scale_ragged(
        x, jnp.asarray(counts, jnp.int32), qv, iscale, group_size=g,
        alpha=1024.0, interpret=True)
    for e, c in enumerate(counts):
        np.testing.assert_array_equal(
            np.asarray(y[e, c:]), np.zeros((C - c, N), np.float32))


@pytest.mark.parametrize("counts", [[3, 17], None],
                         ids=["ragged", "row_counts_none"])
def test_ragged_block_shape_sweep(counts):
    """BlockSpec tiling (incl. capacity padding to bm) and m-tile skipping
    must not change results: every block shape equals the reference."""
    E, C, K, N, g = 2, 20, 512, 256, 128
    qv, iscale, _, alphas = _mk_experts(8, E, K, N, g)
    al = jnp.asarray(alphas, jnp.float32)
    rows = [C] * E if counts is None else counts
    x = _ragged_acts(9, E, C, K, rows)
    xq, sa = _dense_quant(x)
    y_ref = _is_ref(xq, sa, qv, iscale, g, alphas)
    rc = None if counts is None else jnp.asarray(counts, jnp.int32)
    for bm, bn, bk in [(8, 128, 128), (16, 256, 256), (128, 128, 512)]:
        y = fg_grouped_gemm_integer_scale_ragged(
            x, rc, qv, iscale, group_size=g, alpha=al,
            bm=bm, bn=bn, bk=bk, interpret=True)
        _assert_rows(y, y_ref, rows)


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one_layer", "layer_stacked"])
def test_ragged_is_stream_blocks_bit_exact(stacked):
    """At one m-tile of capacity the kernel takes its blocks by shape: here
    the whole N and a 2048-deep K block (16 groups a step, two k-steps),
    and its output still equals the per-expert oracle bit for bit, with a
    zero-routed expert, and reading layer 1 of a stack at a traced index."""
    from repro.kernels import w4a8_gemm as W

    E, C, K, N, g = 3, 8, 4096, 256, 128
    assert W.dense_blocks(C, K, N, group_size=g, w_bits=4,
                          slab_itemsize=4) == (8, 256, 2048)
    qv, iscale, _, alphas = _mk_experts(20, E, K, N, g)
    al = jnp.asarray(alphas, jnp.float32)
    counts = [0, 5, C]
    x = _ragged_acts(21, E, C, K, counts)
    xq, sa = _dense_quant(x)
    y_ref = _is_ref(xq, sa, qv, iscale, g, alphas)
    layer = None
    if stacked:
        qv0, is0, _, _ = _mk_experts(22, E, K, N, g)
        qv, iscale = jnp.stack([qv0, qv]), jnp.stack([is0, iscale])
        layer = jnp.int32(1)
    y = jax.jit(lambda lyr: fg_grouped_gemm_integer_scale_ragged(
        x, jnp.asarray(counts, jnp.int32), qv, iscale, lyr, group_size=g,
        alpha=al, interpret=True))(layer)
    _assert_rows(y, y_ref, counts)


def test_ragged_row_counts_none_vs_reference():
    """row_counts=None treats every capacity slot as routed (fused quant
    only — must still equal the per-expert reference on every row)."""
    E, C, K, N, g = 2, 16, 256, 128, 128
    qv, iscale, _, _ = _mk_experts(10, E, K, N, g, amplifier=1024)
    x = jax.random.normal(jax.random.PRNGKey(11), (E, C, K))
    xq, sa = _dense_quant(x)
    y_rag = fg_grouped_gemm_integer_scale_ragged(
        x, None, qv, iscale, group_size=g, alpha=1024.0, interpret=True)
    _assert_rows(y_rag, _is_ref(xq, sa, qv, iscale, g, [1024.0] * E),
                 [C] * E)


def test_ragged_counts_clamped_to_capacity():
    """bincount counts can exceed capacity (dropped tokens) — the wrapper
    must clamp instead of indexing out of range."""
    E, C, K, N, g = 2, 16, 256, 128, 128
    qv, iscale, _, _ = _mk_experts(12, E, K, N, g, amplifier=1024)
    x = jax.random.normal(jax.random.PRNGKey(13), (E, C, K))
    y_over = fg_grouped_gemm_integer_scale_ragged(
        x, jnp.asarray([100, 16], jnp.int32), qv, iscale, group_size=g,
        alpha=1024.0, interpret=True)
    y_full = fg_grouped_gemm_integer_scale_ragged(
        x, jnp.asarray([16, 16], jnp.int32), qv, iscale, group_size=g,
        alpha=1024.0, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_over), np.asarray(y_full))


def test_qgemm_grouped_row_counts_matches_reference():
    """ops.qgemm_grouped (fused ragged path) == vmapped reference on a
    ragged dispatch buffer, through the qlinear entry point."""
    E, C, K, N, g = 4, 16, 256, 256, 128
    qv, iscale, _, alphas = _mk_experts(14, E, K, N, g)
    params = {"qvalue": qv, "scale": iscale,
              "alpha": jnp.asarray(alphas, jnp.float32)}
    spec = QuantSpec(amplifier="heuristic+6")
    counts = jnp.asarray([0, 5, 16, 11], jnp.int32)
    x = _ragged_acts(15, E, C, K, [0, 5, 16, 11])
    y_pal = qlinear.grouped_linear_apply(params, x, spec,
                                         row_counts=counts,
                                         mode="pallas_interpret")
    y_ref = qlinear.grouped_linear_apply(params, x, spec, mode="reference")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-2)


def test_ragged_tile_stats_accounting():
    stats = ragged_tile_stats([0, 5, 128, 200], C=128, bm=128)
    assert stats == {"bm": 128, "dense_m_tiles": 4, "ragged_m_tiles": 3}
    stats = ragged_tile_stats([0, 5, 9], C=24, bm=8)
    assert stats["dense_m_tiles"] == 9
    assert stats["ragged_m_tiles"] == 0 + 1 + 2
