"""Layer-stacked operands: every quantized GEMM reads a scanned model's
stacked weights in place at a traced layer index.

The invariant: for each kernel behind ``ops.qgemm`` (dense IS, FS,
W4A16) and ``ops.qgemm_grouped`` (the ragged IS, FS, W4A16 kernels), the
output with ``(stacked params, layer)`` is BIT-IDENTICAL to the output
with the params sliced at ``layer`` — including per-layer (and, grouped,
per-expert) amplifiers, an empty expert and a row count that is not a
multiple of the m-block.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import qlinear
from repro.core.recipe import W4A8_FS, W4A8_IS, W4A16_FG
from repro.kernels import ops

jax.config.update("jax_platform_name", "cpu")

R, K, N, E, C = 3, 256, 256, 3, 24
COUNTS = [0, 13, 24]  # empty expert; 13 is not a multiple of bm=8
BLOCK = ops.BlockConfig(bm=8, bn=128, bk=128, interpret=True)
SCHEMES = {
    # heuristic amplifiers: alphas differ per layer and per expert
    "is": dataclasses.replace(W4A8_IS, amplifier="heuristic"),
    "fs": W4A8_FS,
    "w4a16": W4A16_FG,
}


def _stack(trees):
    return jax.tree.map(lambda *a: jnp.stack(a), *trees)


def _linear(key, qspec, scale):
    w = jax.random.normal(key, (K, N)) * scale
    return qlinear.quantize_linear(w, qspec)


def _stacked_params(qspec, grouped: bool):
    """(R, [E,] ...) params; weight magnitudes vary by layer and expert so
    the heuristic amplifiers do too."""
    keys = jax.random.split(jax.random.PRNGKey(7), R * E)
    layers = []
    for r in range(R):
        if grouped:
            layers.append(_stack([
                _linear(keys[r * E + e], qspec, 0.02 * 4.0 ** ((r + e) % 3))
                for e in range(E)]))
        else:
            layers.append(_linear(keys[r], qspec, 0.02 * 4.0 ** r))
    return _stack(layers)


@pytest.mark.parametrize("layer", [0, R - 1])
@pytest.mark.parametrize("grouped", [False, True], ids=["dense", "ragged"])
@pytest.mark.parametrize("scheme", sorted(SCHEMES))
def test_stacked_layer_bit_identical_to_sliced(scheme, grouped, layer):
    qspec = SCHEMES[scheme]
    stacked = _stacked_params(qspec, grouped)
    if "alpha" in stacked:
        assert len(np.unique(np.asarray(stacked["alpha"]))) > 1
    sliced = jax.tree.map(lambda a: a[layer], stacked)
    if grouped:
        mask = jnp.arange(C)[None, :, None] < jnp.asarray(COUNTS)[:, None,
                                                                  None]
        x = jnp.where(mask, jax.random.normal(jax.random.PRNGKey(1),
                                              (E, C, K)), 0.0)
        rc = jnp.asarray(COUNTS, jnp.int32)

        def call(p, lyr):
            return ops.qgemm_grouped(x, p, qspec, row_counts=rc, layer=lyr,
                                     block=BLOCK)
    else:
        x = jax.random.normal(jax.random.PRNGKey(1), (5, K))

        def call(p, lyr):
            return ops.qgemm(x, p, qspec, layer=lyr, block=BLOCK)

    reg = obs.Registry()
    with obs.use_registry(reg):
        got = jax.jit(call)(stacked, jnp.int32(layer))
        want = jax.jit(lambda p: call(p, None))(sliced)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.any(np.asarray(want) != 0)
    kind = "grouped" if grouped else "dense"
    indexed = reg.counter("qgemm_layer_indexed_total", "", ("kind",))
    assert indexed.get(kind=kind) == 1  # the stacked call only
