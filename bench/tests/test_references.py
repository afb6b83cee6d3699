"""The plain references against the program, at small sizes on the CPU.

The program's forward (the engine's own model code, Pallas kernels in
interpret mode) and the reference (``bench/references``) share only the
seeded draws of ``bench/weights.py``; the reference quantizes again on
its own. Their logits agree to the rounding that bf16 activations add,
and the engine's greedy tokens are the reference's best or within a
hair of it.
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, program, weights

DATA = pathlib.Path(__file__).parent / "data"


def tiny(name):
    return json.loads((DATA / "configs" / f"{name}.json").read_text())


def program_logits(cfg, seed, tokens):
    mcfg = program.model_config(cfg, "pallas_interpret")
    rcp = program.recipe(cfg)
    from repro.models.registry import get_model

    api = get_model(mcfg)
    params, _ = program.build_params(api, mcfg, rcp, weights.base_key(seed))
    logits, _, _ = api.apply(params, mcfg, jnp.asarray(tokens), recipe=rcp,
                             mode="train")
    return np.asarray(logits)


@pytest.mark.parametrize("name", ["mixtral-tiny", "qwen2-tiny"])
def test_reference_logits_match_the_program(name):
    cfg = tiny(name)
    seed = 2 ** 31 + 99
    tokens = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 24)).astype(np.int32)
    got = program_logits(cfg, seed, tokens)
    rows = np.tile(np.arange(24, dtype=np.int32), (2, 1))
    ref = np.asarray(check.reference_logits(cfg, seed, tokens, rows,
                                            bits=8))
    scale = np.abs(ref).max()
    # bf16 residual stream and activations: a few bf16 ulps of the
    # largest logit; a wrong weight, scale, bias or expert is O(1)
    assert np.abs(got - ref).max() <= 3e-2 * scale
    assert np.mean(got.argmax(-1) == ref.argmax(-1)) >= 0.9


@pytest.mark.parametrize("name", ["mixtral-tiny", "qwen2-tiny"])
def test_lower_precision_reference_departs(name):
    """The control's reference (int4 activations) is far from the int8
    one: what the comparison must be able to see."""
    cfg = tiny(name)
    tokens = np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (2, 24)).astype(np.int32)
    rows = np.tile(np.arange(24, dtype=np.int32), (2, 1))
    a8 = np.asarray(check.reference_logits(cfg, 5, tokens, rows, bits=8))
    a4 = np.asarray(check.reference_logits(cfg, 5, tokens, rows, bits=4))
    assert np.abs(a8 - a4).max() > 0.1 * np.abs(a8).max()


def test_weights_ignore_nothing_of_a_large_seed():
    a = weights.matrix(weights.base_key(5), 0, "attn/q", 4, 4)
    b = weights.matrix(weights.base_key(5 + 2 ** 31), 0, "attn/q", 4, 4)
    assert not np.allclose(np.asarray(a), np.asarray(b))
    with jax.default_device(jax.devices("cpu")[0]):
        c = weights.matrix(weights.base_key(5), 0, "attn/q", 4, 4)
    assert np.array_equal(np.asarray(a), np.asarray(c))
