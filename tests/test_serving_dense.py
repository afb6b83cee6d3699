"""Engine-served dense model with QKV biases against the plain reference.

A tiny Qwen2-shaped model (GQA 8:1, biases on q, k and v, an MLP width
that is a multiple of 128 but not of 256 so the dense IS GEMM's ``bn``
snaps to 128 and the down projection's ``bk`` to 384, as at Qwen2-72B's
d_ff 29568, and a vocabulary that is not a power of two) is built from
seeded weights by the benchmark's own builder (``bench.program``) and
served by the engine in ``pallas_interpret`` mode: prefill and decode
run the engine's jitted programs, whose layer scan reads the stacked
quantized weights in place by a layer index. Its logits, at every
prefill row and at every decode step through the cache, are compared
with the plain float32 forward over the whole sequence
(``bench.check.reference_logits``), which shares only the seeded draws.
"""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))  # the benchmark package: bench/

from bench import check, program, weights  # noqa: E402
from repro import obs  # noqa: E402
from repro.models.registry import get_model  # noqa: E402
from repro.serving.engine import Engine, ServeConfig  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

CFG = {
    "name": "qwen2-dense-tiny", "reference": "qwen2",
    "hidden_size": 256, "intermediate_size": 384,
    "num_attention_heads": 8, "num_key_value_heads": 1, "head_dim": 32,
    "num_hidden_layers": 2, "vocab_size": 1000,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "attention_bias": True,
    "quantization": {"w_bits": 4, "a_bits": 8, "group_size": 128,
                     "scale_mode": "integer", "amplifier": 1024,
                     "algo": "rtn"},
    "serving": {"dtype": "bfloat16", "kv_cache_dtype": "bfloat16"},
}
SEED = 2 ** 31 + 15
PREFILL, MAX_SEQ, NEW = 16, 32, 5
# bf16 residual stream and activations in the program, float32 in the
# reference: a few bf16 ulps of the largest logit (as in
# bench/tests/test_references.py); a wrong weight, scale, bias or layer
# is O(1) of it
TOL = 3e-2


@pytest.fixture(scope="module")
def model():
    mcfg = program.model_config(CFG, "pallas_interpret")
    rcp = program.recipe(CFG)
    api = get_model(mcfg)
    params, _ = program.build_params(api, mcfg, rcp,
                                     weights.base_key(SEED))
    return api, mcfg, rcp, params


def _serve_config(max_slots=2):
    return ServeConfig(max_slots=max_slots, max_seq=MAX_SEQ,
                       prefill_len=PREFILL, max_new_tokens=NEW, eos_id=-1,
                       temperature=0.0, kernel_mode="pallas_interpret")


@pytest.fixture(scope="module")
def served(model):
    """Two requests through ``Engine.submit``/``Engine.run``; the logits
    of every prefill row and every decode step, by request."""
    api, mcfg, rcp, params = model
    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = Engine(api, mcfg, params, _serve_config(), rcp)
        prefills, ticks = [], []
        prefill = eng._prefill

        def capture_prefill(*args):
            out = prefill(*args)
            prefills.append(np.asarray(out[0][0]))
            return out

        eng._prefill = capture_prefill

        def capture_decode(fn):
            def wrapped(params, tokens, cache, pos):
                logits, cache = fn(params, tokens, cache, pos)
                ticks.append((np.asarray(pos), np.asarray(logits)))
                return logits, cache
            return wrapped

        eng.add_decode_wrapper(capture_decode)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, CFG["vocab_size"], n).tolist()
                   for n in (PREFILL, 11)]
        rids = [eng.submit(p) for p in prompts]
        outs = eng.run()
    return eng, reg, prompts, [outs[r] for r in rids], prefills, ticks


def _program_and_reference(served, bits):
    """-> (program logits, reference logits), each (n, rows, V) over the
    rows that produced a served token or a prompt token's successor."""
    _, _, prompts, outs, prefills, ticks = served
    n = len(prompts)
    length = max(len(p) + len(o) - 1 for p, o in zip(prompts, outs))
    tokens = np.zeros((n, length), np.int32)
    rows = np.zeros((n, length), np.int32)
    got = []
    for i, (p, o) in enumerate(zip(prompts, outs)):
        seq = p + o[:-1]
        tokens[i, :len(seq)] = seq
        rows[i] = np.minimum(np.arange(length), len(seq) - 1)
        # slot i was admitted i-th and served at every tick it was live
        mine = [lg[i] for pos, lg in ticks
                if len(p) <= pos[i] < len(seq)][:len(o) - 1]
        assert len(mine) == len(o) - 1 == NEW - 1
        got.append(np.concatenate([prefills[i][:len(p)], np.stack(mine)]))
    ref = np.asarray(check.reference_logits(CFG, SEED, tokens, rows,
                                            bits=bits))
    return got, [ref[i, :len(g)] for i, g in enumerate(got)]


def test_prefill_and_decode_match_the_reference(served):
    got, ref = _program_and_reference(served, bits=8)
    for g, r in zip(got, ref):
        scale = np.abs(r).max()
        assert np.abs(g - r).max() <= TOL * scale
        # greedy tokens: the reference's best or within a hair of it
        assert np.mean(g.argmax(-1) == r.argmax(-1)) >= 0.9


def test_int4_activation_reference_departs_beyond_the_tolerance(served):
    """The reference at int4 activations, the next precision below the
    configuration's int8, is further from the program than ``TOL``: the
    comparison above would fail a program computed in it."""
    got, ref4 = _program_and_reference(served, bits=4)
    assert any(np.abs(g - r).max() > TOL * np.abs(r).max()
               for g, r in zip(got, ref4))


def _indexed_and_calls(reg):
    indexed = reg.counter("qgemm_layer_indexed_total", "", ("kind",))
    calls = reg.counter("qgemm_calls_total", "",
                        ("scheme", "kind", "shape", "block"))
    n_calls = sum(v for key, v in calls.items()
                  if dict(zip(calls.labelnames, key))["kind"] == "dense")
    return indexed.get(kind="dense"), n_calls


def test_layer_indexed_scan_matches_unrolled_with_bias():
    """Every quantized linear of the scanned blocks reads the stack by
    the layer index (q, k, v, o, gate, up, down in the prefill and the
    decode trace), decode traces once, and a decode step with the bias
    present gives bit-identical logits and cache to the unrolled path,
    which slices each layer's params. In float32 activations: in bf16,
    XLA drops the rounding of the residual stream between unrolled
    layers (excess precision; the scan's carry keeps it), so the two
    paths differ by bf16 rounding in the reference kernel mode too."""
    cfg = {**CFG, "serving": {"dtype": "float32",
                              "kv_cache_dtype": "float32"}}
    mcfg = program.model_config(cfg, "pallas_interpret")
    rcp = program.recipe(cfg)
    api = get_model(mcfg)
    params, _ = program.build_params(api, mcfg, rcp,
                                     weights.base_key(SEED))
    assert "b" in params["blocks"]["s0"]["attn"]["q"]
    rng = np.random.default_rng(9)
    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = Engine(api, mcfg, params, _serve_config(), rcp)
        for n in (PREFILL, 9):
            eng.submit(rng.integers(0, CFG["vocab_size"], n).tolist())
        eng.run()
    assert eng.decode_traces == 1 and eng.prefill_traces == 1
    indexed, calls = _indexed_and_calls(reg)
    assert indexed == calls == 2 * 7, (indexed, calls)

    unrolled = Engine(api, dataclasses.replace(mcfg, scan_layers=False),
                      params, _serve_config(), rcp)
    toks = jnp.asarray(rng.integers(0, CFG["vocab_size"], (2, 1)),
                       jnp.int32)
    pos = jnp.asarray([PREFILL + 3, 12], jnp.int32)
    outs = [e._decode_base.__wrapped__.__wrapped__(params, toks, eng.cache,
                                                    pos)
            for e in (eng, unrolled)]
    assert eng.decode_traces == 1
    for a, b in zip(*(jax.tree.leaves(o) for o in outs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
