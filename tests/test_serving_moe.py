"""Engine-driven quantized MoE: parity, ragged decode, no-retrace."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import ptq
from repro.core.recipe import DEFAULT_RECIPE
from repro.models import moe
from repro.models.registry import get_arch, get_model
from repro.nn import spec as S
from repro.serving.engine import Engine, ServeConfig

jax.config.update("jax_platform_name", "cpu")

MAX_NEW = 3


@pytest.fixture(scope="module")
def moe_quantized():
    """CPU-sized Mixtral shape (8 experts top-2), W4A8-IS everywhere.

    capacity_factor = E/top_k in the smoke config means per-expert capacity
    always covers every routed token, so capacity drops can never occur and
    engine decode is comparable against a full-forward oracle.
    """
    cfg = get_arch("mixtral-8x7b", smoke=True)
    api = get_model(cfg)
    params = S.materialize(api.param_specs(cfg, None), jax.random.PRNGKey(0))
    qp = ptq.post_training_quantize(api, cfg, params, DEFAULT_RECIPE, None)
    return api, cfg, qp


def _reference_generate(api, cfg, params, prompt, n_new):
    """Greedy generation via full re-forward (no cache) — the oracle."""
    toks = list(prompt)
    for _ in range(n_new):
        logits, _, _ = api.apply(params, cfg,
                                 jnp.asarray([toks], jnp.int32),
                                 recipe=DEFAULT_RECIPE, mode="train")
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_engine_moe_parity_and_zero_routed_expert(moe_quantized):
    """Engine tokens under the quantized-MoE pallas_interpret path match
    direct full-forward decoding, and the decode ticks include experts
    with zero routed rows (the ragged kernel's m-tile-skip case)."""
    api, cfg, qp = moe_quantized
    sc = ServeConfig(max_slots=2, max_seq=32, prefill_len=8,
                     max_new_tokens=MAX_NEW, kernel_mode="pallas_interpret")
    trace = moe.start_routing_trace()
    try:
        eng = Engine(api, cfg, qp, sc, recipe=DEFAULT_RECIPE)
        rng = np.random.default_rng(3)
        # ONE request: at most top_k=2 of 8 experts get rows per tick, so
        # every decode tick has zero-routed experts by construction
        prompt = rng.integers(0, cfg.vocab_size, size=8).tolist()
        rid = eng.submit(prompt)
        outs = eng.run()
    finally:
        moe.stop_routing_trace()

    # decode records follow the single prefill's (one per MoE layer)
    n_layers = cfg.num_layers
    decode_records = trace[n_layers:]
    assert len(decode_records) == (MAX_NEW - 1) * n_layers
    assert any(int(c) == 0 for r in decode_records
               for c in r["counts"][0]), \
        "expected a decode tick where an expert receives zero routed rows"

    pallas_cfg = eng.cfg  # cfg + kernel_mode from ServeConfig
    assert pallas_cfg.kernel_mode == "pallas_interpret"
    ref = _reference_generate(api, pallas_cfg, qp, prompt, MAX_NEW)
    assert outs[rid] == ref, (outs[rid], ref)


def test_engine_moe_decode_row_counts_do_not_retrace(moe_quantized):
    """Per-tick row_counts are traced operands: many decode ticks with
    changing routed dispatch must reuse ONE decode trace."""
    api, cfg, qp = moe_quantized
    sc = ServeConfig(max_slots=4, max_seq=32, prefill_len=8,
                     max_new_tokens=MAX_NEW, kernel_mode="pallas_interpret")
    eng = Engine(api, cfg, qp, sc, recipe=DEFAULT_RECIPE)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=8).tolist()
               for _ in range(4)]
    rids = [eng.submit(p) for p in prompts]
    outs = eng.run()
    assert set(outs) == set(rids)
    assert eng.ticks >= MAX_NEW - 1
    assert eng.prefill_traces == 1
    assert eng.decode_traces == 1, \
        f"decode retraced {eng.decode_traces}x — row_counts became static"


def test_engine_moe_reference_route_matches_interpret(moe_quantized):
    """Same engine, reference kernel mode: identical token streams (the
    serving benchmark's bit-exact claim, minimally)."""
    api, cfg, qp = moe_quantized
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=8).tolist()
               for _ in range(2)]
    outs = {}
    for mode in ("reference", "pallas_interpret"):
        sc = ServeConfig(max_slots=2, max_seq=32, prefill_len=8,
                         max_new_tokens=MAX_NEW, kernel_mode=mode)
        eng = Engine(api, cfg, qp, sc, recipe=DEFAULT_RECIPE)
        rids = [eng.submit(p) for p in prompts]
        got = eng.run()
        outs[mode] = [got[r] for r in rids]
    assert outs["reference"] == outs["pallas_interpret"]


def _lowered_steps(eng, cfg):
    """The lowered text of the engine's jitted prefill and decode."""
    sc = eng.sc
    spec1 = eng._cache1_specs
    cache1 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec1,
                          is_leaf=S.is_spec)
    prefill = eng._prefill.__wrapped__.lower(
        eng.params, jnp.zeros((1, sc.prefill_len), jnp.int32), cache1)
    decode = eng._decode_base.__wrapped__.__wrapped__.lower(
        eng.params, jnp.zeros((sc.max_slots, 1), jnp.int32), eng.cache,
        jnp.zeros((sc.max_slots,), jnp.int32))
    return prefill.as_text(), decode.as_text()


def test_engine_steps_compile_no_host_callback(moe_quantized):
    """The routed counts leave the step as outputs: whatever routing sinks
    exist, the compiled prefill and decode carry no host callback."""
    api, cfg, qp = moe_quantized
    sc = ServeConfig(max_slots=2, max_seq=32, prefill_len=8,
                     max_new_tokens=MAX_NEW, kernel_mode="pallas_interpret")
    trace = moe.start_routing_trace()
    try:
        eng = Engine(api, cfg, qp, sc, recipe=DEFAULT_RECIPE)
        texts = _lowered_steps(eng, cfg)
    finally:
        moe.stop_routing_trace(trace)
    for text in texts:
        assert "callback" not in text.lower()
    # ...and the counts are among the outputs: (layers, groups, experts)
    for text in texts:
        assert f"tensor<{cfg.num_layers}x1x{cfg.num_experts}xi32>" in text


def test_late_sink_receives_each_steps_routed_counts(moe_quantized):
    """A sink registered after the engine's first step receives one record
    per MoE layer per step — prefill records before the tick's decode
    records — carrying the counts the model's dispatch computes."""
    api, cfg, qp = moe_quantized
    sc = ServeConfig(max_slots=2, max_seq=32, prefill_len=8,
                     max_new_tokens=MAX_NEW, kernel_mode="pallas_interpret")
    eng = Engine(api, cfg, qp, sc, recipe=DEFAULT_RECIPE)
    rng = np.random.default_rng(6)
    eng.submit(rng.integers(0, cfg.vocab_size, size=8).tolist())
    eng.run()  # compiles both steps: no sink is registered yet
    trace = moe.start_routing_trace()
    try:
        prompt = rng.integers(0, cfg.vocab_size, size=8).tolist()
        eng.submit(prompt)
        eng.run()
    finally:
        moe.stop_routing_trace(trace)
    L, E = cfg.num_layers, cfg.num_experts
    assert len(trace) == MAX_NEW * L  # one prefill, MAX_NEW - 1 ticks
    C_prefill = moe.capacity(sc.prefill_len, cfg.top_k, E,
                             cfg.capacity_factor)
    C_decode = moe.capacity(sc.max_slots, cfg.top_k, E, cfg.capacity_factor)
    assert [r["capacity"] for r in trace] == \
        [C_prefill] * L + [C_decode] * (MAX_NEW - 1) * L
    for r in trace:
        assert r["counts"].shape == (1, E) and r["counts"].dtype == np.int32
    # the prefill's counts are the dispatch's own (eager forward)
    cache1 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          eng._cache1_specs, is_leaf=S.is_spec)
    *_, routed = api.apply(qp, eng.cfg, jnp.asarray([prompt], jnp.int32),
                           recipe=DEFAULT_RECIPE, mode="train",
                           cache=cache1, pos=0, routing=True)
    np.testing.assert_array_equal(
        np.stack([r["counts"] for r in trace[:L]]), np.asarray(routed))
    # every slot is computed at a tick: B * top_k routed rows per layer
    # (capacity E/top_k per token drops none)
    for r in trace[L:]:
        assert int(r["counts"].sum()) == sc.max_slots * cfg.top_k


def _indexed_and_calls(reg, kind):
    indexed = reg.counter("qgemm_layer_indexed_total", "", ("kind",))
    calls = reg.counter("qgemm_calls_total", "",
                        ("scheme", "kind", "shape", "block"))
    n_calls = sum(v for key, v in calls.items()
                  if dict(zip(calls.labelnames, key))["kind"] == kind)
    return indexed.get(kind=kind), n_calls


def test_engine_layer_indexed_scan_matches_unrolled(moe_quantized):
    """The serving scan reads the stacked quantized weights in place by
    the layer index: every quantized linear of the scanned blocks is
    layer-indexed in the engine's prefill and decode traces, decode still
    traces once, and a decode step gives bit-identical logits, routed
    counts and cache to the unrolled (``scan_layers=False``) path."""
    api, cfg, qp = moe_quantized
    sc = ServeConfig(max_slots=2, max_seq=32, prefill_len=8,
                     max_new_tokens=MAX_NEW, kernel_mode="pallas_interpret")
    rng = np.random.default_rng(7)
    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = Engine(api, cfg, qp, sc, recipe=DEFAULT_RECIPE)
        for _ in range(2):
            eng.submit(rng.integers(0, cfg.vocab_size, size=8).tolist())
        eng.run()
    assert eng.decode_traces == 1
    for kind in ("dense", "grouped"):
        indexed, calls = _indexed_and_calls(reg, kind)
        # 4 attention projections and 3 expert GEMMs per block, per trace
        per_block = 4 if kind == "dense" else 3
        assert indexed == calls == 2 * per_block, (kind, indexed, calls)

    unrolled = Engine(api, dataclasses.replace(cfg, scan_layers=False), qp,
                      sc, recipe=DEFAULT_RECIPE)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 1)),
                       jnp.int32)
    pos = jnp.asarray([8, 9], jnp.int32)
    outs = [e._decode_base.__wrapped__.__wrapped__(qp, toks, eng.cache, pos)
            for e in (eng, unrolled)]
    assert eng.decode_traces == 1
    for a, b in zip(*(jax.tree.leaves(o) for o in outs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("route", ["train", "reference"])
def test_layer_index_not_used_for_training_or_reference(moe_quantized,
                                                        route):
    """Training (``mode="train"`` without a cache) keeps scanning over the
    weights, as does the reference kernel mode: no call is layer-indexed."""
    api, cfg, qp = moe_quantized
    km = "reference" if route == "reference" else "pallas_interpret"
    c = dataclasses.replace(cfg, kernel_mode=km)
    reg = obs.Registry()
    with obs.use_registry(reg):
        jax.jit(lambda p, t: api.apply(p, c, t, recipe=DEFAULT_RECIPE,
                                       mode="train")[0]).lower(
            qp, jnp.zeros((1, 8), jnp.int32))
    for kind in ("dense", "grouped"):
        indexed, calls = _indexed_and_calls(reg, kind)
        assert indexed == 0
        assert (calls == 0) == (route == "reference"), (kind, calls)
