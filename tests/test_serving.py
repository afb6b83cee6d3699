"""Serving engine: continuous batching correctness + quantized serving."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ModelConfig
from repro.models.registry import get_model
from repro.nn import spec as S
from repro.serving.engine import Engine, ServeConfig

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
                      dtype="float32", q_chunk=16, kv_chunk=16, remat=False)
    api = get_model(cfg)
    params = S.materialize(api.param_specs(cfg, None), jax.random.PRNGKey(0))
    return api, cfg, params


def _reference_generate(api, cfg, params, prompt, n_new):
    """Single-request greedy generation via full re-forward (oracle)."""
    toks = list(prompt)
    for _ in range(n_new):
        logits, _, _ = api.apply(params, cfg,
                                 jnp.asarray([toks], jnp.int32),
                                 mode="train")
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_engine_matches_reference_generation(tiny):
    api, cfg, params = tiny
    sc = ServeConfig(max_slots=3, max_seq=64, prefill_len=8,
                     max_new_tokens=6)
    eng = Engine(api, cfg, params, sc)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, size=8).tolist() for _ in range(5)]
    rids = [eng.submit(p) for p in prompts]
    outs = eng.run()
    assert set(outs) == set(rids)
    for rid, p in zip(rids, prompts):
        ref = _reference_generate(api, cfg, params, p, 6)
        assert outs[rid] == ref, (rid, outs[rid], ref)


def test_engine_staggered_admission(tiny):
    """More requests than slots: retirement frees slots; all finish with
    per-slot positions staying correct."""
    api, cfg, params = tiny
    sc = ServeConfig(max_slots=2, max_seq=64, prefill_len=8,
                     max_new_tokens=4)
    eng = Engine(api, cfg, params, sc)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, size=5).tolist() for _ in range(5)]
    rids = [eng.submit(p) for p in prompts]
    outs = eng.run()
    assert set(outs) == set(rids)
    for rid, p in zip(rids, prompts):
        ref = _reference_generate(api, cfg, params, p, 4)
        assert outs[rid] == ref


def test_engine_quantized_serving(tiny):
    """W4A8-IS quantized engine runs and mostly agrees with fp greedy."""
    from repro.core import ptq
    from repro.core.recipe import QuantRecipe, QuantSpec

    api, cfg, params = tiny
    recipe = QuantRecipe(rules=(("*", QuantSpec(group_size=64)),),
                         name="w4a8-is")
    qp = ptq.post_training_quantize(api, cfg, params, recipe, None)
    sc = ServeConfig(max_slots=2, max_seq=64, prefill_len=8,
                     max_new_tokens=5)
    eng = Engine(api, cfg, qp, sc, recipe=recipe)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 64, size=8).tolist() for _ in range(3)]
    rids = [eng.submit(p) for p in prompts]
    outs = eng.run()
    assert set(outs) == set(rids)
    for v in outs.values():
        assert len(v) == 5
        assert all(0 <= t < 64 for t in v)


_SERVE_SMOKE = ["--arch", "mixtral-8x7b", "--smoke", "--num-layers", "1",
                "--kernel-mode", "reference", "--requests", "3",
                "--slots", "2", "--prefill-len", "8", "--max-seq", "32",
                "--max-new", "3"]


def test_serve_entry_point_quantizes_and_serves(capsys):
    """``repro.launch.serve`` as ``chip_smoke.py`` drives it: parse_args +
    run, depth cut reported, every request ok on one decode trace."""
    from repro import obs
    from repro.launch import serve

    with obs.use_registry(obs.Registry()):
        res = serve.run(serve.parse_args(_SERVE_SMOKE))
    out = capsys.readouterr().out
    assert "reduced: num_layers 2->1" in out
    assert res.cfg.num_layers == 1 and res.cfg.kernel_mode == "reference"
    assert sorted(res.engine.outcomes.values()) == ["ok"] * 3
    assert res.engine.decode_traces == 1 and res.engine.fallbacks == 0
    assert res.tokens == 9 and "qvalue" in str(jax.tree.structure(res.params))


def test_serve_fails_on_nan_outcome_without_chaos(monkeypatch):
    """A request ending ``nan`` with no chaos drill armed fails the run
    (non-zero exit from the CLI) instead of reporting a healthy serve."""
    from repro import obs
    from repro.launch import serve

    class PoisonedEngine(Engine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)

            def wrap(fn):
                def decode(params, tokens, cache, pos):
                    logits, cache = fn(params, tokens, cache, pos)
                    return logits.at[0].set(jnp.nan), cache
                return decode

            self.add_decode_wrapper(wrap)

    monkeypatch.setattr(serve, "Engine", PoisonedEngine)
    with obs.use_registry(obs.Registry()):
        with pytest.raises(serve.ServeFailed, match="ended error/nan"):
            serve.run(serve.parse_args(
                _SERVE_SMOKE + ["--breaker-threshold", "100"]))
