"""Correctness of the model-level optimizations: chunkwise-parallel mLSTM,
int8 MoE dispatch, int8 KV cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis_compat import given, settings, st  # optional dev dep shim

from repro.models.registry import get_arch, get_model
from repro.models.xlstm import _mlstm_cell, _mlstm_chunked
from repro.nn import spec as S

jax.config.update("jax_platform_name", "cpu")


def _seq_ref(q, k, v, ir, fr, C0, n0, m0):
    def step(c, t):
        return _mlstm_cell(c, t)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, ir, fr))
    (C, n, m), hs = jax.lax.scan(step, (C0, n0, m0), xs)
    return jnp.moveaxis(hs, 0, 1), (C, n, m)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), chunk=st.sampled_from([2, 4, 8, 16]),
       s=st.sampled_from([16, 32]))
def test_chunked_mlstm_exact_vs_sequential(seed, chunk, s):
    """The chunkwise closed form must equal the step recurrence (f32)."""
    B, H, dh = 2, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, s, H, dh))
    k = jax.random.normal(ks[1], (B, s, H, dh))
    v = jax.random.normal(ks[2], (B, s, H, dh))
    ir = jax.random.normal(ks[3], (B, s, H)) * 3
    fr = jax.random.normal(ks[4], (B, s, H)) * 3
    C0 = jnp.zeros((B, H, dh, dh))
    n0 = jnp.zeros((B, H, dh))
    m0 = jnp.zeros((B, H))
    h_s, (C_s, n_s, m_s) = _seq_ref(q, k, v, ir, fr, C0, n0, m0)
    h_c, (C_c, n_c, m_c) = _mlstm_chunked(q, k, v, ir, fr, C0, n0, m0,
                                          chunk)
    np.testing.assert_allclose(np.asarray(h_c), np.asarray(h_s),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(C_c), np.asarray(C_s),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(m_c), np.asarray(m_s),
                               rtol=1e-4, atol=1e-5)


def test_chunked_mlstm_nonzero_initial_state():
    """Carrying state across calls (prefill -> prefill continuation)."""
    B, s, H, dh = 1, 12, 2, 4
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    q = jax.random.normal(ks[0], (B, s, H, dh))
    k = jax.random.normal(ks[1], (B, s, H, dh))
    v = jax.random.normal(ks[2], (B, s, H, dh))
    ir = jax.random.normal(ks[3], (B, s, H))
    fr = jax.random.normal(ks[4], (B, s, H))
    C0 = jnp.zeros((B, H, dh, dh))
    n0 = jnp.zeros((B, H, dh))
    m0 = jnp.zeros((B, H))
    h_full, _ = _seq_ref(q, k, v, ir, fr, C0, n0, m0)
    # first half sequential, second half chunked from the carried state
    h1, (C1, n1, m1) = _seq_ref(q[:, :6], k[:, :6], v[:, :6], ir[:, :6],
                                fr[:, :6], C0, n0, m0)
    h2, _ = _mlstm_chunked(q[:, 6:], k[:, 6:], v[:, 6:], ir[:, 6:],
                           fr[:, 6:], C1, n1, m1, chunk=3)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full[:, 6:]),
                               rtol=1e-4, atol=1e-5)


def test_chunked_model_close_to_sequential():
    cfg = get_arch("xlstm-1.3b", smoke=True)
    api = get_model(cfg)
    params = S.materialize(api.param_specs(cfg, None), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                              cfg.vocab_size)
    l_seq, _, _ = api.apply(params, cfg, toks, mode="train")
    cfg_c = dataclasses.replace(cfg, mlstm_impl="chunked", chunk_size=16)
    l_chk, _, _ = api.apply(params, cfg_c, toks, mode="train")
    # bf16 activations: different-but-equivalent op orders
    rel = float(jnp.linalg.norm(l_chk - l_seq) / jnp.linalg.norm(l_seq))
    assert rel < 0.02, rel


def test_int8_moe_dispatch_close_to_bf16():
    cfg = get_arch("phi3.5-moe-42b-a6.6b", smoke=True)
    api = get_model(cfg)
    params = S.materialize(api.param_specs(cfg, None), jax.random.PRNGKey(2))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0,
                              cfg.vocab_size)
    l_ref, _, _ = api.apply(params, cfg, toks, mode="train")
    cfg_i8 = dataclasses.replace(cfg, moe_int8_dispatch=True)
    l_i8, _, aux = api.apply(params, cfg_i8, toks, mode="train")
    rel = float(jnp.linalg.norm(l_i8 - l_ref) / jnp.linalg.norm(l_ref))
    assert rel < 0.03, rel
    # gradients flow through the straight-through estimator
    from repro.training.train_step import make_loss_fn

    loss_fn = make_loss_fn(api, cfg_i8, None)
    (_, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
        params, {"tokens": toks, "labels": toks})
    gn = sum(float(jnp.sum(jnp.abs(x.astype(jnp.float32))))
             for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0
