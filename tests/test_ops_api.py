"""kernels.ops v2 call convention: BlockConfig, alpha resolution, and the
hard removal of the v1 shims (their one-release window has passed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import qlinear
from repro.core.recipe import QuantSpec
from repro.kernels import ops

jax.config.update("jax_platform_name", "cpu")


def _dense_case(seed=0, K=512, N=256, M=8):
    spec = QuantSpec()
    w = jax.random.normal(jax.random.PRNGKey(seed), (K, N)) * 0.03
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (M, K)).astype(
        jnp.float32)
    return spec, qlinear.quantize_linear(w, spec), x


class TestBlockConfig:
    def test_defaults_match_kernel_defaults(self):
        """``bn``/``bk`` default to None, as the integer-scale kernels'
        own, dense and ragged grouped (chosen by shape); every other GEMM
        kernel defaults to the blocks ops fills in for None."""
        import inspect

        from repro.kernels import moe_gemm, w4a8_gemm, w4a8_gemm_fscale
        from repro.kernels import w4a16_gemm

        b = ops.BlockConfig()
        assert (b.bm, b.bn, b.bk, b.interpret) == (128, None, None, False)

        def defaults(fn):
            p = inspect.signature(fn).parameters
            return tuple(p[n].default for n in ("bm", "bn", "bk"))

        for fn in (w4a8_gemm.fg_gemm_integer_scale,
                   moe_gemm.fg_grouped_gemm_integer_scale_ragged):
            assert defaults(fn) == (128, None, None), fn.__name__
        fixed = (128, w4a8_gemm.DEFAULT_BN, w4a8_gemm.DEFAULT_BK)
        assert fixed == (128, 256, 512)
        for fn in (w4a8_gemm_fscale.fg_gemm_float_scale,
                   w4a16_gemm.w4a16_gemm,
                   moe_gemm.fg_grouped_gemm_float_scale_ragged,
                   moe_gemm.grouped_w4a16_gemm_ragged):
            assert defaults(fn) == fixed, fn.__name__

    @pytest.mark.parametrize("kw", [dict(bm=7), dict(bm=0), dict(bn=100),
                                    dict(bk=-128), dict(bn=64)])
    def test_divisibility_validated_at_construction(self, kw):
        with pytest.raises(ValueError):
            ops.BlockConfig(**kw)

    def test_frozen(self):
        with pytest.raises(Exception):
            ops.BlockConfig().bm = 64

    def test_dict_form_removed(self):
        with pytest.raises(TypeError, match="BlockConfig"):
            ops._as_block({"bm": 64, "bn": 128, "bk": 256})

    def test_rejects_non_block(self):
        with pytest.raises(TypeError):
            ops._as_block("128x256")


class TestUnifiedQgemm:
    def test_param_dict_is_primary_signature(self):
        spec, params, x = _dense_case()
        y = ops.qgemm(x, params, spec, block=ops.INTERPRET)
        y_ref = qlinear.linear_apply(params, x, spec, mode="reference")
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-3, atol=2e-2)

    def test_legacy_positional_form_raises(self):
        spec, params, x = _dense_case()
        with pytest.raises(TypeError):
            ops.qgemm(x, params["qvalue"], params["scale"], spec,
                      alpha=params["alpha"], interpret=True)

    def test_from_params_shim_removed(self):
        assert not hasattr(ops, "qgemm_from_params")
        assert not hasattr(ops, "qgemm_grouped_from_params")

    def test_interpret_kwarg_removed(self):
        spec, params, x = _dense_case()
        with pytest.raises(TypeError):
            ops.qgemm(x, params, spec, interpret=True)

    def test_non_dict_params_raises(self):
        spec, params, x = _dense_case()
        with pytest.raises(TypeError, match="param dict"):
            ops.qgemm(x, params["qvalue"], spec)


class TestUnifiedQgemmGrouped:
    def _grouped_case(self, E=2, C=16, K=256, N=256):
        spec = QuantSpec()
        qps = [qlinear.quantize_linear(
            jax.random.normal(jax.random.PRNGKey(10 + e), (K, N)) * 0.03,
            spec) for e in range(E)]
        params = {k: jnp.stack([p[k] for p in qps]) for k in qps[0]}
        x = jax.random.normal(jax.random.PRNGKey(20), (E, C, K)).astype(
            jnp.float32)
        return spec, params, x

    def test_matches_grouped_linear_apply(self):
        spec, params, x = self._grouped_case()
        y = ops.qgemm_grouped(x, params, spec, block=ops.INTERPRET)
        y_ref = qlinear.grouped_linear_apply(x=x, params=params, qspec=spec,
                                             mode="pallas_interpret")
        np.testing.assert_array_equal(
            np.asarray(y, np.float32), np.asarray(y_ref, np.float32))

    def test_grouped_legacy_positional_raises(self):
        spec, params, x = self._grouped_case()
        with pytest.raises(TypeError, match="param dict"):
            ops.qgemm_grouped(x, params["qvalue"], spec)


class TestAlphaResolution:
    def test_static_int_amplifier_is_exact_fallback(self):
        assert ops._resolve_alpha(None, QuantSpec(amplifier=2048)) == 2048.0

    def test_stored_alpha_wins(self):
        assert ops._resolve_alpha(512.0, QuantSpec(amplifier=2048)) == 512.0

    @pytest.mark.parametrize("amp", ["heuristic", "heuristic+6"])
    def test_heuristic_amplifier_without_stored_alpha_raises(self, amp):
        with pytest.raises(ValueError, match="per layer"):
            ops._resolve_alpha(None, QuantSpec(amplifier=amp))


class TestBlockLabel:
    # Qwen2-72B's gate projection over its 10-layer stack: the decode call
    # (32 slots) takes the blocks chosen by shape, prefill (512 rows) the
    # defaults, with bn snapped to 128 (29568 = 231 * 128)
    @pytest.mark.parametrize("M,block", [(32, "32x1408x2048"),
                                         (512, "128x128x512")])
    def test_traced_call_records_launched_block(self, M, block):
        R, K, N = 10, 8192, 29568
        spec = QuantSpec()

        def call(x, q, s, layer):
            return ops.qgemm(x, {"qvalue": q, "scale": s, "alpha": 1024.0},
                             spec, layer=layer)

        reg = obs.Registry()
        with obs.use_registry(reg):
            jax.eval_shape(call, jax.ShapeDtypeStruct((M, K), jnp.bfloat16),
                           jax.ShapeDtypeStruct((R, K // 2, N), jnp.int8),
                           jax.ShapeDtypeStruct((R, K // 128, N), jnp.int32),
                           jax.ShapeDtypeStruct((), jnp.int32))
        calls = reg.counter("qgemm_calls_total", "",
                            ("scheme", "kind", "shape", "block"))
        labels = [dict(zip(calls.labelnames, key)) for key, _ in calls.items()]
        assert labels == [{"scheme": "w4a8-is", "kind": "dense",
                           "shape": f"{M}x{K}x{N}", "block": block}]

    # Mixtral's expert up projection over its 8-layer stack: the decode
    # call (C = 32, one m-tile) takes the blocks chosen by shape, prefill
    # (C = 512) the defaults
    @pytest.mark.parametrize("C,block", [(32, "32x2048x2048"),
                                         (512, "128x256x512")])
    def test_traced_grouped_call_records_launched_block(self, C, block):
        R, E, K, N = 8, 8, 4096, 14336
        spec = QuantSpec()

        def call(x, rc, q, s, layer):
            return ops.qgemm_grouped(
                x, {"qvalue": q, "scale": s, "alpha": 1024.0}, spec,
                row_counts=rc, layer=layer)

        reg = obs.Registry()
        with obs.use_registry(reg):
            jax.eval_shape(
                call, jax.ShapeDtypeStruct((E, C, K), jnp.bfloat16),
                jax.ShapeDtypeStruct((E,), jnp.int32),
                jax.ShapeDtypeStruct((R, E, K // 2, N), jnp.int8),
                jax.ShapeDtypeStruct((R, E, K // 128, N), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
        calls = reg.counter("qgemm_calls_total", "",
                            ("scheme", "kind", "shape", "block"))
        labels = [dict(zip(calls.labelnames, key)) for key, _ in calls.items()]
        assert labels == [{"scheme": "w4a8-is", "kind": "grouped",
                           "shape": f"{E}x{C}x{K}x{N}", "block": block}]

    def test_explicit_block_is_recorded_as_launched(self):
        spec, params, x = _dense_case(K=512, N=384, M=8)
        reg = obs.Registry()
        with obs.use_registry(reg):
            ops.qgemm(x, params, spec,
                      block=ops.BlockConfig(bn=256, bk=512, interpret=True))
        calls = reg.counter("qgemm_calls_total", "",
                            ("scheme", "kind", "shape", "block"))
        (key, _), = calls.items()
        # bn=256 does not tile N=384: the kernel snaps it to 128
        assert dict(zip(calls.labelnames, key))["block"] == "8x128x512"


class TestKernelModeContext:
    def test_nesting_and_default(self):
        assert qlinear.current_kernel_mode() == "reference"
        with qlinear.kernel_mode("pallas_interpret"):
            assert qlinear.current_kernel_mode() == "pallas_interpret"
            with qlinear.kernel_mode("pallas"):
                assert qlinear.current_kernel_mode() == "pallas"
            assert qlinear.current_kernel_mode() == "pallas_interpret"
        assert qlinear.current_kernel_mode() == "reference"

    def test_invalid_mode_rejected(self):
        with pytest.raises(ValueError):
            with qlinear.kernel_mode("cuda"):
                pass

    def test_legacy_setter_removed(self):
        assert not hasattr(qlinear, "set_default_kernel_mode")
        assert not hasattr(qlinear, "default_kernel_mode")
