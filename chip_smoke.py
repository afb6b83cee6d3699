"""Smoke run of the quantized serving path on one TPU chip.

    python chip_smoke.py        # from the repository root, on a TPU host

One process, phases in order; any failed phase raises and exits non-zero:

1. device: refuse to run unless JAX's first device is a TPU (no CPU
   branch, no interpret mode);
2. serve: ``repro.launch.serve`` in-process, exactly as
   ``python -m repro.launch.serve`` runs it — Mixtral-8x7B at its published
   widths (d_model 4096, 32 heads, 8 KV heads, head_dim 128, 8 experts
   top-2, expert d_ff 14336, vocab 32000, rope theta 1e6) cut to 2 layers,
   random weights from a fixed seed, RTN W4A8 g128 integer scale with
   amplifier 1024, 8 requests of 512-token prompts through the engine on
   the Pallas kernels with no fallback route;
3. checks: every outcome ``ok``, no kernel failure, no fallback, one
   decode trace, and executed MoE m-tiles > 0 (the ragged grouped kernel
   ran);
4. logits: prefill logits of the first prompt on the ``pallas`` route
   against the ``reference`` (pure jnp) route, same quantized params, on
   the chip (tolerance argued at ``REL_TOL``), and the engine's greedy
   first token against both.

Times printed on the way are smoke output, not benchmark results. The
last line of stdout is the JSON device record, printed only on success.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

PROMPT_LEN = 512
SERVE_ARGS = [
    "--arch", "mixtral-8x7b", "--num-layers", "2",
    "--algo", "rtn", "--w-bits", "4", "--a-bits", "8", "--group", "128",
    "--scale-mode", "integer", "--amplifier", "1024",
    "--requests", "8", "--prefill-len", str(PROMPT_LEN), "--max-new", "16",
    "--slots", "4", "--max-seq", "640",
    "--kernel-mode", "pallas", "--fallback-kernel-mode", "none",
]

# Pallas vs reference prefill logits: max|diff| <= REL_TOL * max|ref|.
# Both routes compute the Eq. 2 accumulation in int32, which is exact, from
# the same int8 codes, and apply the same f32 epilogue acc * (s_a / alpha).
# What can differ is float work around the integer core: the activation
# quantizer's x / scale (the TPU's f32 divide is not correctly rounded, and
# a Mosaic kernel and an XLA fusion need not agree to the last ulp) and
# XLA's fusion order. That can move one activation code by +-1 at a rounding
# boundary, which shifts that token's GEMM output by about 1/127 of its
# range at most, and the bf16 residual stream then rounds at 2^-8 relative.
# 2e-2 of the largest logit is five bf16 ulps at the top of the range: room
# for such flips through two layers, far below what a wrong scale, group or
# expert (an O(1) change) produces.
REL_TOL = 2e-2


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


def main() -> int:
    if not (SRC / "repro" / "launch" / "serve.py").is_file():
        print(f"chip_smoke: {SRC} does not hold the repro package; run this "
              "script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    # -- phase 1: device ----------------------------------------------------
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this script runs only on a TPU",
              file=sys.stderr)
        return 1
    print(f"[smoke] device kind={dev.device_kind} count={len(devices)}")

    import jax.numpy as jnp
    import numpy as np

    from repro.analysis import certify
    from repro.data.pipeline import DataConfig, SyntheticPipeline
    from repro.launch import serve

    cache_dir = serve.use_compile_cache()
    compile_s, cache = [0.0, 0], {"hits": 0, "misses": 0}

    def on_duration(event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs
            compile_s[1] += 1

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    print(f"[smoke] compile cache: {cache_dir}")

    # -- phase 2: serve through the CLI's own path ---------------------------
    n_certs = len(certify.log())
    res = serve.run(serve.parse_args(SERVE_ARGS))
    cfg, eng = res.cfg, res.engine
    print(f"[smoke] config {cfg.name}: layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"head_dim={cfg.head_dim} experts={cfg.num_experts} "
          f"top_k={cfg.top_k} moe_d_ff={cfg.moe_d_ff} "
          f"vocab={cfg.vocab_size} rope_theta={cfg.rope_theta}")
    print(f"[smoke] qcert: {certify.summary(certify.log()[n_certs:])}")

    # -- phase 3: what the run must show ---------------------------------
    snap = res.registry.snapshot()
    c, h = snap["counters"], snap["histograms"]
    outcomes = sorted(eng.outcomes.values())
    kfail = sum(c.get("engine_kernel_failures_total", {}).values())
    tiles = c.get("engine_moe_m_tiles_total", {})
    executed = tiles.get('kind="executed"', 0)
    total = tiles.get('kind="total"', 0)
    print(f"[smoke] outcomes={outcomes} kernel_failures={int(kfail)} "
          f"fallbacks={eng.fallbacks} decode_traces={eng.decode_traces} "
          f"moe_m_tiles executed/total={int(executed)}/{int(total)}")
    check(outcomes == ["ok"] * 8, f"all 8 outcomes ok, got {outcomes}")
    check(kfail == 0, "no kernel failures")
    check(eng.fallbacks == 0, "no fallbacks")
    check(eng.decode_traces == 1, "one decode trace")
    check(executed > 0, "the ragged grouped MoE kernel executed m-tiles")

    # -- phase 4: pallas vs reference logits on the chip ---------------------
    tokens = jnp.asarray(SyntheticPipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=PROMPT_LEN,
        batch_size=1)).batch(300_000)["tokens"], jnp.int32)

    def prefill_logits(mode: str) -> np.ndarray:
        mcfg = dataclasses.replace(cfg, kernel_mode=mode)
        fn = jax.jit(lambda p, t: res.api.apply(
            p, mcfg, t, recipe=res.recipe, mode="train")[0])
        return np.asarray(fn(res.params, tokens)[0], np.float32)

    got, ref = prefill_logits("pallas"), prefill_logits("reference")
    check(np.isfinite(got).all() and np.isfinite(ref).all(),
          "finite prefill logits")
    err = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    last = PROMPT_LEN - 1
    first_tok = res.outputs[0][0]
    top_ref, top_got = int(ref[last].argmax()), int(got[last].argmax())
    print(f"[smoke] logits pallas vs reference over {got.shape}: "
          f"max_abs_diff={err!r} max_abs_ref={scale!r} "
          f"rel={err / scale!r} (tol {REL_TOL}) "
          f"mean_abs_diff={float(np.abs(got - ref).mean())!r}")
    print(f"[smoke] greedy first token: engine={first_tok} "
          f"pallas={top_got} reference={top_ref}")
    check(err <= REL_TOL * scale, f"logits within {REL_TOL} of max|ref|")
    check(first_tok == top_got == top_ref, "greedy first token agrees")

    # -- smoke numbers (one run; not a benchmark) ----------------------------
    dev_h = h.get("engine_phase_device_seconds", {})

    def p50(phase: str) -> float:
        return dev_h[f'phase="{phase}"']["quantiles"]["p50"]

    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"[smoke] compile: backend_compile_s={compile_s[0]!r} "
          f"programs={compile_s[1]} cache_hits={cache['hits']} "
          f"cache_misses={cache['misses']} (smoke output, not a benchmark)")
    print(f"[smoke] device p50: prefill={p50('prefill')!r}s "
          f"decode={p50('decode')!r}s; peak_bytes_in_use={peak}; "
          f"tokens/s={res.tokens / res.seconds!r} "
          f"({res.tokens} tokens in {res.seconds!r}s, compile included) "
          f"(smoke output, not a benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
