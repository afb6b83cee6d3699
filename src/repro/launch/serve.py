"""Serving driver: build or load a model, PTQ per recipe, run the
continuous-batching engine over a stream of requests.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
        --num-layers 2 --prefill-len 512 --max-seq 640 --max-new 16

``--arch`` names a registry architecture, built at its published widths
with random weights from a fixed seed; ``--num-layers N`` cuts its depth
(printed as ``reduced: num_layers <published>-><N>``) and ``--smoke`` swaps in the
architecture's CPU-sized smoke shape instead; ``--arch`` defaults to
``mixtral-8x7b``. Quantized layers run the Pallas kernels (``--kernel-mode
pallas``, the default); CPU callers pass ``pallas_interpret`` or
``reference``. ``chip_smoke.py`` drives this same path in-process
through :func:`parse_args` and :func:`run`.

``--metrics-out PATH`` writes the run's telemetry as JSONL: one
event per line (submit / admit / tick / retire / counters / trace /
ptq_run) with a trailing ``{"snapshot": ...}`` line carrying every
counter/gauge/histogram — per-tick decode latency (host and device),
TTFT/TPOT with p50/p95/p99, executed-vs-total ragged m-tiles,
capped-alpha counts. The telemetry outputs are flushed in a ``finally``
block, so a tick that raises still leaves the event log + snapshot on
disk (exactly when it is most needed). ``--trace-out PATH`` exports the
same event log as a Perfetto/chrome://tracing timeline (engine-phase
lane, per-request-slot lifecycle lanes, m-tile/qgemm counter tracks —
open at https://ui.perfetto.dev). ``--profile-dir DIR`` additionally
wraps the serving loop in a ``jax.profiler.trace`` capture window. A
telemetry cell summarizing the snapshot is always printed, and
steady-state ``decode_traces == 1 + fallbacks`` is asserted so
instrumentation can never silently add a retrace (each circuit-breaker
fallback re-establishes the jitted decode: exactly one intentional extra
trace).

The run fails (non-zero exit) when any request ends ``error`` or ``nan``
or any kernel-path exception was counted, unless a chaos drill is armed.
There is no silent fallback: ``--fallback-kernel-mode`` defaults to
``none``, so a kernel that fails is an error, never a quiet run on the
reference route.

Robustness knobs: ``--deadline-s`` / ``--max-queue`` /
``--truncate-prompts`` / ``--breaker-threshold`` /
``--fallback-kernel-mode`` map onto the ``ServeConfig`` lifecycle
hardening, and ``--chaos-nan-ticks`` / ``--chaos-kernel-ticks`` arm the
``repro.serving.chaos`` fault drill (nightly CI injects NaNs and asserts
the ``nan`` outcome lands in the metrics artifact + as distinct
``retire:nan`` Perfetto markers).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time
from typing import Any

import jax

from repro import obs
from repro.core import ptq
from repro.core.recipe import QuantRecipe, QuantSpec
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.nn import spec as S
from repro.serving.engine import Engine, ServeConfig

#: Persistent compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed directory inside the checkout (the path is part of
#: every cache key, so a moving directory never hits). Gitignored.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and no
    other directory is configured here. Called once by each entry point
    (``main`` below, ``chip_smoke.py``), never at import. Every program is
    cached, however fast it compiled, so a second run compiles nothing.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


def load_model(arch: str, *, smoke: bool = False,
               num_layers: int | None = None):
    """(api, cfg, fp_params). Registry architectures are built at
    their published widths (or their smoke shape) with random weights
    from a fixed seed; ``num_layers`` cuts depth only."""
    from repro.models.registry import get_arch, get_model

    cfg = get_arch(arch, smoke=smoke)
    if num_layers is not None and num_layers != cfg.num_layers:
        print(f"[serve] reduced: num_layers {cfg.num_layers}->{num_layers}")
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    api = get_model(cfg)
    params = S.materialize(api.param_specs(cfg, None), jax.random.PRNGKey(0))
    return api, cfg, params


def _fmt_hist(h: dict) -> str:
    n = h["count"]
    if not n:
        return "n=0"
    out = f"n={n} mean={h['sum'] / n * 1e3:.2f}ms"
    q = h.get("quantiles")
    if q:
        out += (f" p50={q['p50'] * 1e3:.2f}ms p95={q['p95'] * 1e3:.2f}ms"
                f" p99={q['p99'] * 1e3:.2f}ms")
    return out


def _telemetry_cell(reg: obs.Registry) -> None:
    snap = reg.snapshot()
    c, g, h = snap["counters"], snap["gauges"], snap["histograms"]

    def csum(name: str) -> float:
        return sum(c.get(name, {}).values())

    print("[serve] --- telemetry ---------------------------------------")
    print(f"[serve] ticks={int(csum('engine_ticks_total'))} "
          f"tokens={int(csum('engine_tokens_total'))} "
          f"requests={c.get('engine_requests_total', {})} "
          f"queue_depth={g.get('engine_queue_depth', {}).get('', 0)}")
    # request outcomes + the conservation law (sums to submitted)
    outcomes = c.get("engine_request_outcomes_total", {})
    submitted = c.get("engine_requests_total", {}).get(
        'event="submitted"', 0)
    if outcomes:
        conserved = sum(outcomes.values()) == submitted
        pretty = {k: int(v) for k, v in sorted(outcomes.items())}
        print(f"[serve] outcomes={pretty} submitted={int(submitted)} "
              f"conserved={'yes' if conserved else 'NO'}")
    if csum("engine_fallback_events_total"):
        print(f"[serve] breaker fallbacks="
              f"{c.get('engine_fallback_events_total', {})} "
              f"kernel_failures="
              f"{c.get('engine_kernel_failures_total', {})}")
    if csum("engine_slow_ticks_total"):
        print(f"[serve] slow_ticks="
              f"{int(csum('engine_slow_ticks_total'))}")
    phases = h.get("engine_phase_seconds", {})
    for sk in sorted(phases):
        print(f"[serve] phase {sk or '<all>'}: {_fmt_hist(phases[sk])}")
    # device-time attribution: host phase span minus this = host overhead
    for sk, st in sorted(h.get("engine_phase_device_seconds", {}).items()):
        print(f"[serve] device {sk or '<all>'}: {_fmt_hist(st)}")
    for name in ("engine_ttft_seconds", "engine_tpot_seconds"):
        for sk, st in h.get(name, {}).items():
            print(f"[serve] {name}{('{' + sk + '}') if sk else ''}: "
                  f"{_fmt_hist(st)}")
    tiles = c.get("engine_moe_m_tiles_total", {})
    if tiles:
        ex = tiles.get('kind="executed"', 0)
        tot = tiles.get('kind="total"', 0)
        frac = f" ({ex / tot:.2f}x dense)" if tot else ""
        print(f"[serve] moe m-tiles executed/total={int(ex)}/{int(tot)}"
              f"{frac}")
    for name in ("qgemm_calls_total", "engine_traces_total",
                 "qcert_verdicts_total"):
        if c.get(name):
            print(f"[serve] {name}: {c[name]}")
    print(f"[serve] alpha_cap_events_total="
          f"{int(csum('alpha_cap_events_total'))} "
          f"int_scale_floor_hits_total="
          f"{int(csum('int_scale_floor_hits_total'))} "
          f"amax_floor_hits={c.get('amax_floor_hits_total', {})}")


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b",
                    help="a registry architecture, built at its published "
                         "widths")
    ap.add_argument("--smoke", action="store_true",
                    help="build the registry architecture at its CPU-sized "
                         "smoke shape instead of its published widths")
    ap.add_argument("--num-layers", type=int, default=None,
                    help="cut the architecture's depth to N layers "
                         "(widths unchanged)")
    ap.add_argument("--algo", default="rtn",
                    choices=["rtn", "gptq", "awq", "smoothquant",
                             "omniquant"])
    ap.add_argument("--scale-mode", default="integer",
                    choices=["integer", "float"])
    ap.add_argument("--w-bits", type=int, default=4)
    ap.add_argument("--a-bits", type=int, default=8)
    ap.add_argument("--group", type=int, default=128)
    ap.add_argument("--amplifier", default="1024")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--prefill-len", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--fp", action="store_true",
                    help="serve unquantized (baseline)")
    ap.add_argument("--kernel-mode", default="pallas",
                    choices=["reference", "pallas", "pallas_interpret"],
                    help="qlinear backend inside prefill/decode")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request deadline in seconds (0 = none); "
                         "overruns retire with outcome=timeout")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="admission queue bound (0 = unbounded); surplus "
                         "submits are rejected (backpressure)")
    ap.add_argument("--truncate-prompts", action="store_true",
                    help="opt into clipping over-length prompts to "
                         "prefill-len instead of rejecting them")
    ap.add_argument("--breaker-threshold", type=int, default=3,
                    help="consecutive kernel failures / poisoned ticks "
                         "that trip the fallback circuit breaker")
    ap.add_argument("--fallback-kernel-mode", default="none",
                    choices=["reference", "pallas", "pallas_interpret",
                             "none"],
                    help="kernel mode the breaker degrades to (default "
                         "'none': a failing kernel aborts the run)")
    ap.add_argument("--chaos-nan-ticks", default="",
                    help="comma-separated decode ticks at which to inject "
                         "NaN logits into every active slot "
                         "(repro.serving.chaos fault drill)")
    ap.add_argument("--chaos-kernel-ticks", default="",
                    help="comma-separated decode ticks at which to inject "
                         "one kernel exception (breaker drill)")
    ap.add_argument("--metrics-out", default="",
                    help="write telemetry JSONL (events + final snapshot "
                         "line) to this path")
    ap.add_argument("--trace-out", default="",
                    help="write a Perfetto/chrome://tracing timeline JSON "
                         "of the run to this path (ui.perfetto.dev)")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax.profiler trace of the serving "
                         "loop into this directory (TensorBoard profile "
                         "plugin format)")
    return ap.parse_args(argv)


@dataclasses.dataclass
class ServeResult:
    """What one :func:`run` served, for in-process callers."""

    api: Any
    cfg: Any            # the ModelConfig the engine was built from
    params: Any         # the served (quantized unless --fp) params
    recipe: Any
    engine: Engine
    registry: obs.Registry
    outputs: dict
    seconds: float      # wall time of the serving loop
    tokens: int


class ServeFailed(RuntimeError):
    """A request ended ``error``/``nan`` or a kernel path raised."""


def run(args: argparse.Namespace) -> ServeResult:
    reg = obs.current_registry()
    api, cfg, params = load_model(
        args.arch, smoke=args.smoke, num_layers=args.num_layers)
    print(f"[serve] model={cfg.name} layers={cfg.num_layers} "
          f"d_model={cfg.d_model}")
    if args.fp:
        recipe, qparams = None, params
    else:
        amp = (args.amplifier if not args.amplifier.isdigit()
               else int(args.amplifier))
        spec = QuantSpec(w_bits=args.w_bits, a_bits=args.a_bits,
                         group_size=args.group, scale_mode=args.scale_mode,
                         amplifier=amp, algo=args.algo)
        recipe = QuantRecipe(rules=(("*", spec),), name=spec.name)
        t0 = time.time()
        qparams = ptq.post_training_quantize(api, cfg, params, recipe)
        del params  # the fp weights are not served
        print(f"[serve] quantized ({spec.name}) in {time.time()-t0:.1f}s")

    fb = args.fallback_kernel_mode
    sc = ServeConfig(max_slots=args.slots, max_seq=args.max_seq,
                     prefill_len=args.prefill_len,
                     max_new_tokens=args.max_new,
                     temperature=args.temperature,
                     kernel_mode=args.kernel_mode,
                     deadline_s=args.deadline_s,
                     max_queue=args.max_queue,
                     truncate_prompts=args.truncate_prompts,
                     breaker_threshold=args.breaker_threshold,
                     fallback_kernel_mode=None if fb == "none" else fb)
    eng = Engine(api, cfg, qparams, sc, recipe=recipe)
    chaos_armed = bool(args.chaos_nan_ticks or args.chaos_kernel_ticks)
    if chaos_armed:
        from repro.serving import chaos

        ccfg = chaos.ChaosConfig(
            nan_logits=tuple(
                chaos.NanFault(tick=int(t))
                for t in args.chaos_nan_ticks.split(",") if t),
            kernel_failures=tuple(
                chaos.KernelFault(tick=int(t))
                for t in args.chaos_kernel_ticks.split(",") if t))
        chaos.ChaosMonkey(ccfg).install(eng)
        print(f"[serve] chaos armed: nan_ticks="
              f"[{args.chaos_nan_ticks}] kernel_ticks="
              f"[{args.chaos_kernel_ticks}]")
    pipe = SyntheticPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=args.prefill_len,
                                        batch_size=1))
    for i in range(args.requests):
        eng.submit(pipe.batch(300_000 + i)["tokens"][0].tolist())
    # flush-on-failure: the event log + snapshot (and the timeline) are
    # written even when a tick raises — the crashing run is the one whose
    # telemetry matters most.
    try:
        with obs.trace_window(args.profile_dir or None):
            t0 = time.time()
            outs = eng.run()
            dt = time.time() - t0
        total = sum(len(v) for v in outs.values())
        print(f"[serve] {len(outs)} requests, {total} tokens in {dt:.1f}s "
              f"({total/dt:.1f} tok/s, {eng.ticks} decode ticks)")
        for rid in sorted(outs)[:4]:
            print(f"[serve] r{rid}: {outs[rid][:16]}...")

        # instrumentation must add zero retraces: row_counts stay traced
        # operands, so steady-state decode compiles exactly once per
        # established kernel route (each breaker fallback re-establishes
        # the route = exactly one intentional extra trace)
        assert eng.decode_traces == 1 + eng.fallbacks, \
            (f"decode retraced {eng.decode_traces}x with "
             f"{eng.fallbacks} fallbacks — telemetry broke jit")
    finally:
        _telemetry_cell(reg)
        if args.metrics_out:
            n = reg.write_events_jsonl(args.metrics_out)
            print(f"[serve] wrote {n} telemetry lines -> "
                  f"{args.metrics_out}")
        if args.trace_out:
            n = obs.write_trace(args.trace_out, reg)
            print(f"[serve] wrote {n} trace events -> {args.trace_out} "
                  f"(open at https://ui.perfetto.dev)")
        if args.profile_dir:
            print(f"[serve] jax profiler capture -> {args.profile_dir}")
    if not chaos_armed:
        failed = {rid: o for rid, o in eng.outcomes.items()
                  if o in ("error", "nan")}
        kfail = sum(reg.snapshot()["counters"].get(
            "engine_kernel_failures_total", {}).values())
        if failed or kfail:
            raise ServeFailed(
                f"{len(failed)} request(s) ended error/nan {failed}, "
                f"{int(kfail)} kernel failure(s)")
    return ServeResult(api, eng.cfg, eng.params, eng.recipe, eng, reg,
                       outs, dt, total)


def main(argv: list[str] | None = None) -> None:
    use_compile_cache()
    run(parse_args(argv))


if __name__ == "__main__":
    main()
