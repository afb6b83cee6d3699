"""Open-loop serving benchmark of the quantized engine on one TPU chip.

    python3 bench/run.py --workload mixtral-8x7b.chat --seed 7 \\
        --seconds 30 --trace 0

One run is one process, from the root of a checkout:

1. It refuses to run unless JAX finds a TPU with as many chips as the
   cell asks for, and names the device on its last line.
2. Set-up: it builds the cell's configuration (``bench/configs``) with
   weights drawn on the device from ``--seed`` and quantized by the
   program, checks the Eq. 2 bound of every layer, makes the engine and
   warms up the cell's prefill and decode shapes. The request schedule
   (``bench/generator.py`` over ``bench/traffic/<mix>.json`` and
   ``bench/cells/<workload>.json``) starts ``lead_s`` before the window,
   so the window opens on a loaded engine; the lead counts in
   ``setup_s``.
3. The window: ``--seconds`` of open-loop traffic through the engine's
   public API (``bench/drive.py``). With ``--trace 1`` the window is
   traced and the per-layer metrics (``bench/metrics/<name>.py``) are
   read from the trace and the window's counter deltas; with
   ``--trace 0`` the end-to-end metrics are reported.
4. ``correct``: the program's state is freed and a sample of the served
   requests is compared with the plain reference (``bench/check.py``).

The last line of standard output is one JSON object. The numbers that
decide ``correct`` are printed, each beside its limit, as the last lines
of standard error and under the result's last key, ``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

OUT = ROOT / ".bench_out"     # scratch of a run (the trace); gitignored
CACHE = ROOT / ".jax_cache"   # JAX's persistent compilation cache


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_file: pathlib.Path = ROOT / "BENCHMARK.json",
              data: pathlib.Path = BENCH) -> types.SimpleNamespace:
    """The cell's entries and files: configuration (by its ``file``),
    traffic mix (``<data>/traffic/<mix>.json``) and arrivals, lead and
    grace (``<data>/cells/<workload>.json``)."""
    bench = load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in {bench_file.name}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return types.SimpleNamespace(
        name=name, chips=w["chips"], bench=bench,
        cfg=load_json(ROOT / conf["file"]),
        mix=load_json(data / "traffic" / f"{w['traffic']}.json"),
        cell=load_json(data / "cells" / f"{name}.json"))


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Compiles:
    """Counts programs that had to be compiled or fetched from the
    persistent cache (either is a stall of the caller)."""

    def __init__(self, jax):
        self.n = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def counters(reg) -> dict:
    snap = reg.snapshot()
    out = {n: dict(s) for n, s in snap["counters"].items()}
    for n, series in snap["histograms"].items():
        for k, st in series.items():
            out.setdefault(n + ":sum", {})[k] = st["sum"]
            out.setdefault(n + ":count", {})[k] = st["count"]
    return out


def delta(after: dict, before: dict) -> dict:
    return {n: {k: v - before.get(n, {}).get(k, 0) for k, v in s.items()}
            for n, s in after.items()}


def use_compile_cache(jax) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache``; every program is cached."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def warm_up(eng, mix: dict, vocab: int) -> None:
    """Serve one full-length prompt two tokens: the cell's prefill shape
    and its decode shape (every slot is computed at every tick). A slot
    index and a prompt length are data to the engine's eager splice and
    slice, so no other program is needed; ``serve`` counts compilations
    inside the window to show it."""
    import numpy as np

    P = mix["engine"]["prefill_len"]
    rid = eng.submit([int(t) for t in
                      np.random.default_rng(0).integers(0, vocab, P)])
    while eng.outcome(rid) is None:
        eng.run(max_ticks=eng.ticks + 1)
        for s in eng.slots:
            if s.active and len(s.generated) >= 2:
                eng.cancel(s.request_id)


def build(c, seed: int, kernel_mode: str, engine_hook=None):
    """The system under test for one seed: quantized parameters on the
    device, the Eq. 2 check, the engine with its shapes warmed up."""
    import jax

    from bench import program, weights
    from repro.models.registry import get_model

    cfg, mix = c.cfg, c.mix
    q = cfg["quantization"]
    mcfg = program.model_config(cfg, kernel_mode)
    rcp = program.recipe(cfg)
    api = get_model(mcfg)
    t0 = time.perf_counter()
    params, stats = program.build_params(api, mcfg, rcp,
                                         weights.base_key(seed))
    jax.block_until_ready(params)
    qa, qw = 2 ** (q["a_bits"] - 1) - 1, 2 ** (q["w_bits"] - 1) - 1
    bound = max(q["group_size"] * qa * qw * colsum for colsum, _ in stats)
    ones = sum(f for _, f in stats)
    log(f"weights built in {time.perf_counter() - t0:.2f}s: "
        f"{mcfg.num_layers} layers; Eq. 2 bound max_n sum_g "
        f"gs*qa*qw*int_scale = {bound} (limit 2^31 = {2 ** 31}); "
        f"integer scales equal to 1 (the floor): {ones}")
    if bound >= 2 ** 31:
        raise SystemExit("bench: a layer breaks the Eq. 2 int32 bound")
    eng = program.make_engine(api, mcfg, params, rcp, mix)
    if engine_hook is not None:
        engine_hook(eng)
    t0 = time.perf_counter()
    warm_up(eng, mix, cfg["vocab_size"])
    log(f"warm-up in {time.perf_counter() - t0:.2f}s")
    return eng


def serve(eng, c, seed: int, seconds: float, *, trace_dir=None,
          compiles=None) -> types.SimpleNamespace:
    """One open-loop window over a warm engine. Returns the request
    records, the window's marks and its counter deltas."""
    import jax

    from bench import drive, generator
    from repro import obs
    from repro.models import moe

    cfg, mix, cell = c.cfg, c.mix, c.cell
    reg = obs.current_registry()
    routing: list = []
    recording = {"on": False}

    def sink(rec):
        if recording["on"]:
            routing.append(rec)

    moe.add_routing_sink(sink)
    lead = float(cell["lead_s"])
    reqs = generator.schedule(mix, cell["arrival"], seed=seed,
                              segments_s=(lead, seconds, lead),
                              vocab=cfg["vocab_size"])
    n_compiles = (lambda: compiles.n) if compiles else (lambda: 0)
    if trace_dir:
        # the profiler starts before the first request is due and stops
        # after the drive: neither stalls the schedule; the window is the
        # span ``bench.window`` (``bench/trace.py``)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    start = time.perf_counter()
    open_t, close_t = start + lead, start + lead + seconds
    marks: dict = {}

    def at_open():
        if trace_dir:
            # a span's start is taken when it is made: make it here
            marks["span"] = jax.profiler.TraceAnnotation("bench.window")
            marks["span"].__enter__()
        recording["on"] = True
        marks["open"] = (time.perf_counter(), counters(reg), n_compiles(),
                         eng.ticks)

    def at_close():
        marks["close"] = (time.perf_counter(), counters(reg), n_compiles(),
                          eng.ticks)
        recording["on"] = False
        if trace_dir:
            marks.pop("span").__exit__(None, None, None)

    annotate = (jax.profiler.TraceAnnotation if trace_dir
                else (lambda name: contextlib.nullcontext()))
    try:
        recs = drive.drive(eng, reqs, start=start, open_t=open_t,
                           close_t=close_t, grace_s=float(cell["grace_s"]),
                           annotate=annotate, on_open=at_open,
                           on_close=at_close)
        stop_t = time.perf_counter()
    finally:
        moe.remove_routing_sink(sink)
        if trace_dir:
            jax.profiler.stop_trace()
    e2e = drive.end_to_end(recs, open_t, close_t, stop_t)
    log(f"window {seconds}s: attempted={e2e['attempted']} "
        f"failed={e2e['failed']} tokens={e2e['output_tokens']} "
        f"gaps={e2e['gaps']} decode ticks="
        f"{marks['close'][3] - marks['open'][3]}; compiles in window="
        f"{marks['close'][2] - marks['open'][2]}; generator late "
        f"p50={e2e['late_p50_s']!r}s max={e2e['late_max_s']!r}s")
    return types.SimpleNamespace(
        recs=recs, e2e=e2e, open_t=open_t, close_t=close_t, marks=marks,
        routing=routing,
        counters=delta(marks["close"][1], marks["open"][1]))


def compared(c, seed: int, recs: list):
    """The sample that ``correct`` compares (``bench/check.py``): ->
    (sample, tokens, rows, served tokens, mask), or None when no request
    was served in full."""
    from bench import check, drive

    done = [r for r in recs if r.outcome in drive.SERVED
            and len(r.tokens) == r.out_len]
    sample = check.sample(done, seed, int(c.mix["check"]["requests"]))
    if not sample:
        return None
    return (sample, *check.inputs(sample, c.mix["engine"]["max_seq"]))


def checks_of(c, gaps, recs: list) -> dict:
    """{name: {value, limit}}: the numbers that the configuration's
    ``correct`` block names, over the compared positions' ``gaps``, then
    the served tokens compared and the requests that failed in the
    engine."""
    from bench import check

    checks = {name: {"value": None if gaps is None
                     else check.NUMBERS[name](gaps), "limit": float(limit)}
              for name, limit in c.cfg["correct"].items()}
    checks["served_tokens_compared"] = {
        "value": 0 if gaps is None else int(gaps.size),
        "limit": int(c.mix["check"]["min_tokens"])}
    checks["error_outcomes"] = {
        "value": sum(1 for r in recs if r.outcome in ("nan", "error")),
        "limit": 0}
    return checks


def judge(c, seed: int, recs: list) -> dict:
    """The comparison that decides ``correct``, run once the program's
    state is freed: the served tokens of a sample against the plain
    reference (``bench/check.py``)."""
    from bench import check

    t1 = time.perf_counter()
    gaps = None
    cmp = compared(c, seed, recs)
    if cmp is not None:
        _, tokens, rows, served, mask = cmp
        ref = check.reference_logits(c.cfg, seed, tokens, rows,
                                     bits=c.cfg["quantization"]["a_bits"])
        gaps = check.position_gaps(ref, served, mask)
        del ref
    log(f"reference over {0 if cmp is None else len(cmp[0])} requests, "
        f"{0 if gaps is None else gaps.size} served tokens, in "
        f"{time.perf_counter() - t1:.2f}s")
    return checks_of(c, gaps, recs)


def passed(checks: dict) -> bool:
    """Every gap within its limit, enough tokens compared, no request
    lost to a NaN or an engine error."""
    ok = (checks["served_tokens_compared"]["value"]
          >= checks["served_tokens_compared"]["limit"]
          and checks["error_outcomes"]["value"] == 0)
    return ok and all(v["value"] is not None and v["value"] <= v["limit"]
                      for k, v in checks.items() if k.endswith("_gap"))


def free_device(jax) -> None:
    gc.collect()
    for a in jax.live_arrays():
        a.delete()


def main(argv=None, *, require_tpu: bool = True,
         kernel_mode: str = "pallas", engine_hook=None,
         bench_file: pathlib.Path = ROOT / "BENCHMARK.json",
         data: pathlib.Path = BENCH) -> int:
    """One run. Tests pass ``require_tpu=False`` with an interpreted
    ``kernel_mode``, small cells (``bench_file``/``data``), and an
    ``engine_hook`` that may break the engine underneath."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default=None,
                    help="with --trace 1, also write the traced window's "
                         "events (events.json.gz) and the trace's planes "
                         "and lines (structure.txt) into this directory")
    args = ap.parse_args(argv)
    c = load_cell(args.workload, bench_file, data)

    import jax

    use_compile_cache(jax)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < c.chips):
        log(f"needs {c.chips} TPU chip(s); JAX found {len(devices)} "
            f"{dev.platform} device(s)")
        return 1
    peaks = load_json(BENCH / "peaks.json")["devices"].get(dev.device_kind)
    if require_tpu and peaks is None:
        log(f"no peaks for device kind {dev.device_kind!r} in "
            "bench/peaks.json")
        return 1

    from bench import program

    program.import_program(ROOT)
    from bench import trace as tr

    compiles = Compiles(jax)
    eng = build(c, args.seed, kernel_mode, engine_hook)
    trace_dir = OUT / "trace" if args.trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    w = serve(eng, c, args.seed, args.seconds, trace_dir=trace_dir,
              compiles=compiles)
    setup_s = w.marks["open"][0] - T_START
    stats_dev = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats_dev.get("peak_bytes_in_use",
                                                     0))}
    res: dict = {"correct": False, "attempted": w.e2e["attempted"],
                 "failed": w.e2e["failed"], "metrics": {}, "device": device}
    if args.trace:
        t0 = time.perf_counter()
        trace = tr.load(str(trace_dir))
        if args.dump:
            dump = pathlib.Path(args.dump)
            tr.dump(trace, dump / "events.json.gz")
            (dump / "structure.txt").write_text(tr.describe(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        busy = tr.busy(trace)
        plane = min(busy) if busy else None
        lo, hi = trace["window_ns"]
        device["busy_s"] = sum(busy.values()) / len(busy) if busy else 0.0
        device["window_s"] = (hi - lo) / 1e9
        if plane:
            res["breakdown"] = tr.breakdown(trace, plane)
        ctx = types.SimpleNamespace(
            cfg=c.cfg, mix=c.mix, cell=c.cell, window_s=device["window_s"],
            counters=w.counters, trace=trace, plane=plane,
            routing=w.routing, records=w.recs, open_t=w.open_t,
            close_t=w.close_t, peaks=peaks, device=device)
        for m in c.bench["per_layer"]:
            if applies(m, c.name):
                v = reader(m["name"])(ctx)
                if v is not None:
                    res["metrics"][m["name"]] = {"value": v,
                                                 "unit": m["unit"]}
        log(f"trace read in {time.perf_counter() - t0:.2f}s: "
            f"{len(trace['events'])} events")
    else:
        e2e = w.e2e
        values = {"output_tokens_per_s": e2e["output_tokens_per_s"],
                  "ttft_p95_ms": _ms(e2e["ttft_p95_s"]),
                  "itl_p50_ms": _ms(e2e["itl_p50_s"]),
                  "itl_p95_ms": _ms(e2e["itl_p95_s"]),
                  "setup_s": setup_s}
        for m in c.bench["end_to_end"]:
            if applies(m, c.name) and values.get(m["name"]) is not None:
                res["metrics"][m["name"]] = {"value": values[m["name"]],
                                             "unit": m["unit"]}
    log(f"setup_s={setup_s!r} memory_peak_bytes="
        f"{device['memory_peak_bytes']}")

    # -- correct: free the program's state, then the plain reference -------
    eng.close()
    del eng
    free_device(jax)
    checks = judge(c, args.seed, w.recs)
    res["correct"] = passed(checks)
    res["checks"] = checks
    for name, ck in checks.items():
        print(f"check {name}: {ck['value']!r} (limit {ck['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res, allow_nan=False), flush=True)
    return 0


def _ms(s):
    return None if s is None else s * 1e3


if __name__ == "__main__":
    sys.exit(main())
