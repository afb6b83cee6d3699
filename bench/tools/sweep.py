"""Find a cell's knee: the highest arrival rate it sustains.

    python3 bench/tools/sweep.py --workload mixtral-8x7b.chat \\
        --rates 1.0,1.5,2.0,2.5 --seconds 30 --seed 11

One process builds the cell once and serves one open-loop window per
rate, in order, draining the engine between windows. For each rate it
prints one JSON line: output tokens/s against the tokens/s offered, the
TTFT median and 95th percentile, the inter-token gaps, and the backlog
at the window's close (requests due in the window that had not started).
Above the knee the backlog grows with the window and TTFT with it. Run
it on the chip; the cell's rate (``bench/cells/<workload>.json``) is set
by hand from what it shows.
"""
from __future__ import annotations

import argparse
import copy
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import run  # noqa: E402


def drain(eng) -> None:
    for rid, _ in list(eng.queue):
        eng.cancel(rid)
    for s in eng.slots:
        if s.active:
            eng.cancel(s.request_id)


def main(argv=None, *, require_tpu: bool = True,
         kernel_mode: str = "pallas", bench_file=None, data=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates, requests/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args(argv)
    import jax

    run.use_compile_cache(jax)
    if require_tpu and jax.devices()[0].platform != "tpu":
        run.log("the sweep measures the chip; JAX found no TPU")
        return 1
    from bench import generator, program

    program.import_program(run.ROOT)
    c = run.load_cell(args.workload, *(
        (bench_file, data) if bench_file else ()))
    eng = run.build(c, args.seed, kernel_mode)
    mix = c.mix
    mean_out = sum(r.out_len for r in generator.schedule(
        mix, {"process": "poisson", "rate_per_s": 1.0}, seed=0,
        segments_s=(400.0,), vocab=16)) / 400.0
    for rate in (float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(c)
        cell.cell["arrival"]["rate_per_s"] = rate
        w = run.serve(eng, cell, args.seed, args.seconds)
        started = sum(1 for r in w.recs if w.open_t <= r.due < w.close_t
                      and r.times and r.times[0] < w.close_t)
        due = w.e2e["attempted"]
        ttft = sorted((r.times[0] if r.times else float("inf")) - r.due
                      for r in w.recs if w.open_t <= r.due < w.close_t)
        print(json.dumps({
            "rate_per_s": rate,
            "offered_tokens_per_s": rate * mean_out,
            "output_tokens_per_s": w.e2e["output_tokens_per_s"],
            "ttft_p50_ms": 1e3 * ttft[len(ttft) // 2] if ttft else None,
            "ttft_p95_ms": (1e3 * w.e2e["ttft_p95_s"]
                            if w.e2e["ttft_p95_s"] is not None else None),
            "itl_p50_ms": (1e3 * w.e2e["itl_p50_s"]
                           if w.e2e["itl_p50_s"] is not None else None),
            "itl_p95_ms": (1e3 * w.e2e["itl_p95_s"]
                           if w.e2e["itl_p95_s"] is not None else None),
            "due": due, "backlog_at_close": due - started,
            "failed": w.e2e["failed"]}), flush=True)
        drain(eng)
    return 0


if __name__ == "__main__":
    sys.exit(main())
