"""Readings that a cell's ``correct`` limit is set from.

    python3 bench/tools/calibrate.py --workload mixtral-8x7b.chat \\
        --seeds 101,102,103 --seconds 10 [--control]

For each seed, in one process: build the cell from that seed, serve one
open-loop window at the cell's own load, free the program's state, and
compare the served tokens with the plain reference exactly as a run does
(``bench/run.py``'s ``judge``). With ``--control`` the reference is also
computed with int4 activations, the next precision below the
configuration's int8, and the gap of the tokens it puts first is read at
the same positions (``bench/check.py``'s ``control_gap``). One JSON line
per seed. The limit lies between the largest program reading over a
dozen seeds or more and the smallest control reading (``PERF.md``).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import run  # noqa: E402


def readings(c, seed: int, seconds: float, *, control: bool,
             kernel_mode: str = "pallas") -> dict:
    """One seed's readings: every number of ``bench/check.py``'s
    ``NUMBERS`` for the program and, with ``control``, for the control."""
    import jax

    from bench import check

    bits = c.cfg["quantization"]["a_bits"]
    eng = run.build(c, seed, kernel_mode)
    w = run.serve(eng, c, seed, seconds)
    eng.close()
    del eng
    run.free_device(jax)
    t0 = time.perf_counter()
    out = {"seed": seed}
    cmp = run.compared(c, seed, w.recs)
    gaps = None
    if cmp is not None:
        _, tokens, rows, served, mask = cmp
        ref = check.reference_logits(c.cfg, seed, tokens, rows, bits=bits)
        gaps = check.position_gaps(ref, served, mask)
        # every number a configuration may compare, named or not
        out.update({k: fn(gaps) for k, fn in check.NUMBERS.items()})
        if control:
            low = check.reference_logits(c.cfg, seed, tokens, rows,
                                         bits=bits // 2)
            low_gaps = check.control_gaps(ref, low, mask)
            out.update({"control_" + k: fn(low_gaps)
                        for k, fn in check.NUMBERS.items()})
            del low
        del ref
    checks = run.checks_of(c, gaps, w.recs)
    out.update(correct=run.passed(checks),
               served_tokens_compared=checks[
                   "served_tokens_compared"]["value"],
               error_outcomes=checks["error_outcomes"]["value"],
               reference_s=time.perf_counter() - t0)
    run.free_device(jax)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    import jax

    run.use_compile_cache(jax)
    if jax.devices()[0].platform != "tpu":
        run.log("calibration reads the chip; JAX found no TPU")
        return 1
    from bench import program

    program.import_program(run.ROOT)
    c = run.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(c, seed, args.seconds,
                                  control=args.control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
