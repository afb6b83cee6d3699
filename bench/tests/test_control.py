"""The control reads incorrect; the program reads correct.

At a size a test run can hold (the small cells of ``tests/data``), on the
CPU with the Pallas kernels interpreted: a run's served tokens are read
against the plain reference (the program's readings) and against the
reference computed with int4 activations, the next precision below the
configuration's int8 (the control's readings). The program stays within
every limit of its configuration; the control breaks at least one.
"""
import pathlib

import pytest

from bench import run
from bench.tools import calibrate

DATA = pathlib.Path(__file__).parent / "data"
SEEDS = (11, 2 ** 31 + 7, 4_000_000_123)


@pytest.mark.parametrize("workload", ["mixtral-tiny.tiny",
                                      "qwen2-tiny.tiny"])
def test_control_fails_where_the_program_passes(workload):
    c = run.load_cell(workload, DATA / "bench.json", DATA)
    limits = c.cfg["correct"]
    for seed in SEEDS:
        r = calibrate.readings(c, seed, 2.0, control=True,
                               kernel_mode="pallas_interpret")
        assert r["correct"], r
        assert any(r["control_" + k] > v for k, v in limits.items()), r
