"""The reader of the engine's host time on the decode logits, on
synthetic window deltas (``ctx.counters`` as ``bench/run.py`` builds
them)."""
import importlib.util
import pathlib
import types

import pytest

PATH = (pathlib.Path(__file__).resolve().parents[1] / "metrics"
        / "engine.logits_host_ms_per_tick.py")


def read(counters):
    spec = importlib.util.spec_from_file_location("m_logits_host", PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(types.SimpleNamespace(counters=counters))


def test_sums_the_transfer_and_the_scan_over_ticks():
    c = {"engine_ticks_total": {"": 20.0},
         "engine_host_seconds:sum": {'part="decode.logits_to_host"': 0.12,
                                     'part="decode.nan_guard"': 0.03,
                                     'part="decode.sample"': 0.5,
                                     'part="admit.splice"': 0.2},
         "engine_phase_seconds:sum": {'phase="retire"': 0.4}}
    assert read(c) == pytest.approx(7.5)


@pytest.mark.parametrize("counters", [
    {},
    {"engine_ticks_total": {"": 0.0},
     "engine_host_seconds:sum": {'part="decode.logits_to_host"': 0.1,
                                 'part="decode.nan_guard"': 0.01}},
    {"engine_ticks_total": {"": 5.0}},
    # a program that times the transfer but not the scan (the parent)
    {"engine_ticks_total": {"": 5.0},
     "engine_host_seconds:sum": {'part="decode.logits_to_host"': 0.1,
                                 'part="decode.sample"': 0.05}},
])
def test_silent_without_ticks_or_the_scan(counters):
    assert read(counters) is None
