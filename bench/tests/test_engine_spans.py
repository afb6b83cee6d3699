"""The readers of the engine's host spans and queue wait, on synthetic
window deltas (``ctx.counters`` as ``bench/run.py`` builds them)."""
import importlib.util
import pathlib
import types

import pytest

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ctx(counters):
    return types.SimpleNamespace(counters=counters)


def test_host_ms_per_tick_sums_every_part_and_retire():
    c = {"engine_ticks_total": {"": 10.0},
         "engine_host_seconds:sum": {'part="decode.sample"': 0.012,
                                     'part="decode.logits_to_host"': 0.02,
                                     'part="admit.splice"': 0.003},
         "engine_phase_seconds:sum": {'phase="retire"': 0.005,
                                      'phase="decode"': 0.9}}
    assert reader("engine.host_ms_per_tick")(ctx(c)) == pytest.approx(4.0)


@pytest.mark.parametrize("counters", [
    {},                                                   # parent program
    {"engine_ticks_total": {"": 0.0},
     "engine_host_seconds:sum": {'part="decode.sample"': 0.1}},
    {"engine_ticks_total": {"": 5.0}},                    # no host spans
])
def test_host_ms_per_tick_silent_without_ticks_or_spans(counters):
    assert reader("engine.host_ms_per_tick")(ctx(counters)) is None


def test_queue_wait_ms_is_window_mean():
    c = {"engine_queue_wait_seconds:sum": {"": 0.9},
         "engine_queue_wait_seconds:count": {"": 12}}
    assert reader("sched.queue_wait_ms")(ctx(c)) == pytest.approx(75.0)


@pytest.mark.parametrize("counters", [
    {},
    {"engine_queue_wait_seconds:sum": {"": 0.0},
     "engine_queue_wait_seconds:count": {"": 0}},
])
def test_queue_wait_ms_silent_without_admissions(counters):
    assert reader("sched.queue_wait_ms")(ctx(counters)) is None
