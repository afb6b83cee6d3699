"""The trace reduction on a small recorded trace.

``data/trace.json.gz`` is a slice of a traced ``mixtral-8x7b.chat`` run
on a TPU v5e (``bench/run.py --trace 1 --dump``), cut to a few decode
steps around one prefill: what ``bench/trace.py``'s ``load`` keeps of a
real ``.xplane.pb``.
"""
import json
import pathlib
import types

import pytest

from bench import trace as tr

DATA = pathlib.Path(__file__).parent / "data"
ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def trace():
    return tr.load_dump(DATA / "trace.json.gz")


@pytest.fixture(scope="module")
def plane(trace):
    return min(tr.busy(trace))


def test_op_names():
    assert tr.op_name("%fusion.12 = bf16[2]{0} fusion(...)") == "fusion"
    assert tr.op_name("%fg_gemm_integer_scale.26 = f32[32,4096] "
                      "custom-call(...)") == "fg_gemm_integer_scale"
    assert tr.op_name("%copy-start.11 = (f32[8]) copy-start(...)") \
        == "copy-start"
    assert tr.op_name("%broadcast.31.clone = bf16[8] broadcast(...)") \
        == "broadcast"
    assert tr.op_name("jit_decode_fn(15841740132358481371)") \
        == "jit_decode_fn"


def test_busy_is_the_union_of_self_times(trace, plane):
    lo, hi = trace["window_ns"]
    busy = tr.busy(trace)[plane]
    assert 0 < busy <= (hi - lo) / 1e9
    own = tr.self_seconds(trace, plane)
    assert sum(own.values()) == pytest.approx(busy, rel=1e-9)
    gaps = tr.idle_gaps(trace, plane)
    idle = sum(b - a for a, b, _ in gaps) / 1e9
    assert busy + idle == pytest.approx((hi - lo) / 1e9, rel=1e-9)


def test_kernel_time_inside_whole_decode_runs(trace, plane):
    lo, hi = trace["window_ns"]
    kernel = "fg_grouped_gemm_integer_scale_ragged"
    secs, runs = tr.kernel_seconds(trace, plane, kernel=kernel,
                                   module="jit_decode_fn")
    whole = [m for m in tr.modules(trace, plane)
             if m["name"] == "jit_decode_fn" and m["start_ns"] >= lo
             and m["start_ns"] + m["dur_ns"] <= hi]
    assert runs == len(whole) >= 2
    by_hand = sum(e["dur_ns"] for e in tr.ops(trace, plane)
                  if e["name"] == kernel and e["custom"] and any(
                      m["start_ns"] <= e["start_ns"]
                      < m["start_ns"] + m["dur_ns"] for m in whole))
    assert secs == pytest.approx(by_hand / 1e9) and secs > 0
    # the same kernel in the prefill run is not counted
    all_calls = sum(e["dur_ns"] for e in tr.ops(trace, plane)
                    if e["name"] == kernel and e["custom"]) / 1e9
    assert all_calls > secs


def test_breakdown_shape(trace, plane):
    b = tr.breakdown(trace, plane)
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(b[key]) <= 10
        secs = [s for _, s in b[key]]
        assert secs == sorted(secs, reverse=True)
    assert {lab for lab, _ in b["idle_gaps"]} <= {
        "bench.step", "bench.collect", "bench.submit", "bench.idle",
        "no host span"}


def test_decode_rooflines_stay_under_100(trace, plane):
    from bench import run

    cfg = json.loads((ROOT / "bench/configs/mixtral-8x7b.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/chat.json").read_text())
    peaks = json.loads((ROOT / "bench/peaks.json").read_text())[
        "devices"]["TPU v5 lite"]
    # 32 slots x top-2 over 8 experts, as evenly as the router can
    routing = [{"capacity": 32, "counts": [[8] * 8]}] * 8
    ctx = types.SimpleNamespace(cfg=cfg, mix=mix, trace=trace, plane=plane,
                                peaks=peaks, routing=routing)
    for name in ("gemm_is.decode_roofline", "moe_gemm_is.decode_roofline"):
        v = run.reader(name)(ctx)
        assert v is not None and 0 < v <= 100, (name, v)
