"""repro.obs: registry determinism, label isolation, Prometheus golden,
and engine telemetry wired end-to-end (ragged m-tile ground truth)."""
import json

import jax
import numpy as np
import pytest

from repro import obs
from repro.kernels.moe_gemm import ragged_tile_stats

jax.config.update("jax_platform_name", "cpu")


class TestHistogram:
    def test_deterministic_bucketing(self):
        reg = obs.Registry()
        h = reg.histogram("lat_seconds", "t", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.01, 0.02, 0.5, 2.0):  # 0.01 is inclusive (le)
            h.observe(v)
        st = h.get()
        assert st["buckets"] == [2, 1, 1, 1]  # last slot = +Inf overflow
        assert st["count"] == 5
        assert st["sum"] == pytest.approx(2.535)
        assert h.cumulative() == {"0.01": 2, "0.1": 3, "1": 4, "+Inf": 5}

    def test_edges_frozen_and_sorted(self):
        reg = obs.Registry()
        h = reg.histogram("h", "", buckets=(1.0, 0.5))
        assert h.buckets == (0.5, 1.0)
        # get-or-create returns the SAME metric; edges cannot be re-declared
        assert reg.histogram("h", "", buckets=(9.0,)) is h
        assert h.buckets == (0.5, 1.0)

    def test_empty_or_duplicate_edges_rejected(self):
        reg = obs.Registry()
        with pytest.raises(ValueError):
            reg.histogram("a", "", buckets=())
        with pytest.raises(ValueError):
            reg.histogram("b", "", buckets=(1.0, 1.0))


class TestQuantiles:
    """Histogram.quantile(): Prometheus histogram_quantile semantics —
    linear interpolation inside the bucket holding the q*count-th
    observation, lower bound 0, overflow clamped to the last edge."""

    def _hist(self):
        reg = obs.Registry()
        h = reg.histogram("q_seconds", "", buckets=(1.0, 2.0, 4.0))
        return h

    def test_in_bucket_interpolation(self):
        h = self._hist()
        for v in (0.5, 1.5, 1.5, 3.0):
            h.observe(v)
        # p50: target=2.0 obs; bucket (1,2] holds obs 2..3 -> interpolate
        # 1 + (2-1) * (2-1)/2 = 1.5
        assert h.quantile(0.5) == pytest.approx(1.5)
        # p25 lands in the first bucket: interpolation starts from the
        # lower bound 0 (not -inf); target == full bucket -> the edge
        assert h.quantile(0.25) == pytest.approx(1.0)
        assert h.quantile(0.0) == pytest.approx(0.0)
        assert h.quantile(1.0) == pytest.approx(4.0)

    def test_overflow_clamps_to_last_edge(self):
        h = self._hist()
        h.observe(100.0)
        h.observe(200.0)
        # both observations are beyond the last finite edge: the best the
        # fixed buckets can say is ">= 4.0" -> clamp, never extrapolate
        assert h.quantile(0.5) == pytest.approx(4.0)
        assert h.quantile(0.99) == pytest.approx(4.0)

    def test_empty_is_nan_and_bounds_checked(self):
        import math
        h = self._hist()
        assert math.isnan(h.quantile(0.5))
        h.observe(0.5)
        with pytest.raises(ValueError):
            h.quantile(-0.1)
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_labeled_series_independent(self):
        reg = obs.Registry()
        h = reg.histogram("q_seconds", "", ("phase",),
                          buckets=(1.0, 2.0))
        h.observe(0.5, phase="prefill")
        h.observe(1.5, phase="decode")
        assert h.quantile(0.5, phase="prefill") < 1.0
        assert h.quantile(0.5, phase="decode") > 1.0
        qs = h.quantiles(phase="decode")
        assert set(qs) == {"p50", "p95", "p99"}

    def test_snapshot_carries_quantiles(self):
        reg = obs.Registry()
        h = reg.histogram("lat_seconds", "", buckets=(1.0, 2.0))
        for v in (0.5, 1.5, 3.0):
            h.observe(v)
        st = reg.snapshot()["histograms"]["lat_seconds"][""]
        assert st["quantiles"]["p50"] == pytest.approx(1.5)
        assert st["quantiles"]["p99"] == pytest.approx(2.0)


class TestLabels:
    def test_series_isolation(self):
        reg = obs.Registry()
        c = reg.counter("calls_total", "", ("scheme", "kind"))
        c.inc(scheme="is", kind="dense")
        c.inc(3, scheme="is", kind="grouped")
        c.inc(scheme="fs", kind="dense")
        assert c.get(scheme="is", kind="dense") == 1
        assert c.get(scheme="is", kind="grouped") == 3
        assert c.get(scheme="fs", kind="grouped") == 0
        assert c.total() == 5

    def test_label_mismatch_rejected(self):
        reg = obs.Registry()
        c = reg.counter("c", "", ("a",))
        with pytest.raises(ValueError):
            c.inc(b="x")
        with pytest.raises(ValueError):
            c.inc()  # missing declared label

    def test_redeclaration_shape_checked(self):
        reg = obs.Registry()
        reg.counter("m", "", ("a",))
        with pytest.raises(ValueError):
            reg.gauge("m", "", ("a",))
        with pytest.raises(ValueError):
            reg.counter("m", "", ("a", "b"))

    def test_counter_monotone(self):
        reg = obs.Registry()
        with pytest.raises(ValueError):
            reg.counter("c", "").inc(-1)


class TestPrometheusGolden:
    def test_golden_snapshot(self):
        reg = obs.Registry()
        reg.counter("b_total", "calls", ("scheme",)).inc(2, scheme="is")
        reg.counter("b_total", "calls", ("scheme",)).inc(scheme="fs")
        reg.gauge("a_depth", "queue").set(3)
        h = reg.histogram("c_seconds", "lat", ("phase",),
                          buckets=(0.1, 1.0))
        h.observe(0.05, phase="decode")
        h.observe(0.5, phase="decode")
        golden = "\n".join([
            '# HELP a_depth queue',
            '# TYPE a_depth gauge',
            'a_depth 3',
            '# HELP b_total calls',
            '# TYPE b_total counter',
            'b_total{scheme="fs"} 1',
            'b_total{scheme="is"} 2',
            '# HELP c_seconds lat',
            '# TYPE c_seconds histogram',
            'c_seconds_bucket{phase="decode",le="0.1"} 1',
            'c_seconds_bucket{phase="decode",le="1"} 2',
            'c_seconds_bucket{phase="decode",le="+Inf"} 2',
            'c_seconds_sum{phase="decode"} 0.55',
            'c_seconds_count{phase="decode"} 2',
        ]) + "\n"
        assert reg.prometheus_text() == golden
        # deterministic: a second render is byte-identical
        assert reg.prometheus_text() == golden


class TestPrometheusConformance:
    """Exposition-format conformance locked with a golden file:
    ascending ``le`` ordering, an explicit ``+Inf`` bucket line,
    ``_sum``/``_count`` emission, and label-value escaping of
    backslash, double-quote, and newline."""

    def test_conformance_golden_file(self):
        import pathlib

        reg = obs.Registry()
        c = reg.counter("req_total", "requests served",
                        ("route", "status"))
        c.inc(3, route="decode", status="ok")
        c.inc(route='we"ird\\path\nx', status="err")
        reg.gauge("queue_depth", "pending requests\nsecond line").set(2)
        h = reg.histogram("lat_seconds", "phase latency", ("phase",),
                          buckets=(0.1, 1.0, 10.0))
        h.observe(0.0625, phase="decode")
        h.observe(0.5, phase="decode")
        h.observe(99.0, phase="decode")
        h.observe(0.25, phase="prefill")
        golden = (pathlib.Path(__file__).parent / "golden"
                  / "prometheus_conformance.txt").read_text()
        assert reg.prometheus_text() == golden

    def test_escaping_unit(self):
        reg = obs.Registry()
        reg.counter("c_total", "", ("p",)).inc(p='a\\b"c\nd')
        line = reg.prometheus_text().splitlines()[-1]
        assert line == 'c_total{p="a\\\\b\\"c\\nd"} 1'


class TestRegistryStackAndEvents:
    def test_use_registry_isolates(self):
        inner = obs.Registry()
        obs.current_registry().counter("x_total", "")
        with obs.use_registry(inner):
            assert obs.current_registry() is inner
            inner2 = obs.Registry()
            with obs.use_registry(inner2):
                assert obs.current_registry() is inner2
            assert obs.current_registry() is inner
        assert obs.current_registry() is not inner

    def test_events_jsonl_roundtrip(self, tmp_path):
        reg = obs.Registry()
        reg.emit({"ev": "tick", "n": 1})
        reg.emit({"ev": "retire", "rid": 7})
        reg.counter("t_total", "").inc()
        p = tmp_path / "m.jsonl"
        n = reg.write_events_jsonl(str(p))
        lines = [json.loads(ln) for ln in p.read_text().splitlines()]
        assert n == 3 and len(lines) == 3
        assert [ln.get("ev") for ln in lines[:2]] == ["tick", "retire"]
        assert lines[0]["seq"] == 1 and lines[1]["seq"] == 2
        snap = lines[-1]["snapshot"]
        assert snap["counters"]["t_total"] == {"": 1.0}
        assert snap["events_total"] == 2

    def test_span_records_histogram_and_event(self):
        reg = obs.Registry()
        with obs.span(reg, "p_seconds", event="tick", phase="decode") as sp:
            sp.fields["tick"] = 0
        assert sp.seconds >= 0
        st = reg.histogram("p_seconds", "", ("phase",)).get(phase="decode")
        assert st["count"] == 1
        ev = reg.events()[-1]
        assert ev["ev"] == "tick" and ev["phase"] == "decode"
        assert ev["tick"] == 0 and "seconds" in ev

    def test_full_event_log_drops_oldest(self, monkeypatch):
        from repro.obs import metrics

        monkeypatch.setattr(metrics, "MAX_EVENTS", 3)
        reg = obs.Registry()
        for i in range(5):
            reg.emit({"ev": "e", "i": i})
        assert [e["i"] for e in reg.events()] == [2, 3, 4]
        snap = reg.snapshot()
        assert snap["events_total"] == 5 and snap["events_dropped"] == 2

    @pytest.mark.parametrize("metric,labels,name", [
        ("engine_phase_seconds", {"phase": "decode"}, "engine.decode"),
        ("engine_host_seconds", {"part": "admit.splice"},
         "engine.admit.splice"),
        ("ptq_run_seconds", {}, "ptq_run"),
    ])
    def test_span_profiler_annotation_name(self, metric, labels, name):
        assert obs.Span(obs.Registry(), metric, **labels).name == name


def test_engine_spans_on_profiler_host_plane(tmp_path):
    """Under a CPU ``jax.profiler`` trace the engine's spans land on the
    host plane (``engine.<phase>``, ``engine.<part>``, ``engine.tick``),
    on the same clock as the device's events."""
    import glob

    from repro.models.config import ModelConfig
    from repro.models.registry import get_model
    from repro.nn import spec as S
    from repro.serving.engine import Engine, ServeConfig

    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      dtype="float32", q_chunk=16, kv_chunk=16, remat=False)
    api = get_model(cfg)
    params = S.materialize(api.param_specs(cfg, None), jax.random.PRNGKey(0))
    with obs.use_registry(obs.Registry()):
        eng = Engine(api, cfg, params,
                     ServeConfig(max_slots=2, max_seq=32, prefill_len=8,
                                 max_new_tokens=3))
        eng.submit([1, 2, 3])
        eng.run()  # compiles outside the trace
        with jax.profiler.trace(str(tmp_path)):
            eng.submit([4, 5, 6])
            eng.run()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in data.planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events}
    assert {"engine.tick", "engine.admit", "engine.prefill",
            "engine.decode", "engine.retire", "engine.admit.inputs",
            "engine.admit.splice", "engine.admit.first_token",
            "engine.admit.routing", "engine.decode.inputs",
            "engine.decode.logits_to_host", "engine.decode.sample",
            "engine.decode.nan_guard", "engine.decode.routing"} <= names


class TestEngineTelemetry:
    """Engine run (pallas_interpret, Mixtral smoke shape): ragged
    executed-m-tile counters must match ``ragged_tile_stats`` ground truth
    (the same accounting tests/test_moe_ragged.py validates against the
    kernel), and instrumentation must add zero retraces."""

    @pytest.fixture(scope="class")
    def run(self):
        from repro.core import ptq
        from repro.core.recipe import DEFAULT_RECIPE
        from repro.models import moe
        from repro.models.registry import get_arch, get_model
        from repro.nn import spec as S
        from repro.serving.engine import Engine, ServeConfig

        cfg = get_arch("mixtral-8x7b", smoke=True)
        api = get_model(cfg)
        params = S.materialize(api.param_specs(cfg, None),
                               jax.random.PRNGKey(0))
        reg = obs.Registry()
        with obs.use_registry(reg):
            qp = ptq.post_training_quantize(api, cfg, params,
                                            DEFAULT_RECIPE, None)
            sc = ServeConfig(max_slots=2, max_seq=32, prefill_len=8,
                             max_new_tokens=3,
                             kernel_mode="pallas_interpret")
            trace = moe.start_routing_trace()
            eng = Engine(api, cfg, qp, sc, recipe=DEFAULT_RECIPE)
            rng = np.random.default_rng(0)
            for _ in range(3):
                eng.submit(rng.integers(1, cfg.vocab_size, 6).tolist())
            outs = eng.run()
            moe.stop_routing_trace(trace)
            eng.close()
        return reg, eng, trace, outs

    def test_ragged_m_tiles_match_ground_truth(self, run):
        reg, eng, trace, _ = run
        assert trace, "routing trace captured no records"
        expected_exec = expected_total = 0
        for rec in trace:
            counts, C = rec["counts"], rec["capacity"]
            for g in range(counts.shape[0]):
                st = ragged_tile_stats([int(v) for v in counts[g]], C)
                expected_total += st["dense_m_tiles"]
                expected_exec += (st["ragged_m_tiles"]
                                  if counts.shape[0] == 1
                                  else st["dense_m_tiles"])
        tiles = reg.snapshot()["counters"]["engine_moe_m_tiles_total"]
        assert tiles['kind="executed"'] == expected_exec
        assert tiles['kind="total"'] == expected_total
        assert 0 < expected_exec < expected_total  # skipping really engaged

    def test_no_retrace_and_tick_accounting(self, run):
        reg, eng, _, outs = run
        assert eng.decode_traces == 1
        assert eng.prefill_traces == 1
        snap = reg.snapshot()
        c = snap["counters"]
        assert c["engine_traces_total"] == {'fn="decode"': 1.0,
                                            'fn="prefill"': 1.0}
        assert c["engine_ticks_total"][""] == eng.ticks
        assert c["engine_requests_total"] == {'event="submitted"': 3.0,
                                              'event="admitted"': 3.0,
                                              'event="retired"': 3.0}
        # conservation: every submitted rid reached exactly one outcome
        out = c["engine_request_outcomes_total"]
        assert out['outcome="ok"'] == 3.0
        assert sum(out.values()) == c["engine_requests_total"][
            'event="submitted"']
        assert c["engine_tokens_total"][""] == sum(
            len(v) - 1 for v in outs.values())  # first token from prefill
        # per-request latency histograms: one observation per request
        h = snap["histograms"]
        assert h["engine_ttft_seconds"][""]["count"] == 3
        assert h["engine_tpot_seconds"][""]["count"] == 3
        assert h["engine_phase_seconds"]['phase="decode"']["count"] \
            == eng.ticks
        # one queue wait per admitted request; host sections per step
        assert h["engine_queue_wait_seconds"][""]["count"] == 3
        host = h["engine_host_seconds"]
        for part in ("decode.inputs", "decode.logits_to_host",
                     "decode.sample", "decode.nan_guard", "decode.routing"):
            assert host[f'part="{part}"']["count"] == eng.ticks
        for part in ("admit.inputs", "admit.splice", "admit.first_token",
                     "admit.routing"):
            assert host[f'part="{part}"']["count"] == 3
        # headline health keys exist in the snapshot (explicit zeros ok)
        assert c["alpha_cap_events_total"] == {"": 0.0}
        assert any('scheme="w4a8-is"' in k
                   for k in c["qgemm_calls_total"])

    def test_events_carry_decode_latency_and_rids(self, run):
        reg, _, _, outs = run
        evs = reg.events()
        ticks = [e for e in evs if e.get("ev") == "tick"]
        assert ticks and all("seconds" in e and "slots_active" in e
                             for e in ticks)
        retired = {e["rid"] for e in evs if e.get("ev") == "retire"}
        assert retired == set(outs)

    def test_timeline_lifecycle_exactly_once(self, run):
        # interpret-mode quantized run: every admitted request's
        # lifecycle events appear exactly once in the exported timeline
        reg, eng, _, outs = run
        names = [e["name"] for e in obs.build_trace(reg)["traceEvents"]]
        for rid in outs:
            for stage in ("queued", "prefill", "TTFT", "retire"):
                assert names.count(f"r{rid} {stage}") == 1, \
                    f"r{rid} {stage} not exactly-once"
            assert names.count(f"r{rid} decode") == len(outs[rid]) - 1
        assert names.count("prefill") == len(outs)  # engine-phase lane
