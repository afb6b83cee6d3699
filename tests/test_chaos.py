"""Serving fault-injection suite (``pytest -m chaos``).

Drives ``repro.serving.chaos`` against the real engine on a tiny dense
model and asserts the ISSUE-10 robustness contract: co-batched requests
stay bit-exact under injected faults, steady-state decode holds ONE
trace per established kernel route (``decode_traces == 1 + fallbacks``),
and the conservation law — every submitted rid ends in exactly one
terminal outcome — survives NaNs, kernel exceptions, deadline overruns,
queue floods, cancellation, and engine aborts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.models.config import ModelConfig
from repro.models.registry import get_model
from repro.nn import spec as S
from repro.serving.chaos import (ChaosConfig, ChaosMonkey, KernelFault,
                                 NanFault, SlowTick, flood)
from repro.serving.engine import OUTCOMES, Engine, EngineAborted, ServeConfig

jax.config.update("jax_platform_name", "cpu")

pytestmark = pytest.mark.chaos


class StepClock:
    """Monotonic stub: every reading advances by ``step``; ``advance``
    jumps time (the chaos SlowTick sleep_fn)."""

    def __init__(self, step: float = 0.0):
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t

    def advance(self, s: float) -> None:
        self.t += s


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
                      dtype="float32", q_chunk=16, kv_chunk=16, remat=False)
    api = get_model(cfg)
    params = S.materialize(api.param_specs(cfg, None), jax.random.PRNGKey(0))
    return api, cfg, params


def _prompts(n, seed=0, size=8):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, size=size).tolist() for _ in range(n)]


def _engine(tiny, **sc_kw):
    api, cfg, params = tiny
    sc_kw.setdefault("max_slots", 3)
    sc_kw.setdefault("max_seq", 64)
    sc_kw.setdefault("prefill_len", 8)
    sc_kw.setdefault("max_new_tokens", 6)
    return Engine(api, cfg, params, ServeConfig(**sc_kw))


def _conserved(reg: obs.Registry, eng: Engine) -> None:
    """The conservation law, checked from the metrics snapshot AND the
    engine's own bookkeeping: every submitted rid has exactly one
    terminal outcome, no slot is left active, nothing is queued."""
    c = reg.snapshot()["counters"]
    outcomes = c["engine_request_outcomes_total"]
    submitted = c["engine_requests_total"]['event="submitted"']
    assert sum(outcomes.values()) == submitted
    assert len(eng.outcomes) == submitted
    assert set(eng.outcomes.values()) <= set(OUTCOMES)


def _baseline(tiny, prompts, **sc_kw):
    """Fault-free token streams for bit-exactness comparisons."""
    with obs.use_registry(obs.Registry()):
        eng = _engine(tiny, **sc_kw)
        rids = [eng.submit(p) for p in prompts]
        outs = eng.run()
        eng.close()
    return {r: outs[r] for r in rids}


# ---------------------------------------------------------------------------
# NaN quarantine
# ---------------------------------------------------------------------------


def test_nan_quarantine_cobatch_bit_exact(tiny):
    """A poisoned slot retires with outcome=nan; its co-batched
    neighbours finish BIT-EXACT vs a fault-free run, on one decode
    trace."""
    prompts = _prompts(3, seed=0)
    ref = _baseline(tiny, prompts)
    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = _engine(tiny)
        monkey = ChaosMonkey(ChaosConfig(
            nan_logits=(NanFault(tick=2, rid=1),))).install(eng)
        rids = [eng.submit(p) for p in prompts]
        outs = eng.run()
        eng.close()
    assert monkey.injected == [{"kind": "nan", "tick": 2, "rid": 1,
                                "slot": 1}]
    assert eng.outcome(1) == "nan"
    # the poisoned request delivers its pre-fault partial stream (the
    # garbage token is never appended) and it is a prefix of the
    # fault-free stream
    assert outs[1] == ref[1][:len(outs[1])]
    assert len(outs[1]) < len(ref[1])
    # co-batched requests: bit-exact, full length
    for r in (0, 2):
        assert eng.outcome(r) == "ok"
        assert outs[r] == ref[r]
    assert eng.decode_traces == 1 and eng.fallbacks == 0
    _conserved(reg, eng)
    c = reg.snapshot()["counters"]
    assert c["engine_request_outcomes_total"]['outcome="nan"'] == 1
    assert c["engine_request_outcomes_total"]['outcome="ok"'] == 2


def test_nan_slot_reuse_after_quarantine(tiny):
    """A quarantined slot is freed and reused: the next request admits
    into the SAME slot and serves a clean, bit-exact stream."""
    api, cfg, params = tiny
    prompts = _prompts(2, seed=3)
    ref = _baseline(tiny, prompts)
    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = _engine(tiny, max_slots=1)
        # poison the single slot's first decode tick: rid 0 dies, rid 1
        # then admits into the SAME slot and must be unaffected
        ChaosMonkey(ChaosConfig(
            nan_logits=(NanFault(tick=0, rid=0),))).install(eng)
        for p in prompts:
            eng.submit(p)
        outs = eng.run()
        eng.close()
    assert eng.outcome(0) == "nan"
    assert eng.outcome(1) == "ok"
    assert outs[1] == ref[1]
    _conserved(reg, eng)


# ---------------------------------------------------------------------------
# Kernel faults, retry, breaker
# ---------------------------------------------------------------------------


def test_kernel_fault_below_threshold_retries_bit_exact(tiny):
    """A transient decode exception is retried WITHOUT advancing the
    tick or the sampling stream: final streams bit-exact vs fault-free,
    still one trace, no fallback."""
    prompts = _prompts(3, seed=1)
    ref = _baseline(tiny, prompts)
    reg = obs.Registry()
    with obs.use_registry(reg):
        # a fallback route exists, so "no fallback" below is the breaker
        # holding under its threshold, not the route chain being empty
        eng = _engine(tiny, breaker_threshold=3,
                      fallback_kernel_mode="reference")
        monkey = ChaosMonkey(ChaosConfig(
            kernel_failures=(KernelFault(tick=1, count=2),))).install(eng)
        rids = [eng.submit(p) for p in prompts]
        outs = eng.run()
        eng.close()
    assert [e["kind"] for e in monkey.injected] == ["kernel", "kernel"]
    assert {r: outs[r] for r in rids} == ref
    assert all(eng.outcome(r) == "ok" for r in rids)
    assert eng.decode_traces == 1 and eng.fallbacks == 0
    c = reg.snapshot()["counters"]
    assert c["engine_kernel_failures_total"]['phase="decode"'] == 2
    _conserved(reg, eng)


def test_breaker_trips_fallback_and_reestablishes(tiny):
    """breaker_threshold consecutive decode failures trip the fallback:
    kernel_mode swaps, decode re-jits EXACTLY once more
    (decode_traces == 1 + fallbacks), and every request still finishes
    ok bit-exact (the tiny model is unquantized, so both routes compute
    the same graph)."""
    prompts = _prompts(3, seed=2)
    ref = _baseline(tiny, prompts, kernel_mode="reference")
    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = _engine(tiny, kernel_mode="pallas_interpret",
                      fallback_kernel_mode="reference",
                      breaker_threshold=2)
        ChaosMonkey(ChaosConfig(
            kernel_failures=(KernelFault(tick=1, count=2),))).install(eng)
        rids = [eng.submit(p) for p in prompts]
        outs = eng.run()
        eng.close()
    assert eng.fallbacks == 1
    assert eng.decode_traces == 1 + eng.fallbacks == 2
    assert eng.cfg.kernel_mode == "reference"
    assert {r: outs[r] for r in rids} == ref
    c = reg.snapshot()["counters"]
    assert c["engine_fallback_events_total"][
        'reason="decode_exception"'] == 1
    fallbacks = [e for e in reg.events() if e.get("ev") == "fallback"]
    assert fallbacks and fallbacks[0]["from"] == "pallas_interpret" \
        and fallbacks[0]["to"] == "reference"
    _conserved(reg, eng)


def test_breaker_exhausted_aborts_with_error_outcomes(tiny):
    """With no fallback route left, a persistent failure aborts the
    engine: EngineAborted propagates, every in-flight request retires
    with outcome=error, and NO slot stays active (teardown-under-fault
    contract for the driver's finally-flush)."""
    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = _engine(tiny, fallback_kernel_mode=None, breaker_threshold=2)
        ChaosMonkey(ChaosConfig(
            kernel_failures=(KernelFault(tick=0, count=99),))).install(eng)
        rids = [eng.submit(p) for p in _prompts(3, seed=4)]
        with pytest.raises(EngineAborted, match="no fallback route"):
            eng.run()
    assert all(eng.outcome(r) == "error" for r in rids)
    assert not any(s.active for s in eng.slots)
    assert eng.queue == []
    _conserved(reg, eng)
    # the abort is a distinct timeline marker
    names = [e["name"] for e in obs.timeline.trace_events(reg.events())]
    assert any(n.startswith("engine abort:") for n in names)
    assert any(n.startswith("kernel_failure:decode") for n in names)
    eng.close()
    eng.close()  # idempotent


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_first_trace_failure_propagates_without_fallback(tiny, phase):
    """An exception raised while a route first traces/compiles (a kernel
    the compiler refuses) is a program fault: it is counted, then it
    propagates out of Engine.run — even with a fallback route and a
    breaker threshold of 1, nothing is served by another route."""
    from repro.models.registry import ModelApi

    api, cfg, params = tiny
    mode = "train" if phase == "prefill" else "decode"

    def apply(p, c, tokens, **kw):
        if kw.get("mode") == mode:
            raise RuntimeError("refused at lowering")
        return api.apply(p, c, tokens, **kw)

    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = Engine(ModelApi(api.param_specs, api.cache_specs, apply),
                     cfg, params,
                     ServeConfig(max_slots=2, max_seq=64, prefill_len=8,
                                 max_new_tokens=4,
                                 kernel_mode="pallas_interpret",
                                 fallback_kernel_mode="reference",
                                 breaker_threshold=1))
        rids = [eng.submit(p) for p in _prompts(3, seed=11)]
        with pytest.raises(RuntimeError, match="refused at lowering"):
            eng.run()
        eng.close()
    assert eng.fallbacks == 0 and eng.cfg.kernel_mode == "pallas_interpret"
    assert all(eng.outcome(r) == "error" for r in rids)
    assert not any(s.active for s in eng.slots)
    c = reg.snapshot()["counters"]
    assert c["engine_kernel_failures_total"][f'phase="{phase}"'] == 1
    assert not any(c.get("engine_fallback_events_total", {}).values())
    _conserved(reg, eng)


def test_nan_streak_trips_breaker(tiny):
    """Persistently poisoned logits are a quant-health alarm: after
    breaker_threshold consecutive poisoned ticks the engine degrades to
    the fallback route instead of burning ticks on NaNs."""
    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = _engine(tiny, max_slots=2, kernel_mode="pallas_interpret",
                      fallback_kernel_mode="reference",
                      breaker_threshold=2, max_new_tokens=12)
        # poison every active slot for two consecutive ticks: exactly
        # the streak that trips the breaker (a third poisoned tick after
        # the fallback would exhaust the route chain and abort)
        ChaosMonkey(ChaosConfig(nan_logits=tuple(
            NanFault(tick=t) for t in (1, 2)))).install(eng)
        for p in _prompts(6, seed=5):
            eng.submit(p)
        eng.run()
        eng.close()
    assert eng.fallbacks == 1
    assert eng.decode_traces == 2
    c = reg.snapshot()["counters"]
    assert c["engine_fallback_events_total"]['reason="nan_logits"'] == 1
    assert c["engine_request_outcomes_total"]['outcome="nan"'] > 0
    _conserved(reg, eng)


def test_external_breaker_trip(tiny):
    """External quant-health monitors can force the fallback via
    Engine.trip_breaker."""
    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = _engine(tiny, kernel_mode="pallas_interpret",
                      fallback_kernel_mode="reference")
        eng.trip_breaker("alpha_cap_alarm")
        rids = [eng.submit(p) for p in _prompts(2, seed=6)]
        outs = eng.run()
        eng.close()
    assert eng.fallbacks == 1 and eng.cfg.kernel_mode == "reference"
    assert all(eng.outcome(r) == "ok" for r in rids)
    assert reg.snapshot()["counters"]["engine_fallback_events_total"][
        'reason="alpha_cap_alarm"'] == 1
    assert len(outs) == 2
    _conserved(reg, eng)


# ---------------------------------------------------------------------------
# Deadlines, cancellation, backpressure
# ---------------------------------------------------------------------------


def test_deadline_timeout_active_and_queued(tiny):
    """Deadline overruns retire with outcome=timeout — mid-decode with
    partial output, and straight from the queue for requests that never
    reach a slot (driven deterministically by an injected clock)."""
    clock = StepClock(step=0.01)
    reg = obs.Registry(clock=clock)
    with obs.use_registry(reg):
        eng = _engine(tiny, max_slots=1, max_new_tokens=30,
                      deadline_s=1.0)
        rid_a, rid_b = (eng.submit(p) for p in _prompts(2, seed=7))
        # jump to just shy of the deadlines: rid_a admits and overruns
        # within its first ticks; rid_b expires straight from the queue
        clock.advance(0.9)
        outs = eng.run()
        eng.close()
    assert eng.outcome(rid_a) == "timeout"
    assert 0 < len(outs[rid_a]) < 30  # partial stream delivered
    # the queued request's deadline expired before the slot freed
    assert eng.outcome(rid_b) == "timeout"
    retires = {e["rid"]: e for e in reg.events()
               if e.get("ev") == "retire"}
    assert retires[rid_b].get("where") == "queued"
    _conserved(reg, eng)


def test_cancel_queued_and_active(tiny):
    clock = StepClock(step=0.0)
    reg = obs.Registry(clock=clock)
    with obs.use_registry(reg):
        eng = _engine(tiny, max_slots=1, max_new_tokens=10)
        rid_a, rid_b = (eng.submit(p) for p in _prompts(2, seed=8))
        assert eng.cancel(rid_b) is True          # queued -> cancelled
        assert eng.cancel(rid_b) is False         # already terminal
        assert eng.cancel(999) is False           # unknown rid
        eng.run(max_ticks=3)                      # rid_a still active
        assert eng.outcome(rid_a) is None
        assert eng.cancel(rid_a) is True          # active -> cancelled
        assert not any(s.active for s in eng.slots)  # slot freed
        eng.close()
    assert eng.outcome(rid_a) == "cancelled"
    assert eng.outcome(rid_b) == "cancelled"
    assert len(eng.outputs[rid_a]) > 0            # partial tokens kept
    _conserved(reg, eng)


def test_queue_flood_backpressure(tiny):
    """max_queue bounds admission: the surplus of a flood is REJECTED
    (terminal outcome, no silent growth) and the accepted requests all
    finish ok."""
    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = _engine(tiny, max_slots=2, max_queue=3)
        rids = flood(eng, 8, prompt=[1, 2, 3])
        rejected = [r for r in rids if eng.outcome(r) == "rejected"]
        assert len(rejected) == 5  # 8 submitted, queue bound 3
        outs = eng.run()
        eng.close()
    accepted = [r for r in rids if r not in rejected]
    assert all(eng.outcome(r) == "ok" for r in accepted)
    assert set(outs) == set(accepted)
    c = reg.snapshot()["counters"]
    assert c["engine_request_outcomes_total"]['outcome="rejected"'] == 5
    retires = [e for e in reg.events() if e.get("ev") == "retire"
               and e.get("outcome") == "rejected"]
    assert all(e["reason"] == "queue_full" for e in retires)
    _conserved(reg, eng)


def test_overlength_prompt_rejected_not_truncated(tiny):
    """Prompts longer than prefill_len are rejected with a structured
    reason — silent clipping only happens under the explicit
    truncate_prompts opt-in."""
    long_prompt = list(range(1, 20))  # 19 > prefill_len=8
    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = _engine(tiny)
        rid = eng.submit(long_prompt)
        assert eng.outcome(rid) == "rejected"
        assert eng.queue == []
        ev = [e for e in reg.events() if e.get("ev") == "retire"][-1]
        assert ev["reason"] == "prompt_overlength"
        assert eng.run() == {}  # nothing admitted
        eng.close()
        _conserved(reg, eng)
    # explicit opt-in: same prompt is clipped to prefill_len and served
    with obs.use_registry(obs.Registry()):
        eng2 = _engine(tiny, truncate_prompts=True)
        rid2 = eng2.submit(long_prompt)
        outs = eng2.run()
        eng2.close()
    assert eng2.outcome(rid2) == "ok"
    assert len(outs[rid2]) == 6


# ---------------------------------------------------------------------------
# Watchdog, double-retire guard, mixed drill
# ---------------------------------------------------------------------------


def test_slow_tick_watchdog(tiny):
    """An injected stall inside the decode window trips the Heartbeat
    straggler path: engine_slow_ticks_total + a slow_tick timeline
    marker (deterministic via the fake clock as both registry clock and
    chaos sleep)."""
    clock = StepClock(step=0.01)
    reg = obs.Registry(clock=clock)
    with obs.use_registry(reg):
        eng = _engine(tiny, max_new_tokens=12, slow_tick_factor=3.0)
        monkey = ChaosMonkey(
            ChaosConfig(slow_ticks=(SlowTick(tick=8, seconds=5.0),)),
            sleep_fn=clock.advance).install(eng)
        eng.submit(_prompts(1, seed=9)[0])
        eng.run()
        eng.close()
    assert [e["kind"] for e in monkey.injected] == ["slow"]
    c = reg.snapshot()["counters"]
    assert c["engine_slow_ticks_total"][""] == 1
    slow = [e for e in reg.events() if e.get("ev") == "slow_tick"]
    assert slow and slow[0]["tick"] == 8
    names = [e["name"] for e in obs.timeline.trace_events(reg.events())]
    assert "slow_tick" in names
    _conserved(reg, eng)


def test_double_retire_raises(tiny):
    """The _finish chokepoint enforces the no-double-retire half of the
    conservation law."""
    with obs.use_registry(obs.Registry()):
        eng = _engine(tiny)
        rid = eng.submit([1, 2, 3])
        eng.run()
        assert eng.outcome(rid) == "ok"
        with pytest.raises(RuntimeError, match="already terminal"):
            eng._finish(rid, "error")
        eng.close()


def test_mixed_fault_drill_conservation(tiny):
    """Everything at once — NaN, transient kernel fault, flood-rejects,
    a cancel, an over-length reject — and the books still balance, on
    one decode trace."""
    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = _engine(tiny, max_slots=2, max_queue=4, breaker_threshold=5,
                      fallback_kernel_mode="reference")
        ChaosMonkey(ChaosConfig(
            nan_logits=(NanFault(tick=1, rid=0),),
            kernel_failures=(KernelFault(tick=3, count=1),))).install(eng)
        rids = flood(eng, 6, prompt=[4, 5, 6])   # 2 rejected (queue=4)
        over = eng.submit(list(range(30)))       # rejected: over-length
        cancelled = next(r for r in rids if eng.outcome(r) is None
                         and r != rids[0])
        eng.cancel(cancelled)
        eng.run()
        eng.close()
    assert eng.decode_traces == 1 and eng.fallbacks == 0
    assert eng.outcome(over) == "rejected"
    assert eng.outcome(cancelled) == "cancelled"
    assert eng.outcome(rids[0]) == "nan"
    from collections import Counter
    tally = Counter(eng.outcomes.values())
    assert tally["rejected"] == 3 and tally["cancelled"] == 1 \
        and tally["nan"] == 1 and tally["error"] == 0
    assert tally["ok"] == 7 - 3 - 1 - 1
    _conserved(reg, eng)


# ---------------------------------------------------------------------------
# Teardown under fault
# ---------------------------------------------------------------------------


def test_teardown_removes_routing_sink_and_is_idempotent(tiny):
    from repro.models import moe

    with obs.use_registry(obs.Registry()):
        eng = _engine(tiny)
        assert eng._routing_sink in moe._ROUTING_SINKS
        eng.close()
        assert eng._routing_sink not in moe._ROUTING_SINKS
        eng.close()  # second close is a no-op, not an error


def test_crashed_run_flushes_conserved_telemetry(tiny, tmp_path):
    """The serve.py failure-path contract: after a crashed run() the
    event log + snapshot still flush, the snapshot satisfies the
    conservation law, and the trace is well-formed with error markers."""
    reg = obs.Registry()
    with obs.use_registry(reg):
        eng = _engine(tiny, fallback_kernel_mode=None, breaker_threshold=1)
        ChaosMonkey(ChaosConfig(
            kernel_failures=(KernelFault(tick=1, count=9),))).install(eng)
        for p in _prompts(2, seed=10):
            eng.submit(p)
        with pytest.raises(EngineAborted):
            eng.run()
        # the driver's finally-block equivalents:
        mpath, tpath = tmp_path / "m.jsonl", tmp_path / "t.json"
        n = reg.write_events_jsonl(str(mpath))
        assert n > 0 and mpath.exists()
        obs.write_trace(str(tpath), reg)
        eng.close()
    import json
    snap = json.loads(mpath.read_text().splitlines()[-1])["snapshot"]
    c = snap["counters"]
    outcomes = c["engine_request_outcomes_total"]
    assert outcomes['outcome="error"'] == 2
    assert sum(outcomes.values()) == \
        c["engine_requests_total"]['event="submitted"']
    names = [e["name"]
             for e in json.loads(tpath.read_text())["traceEvents"]]
    assert any(n.endswith("retire:error") for n in names)
