"""Continuous-batching serving engine (vLLM-style slots, JAX-native).

Slot model: a fixed decode batch of ``max_slots`` sequences. New requests
prefill (padded to ``prefill_len``) into free slots; every engine tick runs
ONE batched decode step across all slots with per-slot positions; finished
sequences (eos / max_new) retire and free their slot. This is the
end-to-end path the paper accelerates: all linear layers inside run the
fine-grained quantized GEMMs when a recipe is attached.

Request lifecycle / fault tolerance
-----------------------------------
Every submitted request ends in EXACTLY ONE terminal outcome::

    submitted -> rejected                 (queue full / over-length prompt)
              -> queued    -> cancelled   (Engine.cancel on a queued rid)
                           -> timeout     (deadline expired before a slot)
                           -> error       (engine aborted while queued)
              -> active    -> ok          (eos / max_new / max_seq)
                           -> cancelled   (Engine.cancel on an active rid)
                           -> timeout     (deadline expired mid-decode)
                           -> nan         (non-finite logits quarantined)
                           -> error       (prefill raised / engine aborted)

The conservation law — ``sum(engine_request_outcomes_total) ==
engine_requests_total{event="submitted"}`` once the engine drains — is a
hard invariant: outcomes are recorded through one chokepoint
(:meth:`Engine._finish`) that raises on a double retire, and
``benchmarks/regression.py`` enforces the law over benchmark metric
snapshots.

* **Backpressure**: ``ServeConfig.max_queue`` bounds the admission queue;
  surplus submits are *rejected* (terminal outcome, structured retire
  event) instead of growing an unbounded list.
* **Over-length prompts** are rejected at submit — never silently
  truncated — unless ``ServeConfig.truncate_prompts`` explicitly opts
  into clipping to ``prefill_len``.
* **Deadlines**: ``ServeConfig.deadline_s`` arms a per-request deadline
  (registry clock) checked host-side at tick boundaries, for queued and
  active requests alike; overruns retire with partial output.
* **NaN quarantine**: with ``ServeConfig.nan_guard`` (default on) decode
  logits are checked host-side — outside jit, per the ``repro.obs``
  cardinal rule — and only the poisoned slots retire with outcome
  ``nan``; co-batched requests continue bit-exact (each slot's token
  stream depends only on its own cache rows).
* **Circuit breaker / graceful degradation**: ``breaker_threshold``
  consecutive kernel-path exceptions (prefill/decode), or that many
  consecutive poisoned decode ticks, trip a fallback — the engine swaps
  ``kernel_mode`` to ``ServeConfig.fallback_kernel_mode`` (an explicit
  opt-in, e.g. ``pallas -> reference``; the default is none) and, when
  ``fallback_params``/``fallback_recipe`` were provided at construction,
  the quantized parameter set too (integer-scale -> float-scale, the
  DGQ-style two-tier degradation), then RE-ESTABLISHES the jitted
  prefill/decode.
  Each fallback is one intentional extra trace — steady state must still
  hold ``decode_traces == 1 + fallbacks``. ``engine_fallback_events_total
  {reason}`` counts trips; with no fallback remaining the engine aborts:
  every in-flight request retires with outcome ``error`` (no slot stays
  active) and :class:`EngineAborted` propagates so the driver's
  ``finally`` can flush telemetry. External quant-health monitors (e.g.
  watching ``alpha_cap_events_total`` / ``qcert_verdicts_total{verdict=
  "fallback"}`` deltas) can force the same path via
  :meth:`Engine.trip_breaker`. An exception raised by a call that traced
  the route (its first trace, lowering or compile — a kernel the compiler
  refuses) is a fault in the program, not a transient one: it is counted
  in ``engine_kernel_failures_total`` and then propagates out of
  :meth:`Engine.run` without touching the breaker.
* **Tick watchdog**: a ``distributed.fault.Heartbeat`` on the registry
  clock times every decode tick; stragglers (> ``slow_tick_factor`` x
  rolling median) bump ``engine_slow_ticks_total`` + a ``slow_tick``
  event (a timeline marker).

Fault injection for all of the above lives in ``repro.serving.chaos``
(deterministic NaN / kernel-exception / slow-tick / queue-flood
injection, driving the ``pytest -m chaos`` suite).

Telemetry (repro.obs): every tick emits admit/prefill/decode/retire spans
into ``engine_phase_seconds{phase}`` plus a ``tick`` event carrying the
decode latency, slot occupancy, queue depth, and the rid occupying each
slot (``slot_rids`` — what places decode slices on per-request timeline
lanes); per request the engine observes TTFT (submit -> first token) and
TPOT (mean inter-token time) histograms and emits ``submit``/``admit``/
``retire`` lifecycle events threaded with a per-request ``trace_id``
(``eng<N>/r<rid>``); retire events carry the terminal ``outcome``, which
``engine_request_outcomes_total{outcome}`` counts. The jitted
prefill/decode callables are wrapped in ``obs.device_timer`` —
block_until_ready-bracketed, first (compile) call excluded — populating
``engine_phase_device_seconds{phase}`` so host overhead vs device compute
is separable per phase. The host sections between the jitted programs
are timed into ``engine_host_seconds{part}`` (``admit.inputs``,
``admit.splice``, ``admit.first_token``, ``admit.routing``,
``decode.inputs``, ``decode.logits_to_host``, ``decode.sample``,
``decode.nan_guard``, ``decode.routing``; retire is the ``retire`` phase
span), and each request's wait from ``submit`` to the start of its
admission into ``engine_queue_wait_seconds``. Every span is also a
``jax.profiler.TraceAnnotation`` (``engine.<phase>``,
``engine.<part>``) and each loop iteration a ``StepTraceAnnotation``
(``engine.tick``), so a profiler trace places them beside the device's
ops. After each tick a ``counters`` event samples
cumulative m-tile/qgemm counters for the timeline's counter tracks. Jit
retraces bump ``engine_traces_total{fn}`` and emit a ``trace`` event (the
per-engine ``prefill_traces``/``decode_traces`` properties keep their
exact PR-2 semantics — steady-state serving must hold decode at ONE trace
per established kernel route, asserted in tests). A MoE model's jitted
prefill and decode return each MoE layer's routed counts beside the
logits; the engine fetches them with the data it syncs anyway, folds
them into ``engine_moe_m_tiles_total{kind=executed|total}`` (ragged
skipping observable from the LIVE dispatch) and hands them to the
``models.moe`` routing sinks. All of it is host-side at trace/tick
boundaries — nothing records from inside the jitted bodies and no host
callback is compiled in (see ``repro.obs``).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.distributed.fault import Heartbeat, HeartbeatConfig
from repro.models import moe
from repro.models.config import ModelConfig
from repro.models.registry import ModelApi
from repro.nn import spec as S
from . import sampler

_PALLAS_MODES = ("pallas", "pallas_interpret")

#: The terminal request outcomes (the state machine's accepting states).
OUTCOMES = ("ok", "timeout", "cancelled", "rejected", "nan", "error")


class EngineAborted(RuntimeError):
    """The circuit breaker exhausted every fallback: the engine quiesced
    (all in-flight requests retired with outcome ``error``, no slot left
    active) and refuses further ticks. Telemetry flushed by the caller's
    ``finally`` still contains the full event log."""


@dataclasses.dataclass
class ServeConfig:
    max_slots: int = 4
    max_seq: int = 256
    prefill_len: int = 64          # prompts padded to this length
    max_new_tokens: int = 32
    eos_id: int = -1               # -1: never stop early
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    # qlinear backend for quantized layers inside prefill/decode:
    # "reference" | "pallas" | "pallas_interpret" | None (= keep the model
    # config's own kernel_mode). Carried onto ModelConfig.kernel_mode so
    # the jitted fns bake the chosen backend in — e.g. every expert FFN in
    # a quantized-MoE decode runs the ragged grouped kernel.
    kernel_mode: str | None = None
    # -- robustness ---------------------------------------------------------
    max_queue: int = 0             # admission queue bound; 0 = unbounded
    deadline_s: float = 0.0        # per-request deadline; 0 = none
    truncate_prompts: bool = False  # opt-in: clip over-length prompts
    nan_guard: bool = True         # host-side NaN/Inf logit quarantine
    breaker_threshold: int = 3     # consecutive failures tripping fallback
    # kernel_mode the breaker degrades to — an explicit opt-in, so a
    # failing kernel is never served by another route unasked (None: no
    # mode fallback; a value equal to the active mode is ignored)
    fallback_kernel_mode: str | None = None
    slow_tick_factor: float = 3.0  # watchdog straggler multiple of median


@dataclasses.dataclass
class _Slot:
    request_id: int = -1
    length: int = 0            # tokens currently in cache
    generated: list = dataclasses.field(default_factory=list)
    active: bool = False
    t_first: float = 0.0       # perf_counter at first generated token


class Engine:
    # process-wide engine numbering: per-request trace ids ("eng3/r7")
    # stay unique when several engines share one registry sequentially
    _ids = itertools.count()

    def __init__(self, api: ModelApi, cfg: ModelConfig, params: Any,
                 serve_cfg: ServeConfig, recipe=None, *,
                 fallback_params: Any = None, fallback_recipe=None):
        self.engine_id = f"eng{next(Engine._ids)}"
        self.api = api
        if serve_cfg.kernel_mode is not None:
            cfg = dataclasses.replace(cfg,
                                      kernel_mode=serve_cfg.kernel_mode)
        self.cfg = cfg
        self.params = params
        self.sc = serve_cfg
        self.recipe = recipe
        # trace counters: jit retraces bump these (the per-tick row_counts
        # of a quantized-MoE decode are traced operands, so steady-state
        # serving must keep decode_traces at 1 per established route —
        # asserted in tests). Kept PER ENGINE (several engines may share
        # one registry sequentially); the registry additionally gets
        # engine_traces_total + an event.
        self._trace_counts = {"prefill": 0, "decode": 0}
        B = serve_cfg.max_slots
        cspecs = api.cache_specs(cfg, B, serve_cfg.max_seq)
        self.cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), cspecs, is_leaf=S.is_spec)
        self.slots = [_Slot() for _ in range(B)]
        self.queue: list[tuple[int, list[int]]] = []
        self.outputs: dict[int, list[int]] = {}
        #: rid -> terminal outcome (exactly one entry per finished request)
        self.outcomes: dict[int, str] = {}
        self._next_id = 0
        self._key = jax.random.PRNGKey(serve_cfg.seed)
        self._steps = 0
        self._submit_t: dict[int, float] = {}
        self._deadlines: dict[int, float] = {}
        self._closed = False
        # circuit-breaker state
        self._fail_streak = 0      # consecutive prefill/decode exceptions
        self._nan_streak = 0       # consecutive poisoned decode ticks
        self._fallbacks = 0
        fb = serve_cfg.fallback_kernel_mode
        self._fallback_modes = [fb] if fb and fb != cfg.kernel_mode else []
        self._fallback_params = fallback_params
        self._fallback_recipe = fallback_recipe
        # host-side wrappers (chaos injection) re-applied on every jit
        # re-establishment — see add_decode_wrapper
        self._decode_wrappers: list = []
        # tick watchdog on the registry clock (deterministic under a fake
        # clock); stragglers surface as engine_slow_ticks_total + events
        self._watchdog = Heartbeat(
            HeartbeatConfig(straggler_factor=serve_cfg.slow_tick_factor),
            on_straggler=self._on_slow_tick,
            clock=lambda: obs.current_registry().now())
        # MoE routing records of the steps since the last drain
        self._routing_buf: list[dict] = []
        self._decode_routed = None  # routed counts of the last decode call

        self._build_jit_fns()
        self._cache1_specs = api.cache_specs(cfg, 1, serve_cfg.max_seq)
        # batch axis per cache leaf = position of "cache_batch" in the
        # spec's logical axes (scanned leaves lead with the LAYER axis)
        self._batch_axes = jax.tree.map(
            lambda s: (s.logical_axes.index("cache_batch")
                       if "cache_batch" in s.logical_axes else 0),
            cspecs, is_leaf=S.is_spec)
        # pre-create the headline series so snapshots show explicit zeros
        # even before the first tick (every outcome series included: the
        # conservation law is checkable from any snapshot)
        reg = obs.current_registry()
        reg.counter("engine_ticks_total", "batched decode ticks")
        reg.counter("engine_tokens_total", "tokens decoded across slots")
        reg.counter("engine_requests_total", "request lifecycle events",
                    ("event",))
        reg.counter("engine_moe_m_tiles_total",
                    "MoE grouped-GEMM m-tiles from live routing: executed "
                    "(ragged skipping applied) vs dense total", ("kind",))
        out = reg.counter("engine_request_outcomes_total",
                          "terminal per-request outcomes (conservation: "
                          "sums to submitted once drained)", ("outcome",))
        for o in OUTCOMES:
            out.inc(0, outcome=o)
        reg.counter("engine_fallback_events_total",
                    "circuit-breaker kernel-route fallbacks", ("reason",))
        reg.counter("engine_kernel_failures_total",
                    "exceptions from the jitted prefill/decode path",
                    ("phase",))
        reg.counter("engine_slow_ticks_total",
                    "watchdog: decode ticks slower than "
                    "slow_tick_factor x rolling median").inc(0)

    # -- jit establishment --------------------------------------------------
    def _build_jit_fns(self) -> None:
        """(Re-)establish the jitted prefill/decode closures from the
        CURRENT ``self.cfg`` / ``self.recipe`` — called at construction
        and again by the circuit breaker after a kernel-route fallback
        (each re-establishment is one intentional extra trace)."""
        recipe = self.recipe

        def forward(params, tokens, **kw):
            """-> (logits, cache, routed counts (L, G, E) | None)."""
            if not self.cfg.num_experts:
                logits, cache, _ = self.api.apply(
                    params, self.cfg, tokens, recipe=recipe, **kw)
                return logits, cache, None
            logits, cache, _, routed = self.api.apply(
                params, self.cfg, tokens, recipe=recipe, routing=True, **kw)
            return logits, cache, routed

        # jit'd single-request prefill (batch 1, fixed length).
        # mode="train" + cache: returns FULL-sequence logits (the engine
        # needs the logit at the true prompt end, which may be before the
        # padded end) while still populating the KV cache. mode="prefill"
        # keeps its last-token-only slicing for the serving dry-run.
        def prefill_fn(params, tokens, cache1):
            self._note_trace("prefill")
            return forward(params, tokens, mode="train", cache=cache1,
                           pos=0)

        # device_timer wraps OUTSIDE jit (args pass through verbatim, so
        # the jit cache — and the one-decode-trace invariant — is
        # untouched); warmup=1 keeps the compile call out of the
        # steady-state *_device_seconds series.
        self._prefill = obs.device_timer(
            jax.jit(prefill_fn), "engine_phase_device_seconds",
            help="device time (block_until_ready) per engine phase",
            phase="prefill")

        # jit'd batched decode with per-slot positions
        def decode_fn(params, tokens, cache, pos_vec):
            self._note_trace("decode")
            logits, cache, routed = forward(params, tokens, mode="decode",
                                            cache=cache, pos=pos_vec)
            return logits[:, 0], cache, routed

        timed_decode = obs.device_timer(
            jax.jit(decode_fn), "engine_phase_device_seconds",
            help="device time (block_until_ready) per engine phase",
            phase="decode")

        def decode(params, tokens, cache, pos_vec):
            # decode wrappers see (logits, cache); the routed counts go
            # to the tick beside them
            logits, cache, self._decode_routed = timed_decode(
                params, tokens, cache, pos_vec)
            return logits, cache

        decode.__wrapped__ = timed_decode
        self._decode_base = decode
        self._rewrap_decode()

    def _rewrap_decode(self) -> None:
        fn = self._decode_base
        for wrap in self._decode_wrappers:
            fn = wrap(fn)
        self._decode = fn

    def add_decode_wrapper(self, wrap) -> None:
        """Install a host-side ``fn -> fn`` wrapper around the jitted
        decode callable (fault injection, extra instrumentation). The
        wrapper composes OUTSIDE jit on concrete arrays — it cannot
        retrace — and is re-applied automatically when the circuit
        breaker re-establishes decode. ``repro.serving.chaos`` is the
        canonical client."""
        self._decode_wrappers.append(wrap)
        self._rewrap_decode()

    # -- telemetry plumbing -------------------------------------------------
    def _note_trace(self, fn: str) -> None:
        """Runs at TRACE time inside the jitted closures (host python) —
        each execution of compiled code does NOT pass through here, which
        is exactly what makes it a retrace detector."""
        self._trace_counts[fn] += 1
        reg = obs.current_registry()
        reg.counter("engine_traces_total", "jit traces per engine function",
                    ("fn",)).inc(fn=fn)
        reg.emit({"ev": "trace", "fn": fn,
                  "engine_count": self._trace_counts[fn]})

    @property
    def prefill_traces(self) -> int:
        return self._trace_counts["prefill"]

    @property
    def decode_traces(self) -> int:
        return self._trace_counts["decode"]

    @property
    def fallbacks(self) -> int:
        """Circuit-breaker fallback count: steady-state decode must hold
        ``decode_traces == 1 + fallbacks``."""
        return self._fallbacks

    def _host(self, reg, part: str):
        """Span of one host section between the jitted programs."""
        return obs.span(reg, "engine_host_seconds", part=part,
                        help="engine host work between jitted programs")

    def _publish_routing(self, routed, tokens: int) -> None:
        """Queue one step's routed counts (np (L, G, E), over ``tokens``
        tokens) for the m-tile counters and hand them to every
        ``models.moe`` routing sink. Nothing after :meth:`close`."""
        if routed is None or self._closed:
            return
        recs = moe.routing_records(routed, self.cfg, tokens)
        self._routing_buf.extend(recs)
        moe.publish_routing(recs)

    def _on_slow_tick(self, step: int, dt: float, med: float) -> None:
        reg = obs.current_registry()
        reg.counter("engine_slow_ticks_total", "").inc()
        reg.emit({"ev": "slow_tick", "tick": step,
                  "seconds": round(dt, 6), "median_s": round(med, 6)})

    def _drain_routing(self) -> None:
        """Fold buffered MoE routing records (the routed counts the
        jitted steps returned) into the executed-vs-total m-tile
        counters. Ragged skipping only applies on the Pallas paths with a
        single dispatch group (G == 1) — other configurations execute
        densely."""
        if not self._routing_buf:
            return
        from repro.kernels.moe_gemm import ragged_tile_stats

        tiles = obs.current_registry().counter(
            "engine_moe_m_tiles_total", "", ("kind",))
        ragged_ok = self.cfg.kernel_mode in _PALLAS_MODES
        executed = total = 0
        buf, self._routing_buf = self._routing_buf, []
        for rec in buf:
            counts = rec["counts"]
            C = rec["capacity"]
            for g in range(counts.shape[0]):
                st = ragged_tile_stats([int(v) for v in counts[g]], C)
                total += st["dense_m_tiles"]
                executed += (st["ragged_m_tiles"]
                             if ragged_ok and counts.shape[0] == 1
                             else st["dense_m_tiles"])
        tiles.inc(executed, kind="executed")
        tiles.inc(total, kind="total")

    def _sample_counters(self, reg) -> None:
        """Emit one ``counters`` event per tick sampling the cumulative
        m-tile / qgemm counters — the timeline's counter tracks. Host-side
        at the tick boundary, after the routing drain."""
        tiles = reg.counter("engine_moe_m_tiles_total", "", ("kind",))
        calls = reg.counter(
            "qgemm_calls_total",
            "kernels.ops wrapper calls (trace-time under jit)",
            ("scheme", "kind", "shape", "block"))
        reg.emit({"ev": "counters", "tick": self._steps - 1,
                  "moe_executed": tiles.get(kind="executed"),
                  "moe_total": tiles.get(kind="total"),
                  "qgemm_calls": calls.total()})

    def close(self) -> None:
        """Stop delivering MoE routing records (to the m-tile counters
        and the ``models.moe`` sinks). Idempotent; safe to skip."""
        self._closed = True

    def trace_id(self, rid: int) -> str:
        """The per-request trace/span id threaded through lifecycle
        events (unique across engines within the process)."""
        return f"{self.engine_id}/r{rid}"

    # -- public API ------------------------------------------------------------
    def submit(self, prompt: list[int]) -> int:
        """Enqueue a request. ALWAYS returns a rid; requests refused by
        admission control (bounded queue, over-length prompt) are
        immediately terminal with outcome ``rejected`` — check
        :meth:`outcome`."""
        rid = self._next_id
        self._next_id += 1
        reg = obs.current_registry()
        reg.counter("engine_requests_total", "", ("event",)).inc(
            event="submitted")
        self._submit_t[rid] = reg.now()
        reg.emit(
            {"ev": "submit", "rid": rid, "trace_id": self.trace_id(rid),
             "prompt_len": len(prompt)})
        if len(prompt) > self.sc.prefill_len and not self.sc.truncate_prompts:
            self._finish(rid, "rejected", reason="prompt_overlength",
                         prompt_len=len(prompt))
            return rid
        if self.sc.max_queue and len(self.queue) >= self.sc.max_queue:
            self._finish(rid, "rejected", reason="queue_full",
                         queue_depth=len(self.queue))
            return rid
        if self.sc.deadline_s > 0:
            self._deadlines[rid] = self._submit_t[rid] + self.sc.deadline_s
        self.queue.append((rid, list(prompt)))
        return rid

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or active request (terminal outcome
        ``cancelled``; any tokens generated so far are delivered in
        ``outputs``). Returns False for unknown or already-terminal
        rids."""
        if rid in self.outcomes or not 0 <= rid < self._next_id:
            return False
        for j, (qrid, _) in enumerate(self.queue):
            if qrid == rid:
                del self.queue[j]
                self._finish(rid, "cancelled")
                return True
        for i, s in enumerate(self.slots):
            if s.active and s.request_id == rid:
                self._finish(rid, "cancelled", slot=i, output=s.generated,
                             tokens=len(s.generated))
                self.slots[i] = _Slot()
                return True
        return False

    def outcome(self, rid: int) -> str | None:
        """Terminal outcome for ``rid`` (None while still in flight)."""
        return self.outcomes.get(rid)

    def trip_breaker(self, reason: str) -> None:
        """Force a circuit-breaker trip (external quant-health monitors —
        e.g. alarming on ``alpha_cap_events_total`` /
        ``qcert_verdicts_total{verdict="fallback"}`` deltas). Falls back
        if a route remains, else aborts the engine."""
        self._trip_breaker(reason)

    def run(self, max_ticks: int = 10_000) -> dict[int, list[int]]:
        reg = obs.current_registry()
        try:
            while (self.queue or any(s.active for s in self.slots)) \
                    and self._steps < max_ticks:
                with jax.profiler.StepTraceAnnotation(
                        "engine.tick", step_num=self._steps):
                    self._expire_queued()
                    with obs.span(reg, "engine_phase_seconds",
                                  phase="admit", event="phase"):
                        self._admit()
                    self._tick()
        except Exception:
            # a crashed run leaves no slot marked active and every
            # in-flight request with a terminal outcome — the driver's
            # ``finally`` can still flush a conserved metrics snapshot
            self._quiesce("error")
            raise
        return dict(self.outputs)

    @property
    def ticks(self) -> int:
        return self._steps

    # -- request state machine ---------------------------------------------
    def _finish(self, rid: int, outcome: str, *, slot: int | None = None,
                output: list | None = None, **fields) -> None:
        """The SINGLE chokepoint recording a terminal outcome: outcome
        map + counter + structured retire event. Raises on a second
        retire of the same rid (the conservation law's no-double-retire
        half)."""
        if rid in self.outcomes:
            raise RuntimeError(
                f"request {rid} already terminal "
                f"({self.outcomes[rid]!r}); double retire as {outcome!r}")
        self.outcomes[rid] = outcome
        self._submit_t.pop(rid, None)
        self._deadlines.pop(rid, None)
        if output is not None:
            self.outputs[rid] = list(output)
        reg = obs.current_registry()
        reg.counter("engine_request_outcomes_total", "", ("outcome",)).inc(
            outcome=outcome)
        ev = {"ev": "retire", "rid": rid, "outcome": outcome,
              "trace_id": self.trace_id(rid), **fields}
        if slot is not None:
            ev["slot"] = slot
        reg.emit(ev)

    def _quiesce(self, outcome: str) -> None:
        """Drive every in-flight request to a terminal outcome and free
        all slots (abort / crashed-run path). Idempotent per rid."""
        for i, s in enumerate(self.slots):
            if s.active and s.request_id not in self.outcomes:
                self._finish(s.request_id, outcome, slot=i,
                             output=s.generated, tokens=len(s.generated))
            self.slots[i] = _Slot()
        for rid, _ in self.queue:
            if rid not in self.outcomes:
                self._finish(rid, outcome)
        self.queue.clear()

    def _expire_queued(self) -> None:
        """Retire queued requests whose deadline passed before a slot
        freed up (they never prefill)."""
        if not self._deadlines or not self.queue:
            return
        now = obs.current_registry().now()
        keep = []
        for rid, prompt in self.queue:
            dl = self._deadlines.get(rid)
            if dl is not None and now > dl:
                self._finish(rid, "timeout", where="queued")
            else:
                keep.append((rid, prompt))
        self.queue[:] = keep

    # -- circuit breaker ----------------------------------------------------
    def _on_phase_failure(self, phase: str, exc: Exception,
                          rid: int | None = None, *,
                          breaker: bool = True) -> None:
        """A kernel-path exception escaped the jitted ``phase``: count it,
        retire the directly-affected rid (prefill only — decode failures
        leave slots intact for the retry), and — unless ``breaker`` is
        off (the failing call traced the route; the caller re-raises) —
        trip the breaker when the streak reaches the threshold."""
        self._fail_streak += 1
        reg = obs.current_registry()
        reg.counter("engine_kernel_failures_total", "", ("phase",)).inc(
            phase=phase)
        ev = {"ev": "kernel_failure", "phase": phase,
              "streak": self._fail_streak, "error": repr(exc)[:200]}
        if rid is not None:
            ev["rid"] = rid
        reg.emit(ev)
        if rid is not None:
            self._finish(rid, "error", error=repr(exc)[:200])
        if breaker and self._fail_streak >= max(1, self.sc.breaker_threshold):
            self._trip_breaker(f"{phase}_exception", exc)

    def _fallback_available(self) -> bool:
        return bool(self._fallback_modes) \
            or self._fallback_params is not None

    def _trip_breaker(self, reason: str, exc: Exception | None = None):
        if self._fallback_available():
            self._fallback(reason)
        else:
            self._abort(reason, exc)

    def _fallback(self, reason: str) -> None:
        """Graceful degradation: swap to the fallback kernel route (and
        parameter set, when provided), reset breaker state, and
        re-establish the jitted prefill/decode (ONE intentional extra
        trace, surfaced via ``fallbacks``)."""
        reg = obs.current_registry()
        frm = self.cfg.kernel_mode
        if self._fallback_modes:
            to = self._fallback_modes.pop(0)
            self.cfg = dataclasses.replace(self.cfg, kernel_mode=to)
        else:
            to = frm
        swapped = self._fallback_params is not None
        if swapped:
            self.params, self._fallback_params = self._fallback_params, None
            self.recipe, self._fallback_recipe = self._fallback_recipe, None
        self._fallbacks += 1
        self._fail_streak = 0
        self._nan_streak = 0
        reg.counter("engine_fallback_events_total", "", ("reason",)).inc(
            reason=reason)
        reg.emit({"ev": "fallback", "reason": reason, "from": str(frm),
                  "to": str(to), "params_swapped": swapped,
                  "fallbacks": self._fallbacks})
        self._build_jit_fns()

    def _abort(self, reason: str, exc: Exception | None = None):
        reg = obs.current_registry()
        reg.emit({"ev": "abort", "reason": reason,
                  "error": repr(exc)[:200] if exc else None})
        self._quiesce("error")
        raise EngineAborted(
            f"{self.engine_id}: breaker tripped ({reason}) with no "
            f"fallback route remaining") from exc

    # -- internals ----------------------------------------------------------------
    def _free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if not s.active]

    def _admit(self) -> None:
        reg = obs.current_registry()
        for i in self._free_slots():
            if not self.queue:
                break
            rid, prompt = self.queue.pop(0)
            t_sub = self._submit_t.get(rid)
            if t_sub is not None:
                reg.histogram(
                    "engine_queue_wait_seconds",
                    "submit -> start of the request's admission",
                ).observe(reg.now() - t_sub)
            poisoned = False
            traces = self._trace_counts["prefill"]
            try:
                with obs.span(reg, "engine_phase_seconds", phase="prefill",
                              event="admit") as sp:
                    P = self.sc.prefill_len
                    with self._host(reg, "admit.inputs"):
                        # over-length prompts were rejected at submit
                        # unless truncate_prompts opted into this clip
                        toks = (prompt[:P] + [0] * max(0, P - len(prompt)))
                        true_len = min(len(prompt), P)
                        cache1 = jax.tree.map(
                            lambda s: jnp.zeros(s.shape, s.dtype),
                            self._cache1_specs, is_leaf=S.is_spec)
                        tokens = jnp.asarray([toks], jnp.int32)
                    logits, cache1, routed = self._prefill(
                        self.params, tokens, cache1)

                    # splice the prefilled slot into the batched cache
                    # along each leaf's batch axis (scanned leaves lead
                    # with layers)
                    def splice(C, c, ax):
                        idx = tuple([slice(None)] * ax + [i])
                        return C.at[idx].set(jnp.take(c, 0, axis=ax))

                    with self._host(reg, "admit.splice"):
                        self.cache = jax.tree.map(
                            splice, self.cache, cache1, self._batch_axes)
                    with self._host(reg, "admit.first_token"):
                        first_row = logits[:, true_len - 1]
                        # the routed counts come over with the row
                        row, routed = jax.device_get(
                            (first_row if self.sc.nan_guard else None,
                             routed))
                        poisoned = row is not None \
                            and not np.isfinite(row).all()
                        if not poisoned:
                            # token 0 must honor the sampling settings
                            # too — greedy argmax here ignored
                            # temperature/top_k for the first token
                            self._key, k = jax.random.split(self._key)
                            first = int(np.asarray(sampler.sample(
                                first_row, k,
                                temperature=self.sc.temperature,
                                top_k=self.sc.top_k))[0])
                    with self._host(reg, "admit.routing"):
                        self._publish_routing(routed, P)
                    if poisoned:
                        sp.fields.update(rid=rid, slot=i, outcome="nan")
                    else:
                        t_first = reg.now()
                        self.slots[i] = _Slot(request_id=rid,
                                              length=true_len,
                                              generated=[first],
                                              active=True, t_first=t_first)
                        sp.fields.update(rid=rid, slot=i,
                                         prompt_len=true_len,
                                         trace_id=self.trace_id(rid))
                        if t_sub is not None:
                            ttft = t_first - t_sub
                            reg.histogram(
                                "engine_ttft_seconds",
                                "submit -> first generated token",
                            ).observe(ttft)
                            sp.fields["ttft_s"] = round(ttft, 6)
            except Exception as exc:
                traced = self._trace_counts["prefill"] != traces
                self._on_phase_failure("prefill", exc, rid=rid,
                                       breaker=not traced)
                if traced:
                    raise
                continue
            if poisoned:
                self._finish(rid, "nan", slot=i, output=[],
                             where="prefill")
                continue
            self._fail_streak = 0
            reg.counter("engine_requests_total", "", ("event",)).inc(
                event="admitted")
        self._drain_routing()

    def _tick(self) -> None:
        if not any(s.active for s in self.slots):
            return
        reg = obs.current_registry()
        B = self.sc.max_slots
        last = np.zeros((B, 1), np.int32)
        pos = np.zeros((B,), np.int32)
        active = 0
        slot_rids = [-1] * B
        for i, s in enumerate(self.slots):
            if s.active:
                last[i, 0] = s.generated[-1]
                pos[i] = s.length
                slot_rids[i] = s.request_id
                active += 1
        traces = self._trace_counts["decode"]
        try:
            with obs.span(reg, "engine_phase_seconds", phase="decode",
                          event="tick") as sp:
                self._watchdog.start()
                with self._host(reg, "decode.inputs"):
                    tokens, positions = jnp.asarray(last), jnp.asarray(pos)
                self._decode_routed = None
                logits, new_cache = self._decode(
                    self.params, tokens, self.cache, positions)
                routed = self._decode_routed
                with self._host(reg, "decode.logits_to_host"):
                    # host-side numeric guard (outside jit): one transfer
                    # of the (B, V) logits, reused for per-slot
                    # quarantine below
                    lg = np.asarray(logits) if self.sc.nan_guard else None
                with self._host(reg, "decode.sample"):
                    # the key splits AFTER decode succeeds, so failed
                    # attempts never advance the sampling stream (retries
                    # stay bit-exact vs a fault-free run)
                    self._key, k = jax.random.split(self._key)
                    # the routed counts come over with the tokens
                    nxt, routed = jax.device_get((sampler.sample(
                        logits, k, temperature=self.sc.temperature,
                        top_k=self.sc.top_k), routed))
                self._watchdog.stop(self._steps)
                sp.fields.update(tick=self._steps, slots_active=active,
                                 queue_depth=len(self.queue),
                                 slot_rids=slot_rids)
        except Exception as exc:
            # tick NOT advanced, cache NOT committed: the run loop retries
            # (bounded — the breaker trips fallback/abort on a streak)
            traced = self._trace_counts["decode"] != traces
            self._on_phase_failure("decode", exc, breaker=not traced)
            if traced:
                raise
            return
        self.cache = new_cache
        self._fail_streak = 0
        with self._host(reg, "decode.nan_guard"):
            bad = {i for i, s in enumerate(self.slots)
                   if s.active and lg is not None
                   and not np.isfinite(lg[i]).all()}
        if bad:
            self._nan_streak += 1
        else:
            self._nan_streak = 0
        self._steps += 1
        reg.counter("engine_ticks_total", "").inc()
        reg.counter("engine_tokens_total", "").inc(active - len(bad))
        with self._host(reg, "decode.routing"):
            self._publish_routing(routed, B)
            self._drain_routing()
        self._sample_counters(reg)
        with obs.span(reg, "engine_phase_seconds", phase="retire",
                      event="phase"):
            now = reg.now()
            for i, s in enumerate(self.slots):
                if not s.active:
                    continue
                rid = s.request_id
                if i in bad:
                    # quarantine: ONLY the poisoned slot retires; its
                    # garbage token is never appended, co-batched slots
                    # proceed bit-exact (row-isolated computation)
                    self._finish(rid, "nan", slot=i, output=s.generated,
                                 tokens=len(s.generated))
                    self.slots[i] = _Slot()
                    continue
                s.length += 1
                tok = int(nxt[i])
                s.generated.append(tok)
                done = (tok == self.sc.eos_id
                        or len(s.generated) >= self.sc.max_new_tokens
                        or s.length + 1 >= self.sc.max_seq)
                dl = self._deadlines.get(rid)
                if done:
                    n = len(s.generated)
                    tpot = (now - s.t_first) / max(1, n - 1)
                    reg.histogram(
                        "engine_tpot_seconds",
                        "mean inter-token latency per request").observe(
                            tpot)
                    reg.counter("engine_requests_total", "",
                                ("event",)).inc(event="retired")
                    self._finish(rid, "ok", slot=i, output=s.generated,
                                 tokens=n, tpot_s=round(tpot, 6))
                    self.slots[i] = _Slot()
                elif dl is not None and now > dl:
                    self._finish(rid, "timeout", slot=i,
                                 output=s.generated,
                                 tokens=len(s.generated))
                    self.slots[i] = _Slot()
        reg.gauge("engine_queue_depth", "requests waiting for a slot").set(
            len(self.queue))
        if self._nan_streak >= max(1, self.sc.breaker_threshold):
            # persistent poisoned logits = quant-health alarm: degrade to
            # the fallback route instead of burning ticks on NaNs
            self._trip_breaker("nan_logits")
