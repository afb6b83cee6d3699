"""repro.obs — serving + kernel telemetry (metrics registry, tick tracing).

A dependency-free (stdlib-only) observability layer shared by the serving
engine, the kernel dispatch wrappers, and the quantization pipeline:

* :class:`Registry` — process-local counters / gauges / histograms with
  labeled series, deterministic fixed-bucket histograms, a JSONL event
  log, and a Prometheus-text snapshot. Instrumentation resolves the
  active registry via :func:`current_registry` (process default, scoped
  override via :func:`use_registry`).
* :class:`Span` / :func:`span` — host-side wall-clock tick tracing that
  lands in a histogram + the event log (stamped with registry-clock
  start times, so spans double as timeline slices) and, when jax is
  loaded, in a ``jax.profiler.TraceAnnotation`` on the profiler's clock
  (one span system: there is no second tracer).
* :mod:`repro.obs.timeline` — Perfetto/chrome://tracing export of the
  event log: engine-phase lane, per-request-slot lifecycle lanes
  (queued -> prefill -> decode ticks -> retire, TTFT/TPOT markers), and
  m-tile / qgemm counter tracks. ``launch/serve.py --trace-out t.json``
  writes one; open it at https://ui.perfetto.dev ("Open trace file").
* :mod:`repro.obs.profile` — device-time attribution:
  :func:`~repro.obs.profile.device_timer` wraps jitted callables with
  block_until_ready-bracketed, warmup-aware timing into
  ``*_device_seconds`` histograms (so host overhead = host span minus
  device time, per phase), and
  :func:`~repro.obs.profile.trace_window` is the opt-in
  ``jax.profiler.trace`` capture behind ``--profile-dir``.
* :meth:`Histogram.quantile` / ``snapshot()["histograms"][...]
  ["quantiles"]`` — p50/p95/p99 derived from the fixed cumulative
  buckets (Prometheus ``histogram_quantile`` interpolation; overflow
  clamps to the last finite edge), surfaced in the serve telemetry
  cell, ``launch/dryrun.py``, and benchmark JSON.
* ``benchmarks/regression.py`` (consumer, not part of this package)
  turns two ``benchmarks.run --json`` documents into an enforced perf
  contract — see its docstring for the baseline-refresh procedure.

What is instrumented where
--------------------------
* ``serving/engine.py``: per-tick admit/prefill/decode/retire spans
  (``engine_phase_seconds``; profiler annotations ``engine.<phase>``),
  the host sections between the jitted programs
  (``engine_host_seconds{part}``: ``admit.inputs``, ``admit.splice``,
  ``admit.first_token``, ``admit.routing``, ``decode.inputs``,
  ``decode.logits_to_host``, ``decode.sample``, ``decode.nan_guard``,
  ``decode.routing``; annotations ``engine.<part>``), one
  ``StepTraceAnnotation`` ``engine.tick`` per loop iteration, device
  time per jitted call (``engine_phase_device_seconds``), each
  request's queue wait from
  submit to admission (``engine_queue_wait_seconds``),
  tick/token/request counters, a queue-depth gauge, per-request
  TTFT/TPOT histograms, jit retrace events (``engine_traces_total``),
  and executed-vs-total MoE m-tile counters
  (``engine_moe_m_tiles_total``) from the routed counts that the jitted
  steps return; the same counts reach the ``models/moe.py`` routing
  sinks. Fault tolerance (PR 10):
  ``engine_request_outcomes_total{outcome}`` counts every request's
  terminal outcome (``ok|timeout|cancelled|rejected|nan|error``; all
  series zero-seeded, so the conservation law — outcomes sum to
  ``engine_requests_total{event="submitted"}`` once drained — is
  checkable from any snapshot), ``engine_fallback_events_total{reason}``
  counts circuit-breaker kernel-route fallbacks,
  ``engine_kernel_failures_total{phase}`` counts exceptions escaping the
  jitted paths, and ``engine_slow_ticks_total`` counts watchdog
  stragglers. ``repro.serving.chaos`` injects all of the above
  deterministically.
* ``kernels/ops.py``: ``qgemm_calls_total{scheme,kind,shape,block}`` per
  wrapper call, and ``qgemm_layer_indexed_total{kind}`` per call that
  reads layer-stacked weights in place by a layer index (trace-time
  counts under jit).
* ``core/qlinear.py`` / ``core/ptq.py`` / ``core/integer_scale.py`` /
  ``analysis/certify.py``: quantization health — ``alpha_cap_events_total``,
  ``qcert_verdicts_total{verdict}``, ``amax_floor_hits_total{where}``,
  ``int_scale_floor_hits_total``, ``quantized_layers_total{scheme}``.
* Surfacing: ``launch/serve.py --metrics-out`` (JSONL trace + final
  snapshot line), ``launch/dryrun.py`` telemetry cell, and benchmark JSON
  documents (``benchmarks/run.py`` / ``benchmarks/serving_moe.py`` attach
  a registry snapshot + host provenance).

THE RULE: no metrics and no host callbacks in compiled code
-----------------------------------------------------------
Never read or write a metric from code that executes inside a traced /
jitted computation, and never stage a host callback
(``jax.debug.callback``, ``io_callback``, ``pure_callback``) into one. A
python-side increment inside a traced function runs at TRACE time (once
per compilation, not once per step); a callback runs on every step,
whether or not anyone reads it, and stalls the device each time.
Instrument at these boundaries only:

* host code around a jitted call (engine tick phases and host sections,
  wrapper entry points — note wrapper counts are *trace-time* counts
  under jit, which is exactly what makes them a retrace detector);
* offline paths that are eager by construction (PTQ, certification).

Data-dependent counts (e.g. the MoE layers' routed ``counts``) leave the
step as OUTPUTS of the jitted function; the host fetches them together
with data it syncs anyway (the engine: with the sampled tokens) and
records them there.

How to add a new counter
------------------------
1. Pick the layer's boundary per the rule above. 2. Create lazily at the
use site — ``obs.current_registry().counter("my_total", "help",
("label",)).inc(label="x")``; get-or-create is idempotent, so no central
declaration list exists. 3. Create the metric unconditionally and ``inc``
conditionally when dashboards must see an explicit zero (e.g.
``alpha_cap_events_total``). 4. Name per Prometheus convention:
``*_total`` counters, ``*_seconds`` histograms, unit-suffixed gauges.
"""
from . import profile, timeline
from .metrics import (DEFAULT_LATENCY_BUCKETS, Counter, Gauge, Histogram,
                      Registry, current_registry, default_registry,
                      use_registry)
from .profile import device_timer, trace_window
from .timeline import build_trace, write_trace
from .tracing import Span, span

__all__ = [
    "DEFAULT_LATENCY_BUCKETS", "Counter", "Gauge", "Histogram", "Registry",
    "Span", "build_trace", "current_registry", "default_registry",
    "device_timer", "profile", "span", "timeline", "trace_window",
    "use_registry", "write_trace",
]
