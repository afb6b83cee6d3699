"""Device-time attribution and opt-in profiler capture windows.

The host wall-clock spans (``repro.obs.tracing``) time whole tick phases
— jitted compute, dispatch overhead, sampling, cache splicing, python
bookkeeping, all mixed. This module separates the device component:

* :func:`device_timer` wraps a (typically jitted) callable so every call
  is ``jax.block_until_ready``-bracketed and observed into a
  ``*_device_seconds`` histogram on the *current* registry. The first
  ``warmup`` calls — which pay trace+compile — are excluded from the
  histogram (they land in a ``*_device_warmup_total`` counter instead),
  so the series reflects steady-state device time. Subtracting it from
  the enclosing host span gives host overhead per phase.
* :func:`trace_window` is the ``jax.profiler.trace`` capture window
  behind ``launch/serve.py --profile-dir``: a no-op when the dir is
  falsy, otherwise the XLA profiler writes
  ``plugins/profile/<ts>/*.xplane.pb`` under the dir (open in
  TensorBoard's profile plugin or convert for Perfetto).

The "no metrics inside jitted bodies" rule holds: both helpers sit on
the host side of the jit boundary — the wrapped callable's jit cache is
untouched (arguments pass through verbatim), so decode still traces
exactly once with a device timer attached. ``jax`` is imported lazily so
``repro.obs`` itself stays importable without it.
"""
from __future__ import annotations

import contextlib

from .metrics import DEFAULT_LATENCY_BUCKETS, current_registry


def device_timer(fn, metric: str, *, warmup: int = 1, help: str = "",
                 **labels):
    """Wrap ``fn`` with block_until_ready-bracketed device timing.

    ``metric`` must end in ``_device_seconds`` (the naming contract that
    pairs it with the host ``*_seconds`` span histogram). The registry is
    resolved per call via :func:`current_registry`, and its clock is used
    — a fake clock drives deterministic tests end-to-end.
    """
    if not metric.endswith("_device_seconds"):
        raise ValueError(
            f"device_timer metric {metric!r} must end '_device_seconds'")
    warm_metric = metric[: -len("_device_seconds")] + "_device_warmup_total"
    state = {"calls": 0}

    def timed(*args, **kwargs):
        import jax

        reg = current_registry()
        t0 = reg.now()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        dt = reg.now() - t0
        state["calls"] += 1
        if state["calls"] > warmup:
            reg.histogram(metric, help, tuple(sorted(labels)),
                          buckets=DEFAULT_LATENCY_BUCKETS,
                          ).observe(dt, **labels)
        else:
            reg.counter(warm_metric,
                        "device_timer calls excluded as warmup/compile",
                        tuple(sorted(labels))).inc(**labels)
        return out

    timed.calls = lambda: state["calls"]
    timed.__wrapped__ = fn
    return timed


@contextlib.contextmanager
def trace_window(log_dir: str | None):
    """Opt-in ``jax.profiler.trace`` capture: no-op when ``log_dir`` is
    falsy, else profile the enclosed block into ``log_dir``."""
    if not log_dir:
        yield None
        return
    import jax

    with jax.profiler.trace(log_dir):
        yield log_dir
