"""Engine host time spent on the decode logits, per decode tick: window
delta of ``engine_host_seconds`` over the parts ``decode.logits_to_host``
(the (slots, vocab) float32 logits copied to the host) and
``decode.nan_guard`` (the per-slot scan of them for NaN/Inf), over the
window delta of ``engine_ticks_total``, in ms (``serving/engine.py``
spans). None where the program times no ``decode.nan_guard`` part: the
transfer alone would be another quantity."""

PARTS = ('part="decode.logits_to_host"', 'part="decode.nan_guard"')


def read(ctx):
    ticks = ctx.counters.get("engine_ticks_total", {}).get("", 0)
    host = ctx.counters.get("engine_host_seconds:sum", {})
    if not ticks or PARTS[1] not in host:
        return None
    return 1e3 * sum(host.get(p, 0.0) for p in PARTS) / ticks
