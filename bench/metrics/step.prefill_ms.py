"""Mean device time of one prefill (batch 1, padded to ``prefill_len``):
window delta of the sum over the count of
``engine_phase_device_seconds{phase="prefill"}``."""


def read(ctx):
    key = 'phase="prefill"'
    s = ctx.counters.get("engine_phase_device_seconds:sum", {}).get(key, 0)
    n = ctx.counters.get("engine_phase_device_seconds:count", {}).get(key, 0)
    return 1e3 * s / n if n else None
