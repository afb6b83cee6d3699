"""Mean device time of one batched decode step: window delta of the sum
over the count of ``engine_phase_device_seconds{phase="decode"}`` (the
engine's ``block_until_ready``-bracketed timer; exact, not a bucket
quantile)."""


def read(ctx):
    key = 'phase="decode"'
    s = ctx.counters.get("engine_phase_device_seconds:sum", {}).get(key, 0)
    n = ctx.counters.get("engine_phase_device_seconds:count", {}).get(key, 0)
    return 1e3 * s / n if n else None
