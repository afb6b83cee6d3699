"""Engine host time between the jitted programs, per decode tick: window
delta of the sum of ``engine_host_seconds`` over every part (the inputs,
cache splice, first token and routing fan-out of each admission; the
inputs, logits transfer, sampling and routing fan-out of each decode),
plus the ``retire`` phase span (``engine_phase_seconds{phase="retire"}``),
over the window delta of ``engine_ticks_total``, in ms
(``serving/engine.py`` spans). None where the program has no such span."""


def read(ctx):
    ticks = ctx.counters.get("engine_ticks_total", {}).get("", 0)
    host = ctx.counters.get("engine_host_seconds:sum")
    if not ticks or not host:
        return None
    retire = ctx.counters.get("engine_phase_seconds:sum", {}).get(
        'phase="retire"', 0)
    return 1e3 * (sum(host.values()) + retire) / ticks
