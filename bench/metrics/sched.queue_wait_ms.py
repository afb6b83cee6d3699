"""Mean wait of a request in the engine's queue, from ``Engine.submit``
to the start of its admission: window delta of the sum over the count of
``engine_queue_wait_seconds`` (``serving/engine.py``), in ms. None where
the program has no such histogram or admitted nothing in the window."""


def read(ctx):
    s = ctx.counters.get("engine_queue_wait_seconds:sum", {}).get("", 0)
    n = ctx.counters.get("engine_queue_wait_seconds:count", {}).get("", 0)
    return 1e3 * s / n if n else None
