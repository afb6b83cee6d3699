"""Share of decode slots that carried a request, over the window's ticks:
window delta of ``engine_tokens_total`` over ``engine_ticks_total`` times
``max_slots`` (``serving/engine.py`` counters)."""


def read(ctx):
    ticks = ctx.counters.get("engine_ticks_total", {}).get("", 0)
    tokens = ctx.counters.get("engine_tokens_total", {}).get("", 0)
    if not ticks:
        return None
    return 100.0 * tokens / (ticks * ctx.mix["engine"]["max_slots"])
