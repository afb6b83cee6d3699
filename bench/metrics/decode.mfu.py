"""Share of the chip's int8 peak that the decode steps reach: model
operations of the decode tokens served in the window (every token after
a request's first, with attention over its context; ``bench/costs.py``)
over the device time of the decode program's runs in the traced window
(``bench/trace.py``) times the peak. Padding slots count nothing. It
bounds the decode kernels' roofline shares from the side of the whole
step."""
from bench import costs
from bench import trace as tr


def read(ctx):
    if ctx.plane is None or ctx.peaks is None:
        return None
    busy = tr.module_seconds(ctx.trace, ctx.plane, "jit_decode_fn")
    ops = sum(costs.token_ops(ctx.cfg, len(r.prompt) + j)
              for r in ctx.records for j, t in enumerate(r.times)
              if j > 0 and ctx.open_t <= t < ctx.close_t)
    if busy <= 0 or not ops:
        return None
    return 100.0 * ops / (busy * ctx.peaks["int8_ops_per_s"])
