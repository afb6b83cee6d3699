"""Model operations of the real tokens served in the window over the
window times the chip's int8 peak. The quantized GEMMs run on the int8
MXU; attention and the bf16 head are counted against the same, higher,
peak, so this is a lower bound of any share of a peak. A prefill counts
its true prompt tokens, not the padding, and the head of its last token
only; a decode step counts the tokens of the slots it served
(``bench/costs.py``)."""
from bench import costs


def read(ctx):
    if ctx.peaks is None:
        return None
    ops = 0
    for r in ctx.records:
        p = len(r.prompt)
        for j, t in enumerate(r.times):
            if ctx.open_t <= t < ctx.close_t:
                ops += (costs.prefill_ops(ctx.cfg, p) if j == 0
                        else costs.token_ops(ctx.cfg, p + j))
    span = ctx.close_t - ctx.open_t
    return 100.0 * ops / (span * ctx.peaks["int8_ops_per_s"]) if ops \
        else None
