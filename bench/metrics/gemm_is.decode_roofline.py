"""Roofline share of the dense W4A8 integer-scale GEMM
(``kernels/w4a8_gemm.py``, custom call ``fg_gemm_integer_scale``) in the
decode program: the least time of one decode step's calls on the chip's
peaks (``bench/costs.py``; M = max_slots, as every slot is computed),
times the decode runs that lie in the traced window, over the kernel's
device time inside those runs (``bench/trace.py``)."""
from bench import costs
from bench import trace as tr

KERNEL, MODULE = "fg_gemm_integer_scale", "jit_decode_fn"


def read(ctx):
    if ctx.plane is None or ctx.peaks is None:
        return None
    busy, runs = tr.kernel_seconds(ctx.trace, ctx.plane, kernel=KERNEL,
                                   module=MODULE)
    if not runs or busy <= 0:
        return None
    shapes = costs.attention_projections(ctx.cfg)
    if not ctx.cfg.get("num_local_experts"):
        shapes += costs.mlp_projections(ctx.cfg)
    M = ctx.mix["engine"]["max_slots"]
    q = ctx.cfg["quantization"]
    step = ctx.cfg["num_hidden_layers"] * sum(
        costs.least_seconds(*costs.dense_is_gemm(
            M, K, N, group=q["group_size"], w_bits=q["w_bits"]),
            ctx.peaks["int8_ops_per_s"], ctx.peaks["hbm_bytes_per_s"])
        for K, N in shapes)
    return 100.0 * step * runs / busy
