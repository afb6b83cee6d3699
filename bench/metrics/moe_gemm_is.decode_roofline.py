"""Roofline share of the ragged grouped W4A8 integer-scale MoE GEMM
(``kernels/moe_gemm.py``, custom call
``fg_grouped_gemm_integer_scale_ragged``) in the decode program: the
least time of a decode step's calls, from the routed rows of each decode
MoE layer in the window (``models/moe.py`` routing sink; an expert with
no routed row costs nothing), times the decode runs that lie in the
traced window, over the kernel's device time inside those runs."""
from bench import costs
from bench import trace as tr

KERNEL, MODULE = "fg_grouped_gemm_integer_scale_ragged", "jit_decode_fn"


def read(ctx):
    cfg = ctx.cfg
    E = cfg.get("num_local_experts", 0)
    if ctx.plane is None or ctx.peaks is None or not E:
        return None
    busy, runs = tr.kernel_seconds(ctx.trace, ctx.plane, kernel=KERNEL,
                                   module=MODULE)
    B = ctx.mix["engine"]["max_slots"]
    cap = costs.moe_capacity(B, cfg)
    recs = [r for r in ctx.routing if r["capacity"] == cap]
    if not runs or busy <= 0 or not recs:
        return None
    q = cfg["quantization"]
    least = sum(
        costs.least_seconds(*costs.ragged_is_gemm(
            [int(v) for v in r["counts"][0]], K, N, group=q["group_size"],
            w_bits=q["w_bits"]),
            ctx.peaks["int8_ops_per_s"], ctx.peaks["hbm_bytes_per_s"])
        for r in recs for K, N in costs.mlp_projections(cfg))
    per_step = least * cfg["num_hidden_layers"] / len(recs)
    return 100.0 * per_step * runs / busy
