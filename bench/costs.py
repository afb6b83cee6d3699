"""Operations and bytes that a call needs, from its shapes alone.

These are the least work of an algorithm, not what a kernel happens to
do: a multiply-add counts as 2 operations, every operand and result is
moved once, and rows or experts that carry no routed token cost nothing.
A kernel's roofline share is the least time these give on the chip's
peaks (``bench/peaks.json``) over the kernel's measured device time, so
it cannot pass 100% unless the counts below are too high.
"""
from __future__ import annotations


def dense_is_gemm(M: int, K: int, N: int, *, group: int = 128,
                  w_bits: int = 4) -> tuple[int, int]:
    """W4A8 integer-scale GEMM (``kernels/w4a8_gemm.py``): int8 activations
    (M, K) with f32 per-token scales, packed int4 weights (K, N), int32
    group scales (K/group, N), f32 output (M, N)."""
    ops = 2 * M * K * N
    nbytes = (M * K + 4 * M               # int8 activations, their scales
              + K * N * w_bits // 8       # packed weights
              + 4 * (K // group) * N      # integer scales
              + 4 * M * N)                # f32 result
    return ops, nbytes


def ragged_is_gemm(rows, K: int, N: int, *, group: int = 128,
                   w_bits: int = 4, x_bytes: int = 2) -> tuple[int, int]:
    """Ragged grouped W4A8 integer-scale GEMM (``kernels/moe_gemm.py``)
    over experts with ``rows[e]`` routed rows each: raw activations
    (``x_bytes`` each) are read and quantized in the kernel, and only the
    experts with a routed row need their weights."""
    routed = sum(int(r) for r in rows)
    active = sum(1 for r in rows if r > 0)
    ops = 2 * routed * K * N
    nbytes = (routed * K * x_bytes
              + active * (K * N * w_bits // 8 + 4 * (K // group) * N + 4)
              + 4 * routed * N)
    return ops, nbytes


def least_seconds(ops: int, nbytes: int, peak_ops: float,
                  peak_bytes: float) -> float:
    return max(ops / peak_ops, nbytes / peak_bytes)


def moe_capacity(tokens: int, cfg: dict) -> int:
    """Rows per expert of the MoE dispatch for ``tokens`` tokens: the
    capacity rule the program states (``serving.capacity_factor``),
    8-aligned."""
    c = int(tokens * cfg["num_experts_per_tok"]
            * cfg["serving"]["capacity_factor"] / cfg["num_local_experts"])
    return max(8, -(-c // 8) * 8)


def attention_projections(cfg: dict) -> list[tuple[int, int]]:
    """(K, N) of the q, k, v and o projections of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return [(d, hq * hd), (d, hkv * hd), (d, hkv * hd), (hq * hd, d)]


def mlp_projections(cfg: dict) -> list[tuple[int, int]]:
    """(K, N) of the gate, up and down projections of one (expert) MLP."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return [(d, f), (d, f), (f, d)]


def token_ops(cfg: dict, ctx: int) -> int:
    """Model operations of one token that attends to ``ctx`` positions
    (itself included): every projection it passes through, the routed
    experts only, attention scores and values, and the head."""
    hd, hq = cfg["head_dim"], cfg["num_attention_heads"]
    experts = cfg.get("num_local_experts", 0)
    per_layer = sum(2 * K * N for K, N in attention_projections(cfg))
    per_layer += 4 * hq * hd * ctx
    mlp = sum(2 * K * N for K, N in mlp_projections(cfg))
    if experts:
        per_layer += cfg["num_experts_per_tok"] * mlp
        per_layer += 2 * cfg["hidden_size"] * experts
    else:
        per_layer += mlp
    return (cfg["num_hidden_layers"] * per_layer
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def prefill_ops(cfg: dict, prompt_len: int) -> int:
    """Model operations of a prefill over ``prompt_len`` real tokens; only
    the last of them needs the head."""
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    hd, hq = cfg["head_dim"], cfg["num_attention_heads"]
    ctx_sum = prompt_len * (prompt_len + 1) // 2
    return (prompt_len * (token_ops(cfg, 0) - head) + head
            + cfg["num_hidden_layers"] * 4 * hq * hd * ctx_sum)
