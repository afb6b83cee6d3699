"""Operation and byte counts against counts made by hand."""
from bench import costs


def test_dense_is_gemm_hand_count():
    # M=2 tokens, K=256, N=128, group 128, int4 weights
    ops, nbytes = costs.dense_is_gemm(2, 256, 128)
    assert ops == 2 * 2 * 256 * 128
    assert nbytes == (2 * 256 + 2 * 4          # int8 rows, f32 row scales
                      + 256 * 128 // 2         # packed int4 weights
                      + 2 * 128 * 4            # int32 scales, 2 groups
                      + 2 * 128 * 4)           # f32 result


def test_ragged_counts_only_routed_experts():
    rows = [3, 0, 1, 0]
    ops, nbytes = costs.ragged_is_gemm(rows, 128, 256)
    assert ops == 2 * 4 * 128 * 256
    w = 128 * 256 // 2 + 1 * 256 * 4 + 4      # one expert's weights
    assert nbytes == 4 * 128 * 2 + 2 * w + 4 * 256 * 4
    # an expert with no routed row adds nothing
    assert costs.ragged_is_gemm(rows + [0], 128, 256) == (ops, nbytes)


def test_least_seconds_takes_the_binding_roof():
    assert costs.least_seconds(100, 10, 10.0, 10.0) == 10.0
    assert costs.least_seconds(10, 100, 10.0, 10.0) == 10.0


CFG = {"hidden_size": 8, "head_dim": 2, "num_attention_heads": 4,
       "num_key_value_heads": 2, "intermediate_size": 16,
       "num_hidden_layers": 3, "vocab_size": 10}


def test_token_ops_dense_hand_count():
    attn = 2 * (8 * 8 + 8 * 4 + 8 * 4 + 8 * 8)   # q, k, v, o
    mlp = 2 * 3 * 8 * 16
    ctx = 5
    scores = 4 * 4 * 2 * ctx                      # q.k and p.v per head
    head = 2 * 8 * 10
    assert costs.token_ops(CFG, ctx) == 3 * (attn + mlp + scores) + head


def test_token_ops_moe_counts_routed_experts_and_router():
    moe = dict(CFG, num_local_experts=4, num_experts_per_tok=2)
    dense = costs.token_ops(CFG, 1)
    mlp = 2 * 3 * 8 * 16
    router = 2 * 8 * 4
    assert costs.token_ops(moe, 1) == dense + 3 * (mlp + router)


def test_prefill_ops_counts_the_causal_triangle_and_one_head():
    P = 4
    head = 2 * 8 * 10
    per_token = costs.token_ops(CFG, 0) - head
    scores = 3 * 4 * 4 * 2 * (1 + 2 + 3 + 4)
    assert costs.prefill_ops(CFG, P) == P * per_token + head + scores
