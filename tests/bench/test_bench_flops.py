"""The required prefill operations, against a hand count at a smoke size:
a configuration that names no family is counted as the dense GQA
decoder."""

from bench import spec

SMOKE = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "intermediate_size": 128, "num_hidden_layers": 2, "vocab_size": 256}
prefill_flops = spec.family(SMOKE).prefill_flops


def test_hand_count_whole_prompt():
    # per token and layer: q 64x64, k and v 64x32 each, o 64x64, MLP 3 x 64x128
    per_token = 2 * (64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128)
    assert per_token == 2 * 36864
    # attention over 4 tokens: positions see 1 + 2 + 3 + 4 = 10 keys,
    # scores and values: 2 matmuls x 2 ops x 4 heads x 16 dims per key
    attention = 10 * 2 * 2 * 4 * 16
    head = 2 * 64 * 256  # the last position only
    assert prefill_flops(SMOKE, 4, 0) == 2 * (4 * per_token + attention) + head


def test_cached_prefix_is_not_counted():
    # 2 new tokens at positions 2 and 3 see 3 + 4 keys
    per_token = 2 * 36864
    attention = 7 * 2 * 2 * 4 * 16
    assert prefill_flops(SMOKE, 4, 2) == 2 * (2 * per_token + attention) + 2 * 64 * 256
    assert prefill_flops(SMOKE, 4, 4) == 0.0
