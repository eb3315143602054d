"""bench/flops.py against hand counts at a reduced shape."""
from bench import flops

M = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 2, "d_ff": 16, "vocab_size": 10}


def test_params_and_token_flops():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, three MLP matrices 8x16
    assert flops.layer_params(M) == 64 + 32 + 32 + 64 + 3 * 128
    assert flops.matmul_flops_per_token(M) == 2 * 2 * 576
    assert flops.head_flops(M) == 2 * 8 * 10


def test_attention_counts_causal_keys():
    # 4 flops per (head, head dim, key), per layer
    assert flops.attn_flops(M, 5) == 4 * 2 * 4 * 2 * 5
    # 3 new tokens after 2 cached: they see 3, 4 and 5 keys
    assert flops.prefill_flops(M, 3, 2) == (
        3 * 2304 + flops.attn_flops(M, 12) + 160)
    assert flops.prefill_flops(M, 3) == 3 * 2304 + flops.attn_flops(M, 6) + 160
    assert flops.decode_flops(M, 7) == 2304 + 160 + flops.attn_flops(M, 7)


def test_paged_attention_bytes_count_each_kv_head_once():
    f, b = flops.paged_attention_call(M, 1, 6)        # decode at position 6
    assert f == flops.attn_flops(M, 7)
    # K and V of 7 positions, 2 layers x 2 KV heads x 2 dims x 2 bytes,
    # plus the query read and output written (2 layers x 4 heads x 2 dims)
    assert b == 2 * 2 * 2 * 2 * 7 * 2 + 2 * 2 * 2 * 4 * 2
