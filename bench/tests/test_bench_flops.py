"""The dense block's work counts against hand counts at a reduced shape,
and against the integers pinned for both benchmarked configurations."""
import json

import pytest

import tiny
from bench import blocks

flops = blocks.of({})                      # no "block" key: dense

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


# The counts the readers divided by before the blocks were split out
# (``bench/flops.py``), for (n, start), keys and (queries, start): the
# per-layer metrics of the benchmarked cells stay comparable across it.
PINNED = {
    "starcoder2-3b": {
        "prefill": [(1, 0, 8323964928), (256, 0, 2065960009728),
                    (1536, 0, 12756637974528), (257, 1024, 2171090608128),
                    (1024, 2560, 9374256857088)],
        "decode": [(1, 8323964928), (1537, 8890195968),
                   (4096, 9833545728)],
        "paged": [(1, 0, (368640, 399360)), (1, 1536, (566599680, 47585280)),
                  (256, 1024, (108763545600, 133693440))],
    },
    "phi4-mini-3.8b": {
        "prefill": [(1, 0, 7672037376), (256, 0, 1663431868416),
                    (1536, 0, 10360992301056), (257, 1024, 1773457244160),
                    (1024, 2560, 7835450867712)],
        "decode": [(1, 7672037376), (1537, 8276017152), (4096, 9282256896)],
        "paged": [(1, 0, (393216, 524288)), (1, 1536, (604372992, 201850880)),
                  (256, 1024, (116014448640, 268435456))],
    },
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_counts_of_the_benchmarked_configurations_are_pinned(name):
    conf = json.loads(
        (tiny.ROOT / "bench" / "configs" / f"{name}.json").read_text())
    block, m, want = blocks.of(conf), conf["model"], PINNED[name]
    assert [(n, s, block.prefill_flops(m, n, s))
            for n, s, _ in want["prefill"]] == want["prefill"]
    assert [(k, block.decode_flops(m, k))
            for k, _ in want["decode"]] == want["decode"]
    assert [(q, s, tuple(block.paged_attention_call(m, q, s)))
            for q, s, _ in want["paged"]] == want["paged"]
