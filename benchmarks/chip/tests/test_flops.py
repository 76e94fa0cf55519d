"""The benchmark's FLOP and byte counts against hand counts at one shape."""

import dataclasses

import pytest

from harness import flops

MINICPM = {"hidden_size": 2304, "num_hidden_layers": 40,
           "num_attention_heads": 36, "num_key_value_heads": 36,
           "intermediate_size": 5760, "vocab_size": 122753,
           "hidden_act": "silu"}
STARCODER2 = {"hidden_size": 3072, "num_hidden_layers": 30,
              "num_attention_heads": 24, "num_key_value_heads": 2,
              "intermediate_size": 12288, "vocab_size": 49152,
              "hidden_act": "gelu_pytorch_tanh"}


def test_chunk_bytes_by_hand():
    # head_dim 64, 64 tokens, 4 bits, rank 4, 2% outliers (1 per extreme):
    # codes 2*64*64/2 = 4096; K stats 2*64*2 = 256; V stats 2*64*2 = 256;
    # low rank 2*(64+64)*4*2 = 2048; outliers (64*2 + 64*2) * (2+4) = 1536
    assert flops.Gear(head_dim=64).chunk_bytes() == 8192
    # head_dim 128: codes 8192; stats 512 + 256; low rank 2*192*4*2 = 3072;
    # K outliers 128*2*6 = 1536, V outliers 64*(2*ceil(128*.02/2)=4)*6 = 1536
    assert flops.Gear(head_dim=128).chunk_bytes() == 15104
    # 184,320 and 14,160 bytes per cached token over all layers
    assert 40 * 36 * 8192 // 64 == 184_320
    assert 30 * 2 * 15104 // 64 == 14_160


def test_decode_paged_cost_by_hand():
    g = flops.Gear(head_dim=64)
    # one row, one live chunk, one query head: scores + values 4*64*64,
    # low rank 4*4*(64+64); bytes: the chunk, q (64 f32), acc + 2x128 lanes
    f, b = flops.decode_paged_cost(g, live_chunks=1, rows=1, group=1)
    assert f == 4 * 64 * 64 + 4 * 4 * 128 == 18432
    assert b == 8192 + 64 * 4 + (64 + 256) * 4
    f2, b2 = flops.decode_paged_cost(g, live_chunks=10, rows=3, group=2)
    assert f2 == 10 * 2 * 18432
    assert b2 == 10 * 8192 + 3 * 2 * 64 * 4 + 3 * 2 * 320 * 4


def test_compress_cost_by_hand():
    g = flops.Gear(head_dim=64)
    # K: read 64*64 f32, codes 64*64/2, stats 2*64 f32, 2 outliers per
    # channel (f32 + i32), residual 64*64 f32
    k = 16384 + 2048 + 512 + 2 * 64 * 8 + 16384
    v = 16384 + 2048 + 512 + 64 * 2 * 8 + 16384
    assert flops.compress_cost(g, 1, "k") == (0, k)
    assert flops.compress_cost(g, 3, "v") == (0, 3 * v)


def test_model_flops_by_hand():
    m = flops.Dense.from_config(MINICPM)
    d, f = 2304, 5760
    per_layer = 2 * d * d * 2 + 3 * d * f          # q, o; k, v; gate up down
    assert m.matmul_params() == 40 * per_layer
    assert m.token_flops(100, logits=False) == 2 * 40 * per_layer + 4 * 40 * 100 * 2304
    assert (m.token_flops(1, logits=True) - m.token_flops(1, logits=False)
            == 2 * d * 122753)
    n = 3
    assert m.prefill_flops(n) == (2 * m.matmul_params() * n
                                  + 4 * 40 * 2304 * 6 + 2 * d * 122753)
    s = flops.Dense.from_config(STARCODER2)
    # q, o 3072x3072; k, v 3072x256; up, down 3072x12288
    assert s.matmul_params() == 30 * (2 * 3072 * 3072 + 2 * 3072 * 256
                                      + 2 * 3072 * 12288)
    # about 2.88e9 weights in the layers, as published (3.03e9 with the
    # tied embedding)
    assert 2.8e9 < s.matmul_params() < 2.95e9


# Mellum2-12B-A2.5B's published widths (published config.json), cut to 8 layers:
# two periods of 3 sliding-window layers (window 1024) then 1 full
MELLUM2 = dict(layers=8, d_model=2304, heads=32, kv_heads=4, head_dim=128, d_ff=0,
               vocab=98304, gated=True, window=1024, experts=64, top_k=8, expert_ff=896)
PERIOD = ("window",) * 3 + ("full",)


@pytest.mark.parametrize("cfg", [MINICPM, STARCODER2], ids=["minicpm", "starcoder2"])
def test_dense_all_full_kinds_count_as_a_dense_decoder(cfg):
    """Layer kinds all full count what a dense decoder counts: every layer
    attends ``context`` keys, a prefill n(n+1)/2 a layer."""
    dense = flops.Dense.from_config(cfg)
    full = dataclasses.replace(dense, kinds=("full",) * dense.layers, window=1)
    L, qd = dense.layers, dense.heads * dense.head_dim
    assert full.gear_layers == dense.gear_layers == L
    assert full.matmul_params() == dense.matmul_params()
    for n in (1, 2, 63, 64, 1024, 1537, 2047):
        for logits in (False, True):
            head = 2 * dense.d_model * dense.vocab if logits else 0
            want = 2 * dense.matmul_params() + 4 * L * n * qd + head
            assert full.token_flops(n, logits) == dense.token_flops(n, logits) == want
        want = (2 * dense.matmul_params() * n + 4 * L * qd * n * (n + 1) // 2
                + 2 * dense.d_model * dense.vocab)
        assert full.prefill_flops(n) == dense.prefill_flops(n) == want


@pytest.mark.parametrize("n", [1023, 1024, 1025, 6000])
def test_dense_window_layers_clip_at_the_window(n):
    m = flops.Dense(kinds=PERIOD * 2, **MELLUM2)
    assert m.layers == 8 and m.gear_layers == 2
    qd = 32 * 128
    # a token at context n: 6 window layers see min(n, 1024) keys, 2 full n
    assert (m.token_flops(n, logits=False) - 2 * m.matmul_params()
            == 4 * qd * (6 * min(n, 1024) + 2 * n))
    # a prefill of n: token i sees min(i, 1024) keys on a window layer
    keys = 6 * sum(min(i, 1024) for i in range(1, n + 1)) + 2 * n * (n + 1) // 2
    assert m.prefill_flops(n) == (2 * m.matmul_params() * n + 4 * qd * keys
                                  + 2 * 2304 * 98304)


def test_dense_expert_layer_counts_top_k_experts_and_the_router():
    m = flops.Dense(kinds=PERIOD * 2, **MELLUM2)
    d, qd, kvd = 2304, 32 * 128, 4 * 128
    attn = 2 * d * qd + 2 * d * kvd
    # 8 SwiGLU experts of 896 (gate, up, down) and the router's d x 64
    assert m.matmul_params() == 8 * (attn + 8 * 3 * d * 896 + d * 64)
    dense_ffn = flops.Dense(kinds=PERIOD * 2, **dict(MELLUM2, experts=0, d_ff=896))
    assert m.matmul_params() - dense_ffn.matmul_params() == 8 * (7 * 3 * d * 896 + d * 64)
    # all 28 published layers: about 2.0e9 weights a token multiplies, 2.44e9
    # with the untied embedding and head (published "A2.5B")
    whole = flops.Dense(kinds=PERIOD * 7, **dict(MELLUM2, layers=28))
    assert 1.95e9 < whole.matmul_params() < 2.0e9
    assert 2.4e9 < whole.matmul_params() + 2 * 98304 * d < 2.5e9


@pytest.mark.parametrize("kinds, window, match", [
    (("sliding", "full") * 4, 1024, "sliding"),
    (PERIOD, 1024, "4 layer kinds for 8 layers"),
    (PERIOD * 2, 0, "window of at least 1")])
def test_dense_refuses_layer_kinds_it_cannot_count(kinds, window, match):
    with pytest.raises(ValueError, match=match):
        flops.Dense(kinds=kinds, **dict(MELLUM2, window=window))
