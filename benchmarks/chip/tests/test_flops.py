"""The benchmark's FLOP and byte counts against hand counts at one shape."""

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
