"""The dense architecture's operation and byte counts against hand
counts at a tiny size."""
from arch import dense
from arch.dense import Dims
from spans import Step

# d 8, 2 heads of 4, 1 KV head, ff 16, vocab 10, 2 layers
M = Dims(layers=2, d=8, heads=2, kv_heads=1, hd=4, ff=16, vocab=10,
         padded_vocab=256, theta=1e4, eps=1e-6, gated=True)
GELU = Dims(**{**M.__dict__, "gated": False})


def test_layer_matmul_params_by_hand():
    # wq 8x2x4=64, wk 8x1x4=32, wv 32, wo 2x4x8=64 -> 192
    # swiglu: 3 x 8 x 16 = 384; gelu: 2 x 8 x 16 = 256
    assert dense.layer_matmul_params(M) == 192 + 384
    assert dense.layer_matmul_params(GELU) == 192 + 256


def test_one_token_flops_by_hand():
    # per layer: 2 x 576 = 1152 matmul, 4 x 2 x 4 x ctx attention
    # head: 2 x 8 x 10 = 160 (published vocab, not the padded one)
    assert dense.token_flops(M, 1) == 2 * (1152 + 32) + 160
    assert dense.token_flops(M, 5) == 2 * (1152 + 160) + 160


def test_step_flops_several_slots():
    assert dense.step_flops(M, Step([1, 5, 5])) == (
        dense.token_flops(M, 1) + 2 * dense.token_flops(M, 5))
    assert dense.step_flops(M, Step([])) == 0


def test_bytes_by_hand():
    # weights: 2 x (576 + 16 norms) + 8 final norm + 80 head, x2 bytes
    w = (2 * (576 + 16) + 8 + 80) * 2
    assert dense.weight_bytes(M) == w
    # KV per position: 2 layers x (k, v) x 1 head x 4 x 2 bytes = 32
    assert dense.kv_bytes_per_position(M) == 32
    # one token at context 1: weights, its embedding row (16 B), no
    # earlier KV, one position written
    assert dense.step_bytes(M, Step([1])) == w + 16 + 0 + 32
    # three tokens at contexts 1, 4, 10: read 0 + 3 + 9 positions
    assert dense.step_bytes(M, Step([1, 4, 10])) == \
        w + 3 * 16 + 12 * 32 + 3 * 32
