"""Operation and byte counts against hand counts for one small shape."""
import pytest

from bench import flops
from bench.kernels import paged_decode_attention as pda

M = {"d": 8, "ff": 16, "heads": 2, "kv_heads": 1, "head_dim": 4,
     "layers": 3, "vocab": 10, "rank": 2}


def test_paged_decode_attention():
    # two rows with 5 and 3 cached tokens, 2 heads of 4, one kv head
    assert pda.flops([5, 3], 2, 4) == 4 * 2 * 4 * 8
    # keys and values: 2 * 8 tokens * 1 * 4 = 64; q and out: 2 * 2 * 2 * 4
    assert pda.bytes_moved([5, 3], 2, 1, 4, 2) == (64 + 32) * 2


def test_layer_and_head_params():
    # q 8x8, k and v 8x4 each, o 8x8, gate/up/down 8x16 each
    assert flops.layer_params(M) == 64 + 64 + 64 + 3 * 128
    assert flops.head_params(M) == 80


def test_decode_flops():
    per_tok = 2 * (3 * 576 + 80)
    attn = 4 * 3 * 2 * 4 * (7 + 2)
    assert flops.decode_flops(M, [7, 2]) == 2 * per_tok + attn


def test_decode_bytes():
    # weights (3 * 576 + 80) * 2; one adapter's factors; kv of 9 live + 2 new
    bank = 3 * 2 * (16 + 12 + 12 + 16 + 24 + 24 + 24) * 4
    kv = 2 * 3 * 1 * 4 * 2 * (9 + 2)
    assert flops.decode_bytes(M, [7, 2], 1, 2, 4, 2) == \
        (3 * 576 + 80) * 2 + bank + kv


@pytest.mark.parametrize("seq", [1, 4])
def test_train_flops(seq):
    attn = 3 * 2 * 3 * 2 * 4 * (seq + 1)
    assert flops.train_flops_per_token(M, seq) == 4 * (3 * 576 + 80) + attn
