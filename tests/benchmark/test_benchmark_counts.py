"""``counts.py`` against FLOPs and bytes worked out by hand."""

import pytest

from benchmark import counts

SASREC = dict(embedding_dim=64, num_blocks=2, num_heads=2, max_sequence_length=50,
              ffn_dim=256, num_items=27278)
BERT4REC = dict(embedding_dim=300, num_blocks=2, num_heads=4, max_sequence_length=100,
                ffn_dim=1200, num_items=27278)


def test_sasrec_step_flops_by_hand():
    # T = 512*50 = 25,600 positions
    head = 2 * 25600 * 64 * 27278  # 89,384,550,400
    assert counts.head_forward_flops(SASREC, 512) == head == 89_384_550_400
    # one block: 4 projections 8*T*d*d, scores+mix 4*T*L*d, FFN 4*T*d*f
    block = 8 * 25600 * 64 * 64 + 4 * 25600 * 50 * 64 + 4 * 25600 * 64 * 256
    assert block == 838_860_800 + 327_680_000 + 1_677_721_600
    assert counts.blocks_forward_flops(SASREC, 512) == 2 * block
    assert counts.step_train_flops(SASREC, 512) == 3 * (head + 2 * block) == 285_219_225_600


def test_bert4rec_step_flops_by_hand():
    head = 2 * 25600 * 300 * 27278
    block = 8 * 25600 * 300 * 300 + 4 * 25600 * 100 * 300 + 4 * 25600 * 300 * 1200
    assert head == 418_990_080_000 and block == 18_432_000_000 + 3_072_000_000 + 36_864_000_000
    assert counts.step_train_flops(BERT4REC, 256) == 3 * (head + 2 * block) == 1_607_178_240_000
    assert counts.head_train_flops(BERT4REC, 256) == 3 * head


def test_head_bytes_and_bound_by_hand():
    # hidden bf16 read + its gradient written, f32 table read + gradient written, int32 labels
    expected = 2 * 25600 * 64 * 2 + 2 * 27278 * 64 * 4 + 25600 * 4
    assert counts.head_train_bytes(SASREC, 512) == expected == 20_622_336
    peaks = counts.load_peaks("TPU v5 lite")
    seconds, bound = counts.head_least_seconds(SASREC, 512, peaks)
    assert bound == "compute"
    assert seconds == pytest.approx(3 * 89_384_550_400 / 197e12)  # 1.361 ms


def test_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="peaks.json"):
        counts.load_peaks("cpu")
