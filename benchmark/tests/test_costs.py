"""Op/byte functions against hand counts."""

import pytest

from benchmark.costs import flash_attention, group_norm, peaks


def test_attention_by_hand():
    # 2 rows x 10 heads; QK^T: 4096*4096*64 MACs, PV the same; 2 flops a MAC
    flops, nbytes = flash_attention.needed(2, 10, 4096, 4096, 64)
    assert flops == 2 * 10 * 2 * (2 * 4096 * 4096 * 64) == 85_899_345_920
    # q, k, v, out: 4 arrays of 2*10*4096*64 bf16
    assert nbytes == 4 * (2 * 10 * 4096 * 64) * 2 == 41_943_040
    seconds, bound = peaks.least_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "compute"
    assert seconds == pytest.approx(85_899_345_920 / 197e12)


def test_cross_attention_is_counted_at_its_true_keys():
    listed = [[4096, 4096, 10, 64], [4096, 77, 10, 64], [2304, 2304, 10, 64]]
    assert flash_attention.true_lengths(10, 4096, 128, 64, listed) == (
        4096, 77, True)
    assert flash_attention.true_lengths(10, 2560, 2560, 64, listed) == (
        2304, 2304, True)
    assert flash_attention.true_lengths(10, 4096, 4096, 64, listed) == (
        4096, 4096, True)
    assert flash_attention.true_lengths(7, 512, 512, 64, listed) == (
        512, 512, False)


def test_group_norm_by_hand():
    flops, nbytes = group_norm.needed(2, 1024, 640)
    assert flops == 7 * 2 * 1024 * 640
    assert nbytes == 2 * (2 * 1024 * 640) * 2
    assert peaks.least_seconds(flops, nbytes, "TPU v5 lite")[1] == "memory"


def test_an_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_of("TPU v9")
