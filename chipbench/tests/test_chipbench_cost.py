"""The work counts and the peak table behind every roofline share."""

import pytest

from chipbench import cost, peaks


@pytest.mark.parametrize("shape, want", [
    ((64, 4_194_304, 16), (17_179_869_184, 67_108_864)),
    ((8, 1000, 16), (512_000, 16_000)),
])
def test_lbc_counts_sax_bytes_and_bound_ops(shape, want):
    # ops = Q * N * w * 4 (lookup, difference, square, add); bytes = N * w
    # (the uint8 SAX words), never the (Q, N) bounds the kernel writes.
    assert cost.lbc(*shape) == want


@pytest.mark.parametrize("shape, want", [
    ((1024, 256, 16, 256), (409_600, 1_064_960)),
    ((4_194_304, 256, 16, 256), (1_677_721_600, 4_362_076_160)),
])
def test_paa_isax_counts(shape, want):
    # ops = N*n adds + N*w*(1 divide + 8 compares); bytes = 4*N*n + N*w.
    assert cost.paa_isax(*shape) == want


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")


def test_roofline_share_from_the_larger_bound():
    # 1.97e11 ops take 1 ms at 197 TFLOP/s; 8.19e8 bytes take 1 ms at
    # 819 GB/s. Either bound over 2 ms reads 50%.
    assert peaks.roofline_pct(1.97e11, 0, 2e-3, "TPU v5 lite") == \
        pytest.approx(50.0)
    assert peaks.roofline_pct(0, 8.19e8, 2e-3, "TPU v5 lite") == \
        pytest.approx(50.0)
    assert peaks.roofline_pct(1.97e11, 8.19e8 / 4, 4e-3, "TPU v5 lite") == \
        pytest.approx(25.0)


def test_roofline_share_above_100_raises_not_clipped():
    with pytest.raises(ValueError, match="above 100%"):
        peaks.roofline_pct(1.97e11, 0, 0.5e-3, "TPU v5 lite")
