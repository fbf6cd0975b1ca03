"""The from-shapes arithmetic reproduces the ROADMAP's hand figures."""

import pytest

from bench_paths import load

shapes = load("shapes.py")


def test_roadmap_hand_figures_1m_x_50_bin64():
    # ROADMAP Queue 1: "hoisted streams n.F.B int8 = 3.2 GB per level ...
    # against 2.n.128.F.B = 8.2e11 flop"
    n, F, B = 1_000_000, 50, 64
    flops = shapes.level_hist_flops(n, F, B, K=32, pad_to_mxu=True)
    assert flops == pytest.approx(8.192e11)
    assert shapes.level_hist_flops(n, F, B, K=1, pad_to_mxu=True) == flops
    onehot = float(n) * F * B  # the program's resident int8 one-hot, streamed
    assert onehot == pytest.approx(3.2e9)
    peaks = shapes.load_peaks("TPU v5 lite")
    assert onehot / peaks["hbm_bytes_per_s"] == pytest.approx(3.9e-3, rel=0.02)


def test_unpadded_flops_scale_with_channels_and_terms():
    a = shapes.level_hist_flops(1000, 4, 16, K=4)
    assert a == 2.0 * 1000 * 4 * 16 * (2 * 4 * 2)
    assert shapes.level_hist_flops(1000, 4, 16, K=4, bf16_terms=1) == a / 2
    assert shapes.round_hist_flops(1000, 4, 16, depth=3) == sum(
        shapes.level_hist_flops(1000, 4, 16, K=k) for k in (1, 2, 4))


def test_level_bytes_are_the_algorithms_not_the_programs():
    # bins, g + h + pos, the histogram: no one-hot, however much is hoisted
    assert shapes.level_hist_bytes(1000, 10, 256, K=2) == \
        1000 * 10 + 12 * 1000 + 4 * 10 * 256 * 4
    assert shapes.level_hist_bytes(1000, 10, 512, K=2) == \
        2 * 1000 * 10 + 12 * 1000 + 4 * 10 * 512 * 4
    assert shapes.round_hist_bytes(1000, 10, 256, depth=2) == \
        shapes.level_hist_bytes(1000, 10, 256, K=1) \
        + shapes.level_hist_bytes(1000, 10, 256, K=2)


def test_walk_bytes_per_row():
    assert shapes.walk_bytes_per_row(50, 500, 6) == 200 + 500 * 100


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        shapes.load_peaks("TPU v9000")
    with pytest.raises(KeyError):
        shapes.load_peaks("_source")


def test_level_roofline_picks_the_larger_bound():
    peaks = shapes.load_peaks("TPU v5 lite")
    # the anchor: the matmul's flops bound every level (bins are 38 MB a
    # level, 0.06 ms), 0.39 ms at the root, 12.5 ms at 32 nodes
    t0, b0 = shapes.level_hist_min_seconds(750_592, 50, 256, 1, peaks)
    t5, b5 = shapes.level_hist_min_seconds(750_592, 50, 256, 32, peaks)
    assert (b0, b5) == ("flops", "flops")
    assert t0 == pytest.approx(0.39e-3, rel=0.02)
    assert t5 == pytest.approx(12.5e-3, rel=0.02)
    # one bin and one node: the reads bound it
    assert shapes.level_hist_min_seconds(10**6, 50, 1, 1, peaks)[1] == "bytes"
