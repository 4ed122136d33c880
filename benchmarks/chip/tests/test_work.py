"""The work model counts the problem's own sizes, and the peak table
knows only the devices it lists."""

import pytest

import work

KIND = "TPU v5 lite"


def test_ijcnn1_counts_unpadded_x():
    l, d, B = 49_990, 22, 9
    w = work.pass_work(l, d, B)
    x = 4 * l * d
    assert x == 4_399_120                      # not 4 * 50176 * 128 = 25.7 MB
    assert w["pass_a"].bytes == x + 5 * B * l
    assert w["pass_b"].bytes == x + 9 * B * l
    assert w["pass_a"].flops == 2 * B * l * d
    assert w["pass_b"].flops == 4 * B * l * d


def test_doubled_state_scales_state_bytes_only():
    one, two = work.pass_work(1000, 8, 4, H=1), work.pass_work(1000, 8, 4, H=2)
    for p in ("pass_a", "pass_b"):
        assert two[p].flops == one[p].flops
        assert two[p].bytes - one[p].bytes == one[p].bytes - 4 * 1000 * 8


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("TPU v99")
    with pytest.raises(KeyError):
        work.least_time(work.pass_work(10, 2, 1)["pass_a"], "cpu")


def test_mnist_passes_are_bound_by_hbm():
    w = work.pass_work(60_000, 780, 10)
    for p in ("pass_a", "pass_b"):
        t, side = work.least_time(w[p], KIND)
        assert side == "hbm"
        assert t == pytest.approx(w[p].bytes / 819e9)
    assert work.least_time(work.Work(1e15, 1.0), KIND)[1] == "flops"


def test_x_streams_only_where_x_exceeds_on_chip_memory():
    assert work.x_streams(60_000, 780, KIND)
    assert not work.x_streams(49_990, 22, KIND)
