"""The algorithm's work counts and the peaks table."""
import pytest

from bench import counts, peaks


def test_rounds_and_work_by_hand():
    # n=8, budget=48: log2 n = 3; t = 48 // (s * 3)
    assert counts.rounds(8, 48) == [(8, 2), (4, 4), (2, 8)]
    w = counts.work(8, 3, 48)
    assert w["pulls"] == 8 * 2 + 4 * 4 + 2 * 8 == 48
    assert w["terms"] == 48 * 3
    assert w["bytes"] == ((8 + 2) + (4 + 4) + (2 + 8)) * 3 * 4


def test_rounds_stop_at_two_arms_and_clip_to_one_reference():
    # n=5, budget=10: t = max(10 // (s * 3), 1) = 1 in every round
    assert counts.rounds(5, 10) == [(5, 1), (3, 1), (2, 1)]
    assert counts.rounds(1, 10) == []


def test_paper_scale_rnaseq_counts():
    n, d, b = 20000, 27998, 30 * 20000
    rs = counts.rounds(n, b)
    assert rs[0] == (20000, 2) and rs[-1] == (2, 20000)
    w = counts.work(n, d, b)
    assert 1.6e10 < w["terms"] < 1.7e10           # ~1.68e10 |x - y| terms
    assert 9e9 < w["bytes"] < 1.2e10              # ~10 GB least bytes


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no peaks"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        peaks.least_seconds("cpu", bytes_=1.0)


@pytest.mark.parametrize("flops,bytes_", [(0.0, 1e9), (1e12, 1e6),
                                          (3.9e14, 1.6e12), (1.0, 1.0)])
@pytest.mark.parametrize("slack", [1.0, 1.0001, 2.0, 1e6])
def test_share_never_exceeds_100_when_kernel_takes_the_least_time(
        flops, bytes_, slack):
    least = peaks.least_seconds("TPU v5 lite", flops=flops, bytes_=bytes_)
    share = peaks.share_pct(least, least * slack)
    assert 0.0 < share <= 100.0


def test_share_reads_nothing_without_kernel_time():
    assert peaks.share_pct(1e-3, 0.0) is None
