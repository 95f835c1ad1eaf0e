"""Work, bytes and peaks."""

import pytest

from bench import work
from repro.core.partitioner import plan_gemm_partition


@pytest.mark.parametrize("m,n,k,budget", [
    (1000, 700, 300, 2**20), (4096, 4096, 4096, 3 * 2**20),
    (513, 129, 1000, 2**21), (40960, 40960, 40960, 16909336064 // 2)])
def test_gemm_work_is_2mnk_whatever_the_partition(m, n, k, budget):
    part = plan_gemm_partition(m, n, k, budget, 4)
    by_blocks = sum(2 * min(part.bm, m - i) * min(part.bn, n - j) * k
                    for i in range(0, m, part.bm)
                    for j in range(0, n, part.bn))
    assert by_blocks == work.gemm_flops(m, n, k) == 2 * m * n * k


def test_min_bytes_read_a_b_c_once_and_write_once():
    assert work.gemm_min_bytes(2, 3, 5, 4) == 4 * (2 * 5 + 5 * 3 + 2 * 2 * 3)


def test_peaks_of_v5e_and_unknown_device_raises():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["flop_per_s"]["bfloat16"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        work.peaks("TPU v9")


def test_least_time_names_its_bound():
    assert work.least_seconds(2e12, 1e9, 1e12, 1e9) == (2.0, "compute")
    assert work.least_seconds(1e12, 4e9, 1e12, 1e9) == (4.0, "memory")


def test_share_over_105_percent_is_an_error():
    assert work.share(1.0, 1.0) == 100.0
    assert work.share(1.05, 1.0) == pytest.approx(105.0)
    with pytest.raises(ValueError, match="over 105"):
        work.share(1.06, 1.0)
