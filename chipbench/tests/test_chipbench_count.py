"""The count of operations and bytes, against hand arithmetic at §IV."""
import numpy as np
import pytest

import count

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "vmem_bytes": 128 * 2 ** 20}


def test_full_epoch_at_sec4():
    # 24 clients x 300 rows, all received: 7,200 rows of d = 500
    rows = count.masked_rows(np.full(24, 300), np.ones((1, 24)))
    w = count.epoch_work(500, rows)
    assert rows.tolist() == [7200.0]
    assert w.resident == 4 * 501 * 7200
    assert w.sys_flops == 4 * 500 * 7200 == 14_400_000
    assert w.sys_bytes == 4 * 501 * 7200 == 14_428_800
    assert w.par_flops == w.par_bytes == 0


def test_masks_count_only_received_clients():
    loads = np.array([300, 250, 0, 100])
    received = np.array([[1, 0, 1, 1], [0, 0, 0, 0], [1, 1, 1, 1]])
    rows = count.masked_rows(loads, received)
    assert rows.tolist() == [400.0, 0.0, 650.0]
    w = count.epoch_work(10, rows)
    assert w.sys_flops == 4 * 10 * 1050
    assert w.sys_bytes == 4 * 11 * 1050


@pytest.mark.parametrize("c, d, flops, nbytes", [
    # c = 2,016 > d = 500: the Gram-folded (d x d) form is the lesser
    (2016, 500, 2 * 500 * 500, 4 * 500 * 501),
    # c = 1,200 < d_feat = 2,000: the raw (c x d) form reads less, the
    # Gram form computes less; each is counted at its lesser
    (1200, 2000, 2 * 2000 * 2000, 4 * 1200 * 2001),
])
def test_parity_counts_the_lesser_form(c, d, flops, nbytes):
    ok = np.array([1, 0, 1])
    w = count.epoch_work(d, np.zeros(3), c, ok)
    assert w.par_flops == 2 * flops
    assert w.par_bytes == 2 * nbytes
    assert w.flops == w.par_flops and w.bytes == w.par_bytes


def test_least_time_and_its_bound():
    # one full §IV epoch: 14.43 MB at 819 GB/s would be 17.6 us, but the
    # block fits in 128 MiB of VMEM and may stay there between epochs, so
    # only the 14.4 MFLOP at 197 TFLOP/s (0.073 us) are owed
    t, bound = count.least_seconds(14_400_000, 14_428_800, PEAKS,
                                   resident=14_428_800)
    assert bound == "compute"
    assert t == pytest.approx(14_400_000 / 197e12)
    # a 60,000 x 2,000 feature block (480 MB) cannot stay: HBM bounds it
    nbytes = 4 * 2001 * 60_000
    t, bound = count.least_seconds(4 * 2000 * 60_000, nbytes, PEAKS,
                                   resident=nbytes)
    assert bound == "hbm" and t == pytest.approx(nbytes / 819e9)
    # by default nothing is taken to stay on the chip
    t, bound = count.least_seconds(1.0, 819e9, PEAKS)
    assert bound == "hbm" and t == pytest.approx(1.0)


def test_work_adds():
    a = count.epoch_work(4, np.array([2.0]), 8, np.array([1]))
    b = count.epoch_work(4, np.array([3.0]))
    s = a + b
    assert s.sys_flops == 4 * 4 * 5 and s.par_flops == a.par_flops
