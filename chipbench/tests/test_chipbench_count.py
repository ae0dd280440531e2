"""The count of operations and bytes, against hand arithmetic at §IV and
at a head of K outputs; and the count of a session, drawn over the masks
its reference answer draws."""
import numpy as np
import pytest

import count
import cut
import deploy
from reference import FLOAT64
from reference import cfl as ref

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "vmem_bytes": 128 * 2 ** 20}


def test_full_epoch_at_sec4():
    # 24 clients x 300 rows, all received: 7,200 rows of d = 500
    rows = count.masked_rows(np.full(24, 300), np.ones((1, 24)))
    w = count.epoch_work(500, rows, outputs=1)
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
    w = count.epoch_work(10, rows, outputs=1)
    assert w.sys_flops == 4 * 10 * 1050
    assert w.sys_bytes == 4 * 11 * 1050


@pytest.mark.parametrize("c, d, k, flops, nbytes", [
    # c = 2,016 > d = 500: the Gram-folded (d x d) form is the lesser
    (2016, 500, 1, 2 * 500 * 500, 4 * 500 * 501),
    # c = 1,200 < d_feat = 2,000: the raw (c x d) form reads less, the
    # Gram form computes less; each is counted at its lesser
    (1200, 2000, 1, 2 * 2000 * 2000, 4 * 1200 * 2001),
    # the same at K = 62 outputs: both forms scale with K
    (2016, 500, 62, 2 * 500 * 500 * 62, 4 * 500 * 562),
    (1200, 2000, 62, 2 * 2000 * 2000 * 62, 4 * 1200 * 2062),
    # c = 300 < d / 2: the raw form is the lesser on both counts
    (300, 784, 62, 4 * 300 * 784 * 62, 4 * 300 * 846),
])
def test_parity_counts_the_lesser_form(c, d, k, flops, nbytes):
    ok = np.array([1, 0, 1])
    w = count.epoch_work(d, np.zeros(3), c, ok, outputs=k)
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
    a = count.epoch_work(4, np.array([2.0]), 8, np.array([1]), outputs=1)
    b = count.epoch_work(4, np.array([3.0]), outputs=1)
    s = a + b
    assert s.sys_flops == 4 * 4 * 5 and s.par_flops == a.par_flops


@pytest.mark.parametrize("k", [1, 10, 62])
def test_rows_scale_with_outputs(k):
    # a (d, K) head: 4 d K operations and d + K values read a row
    rows = np.array([300.0, 0.0, 7200.0])
    w = count.epoch_work(784, rows, outputs=k)
    assert w.sys_flops == 4 * 784 * k * 7500
    assert w.sys_bytes == 4 * (784 + k) * 7500
    assert w.resident == 4 * (784 + k) * 7200
    one = count.epoch_work(784, rows, outputs=1)
    assert w.sys_flops == k * one.sys_flops


def test_codedfedl_count_draws_the_answers_masks():
    # the count of a CodedFedL session draws its masks as its reference
    # answer does: rows of d_feat features, the MEC delay model
    system = deploy.build(cut.config("codedfedl_mnist"), seed=3_000_000_031)
    mod = system.reference
    ans = mod.answer(system, "cfedl", 13, 23, {}, FLOAT64)
    plan = ref.Plan(ans.loads, ans.parity.shape[0], ans.t_star,
                    ans.p_return, float("nan"))
    sched = mod.schedule(system, system.spec("cfedl", {}), plan, 23)
    assert np.array_equal(sched.times, ans.times)
    work = mod.work(system, "cfedl", 23, plan)
    assert work == count.epoch_work(
        cut.D_FEAT, count.masked_rows(plan.loads, sched.received), plan.c,
        sched.parity_ok, outputs=1)
    # the draw at the data's width under the base model is another session
    other = ref.sample_coded(system.ref_fleet, plan, cut.D,
                             system.cfg["epochs"], np.random.default_rng(23))
    assert not np.array_equal(other.times, ans.times)
