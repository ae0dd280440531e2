"""Operations and bytes of training epochs, counted from the problem.

    w = epoch_work(d, row_counts, parity_rows, parity_ok, outputs=K)
    w.sys_flops, w.sys_bytes     # the masked round gradient over X
    w.flops, w.bytes             # plus the parity term

The count never reads the program's layout.  For each epoch it takes the
rows of the clients whose update counts in that epoch's arrival mask,
each row of X read once (d features and its K labels, 4 bytes each): the
residual and the gradient of a (d, K) head are 2 d K operations a row
each.  The parity term counts only in epochs where it arrives, its
operations and its bytes each at the lesser of its raw form (c rows of
d + K read, 4 c d K operations) and its Gram-folded form (d x (d + K)
read, 2 d^2 K).  So it is a lower bound on the work: no packing,
skipping or folding a program does can push a share of a peak past 100%.
"""
from __future__ import annotations

import dataclasses

import numpy as np

ITEM = 4  # float32


@dataclasses.dataclass(frozen=True)
class Work:
    sys_flops: float
    sys_bytes: float
    par_flops: float
    par_bytes: float
    resident: float = 0.0  # the largest data block one session reads

    @property
    def flops(self) -> float:
        return self.sys_flops + self.par_flops

    @property
    def bytes(self) -> float:
        return self.sys_bytes + self.par_bytes

    def __add__(self, other: "Work") -> "Work":
        sums = [a + b for a, b in zip(dataclasses.astuple(self)[:4],
                                      dataclasses.astuple(other)[:4])]
        return Work(*sums, max(self.resident, other.resident))


NONE = Work(0.0, 0.0, 0.0, 0.0)


def epoch_work(d: int, row_counts: np.ndarray, parity_rows: int = 0,
               parity_ok=None, *, outputs: int) -> Work:
    """Work of a run of epochs.

    d: feature width.  row_counts: (E,) rows whose update counts in each
    epoch.  parity_rows: c (0 for no parity).  parity_ok: (E,) 1 where
    the parity gradient arrives in time.  outputs: K, the head's output
    columns.  The block the epochs read is taken as the largest epoch's
    rows."""
    k = float(outputs)
    rows = float(np.sum(row_counts))
    sys_flops = 4.0 * d * k * rows
    sys_bytes = ITEM * (d + k) * rows
    block = ITEM * (d + k) * float(np.max(row_counts, initial=0.0))
    if parity_rows <= 0 or parity_ok is None:
        return Work(sys_flops, sys_bytes, 0.0, 0.0, block)
    hits = float(np.sum(parity_ok))
    c = float(parity_rows)
    par_flops = hits * min(4.0 * c * d * k, 2.0 * d * d * k)
    par_bytes = hits * ITEM * min(c * (d + k), d * (d + k))
    return Work(sys_flops, sys_bytes, par_flops, par_bytes, block)


def masked_rows(loads: np.ndarray, received: np.ndarray) -> np.ndarray:
    """(E,) rows that count per epoch: client i's loads_i rows wherever
    received[e, i] is set."""
    return np.asarray(received, np.float64) @ np.asarray(loads, np.float64)


def least_seconds(flops: float, nbytes: float, peaks: dict,
                  resident: float = float("inf")) -> tuple:
    """(seconds, bound) the chip needs at least: the larger of the compute
    time at the bf16 peak and the HBM time, and which one it is.  Where
    the block an epoch reads (`resident` bytes) fits in the chip's VMEM it
    can stay there from epoch to epoch, so no HBM time is owed."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    if resident <= peaks["vmem_bytes"]:
        t_m = 0.0
    return (t_c, "compute") if t_c >= t_m else (t_m, "hbm")
