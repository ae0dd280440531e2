"""Arithmetic of the plain reference: the precision it computes in.

`FLOAT64` is the reference itself: NumPy in float64 on the host.
`HIGH` is its control, the step below what the configurations state
(float32 with matrix products at `Precision.HIGHEST`): float32 with
every product taken as XLA's `high` precision takes it on a TPU, three
bfloat16 passes (hi*hi + hi*lo + lo*hi, the lo*lo term dropped) summed in
float32.  The passes are done here explicitly, so the control reads the
same on any machine.

    a = ar.prep(x)        # cast once (and split, for HIGH)
    ar.mm(a, v), ar.mm(a.T, r)
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import ml_dtypes
import numpy as np


def _bf16(a: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and widen back to float32 (exact)."""
    return a.astype(ml_dtypes.bfloat16).astype(np.float32)


class Split(NamedTuple):
    """A float32 operand as the sum of two bfloat16 parts."""

    hi: np.ndarray
    lo: np.ndarray

    @property
    def T(self) -> "Split":
        return Split(self.hi.T, self.lo.T)


@dataclasses.dataclass(frozen=True)
class Arith:
    name: str
    dtype: type

    def cast(self, a) -> np.ndarray:
        return np.asarray(a, dtype=self.dtype)

    def prep(self, a):
        """An operand ready for `mm`: cast, and split for HIGH."""
        if isinstance(a, Split) or self.name != "high":
            return a if isinstance(a, Split) else self.cast(a)
        a = self.cast(a)
        hi = _bf16(a)
        return Split(hi, _bf16(a - hi))

    def mm(self, a, b) -> np.ndarray:
        """a @ b in this precision."""
        a, b = self.prep(a), self.prep(b)
        if self.name != "high":
            return a @ b
        # each product of two bfloat16 values is exact in float32; the
        # sums are float32, as on the MXU
        return a.hi @ b.hi + (a.hi @ b.lo + a.lo @ b.hi)


FLOAT64 = Arith("float64", np.float64)
HIGH = Arith("high", np.float32)
