"""Shared machinery for the coded follow-up schemes (`repro.schemes`).

Both follow-up strategies are CFL-family protocols: a one-time redundancy
solve (through `repro.plan`'s batched grid solver), a one-time parity
upload, then deadline-`t*` epochs combining systematic and parity
gradients.  The accounting they share with `CodedFL` — parity-upload bits,
upload-time sampling, uplink totals — lives in ONE place, `repro.core.cfl`
(re-exported here), so the bit-for-bit degenerate-equivalence guarantees
cannot drift; this module adds only the shared state dataclass.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core.cfl import (coded_uplink_bits, fused_coded_device_state,
                            parity_upload_bits, sample_parity_upload_time)
from repro.core.delay_model import DeviceDelayParams
from repro.core.redundancy import RedundancyPlan

__all__ = ["CodedSchemeState", "coded_uplink_bits",
           "fused_coded_device_state", "sample_parity_upload_time"]


@dataclasses.dataclass
class CodedSchemeState:
    """Protocol state shared by the coded follow-up schemes after `plan`.

    plan:      the redundancy solve's output (loads, c, t*, return probs)
    load_mask: (n, ell) 1.0 on each client's systematic points
    x_parity:  (c, d) composite parity features resident at the server
    y_parity:  (c,)   composite parity labels
    """

    plan: RedundancyPlan
    load_mask: jax.Array
    x_parity: jax.Array
    y_parity: jax.Array
    edge: DeviceDelayParams
    server: DeviceDelayParams

    @property
    def c(self) -> int:
        return int(self.x_parity.shape[0])

    def parity_upload_bits(self, bits_per_value: int = 32,
                           header_overhead: float = 0.10) -> np.ndarray:
        """Bits each client uploads for its parity shard (one-time cost)."""
        return parity_upload_bits(self.edge.n, self.c,
                                  int(self.x_parity.shape[1]),
                                  bits_per_value, header_overhead)
