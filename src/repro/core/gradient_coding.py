"""Gradient coding baseline (Tandon et al., ICML 2017 — the paper's ref [5]).

The comparison the paper positions CFL against: instead of coding the DATA
(CFL), gradient coding replicates data across clients and codes the
GRADIENTS.  With replication factor r, client i holds the data of clients
{i, i+1, ..., i+r-1 (mod n)} and uploads a fixed linear combination of
those partial gradients; the server can recover the exact full gradient
from ANY n - (r - 1) clients (tolerates s = r - 1 stragglers).

We implement the "fractional repetition" construction for the common case
r | n (clients split into n/r groups of r; each group member holds the
whole group's data and returns the group-sum; the server needs >= 1
returner per group).  This module holds the plan; the epochs run through
`repro.api.GradientCodingFL`.

Key contrasts with CFL recorded in EXPERIMENTS.md §Ablation:
  * requires SHARING RAW DATA between clients (privacy cost CFL avoids);
  * each client's per-epoch compute is r x larger (it processes r shards);
  * exact recovery (no LLN approximation), but the epoch ends only when
    every group has a returner — the tail is clipped less aggressively
    than CFL's fixed deadline.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class GradCodingPlan:
    r: int                  # replication factor
    groups: np.ndarray      # (n,) group id of each client

    @property
    def tolerated_stragglers_per_group(self) -> int:
        return self.r - 1


def make_plan(n_clients: int, r: int) -> GradCodingPlan:
    if n_clients % r != 0:
        raise ValueError(f"fractional repetition needs r | n "
                         f"({r} does not divide {n_clients})")
    groups = np.repeat(np.arange(n_clients // r), r)
    return GradCodingPlan(r=r, groups=groups)
