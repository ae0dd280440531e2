"""The MEC fleet (arXiv:2011.06223): the §IV ladders plus per-client
erasure probabilities on a ladder of their own (nu_erasure), randomly
assigned from the seed."""
from __future__ import annotations

from typing import Any, Dict

from deploy import derive
from reference import cfl as ref


def build(spec: Dict[str, Any], data_spec: Dict[str, Any], seed: int):
    """(the program's fleet, the reference's), each built from the same
    draw; `d` is the width a client ships per row (default the data's)."""
    from repro.sim import network

    s = derive(seed, 1)
    n, d = data_spec["n"], spec.get("d", data_spec["d"])
    return (network.wireless_fleet(spec["nu_comp"], spec["nu_link"],
                                   spec["nu_erasure"], seed=s, n=n, d=d),
            ref.wireless_fleet(n, d, spec["nu_comp"], spec["nu_link"],
                               spec["nu_erasure"], s))
