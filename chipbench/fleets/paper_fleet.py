"""The paper's §IV fleet (arXiv:2002.09574): geometric MAC-rate and link
ladders with decay nu_comp and nu_link, randomly assigned from the seed."""
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
    return (network.paper_fleet(spec["nu_comp"], spec["nu_link"], seed=s,
                                n=n, d=d),
            ref.paper_fleet(n, d, spec["nu_comp"], spec["nu_link"], s))
