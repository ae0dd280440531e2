"""Wall-clock simulation of heterogeneous federated fleets (paper §IV)."""
from .network import FleetSpec, make_fleet, paper_fleet

__all__ = ["FleetSpec", "make_fleet", "paper_fleet"]
