"""Core CFL building blocks: delay model, redundancy optimization, encoding,
straggler-masked aggregation, and the protocol orchestrator."""
from .delay_model import DeviceDelayParams, compute_cdf, total_cdf, sample_total
from .returns import expected_return, optimal_loads
from .redundancy import RedundancyPlan, solve_redundancy, systematic_weights
from .encoding import ClientParity, generator_matrix, encode_client, encode_fleet
from .aggregation import gd_update, nmse
from .cfl import CFLState, setup

__all__ = [
    "DeviceDelayParams", "compute_cdf", "total_cdf", "sample_total",
    "expected_return", "optimal_loads",
    "RedundancyPlan", "solve_redundancy", "systematic_weights",
    "ClientParity", "generator_matrix", "encode_client", "encode_fleet",
    "gd_update", "nmse",
    "CFLState", "setup",
]
