"""Plain reference of the federated sessions the benchmark times.

It imports nothing of `src/`: the fleet, the plan, the delay sampler,
the parity encode and the epoch engine are written out here from the
paper's equations (arXiv:2002.09574 §II-§III), in the precision an
`Arith` names (`FLOAT64`, or its control `HIGH`).  Random draws that
define the deployment (the generator matrices G_i) are taken with
`jax.random` from the same keys the session was given.

`cfl.py` writes out the equations; a configuration names the module that
computes its sessions from them (`"reference": "linear_head"`,
`linear_head.py`), which `checks.py` and the count call.
"""
from .arith import FLOAT64, HIGH, Arith  # noqa: F401
