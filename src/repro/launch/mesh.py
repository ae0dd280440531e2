"""Production mesh construction.

Single pod: 16 x 16 = 256 chips over ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips over ("pod", "data", "model").

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run must set XLA_FLAGS before the first jax call.
TPU v5e constants (roofline): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax

# TPU v5e per-chip hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # bytes/s
ICI_BW = 50e9                 # bytes/s per link

SINGLE_POD_SHAPE = (16, 16)
SINGLE_POD_AXES = ("data", "model")
MULTI_POD_SHAPE = (2, 16, 16)
MULTI_POD_AXES = ("pod", "data", "model")


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = MULTI_POD_AXES if multi_pod else SINGLE_POD_AXES
    return jax.make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1) -> jax.sharding.Mesh:
    """A tiny mesh over whatever devices exist (CPU tests/examples)."""
    n = len(jax.devices())
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"))


def lane_mesh_size(n_lanes: int) -> int:
    """Device count for a lane mesh built with no device list: the
    largest divisor of `n_lanes` that fits the local device count.

    Divisibility is required: `shard_map` splits a chunk's lanes evenly
    over its mesh, so every device runs the same per-lane program, and
    the sweep engine's bit-for-bit-with-solo guarantee rides on that.
    The serving engine's lane groups take this size; the sweep engine
    places its chunks with `place_lanes` instead.
    """
    if n_lanes < 1:
        raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
    return next(k for k in range(min(len(jax.devices()), n_lanes), 0, -1)
                if n_lanes % k == 0)


def make_lane_mesh(n_lanes: int,
                   devices: Optional[Sequence[int]] = None
                   ) -> jax.sharding.Mesh:
    """1-d mesh over the lane (batch-of-sessions) axis of one engine call.

    Each device runs its block of the call's lanes locally, so the mesh
    never changes any lane's arithmetic.  `devices` are indices into
    `jax.devices()`, one per mesh position, and their count must divide
    `n_lanes` (a chunk from `place_lanes`); without them the mesh is the
    first `lane_mesh_size(n_lanes)` devices.
    """
    if devices is None:
        devices = range(lane_mesh_size(n_lanes))
    if not devices or n_lanes % len(devices):
        raise ValueError(f"{len(devices)} devices cannot split "
                         f"{n_lanes} lanes evenly")
    every = jax.devices()
    return jax.sharding.Mesh([every[i] for i in devices], ("lanes",))


Chunk = Tuple[int, Tuple[int, ...], Tuple[int, ...]]


def place_lanes(buckets: Sequence[Tuple[int, float]],
                n_devices: int) -> List[Chunk]:
    """Split shape buckets into engine calls and place them on devices.

    `buckets` holds one (lane count, lane weight) pair per bucket, the
    weight being a lane's cost (the sweep engine gives the rows of its X
    operand).  Returns chunks `(bucket, lane positions in the bucket,
    device indices)`, in the order to dispatch them: buckets by
    descending total weight, ties to the lower index.  A bucket of B
    lanes runs its first `(B // D) * D` lanes as one chunk on all D
    devices, and the remaining `B % D` as one chunk of one lane a device
    on the least-loaded devices so far (ties to the lower index, listed
    in ascending order).  The placement depends on its arguments alone,
    so one fleet always meets the same compiled engines.
    """
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if any(n < 1 for n, _ in buckets):
        raise ValueError(f"every bucket needs a lane, got {buckets}")
    load = [0.0] * n_devices
    chunks: List[Chunk] = []
    for b in sorted(range(len(buckets)),
                    key=lambda b: (-buckets[b][0] * buckets[b][1], b)):
        n, weight = buckets[b]
        rest = n % n_devices
        full = n - rest
        if full:
            chunks.append((b, tuple(range(full)), tuple(range(n_devices))))
            load = [w + weight * (full // n_devices) for w in load]
        if rest:
            least = sorted(range(n_devices), key=lambda d: (load[d], d))
            devices = tuple(sorted(least[:rest]))
            chunks.append((b, tuple(range(full, n)), devices))
            for d in devices:
                load[d] += weight
    return chunks


def make_shard_mesh() -> jax.sharding.Mesh:
    """1-d mesh over ALL local devices for tensor-sharded solves.

    Unlike the lane mesh (whose size adapts to the lane count), the shard
    mesh always spans every local device: `repro.fleet.solve_fleet`
    splits one problem's DEVICE axis across it, so more devices means a
    smaller per-device slab of the `(t_grid, n, L)` expected-return
    tensor, not more lanes.
    """
    return jax.sharding.Mesh(jax.devices(), ("shards",))


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """The batch-parallel axes of a mesh (includes 'pod' when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
