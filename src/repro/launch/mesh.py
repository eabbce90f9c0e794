"""Production mesh definitions (TPU v5e pods).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state.
"""
from __future__ import annotations

import functools

import jax


def _auto_axes(n_axes: int):
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto_axes(len(axes)))


def make_host_mesh():
    """Degenerate 1x1 mesh for CPU smoke runs of the sharded code paths."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto_axes(2))


@functools.lru_cache(maxsize=None)
def n_local_devices() -> int:
    """Local device count, probed ONCE per process (jax.devices() is a
    platform-initialising call; callers gate mesh decisions on it every
    fabric seal)."""
    return len(jax.devices())


@functools.lru_cache(maxsize=None)
def make_shard_mesh(max_devices: int | None = None):
    """1-D ``("shard",)`` mesh over the local devices for the ledger
    fabric's K shard lanes (kernels/shard_lanes.py).  Lane rows pad to a
    multiple of the mesh size, so any K runs on any device count; under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
    ``shard-mesh`` job) this is a real 8-device CPU mesh.  Cached — jax
    meshes hash by device assignment, and the fused loop asks for the
    mesh once per digest fold."""
    n = n_local_devices()
    if max_devices is not None:
        n = max(1, min(n, max_devices))
    return jax.make_mesh((n,), ("shard",), axis_types=_auto_axes(1))


# TPU v5e hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # bytes/s
ICI_BW = 50e9                 # bytes/s per link (~per-chip effective)
HBM_BYTES = 16 * 1024 ** 3    # 16 GiB
