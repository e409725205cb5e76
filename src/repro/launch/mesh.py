"""Production mesh construction (deliverable e).

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

Functions, not module-level constants, so importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_smoke_mesh", "make_walker_mesh", "HW"]


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: shardings are propagated by
    the compiler and ``with_sharding_constraint`` takes plain specs (JAX's
    default is now ``Explicit`` axes, which the constraints here do not
    use)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, model_parallel: int = 16):
    """Production mesh: 256 chips/pod.  ``model_parallel`` splits the pod
    between data and model axes (16x16 default; 32x8 is the §Perf layout
    for archs whose head counts do not divide 16 — same 256 chips)."""
    assert 256 % model_parallel == 0
    data = 256 // model_parallel
    shape = (2, data, model_parallel) if multi_pod else (data, model_parallel)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_smoke_mesh():
    """1-device mesh for CPU smoke tests (same axis names as production)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_walker_mesh(num_devices: int | None = None):
    """1-D fleet mesh: ``num_devices`` (default: all visible devices) on the
    ``data`` axis — the mesh axis the ``walker`` logical axis of
    ``repro.sharding.rules`` maps to, so a W-walker ``WalkFleet`` shards
    its walker batch across every device and the periodic cross-walker
    model average becomes one all-reduce along ``data``.  On CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
    initializes to get a multi-device fleet mesh (the CI sharded leg)."""
    n = len(jax.devices()) if num_devices is None else num_devices
    return _auto_mesh((n,), ("data",))


class HW:
    """TPU v5e hardware constants for the roofline model (per chip)."""

    PEAK_FLOPS_BF16 = 197e12  # FLOP/s
    HBM_BW = 819e9  # bytes/s
    ICI_BW = 50e9  # bytes/s per link
    HBM_BYTES = 16 * 1024**3
