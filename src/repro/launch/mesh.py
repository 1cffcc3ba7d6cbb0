"""Production meshes. TARGET: TPU v5e pods — 256 chips (16x16) per pod,
2 pods = 512 chips for the multi-pod dry-run.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) for the roofline model
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link


def force_host_devices(n: int) -> None:
    """Give the CPU backend `n` devices, in this process. XLA reads the
    flag when a backend starts, and importing `repro.core` starts one, so
    an entry point calls this before it imports anything else of the
    repo. It adds no device on an accelerator, where the mesh is the
    chips."""
    flag = f"--xla_force_host_platform_device_count={n}"
    cur = os.environ.get("XLA_FLAGS", "")
    if flag not in cur:
        os.environ["XLA_FLAGS"] = (cur + " " + flag).strip()


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU tests (uses however many host devices exist)."""
    return _make_mesh((data, model), ("data", "model"))


def make_data_mesh(devices: int = 0, axis: str = "data"):
    """1-D data-parallel mesh over `devices` local devices (0 = all).

    This is the mesh the `repro.api` sharded execution backend shards the
    rollout batch/stream axis over (`api/backends.py`); on CPU CI it is
    driven with XLA_FLAGS=--xla_force_host_platform_device_count=N.
    """
    n = int(devices) or jax.local_device_count()
    return _make_mesh((n,), (axis,))
