"""Production mesh construction.

A function, not a module constant — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).

Recorded XLA flags for real-TPU runs (collective/compute overlap — these
change nothing on the CPU dry-run but are part of the deployment config):

  --xla_tpu_enable_async_collective_fusion=true
  --xla_tpu_enable_async_collective_fusion_fuse_all_gather=true
  --xla_tpu_overlap_compute_collective_tc=true
  --xla_enable_async_all_gather=true
  --xla_enable_async_collective_permute=true
"""

from __future__ import annotations

import numpy as np

TPU_PERF_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
    "--xla_enable_async_all_gather=true "
    "--xla_enable_async_collective_permute=true"
)

__all__ = ["make_production_mesh", "make_local_mesh", "mesh_desc",
           "TPU_PERF_FLAGS"]


def _auto_mesh(shape, axes, devices):
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings
    through ops (an ``Explicit`` axis makes ``jnp.take`` on a vocab-sharded
    table a sharding-type error)."""
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) data×model single pod; (2,16,16) pod×data×model for 2 pods.

    Uses the first prod(shape) available devices so a 512-device host
    platform can build both meshes."""
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before the first jax import (launch/dryrun.py does this)")
    return _auto_mesh(shape, axes, devices[:n])


def make_local_mesh(n_model: int = 4):
    """(data=1, model=n_model) tensor-parallel mesh over the first
    ``n_model`` local devices — one host's chips, one process."""
    import jax

    devices = jax.local_devices()
    if len(devices) < n_model:
        raise RuntimeError(f"need {n_model} local devices for a "
                           f"(1, {n_model}) mesh, have {len(devices)}")
    return _auto_mesh((1, n_model), ("data", "model"), devices[:n_model])


def mesh_desc(mesh) -> str:
    return "x".join(f"{n}:{s}" for n, s in
                    zip(mesh.axis_names, mesh.devices.shape))
