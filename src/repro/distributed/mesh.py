"""Mesh construction helpers.

``launch/mesh.py`` owns the *production* mesh (16x16 / 2x16x16); this module
holds the generic machinery: building a mesh for any MeshConfig, including
tiny CPU meshes for tests, plus PartitionSpec helpers shared across the stack.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import MeshConfig


def abstract_devices(n: int):
    """The first ``n`` devices visible to this process: chips on an
    accelerator host, host devices on the CPU."""
    devs = jax.devices()
    if len(devs) < n:
        d = devs[0]
        if d.platform == "cpu":
            hint = ("set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                    "*before* importing jax (launch/dryrun.py does this)")
        else:
            hint = f"run on a host with {n} chips, or ask for fewer"
        raise RuntimeError(
            f"mesh needs {n} devices but only {len(devs)} {d.platform} "
            f"device(s) ({d.device_kind}) are visible; {hint}")
    return devs[:n]


def make_mesh(cfg: MeshConfig) -> Mesh:
    devs = abstract_devices(cfg.n_devices)
    import numpy as np
    arr = np.array(devs).reshape(cfg.shape)
    return Mesh(arr, cfg.axes)


def local_mesh(shape=(1, 1), axes=("data", "model")) -> Mesh:
    """Tiny mesh over whatever devices exist — for smoke tests on CPU."""
    return make_mesh(MeshConfig(tuple(shape), tuple(axes)))


def dp_spec(mesh_cfg: MeshConfig) -> tuple:
    """The mesh axes carrying data parallelism, as a PartitionSpec entry."""
    axes = mesh_cfg.dp_axes
    if len(axes) == 1:
        return axes[0]
    return tuple(axes)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
