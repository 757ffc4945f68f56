"""Production mesh: one TPU v5e pod = (data=16, model=16) = 256 chips;
multi-pod adds a leading pod axis (2 pods = 512 chips).

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches jax device state. When the host exposes
more placeholder devices than the mesh needs (the dry-run forces 512), the
single-pod mesh takes the first 256.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — the "
            f"dry-run must set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count=512 before importing jax")
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def dp_axes(mesh: Mesh) -> tuple:
    """Data-parallel axes: ('pod','data') on multi-pod, ('data',) otherwise."""
    return tuple(a for a in mesh.axis_names if a != "model")


def data_shards(mesh: Mesh) -> int:
    """Number of data shards a serving engine partitions its slot axis
    (and page pool) into: the product of the non-model axes."""
    return int(np.prod([mesh.shape[a] for a in dp_axes(mesh)], dtype=int))


def make_serving_mesh(shape: tuple[int, int] = (2, 2), *,
                      devices=None) -> Mesh:
    """(data, model) mesh for a sharded ``StreamingEngine`` over whatever
    devices exist. Real accelerators are used as they are; forced
    host-platform devices (``XLA_FLAGS=--xla_force_host_platform_device_
    count=8`` set BEFORE the first jax import) are for CPU rehearsal and
    tests only. Unlike the production mesh this takes any shape that fits
    the device count, so a (2, 2) mesh exercises real cross-shard paths
    on one host, and ``(1, 1)`` with ``devices=[d]`` pins an engine to
    device ``d``."""
    n = int(np.prod(shape))
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) < n:
        raise RuntimeError(
            f"serving mesh {tuple(shape)} needs {n} devices, have "
            f"{len(devices)} — real accelerators are used as they are; "
            f"forced host devices (XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n} set before importing jax) are for a CPU "
            f"rehearsal only")
    return Mesh(np.asarray(devices[:n]).reshape(shape), ("data", "model"))
