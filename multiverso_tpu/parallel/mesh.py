"""Device-mesh construction and table shardings.

The reference shards tables across *server processes* connected by MPI/ZMQ
(``src/table/array_table.cpp:98-108``). The TPU-native equivalent is a
``jax.sharding.Mesh`` whose ``"server"`` axis enumerates device shards in HBM;
Get/Add traffic becomes XLA collectives over ICI rather than point-to-point
messages. Extra axes ("worker" for data parallelism, "model" for intra-op
sharding) can be requested via the ``mesh_shape`` flag.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.utils.configure import get_flag
from multiverso_tpu.utils.log import log

SERVER_AXIS = "server"
WORKER_AXIS = "worker"


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """Parse ``'axis:size,axis:size'`` into an ordered dict."""
    axes: Dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, size = part.partition(":")
        axes[name.strip()] = int(size)
    return axes


def build_mesh(devices: Optional[Sequence[jax.Device]] = None,
               spec: Optional[str] = None) -> Mesh:
    """Build the framework mesh.

    Default: a 1-D mesh with every visible device on the ``"server"`` axis —
    the direct analog of the reference's "all ranks are servers" default role
    (``src/zoo.cpp:29-35``).
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if spec is None:
        spec = get_flag("mesh_shape")
    if spec:
        axes = parse_mesh_spec(spec)
        total = int(np.prod(list(axes.values())))
        if total > len(devices):
            raise ValueError(
                f"mesh_shape {spec} needs {total} devices, have {len(devices)}")
        dev_array = np.asarray(devices[:total]).reshape(tuple(axes.values()))
        return Mesh(dev_array, tuple(axes.keys()))
    return Mesh(np.asarray(devices), (SERVER_AXIS,))


def log_backend(devices: Sequence[jax.Device]) -> None:
    """One line naming what this process holds: platform, device kind and
    device ids. Every process that initializes a backend logs it, so a log
    shows at a glance whether work sat on the chip and on which one. A
    process a launcher restricted to one chip sees it as device 0, so the
    line also carries the host chip it was given (utils/chips.py)."""
    devices = list(devices)
    chip = os.environ.get("TPU_VISIBLE_CHIPS")
    log.info("jax backend: platform=%s device_kind=%s devices=%s%s",
             devices[0].platform, devices[0].device_kind,
             [d.id for d in devices],
             f" host_chip={chip}" if chip is not None else "")


def table_sharding(mesh: Mesh, ndim: int, axis: int = 0,
                   mesh_axis=SERVER_AXIS) -> NamedSharding:
    """Shard dimension ``axis`` of an ndim-array over ``mesh_axis`` (one
    mesh axis name, or a tuple of names for a combined split — the
    cross-replica state sharding uses ``(server, worker)``).

    ArrayTable: 1-D contiguous split (ref array_table.cpp:98-108).
    MatrixTable: row split (ref matrix_table.cpp:347-369).
    """
    spec = [None] * ndim
    spec[axis] = mesh_axis
    return NamedSharding(mesh, P(*spec))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_to_multiple(n: int, k: int) -> int:
    """Smallest multiple of k that is >= n (physical shard padding)."""
    if k <= 0:
        return n
    return ((n + k - 1) // k) * k


def reference_server_offsets(size: int, num_servers: int) -> Tuple[int, ...]:
    """The reference's contiguous partition: even split, last server takes the
    remainder (``src/table/array_table.cpp:98-108``). Returned offsets have
    length num_servers + 1."""
    each = size // num_servers if num_servers else size
    offsets = [min(i * each, size) for i in range(num_servers)]
    offsets.append(size)
    return tuple(offsets)
