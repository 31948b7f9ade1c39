"""Device-mesh construction helpers.

The intra-silo parallel plane (SURVEY §2.b): where the reference builds
NCCL/Gloo process groups (``torch_process_group_manager.py:26-34``), the TPU
framework builds a ``jax.sharding.Mesh`` over local (or pod-wide) devices
and lets pjit/shard_map insert ICI collectives.
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger(__name__)


def create_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], devices=None) -> Mesh:
    """Named mesh over the first prod(axis_shapes) devices. Device order
    follows the physical topology (``mesh_utils.create_device_mesh``): on a
    2x2 v5e host ids run (0,0),(1,0),(0,1),(1,1), so a plain reshape would
    make ring neighbours 1->2 and 3->0 diagonal (no ICI link). Off-TPU the
    helper is a plain reshape."""
    devices = devices if devices is not None else jax.devices()
    n = int(np.prod(axis_shapes))
    if n > len(devices):
        raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
    chosen = list(devices[:n])
    try:
        arr = mesh_utils.create_device_mesh(
            tuple(axis_shapes), devices=chosen, allow_split_physical_axes=True)
    except (AssertionError, NotImplementedError, ValueError) as e:
        # a subset that is not a physical sub-grid (3 of a 2x2 host's chips):
        # device ORDER is a locality choice, not correctness — keep id order
        # and say so
        log.warning("mesh %s over devices %s does not tile the physical topology "
                    "(%s); using id order — neighbours on a mesh axis may not "
                    "be ICI neighbours", tuple(axis_shapes), [d.id for d in chosen],
                    type(e).__name__)
        arr = np.asarray(chosen).reshape(axis_shapes)
    return Mesh(arr, tuple(axis_names))


def dp_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """1-D data-parallel mesh over local devices (DDP analogue)."""
    devices = devices if devices is not None else jax.devices()
    n = n_devices or len(devices)
    return create_mesh((n,), ("dp",), devices)


def fsdp_mesh(dp: int, fsdp: int, devices=None) -> Mesh:
    return create_mesh((dp, fsdp), ("dp", "fsdp"), devices)


def tp_mesh(dp: int, fsdp: int, tp: int, devices=None) -> Mesh:
    """3-D mesh for the LLM path: data x fully-sharded x tensor."""
    return create_mesh((dp, fsdp, tp), ("dp", "fsdp", "tp"), devices)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    return NamedSharding(mesh, P(axis))
